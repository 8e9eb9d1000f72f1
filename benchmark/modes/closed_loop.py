"""Traffic mode ``closed_loop``: images through
``BatchScanRunner.submit_path``. Parameters in the traffic file:
``in_flight``, ``pool``, ``warmup``, ``sched``, ``security_checks``.

``in_flight`` requests are kept outstanding, images taken from the
pool in order. When the pool is used up the loop waits for what is in
flight, gives the runner a new blob cache (so that no layer is
answered from it) and starts the pool again; that stall is the
harness's own, so a cell sizes its pool to outlast its window and
wraps are counted and printed. The window ends with the first
completion at or after ``seconds`` (or ``grace`` later, if none
comes), and every request done by then counts. One thread: the loop
sleeps on the oldest request in flight, ``WAIT_S`` at the most.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from jax.profiler import TraceAnnotation

WAIT_S = 0.05                   # the longest the loop sleeps
DRAIN_S = 60.0                  # a late answer is late, not wrong
GEN_PROCS = 6


def wait_for(fut, timeout: float) -> None:
    """Sleep until ``fut`` has its answer, or ``timeout``. What the
    answer is, an error too, is read after the window."""
    try:
        fut.result(timeout=timeout)
    except Exception:               # noqa: BLE001
        pass


def loop(pool: list, submit, seconds: float, in_flight: int,
         on_wrap, grace: float = 30.0) -> dict:
    finished, flying, done_at = [], [], []    # (item index, future)
    nxt = wraps = 0
    t0 = t1 = time.monotonic()
    with TraceAnnotation("bench.window"):
        while True:
            still, landed = [], False
            for idx, fut in flying:
                if fut.done:
                    finished.append((idx, fut))
                    landed = True
                else:
                    still.append((idx, fut))
            flying = still
            t1 = time.monotonic()
            done_at += [round(t1 - t0, 3)] * (len(finished)
                                              - len(done_at))
            over = t1 - t0 >= seconds
            # the window ends with the completion that crosses its
            # length, so the rate has no step of one request in it
            if over and (landed or t1 - t0 >= seconds + grace):
                break
            if nxt == len(pool) and not flying:
                with TraceAnnotation("bench.pool_wrap"):
                    on_wrap()
                nxt, wraps = 0, wraps + 1
            while len(flying) < in_flight and nxt < len(pool):
                with TraceAnnotation("bench.submit"):
                    flying.append((nxt, submit(pool[nxt])))
                nxt += 1
            # requests come back in the order sent, all but a few: the
            # loop sleeps until the oldest has its answer and wakes
            # once an image, where a poll of 2 ms took the
            # interpreter's lock from the workers 500 times a second
            wait_for(flying[0][1], WAIT_S)
    return {"finished": finished, "in_flight": flying,
            "window_s": t1 - t0, "wraps": wraps,
            "units": len(finished), "done_at": done_at}


def make_data(cell, work: str) -> dict:
    """Pool and warm-up images. A pool worth the time is written by
    a few generator processes (``gen.py`` run as a command: numpy,
    never jax, so the chip stays this process's alone)."""
    import gen
    n_pool, n_warm = cell.traffic["pool"], cell.traffic["warmup"]
    ns = list(range(n_pool + n_warm))
    procs = min(GEN_PROCS, os.cpu_count() or 1, len(ns) // 16)
    if procs < 2:
        items = gen.build_images(cell.sizes, ns, work, cell.seed)
    else:
        jobs = [subprocess.Popen(
            [sys.executable, gen.__file__, json.dumps(
                {"sizes": cell.sizes, "ns": ns[k::procs],
                 "directory": work, "seed": cell.seed})],
            stdout=subprocess.PIPE) for k in range(procs)]
        items = []
        for job in jobs:
            out, _ = job.communicate()
            if job.returncode != 0:
                raise RuntimeError(f"gen.py exit {job.returncode}")
            items += json.loads(out)
        items.sort(key=lambda it: it["path"])
    return {"pool": items[:n_pool], "warm": items[n_pool:]}


def submit(cell, item):
    return cell.runner.submit_path(item["path"], cell.opts)


def warm_up(cell, data: dict) -> list:
    """The warm-up set through the window's own entry; returns the
    names of slots that did not come back ok."""
    futs = [submit(cell, it) for it in data["warm"]]
    return [r.name for r in (f.result(timeout=1100) for f in futs)
            if r.status != "ok"]


def drive(cell, data: dict, seconds: float) -> dict:
    rec = loop(data["pool"], lambda it: submit(cell, it), seconds,
               cell.traffic["in_flight"], cell.fresh_cache)
    rec["lines"] = [f"completions (s): {rec.pop('done_at')}"]
    return rec


def answers(cell, rec: dict, data: dict) -> dict:
    """What the window produced against the reference: every image
    that finished in it or was in flight at its end (waited for)."""
    import reference
    pool, checks = data["pool"], cell.traffic["security_checks"]
    late, never = [], 0
    end = time.monotonic() + DRAIN_S
    for idx, fut in rec["in_flight"]:
        try:
            fut.result(timeout=max(0.0, end - time.monotonic()))
            late.append((idx, fut))
        except Exception as e:      # noqa: BLE001
            if fut.done:
                late.append((idx, fut))
            else:
                never += 1
                cell.say(f"never answered: {pool[idx]['path']} {e!r}")
    out, jobs = [], set()
    for idx, fut in rec["finished"] + late:
        try:
            res = fut.result(timeout=0)
        except Exception as e:      # noqa: BLE001
            res = e
        facts = pool[idx]
        out.append((
            os.path.basename(facts["path"]), res,
            reference.image_findings(cell.table, facts, checks),
            reference.image_findings(cell.table, facts, checks,
                                     control=True)
            if cell.control else None))
    # the interval jobs the window cannot have done without: those of
    # the images that finished in it, each distinct job once (which
    # images share a wave is the scheduler's business)
    for idx, _ in rec["finished"]:
        jobs |= reference.image_jobs(cell.table, pool[idx])
    return {"answers": out, "never": never,
            "expected_rows": len(jobs)}
