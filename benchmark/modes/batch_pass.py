"""Traffic mode ``batch_pass``: CycloneDX documents through
``BatchScanRunner.scan_boms``. Parameters in the traffic file:
``batch``, ``pool``, ``warmup``, ``check_per_pass``,
``fresh_cache_each_pass``, ``sched``, ``security_checks``.

One call on the next ``batch`` documents a pass, passes repeated
until the clock passes the window's length; the window ends with the
pass that crosses it and the rate is documents over the time really
taken. The pool wraps when it is used up (counted and printed).
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


def loop(pool: list, call, seconds: float, batch: int,
         on_wrap) -> dict:
    finished, pass_s = [], []          # (first item index, results)
    nxt = wraps = units = 0
    t0 = time.monotonic()
    with TraceAnnotation("bench.window"):
        while time.monotonic() - t0 < seconds:
            if nxt + batch > len(pool):
                with TraceAnnotation("bench.pool_wrap"):
                    on_wrap()
                nxt, wraps = 0, wraps + 1
            t_pass = time.monotonic()
            with TraceAnnotation("bench.pass"):
                finished.append((nxt, call(pool[nxt:nxt + batch])))
            pass_s.append(time.monotonic() - t_pass)
            nxt += batch
            units += batch
        t1 = time.monotonic()
    return {"finished": finished, "in_flight": [],
            "window_s": t1 - t0, "wraps": wraps, "units": units,
            "pass_s": pass_s}


def sample_at(cell) -> list:
    """The positions in a pass whose reports are compared, drawn
    from the seed."""
    import numpy as np
    t = cell.traffic
    return sorted(int(k) for k in
                  np.random.default_rng([cell.seed, 9]).choice(
                      t["batch"], t["check_per_pass"], replace=False))


def make_data(cell, work: str) -> dict:
    import gen
    t = cell.traffic
    pool, facts = gen.build_sboms(cell.sizes, t["pool"], cell.seed,
                                  "bom")
    warm, _ = gen.build_sboms(cell.sizes, t["warmup"], cell.seed,
                              "warm")
    return {"pool": pool, "warm": warm, "facts": facts,
            "sample_at": sample_at(cell)}


def call(cell, batch: list, keep: list) -> dict:
    """One pass. Every slot's status is read here, and only the
    slots at the positions drawn from the seed are kept for the
    comparison: the program collects garbage over the whole heap
    after each pass, so the harness keeps its own heap small."""
    if cell.traffic.get("fresh_cache_each_pass"):
        cell.fresh_cache()
    results = cell.runner.scan_boms(batch, cell.opts)
    return {"bad": [(k, r) for k, r in enumerate(results)
                    if r.status != "ok" or r.error],
            "sample": [(k, results[k]) for k in keep
                       if k < len(results)]}


def warm_up(cell, data: dict) -> list:
    return [r.name for _, r in
            call(cell, data["warm"], data["sample_at"])["bad"]]


def drive(cell, data: dict, seconds: float) -> dict:
    rec = loop(data["pool"],
               lambda b: call(cell, b, data["sample_at"]), seconds,
               cell.traffic["batch"], cell.fresh_cache)
    rec["lines"] = [
        f"passes (s): {[round(s, 2) for s in rec.pop('pass_s')]}"]
    return rec


def answers(cell, rec: dict, data: dict) -> dict:
    """Every slot that was not ok, and the sample the passes kept
    for the full comparison. A call is a scan of its own, so the
    interval jobs the window cannot have done without are each
    pass's distinct jobs, summed."""
    import gen
    import reference
    pool, facts, batch = data["pool"], data["facts"], \
        cell.traffic["batch"]
    out, expected = [], 0
    for first, kept in rec["finished"]:
        for k, res in kept["bad"]:
            out.append((pool[first + k][0], res, None, None))
        bad = {k for k, _ in kept["bad"]}
        for k, res in kept["sample"]:
            if k in bad:
                continue
            comps = gen.sbom_components(facts, first + k)
            out.append((
                pool[first + k][0], res,
                reference.sbom_findings(cell.table, comps),
                reference.sbom_findings(cell.table, comps,
                                        control=True)
                if cell.control else None))
        jobs = set()
        for n in range(first, min(first + batch, len(pool))):
            jobs |= reference.library_jobs(
                cell.table, gen.sbom_components(facts, n))
        expected += len(jobs)
    return {"answers": out, "never": 0, "expected_rows": expected}
