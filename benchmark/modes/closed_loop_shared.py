"""Traffic mode ``closed_loop_shared``: a registry's tags through
``BatchScanRunner.submit_path``, with one blob cache and one findings
memo kept for the whole window, both empty when it opens. Parameters
in the traffic file: ``in_flight``, ``tags``, ``bases``,
``tag_zipf_s``, ``base_zipf_s``, ``schedule``, ``warmup``, ``memo``
(``default``: the CLI's), ``sched``, ``security_checks``.

``closed_loop``'s loop, with the configuration's request order as
its pool (``gen_shared.plan``: ``schedule`` requests over ``tags``
distinct images on ``bases`` bases, the same on every seed), so the
k-th request of every run is of the same class: the first of its
base, the first of its tag on a base seen, or a tag seen. The
schedule is sized to outlast the window; should it not, the wrap
starts cache and memo anew, as ``closed_loop``'s does the cache. The
warm-up's images sit on a base of their own that the window never
asks for.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from modes import closed_loop

RENDER_S = 60.0                 # the reports' rendering may take this
RENDER_KEEP = 300               # else: so many, first sights among them
PROBE = 16


def make_data(cell, work: str) -> dict:
    """The tags, written by a few generator processes as
    ``closed_loop``'s pool is, and the request order over them."""
    import gen_shared
    t = cell.traffic
    plan = gen_shared.plan(t)
    n_tags, n_warm = t["tags"], t["warmup"]
    base_of = {str(k): b for k, b in enumerate(plan["base_of"])}
    for k in range(n_warm):         # a base the window never sees
        base_of[str(n_tags + k)] = t["bases"]
    tags = list(range(n_tags + n_warm))
    procs = min(closed_loop.GEN_PROCS, os.cpu_count() or 1,
                len(tags) // 16)
    if procs < 2:
        items = gen_shared.build_tags(cell.sizes, tags, base_of, work,
                                      cell.seed)
    else:
        jobs = [subprocess.Popen(
            [sys.executable, gen_shared.__file__, json.dumps(
                {"sizes": cell.sizes, "tags": tags[k::procs],
                 "base_of": base_of, "directory": work,
                 "seed": cell.seed})],
            stdout=subprocess.PIPE) for k in range(procs)]
        items = []
        for job in jobs:
            out, _ = job.communicate()
            if job.returncode != 0:
                raise RuntimeError(
                    f"gen_shared.py exit {job.returncode}")
            items += json.loads(out)
        items.sort(key=lambda it: it["tag"])
    first: dict = {}                # base -> the first tag asked for
    for tag in plan["schedule"]:
        first.setdefault(items[tag]["base"], items[tag])
    return {"tags": items[:n_tags], "warm": items[n_tags:],
            "pool": [items[tag] for tag in plan["schedule"]],
            "classes": plan["classes"], "first": first}


def new_state(cell) -> None:
    """A new blob cache and, over it, a new memo of the CLI's making
    (``cli._memo``). ``run.py`` builds its runner without one; the
    runner reads ``memo`` and ``cache`` at every use."""
    from trivy_tpu.memo import make_findings_memo
    cell.fresh_cache()
    cell.runner.memo = make_findings_memo(
        cache=cell.runner.cache, backend="tpu") \
        if cell.traffic["memo"] == "default" else None


def warm_up(cell, data: dict) -> list:
    """The warm-up set through the window's own entry, twice: cold,
    and again from cache and memo."""
    new_state(cell)
    return closed_loop.warm_up(cell, data) + \
        closed_loop.warm_up(cell, data)


def drive(cell, data: dict, seconds: float) -> dict:
    new_state(cell)
    rec = closed_loop.loop(
        data["pool"], lambda it: closed_loop.submit(cell, it), seconds,
        cell.traffic["in_flight"], lambda: new_state(cell))
    by_class = {"base_cold": 0, "tag_new": 0, "tag_seen": 0}
    for idx, _ in rec["finished"]:
        by_class[data["classes"][idx]] += 1
    rec["classes"] = by_class
    rec["lines"] = [f"completions (s): {rec.pop('done_at')}",
                    f"requests finished by class: {by_class}"]
    return rec


def answers(cell, rec: dict, data: dict) -> dict:
    """Every request that finished in the window or was in flight at
    its end (waited for) against the reference. Where rendering them
    all would take over ``RENDER_S`` (reckoned from the first
    ``PROBE``), every first sight and every request that was in
    flight is kept and, of the rest, positions drawn from the seed
    down to ``RENDER_KEEP`` reports."""
    import check
    import reference_shared as reference
    pool, checks = data["pool"], cell.traffic["security_checks"]
    late, never = [], 0
    end = time.monotonic() + closed_loop.DRAIN_S
    for idx, fut in rec["in_flight"]:
        closed_loop.wait_for(fut, max(0.0, end - time.monotonic()))
        if fut.done:
            late.append((idx, fut))
        else:
            never += 1
            cell.say(f"never answered: {pool[idx]['path']}")
    took = rec["finished"] + late
    probe = took[:PROBE]
    t0 = time.monotonic()
    for _, fut in probe:
        try:
            check.render(fut.result(timeout=0).report)
        except Exception:           # noqa: BLE001 (compare reads it)
            pass
    each = (time.monotonic() - t0) / max(1, len(probe))
    if each * len(took) > RENDER_S and len(took) > RENDER_KEEP:
        must = [k for k, (idx, _) in enumerate(took)
                if k >= len(rec["finished"])
                or data["classes"][idx] != "tag_seen"]
        rest = sorted(set(range(len(took))) - set(must))
        rng = np.random.default_rng([cell.seed, 8])
        drawn = rng.choice(rest, max(0, RENDER_KEEP - len(must)),
                           replace=False)
        keep = sorted(must + [int(k) for k in drawn])
        cell.say(f"reports: {each * 1e3:.0f} ms each to render, "
                 f"{len(took)} would take over {RENDER_S:.0f} s: "
                 f"{len(keep)} compared, {len(must)} first sights "
                 f"and in flight, the rest drawn from the seed")
        took = [took[k] for k in keep]
    out, want = [], {}
    for idx, fut in took:
        try:
            res = fut.result(timeout=0)
        except Exception as e:      # noqa: BLE001
            res = e
        facts = pool[idx]
        if facts["tag"] not in want:
            want[facts["tag"]] = reference.image_findings(
                cell.table, facts, checks)
        out.append((
            os.path.basename(facts["path"]), res, want[facts["tag"]],
            reference.image_findings(
                cell.table, facts, checks, control=True,
                first=data["first"][facts["base"]])
            if cell.control else None))
    # the interval jobs the window cannot have done without: cache
    # and memo were empty when it opened, so each distinct job of
    # what finished went to the device at least once
    jobs, seen = set(), set()
    for idx, _ in rec["finished"]:
        facts = pool[idx]
        if facts["tag"] not in seen:
            seen.add(facts["tag"])
            jobs |= reference.image_jobs(cell.table, facts)
    return {"answers": out, "never": never,
            "expected_rows": len(jobs)}
