"""Traffic mode ``closed_loop_tree``: directory trees through
``BatchScanRunner.submit_tree``, the next tree submitted when the one
before resolves. Parameters in the traffic file: ``in_flight`` (1: a
CI job or a pre-receive hook runs one ``trivy fs .`` at a time, so all
overlap has to come from inside the tree), ``pool``, ``warmup``,
``warmup_bytes``, ``sched``, ``security_checks``.

``closed_loop``'s loop over a pool of ``pool`` distinct trees
(``gen_tree``: the same files dealt to other directories in another
order a tree and a seed), written by one generator process each. A
wrap scans the pool again with a new blob cache and drains nothing
(one tree is in flight); wraps are counted and printed. The warm-up's
tree is ``warmup_bytes`` of text, the configuration's largest file
among it, at the densest decoy share: the file that rides alone and a
part that passes the compacted fetch, which with the ladder the
runner warms are all the shapes a window meets. One unit is one tree
finished; the window ends with the first tree that finishes at or
after its length.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from modes import closed_loop


def make_data(cell, work: str) -> dict:
    import gen_tree
    from trivy_tpu.runtime import BatchScanRunner
    if not hasattr(BatchScanRunner, "submit_tree"):
        # said before 2 GB are written for nothing
        raise RuntimeError("this program has no BatchScanRunner."
                           "submit_tree: it cannot run the cell")
    t = cell.traffic
    jobs = [{"sizes": cell.sizes, "ns": [n], "directory": work,
             "seed": cell.seed} for n in range(t["pool"])]
    jobs += [{"sizes": cell.sizes, "ns": [t["pool"] + k],
              "directory": work, "seed": cell.seed,
              "tree_bytes": t["warmup_bytes"]}
             for k in range(t["warmup"])]
    if (os.cpu_count() or 1) < 2 or \
            cell.sizes["files"] * len(jobs) < 4096:
        items = [it for job in jobs for it in gen_tree.build_trees(
            job["sizes"], job["ns"], job["directory"], job["seed"],
            job.get("tree_bytes", 0))]
    else:
        procs = [subprocess.Popen(
            [sys.executable, gen_tree.__file__, json.dumps(job)],
            stdout=subprocess.PIPE) for job in jobs]
        items = []
        for proc in procs:
            out, _ = proc.communicate()
            if proc.returncode != 0:
                raise RuntimeError(
                    f"gen_tree.py exit {proc.returncode}")
            items += json.loads(out)
    pool, warm = items[:t["pool"]], items[t["pool"]:]
    for it in pool[:1] + warm:
        cell.say(f"tree {os.path.basename(it['path'])}: "
                 f"{it['files']} files, {it['candidate_files']} "
                 f"candidates of {it['candidate_bytes']} bytes, "
                 f"{it['parts']} parts and {it['oversize']} file "
                 f"alone by the generator's cut")
    return {"pool": pool, "warm": warm}


def submit(cell, item):
    return cell.runner.submit_tree(item["path"], cell.opts)


def warm_up(cell, data: dict) -> list:
    futs = [submit(cell, it) for it in data["warm"]]
    return [r.name for r in (f.result(timeout=1100) for f in futs)
            if r.status != "ok"]


def drive(cell, data: dict, seconds: float) -> dict:
    rec = closed_loop.loop(
        data["pool"], lambda it: submit(cell, it), seconds,
        cell.traffic["in_flight"], cell.fresh_cache, grace=300.0)
    done = sum(data["pool"][idx]["candidate_bytes"]
               for idx, _ in rec["finished"])
    rec["lines"] = [f"completions (s): {rec.pop('done_at')}",
                    f"candidate bytes of the trees finished: {done}"]
    return rec


def answers(cell, rec: dict, data: dict) -> dict:
    """Every tree that finished in the window or was in flight at
    its end (waited for) against the reference."""
    import time

    import reference_tree as reference
    pool, checks = data["pool"], cell.traffic["security_checks"]
    late, never = [], 0
    end = time.monotonic() + 300.0
    for idx, fut in rec["in_flight"]:
        closed_loop.wait_for(fut, max(0.0, end - time.monotonic()))
        if fut.done:
            late.append((idx, fut))
        else:
            never += 1
            cell.say(f"never answered: {pool[idx]['path']}")
    out, jobs = [], set()
    for idx, fut in rec["finished"] + late:
        try:
            res = fut.result(timeout=0)
        except Exception as e:      # noqa: BLE001 (compare reads it)
            res = e
        facts = pool[idx]
        out.append((
            os.path.basename(facts["path"]), res,
            reference.tree_findings(cell.table, facts, checks),
            reference.tree_findings(cell.table, facts, checks,
                                    control=True)
            if cell.control else None))
    for idx, _ in rec["finished"]:
        jobs |= reference.tree_jobs(cell.table, pool[idx])
    return {"answers": out, "never": never,
            "expected_rows": len(jobs)}
