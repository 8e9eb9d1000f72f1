"""Traffic mode ``batch_pass_zipf``: ``batch_pass`` over documents
whose packages are drawn by popularity (``gen_zipf``). Parameters in
the traffic file: ``batch_pass``'s; the exponent and the size of the
name universe are the configuration's (``sizes.zipf_s``,
``sizes.name_universe``).

``batch_pass``'s loop, call, sampling, warm-up and window, with the
pool and the warm-up set made by ``gen_zipf.build_sboms``. What came
back is paired with ``reference_zipf``, the same answers as
``reference``'s, and the compared documents are also read against
each other: every (package, version) that two of them hold has to be
reported alike by both.

The deployment runs with the program's host pool off
(``TRIVY_TPU_HOST_POOL=0``, the program's own documented setting;
the configuration's ``deployment`` says so). Since PR 33 ``scan_boms``
hands the pool nothing, so there the setting changes nothing; on a
program from before it, it makes the document decode run on the
calling thread as it does since, which is what keeps such a
program's runs of this cell together (pooled, a run in three fell
into a mode a tenth slower: ``PERF.md`` section 6, PR 33).
"""

from __future__ import annotations

import os

from modes import batch_pass
from modes.batch_pass import drive, warm_up  # noqa: F401


def make_data(cell, work: str) -> dict:
    import gen_zipf
    # before the runner is built: the pool is made on first use
    os.environ["TRIVY_TPU_HOST_POOL"] = "0"
    t = cell.traffic
    pool, facts = gen_zipf.build_sboms(cell.sizes, t["pool"],
                                       cell.seed, "bom")
    warm, _ = gen_zipf.build_sboms(cell.sizes, t["warmup"], cell.seed,
                                   "warm")
    return {"pool": pool, "warm": warm, "facts": facts,
            "sample_at": batch_pass.sample_at(cell)}


def answers(cell, rec: dict, data: dict) -> dict:
    import check
    import gen
    import reference_zipf
    got = batch_pass.answers(cell, rec, data)
    names = {name: n for n, (name, _) in enumerate(data["pool"])}
    compared = [
        (gen.sbom_components(data["facts"], names[name]),
         check.findings(check.render(res.report)))
        for name, res, want, _ in got["answers"] if want is not None]
    rep = reference_zipf.repeats(compared)
    cell.say(f"repeats: {rep['shared']} (package, version) pairs "
             f"held by two or more of the {len(compared)} compared "
             f"documents, {len(rep['disagree'])} reported "
             f"differently {rep['disagree'][:3]}")
    return got
