"""The interval dispatcher's one path (detect/batch.py): each row
kind, on the host engine, one device and a two-device mesh, with and
without a dispatch ring, at a small and at the default wave size,
answers what the host engine answers — duplicates and host-fallback
rows included — and sends exactly the waves its plan says."""

import numpy as np
import pytest

from trivy_tpu.runtime.ring import DispatchRing

pytestmark = pytest.mark.perf

# more alternatives than MAX_INTERVALS: the exact host path
_WIDE = " || ".join(f">={j}.0.0 <{j}.3.0" for j in range(6))


def _duplicate(jobs: list, rng) -> list:
    """Every third job again, under a payload of its own."""
    from dataclasses import replace
    picks = rng.choice(len(jobs), size=len(jobs) // 3, replace=False)
    return jobs + [replace(jobs[int(i)], payload=("dup", int(i)))
                   for i in picks]


def _classic_jobs(rng) -> list:
    from trivy_tpu.detect.batch import PairJob
    jobs = []
    for k in range(240):
        v = (f"{int(rng.integers(0, 4))}.{int(rng.integers(0, 6))}."
             f"{int(rng.integers(0, 6))}")
        fixed = f"{int(rng.integers(1, 4))}.{int(rng.integers(0, 6))}.1"
        if k % 10 == 0:
            jobs.append(PairJob(grammar="semver", pkg_version=v,
                                vulnerable=[_WIDE], payload=k))
        elif k % 10 == 1:
            jobs.append(PairJob(grammar="npm", pkg_version=f"{v}-rc.1",
                                vulnerable=[f"<{fixed}"],
                                patched=[f">={fixed}"], payload=k))
        elif k % 10 == 2:
            jobs.append(PairJob(grammar="deb", kind="ospkg",
                                pkg_version=f"1.{k % 4}-1",
                                fixed_version=f"1.{k % 3 + 1}-1",
                                payload=k))
        else:
            jobs.append(PairJob(grammar=("semver", "npm", "pep440")[k % 3],
                                pkg_version=v,
                                vulnerable=[f">=0.{k % 3}.0, <{fixed}"],
                                patched=[f">={fixed}"], payload=k))
    return _duplicate(jobs, rng)


def _resident_jobs(rng) -> list:
    from trivy_tpu.db import AdvisoryStore
    from trivy_tpu.db.compiled import CompiledDB
    from trivy_tpu.detect.batch import ResidentPairJob
    store = AdvisoryStore()
    for i in range(40):
        vuln = _WIDE if i % 8 == 0 else f"<1.{i % 6}.0"
        store.put_advisory("npm::Node.js", f"lib{i}", f"CVE-{i}",
                           {"VulnerableVersions": [vuln],
                            "PatchedVersions": [f">=9.{i % 6}.0"]})
    cdb = CompiledDB.compile(store)
    jobs = []
    for k in range(320):
        row = int(rng.integers(0, len(cdb.rows_meta)))
        v = f"{int(rng.integers(0, 3))}.{int(rng.integers(0, 7))}.0"
        if k % 13 == 0:
            v += "-beta.2"
        jobs.append(ResidentPairJob(cdb=cdb, row=row,
                                    grammar=cdb.row_grammar[row],
                                    pkg_version=v, payload=k))
    return _duplicate(jobs, rng)


@pytest.mark.parametrize("wave_rows", [64, None],
                         ids=["small-waves", "default-waves"])
@pytest.mark.parametrize("ring", [False, True], ids=["inline", "ring"])
@pytest.mark.parametrize("engine", ["cpu-ref", "device", "mesh2"])
@pytest.mark.parametrize("kind", ["classic", "resident"])
def test_one_path_answers_as_the_host_engine(kind, engine, ring,
                                             wave_rows, monkeypatch):
    import trivy_tpu.detect.batch as db
    from trivy_tpu.detect.metrics import DETECT_METRICS
    from trivy_tpu.parallel import make_mesh

    rng = np.random.default_rng(20261017)
    jobs = (_classic_jobs if kind == "classic" else _resident_jobs)(rng)
    want = db.dispatch_jobs(list(jobs), backend="cpu-ref")
    if wave_rows is not None:
        monkeypatch.setattr(db, "_WAVE_ROWS", wave_rows)
    backend = "cpu-ref" if engine == "cpu-ref" else "tpu"
    mesh = make_mesh(2) if engine == "mesh2" else None
    disp_ring = DispatchRing(depth=2, name="t-interval") if ring \
        else None
    stats: dict = {}
    before = DETECT_METRICS.snapshot()
    try:
        handle = db.dispatch_jobs_async(list(jobs), backend=backend,
                                        mesh=mesh, stats=stats,
                                        ring=disp_ring)
        got = db.collect_dispatch(handle)
    finally:
        if disp_ring is not None:
            disp_ring.close()
    after = DETECT_METRICS.snapshot()

    assert got == want
    (seg,) = handle.segments
    rows = seg.rows
    # the case holds what it claims to: duplicates, and host-fallback
    # rows whose verdict reaches the output
    assert stats["jobs_unique"] < stats["jobs_in"] == len(jobs)
    host_payloads = {jobs[i].payload for gi in rows.host
                     for i in rows.members[gi]}
    assert host_payloads & set(want)

    kept = len(rows.kept)
    plan = [] if backend == "cpu-ref" else db._wave_plan(kept)
    if wave_rows is not None and backend != "cpu-ref":
        assert len(plan) > 1
    assert after["device_waves"] - before["device_waves"] == len(plan)
    assert after["device_rows"] - before["device_rows"] == \
        sum(n for _, n, _ in plan)
    assert handle.waves == (1 if backend == "cpu-ref" else len(plan))
    assert stats["device_s"] > 0
