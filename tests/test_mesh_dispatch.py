"""Property tests for the bucketed/deduped/sharded dispatch path:
findings must be byte-identical to the naive per-pair host path at
EVERY device count — the differential gate behind the mesh scaling
work (docs/performance.md). Also covers the poison-image quarantine
(PR-2) interacting with device-resident advisory tables."""

import json

import pytest

from tests.test_sched import _norm, make_fleet, make_store
from trivy_tpu.sched import SchedConfig

pytestmark = pytest.mark.perf

DEVICE_COUNTS = (1, 2, 4, 8)


def _random_pair_jobs(rng, n: int) -> list:
    from trivy_tpu.detect.batch import PairJob
    jobs = []
    for k in range(n):
        grammar = ("semver", "npm", "pep440")[
            int(rng.integers(0, 3))]
        v = (f"{int(rng.integers(0, 3))}."
             f"{int(rng.integers(0, 5))}.{int(rng.integers(0, 5))}")
        fixed = (f"{int(rng.integers(1, 3))}."
                 f"{int(rng.integers(0, 5))}.1")
        roll = float(rng.random())
        if roll < 0.6:
            jobs.append(PairJob(
                grammar=grammar, pkg_version=v,
                vulnerable=[f"<{fixed}"], patched=[f">={fixed}"],
                payload=("pj", k)))
        elif roll < 0.8:
            lo = f"{int(rng.integers(0, 2))}.0.0"
            jobs.append(PairJob(
                grammar=grammar, pkg_version=v,
                vulnerable=[f">={lo}, <{fixed}"],
                patched=[f">={fixed}"], payload=("pj", k)))
        else:
            jobs.append(PairJob(
                grammar="deb", pkg_version=f"1.{k % 4}-1",
                kind="ospkg", fixed_version=f"1.{k % 3 + 1}-1",
                payload=("pj", k)))
    return jobs


def _resident_setup(rng, n_jobs: int):
    from trivy_tpu.db import AdvisoryStore
    from trivy_tpu.db.compiled import CompiledDB
    from trivy_tpu.detect.batch import ResidentPairJob
    store = AdvisoryStore()
    for i in range(12):
        store.put_advisory(
            "npm::Node.js", f"lib{i}", f"CVE-{i}",
            {"VulnerableVersions": [f"<1.{i % 6}.0"],
             "PatchedVersions": [f">=1.{i % 6}.0"]})
    cdb = CompiledDB.compile(store)
    jobs = []
    for k in range(n_jobs):
        row = int(rng.integers(0, len(cdb.rows_meta)))
        v = (f"1.{int(rng.integers(0, 7))}."
             f"{int(rng.integers(0, 3))}")
        jobs.append(ResidentPairJob(
            cdb=cdb, row=row, grammar=cdb.row_grammar[row],
            pkg_version=v, payload=("rj", k)))
    return cdb, jobs


def _naive_truth(jobs) -> list:
    """Per-job host evaluation, no dedup, no batching — the oracle
    every device count must match."""
    from trivy_tpu.detect.batch import (PairJob, _host_eval,
                                        dispatch_jobs)
    out = []
    for job in jobs:
        if isinstance(job, PairJob):
            if job.kind == "ospkg":
                # single-job cpu-ref dispatch IS the reference ospkg
                # evaluation (affected/fixed gate semantics)
                if dispatch_jobs([job], backend="cpu-ref",
                                 stats={}):
                    out.append(job.payload)
            elif _host_eval(job):
                out.append(job.payload)
        else:
            if job.cdb.host_eval(job.row, job.pkg_version):
                out.append(job.payload)
    return sorted(out)


@pytest.mark.parametrize("ndev", DEVICE_COUNTS)
def test_dispatch_parity_across_device_counts(ndev):
    """Seeded random mixed job lists (classic + resident, heavy
    duplication) through the full deduped/bucketed/sharded
    dispatcher at {1,2,4,8} devices == the naive per-pair truth."""
    import numpy as np

    from trivy_tpu.detect.batch import dispatch_jobs
    from trivy_tpu.parallel import make_mesh

    rng = np.random.default_rng(20260804 + ndev)
    base = _random_pair_jobs(rng, 60)
    cdb, resident = _resident_setup(rng, 80)
    jobs = base + resident
    # duplicate a random third of the mix (distinct payloads) so the
    # dedup fan-out is exercised at every count
    from trivy_tpu.detect.batch import PairJob, ResidentPairJob
    for idx in rng.choice(len(jobs), size=len(jobs) // 3,
                          replace=False):
        j = jobs[int(idx)]
        if isinstance(j, PairJob):
            dup = PairJob(**{**j.__dict__,
                             "payload": ("dup", int(idx))})
        else:
            dup = ResidentPairJob(**{**j.__dict__,
                                     "payload": ("dup", int(idx))})
        jobs.append(dup)
    rng.shuffle(jobs)

    want = _naive_truth(jobs)
    mesh = make_mesh(ndev)
    stats: dict = {}
    got = sorted(dispatch_jobs(jobs, backend="tpu", mesh=mesh,
                               stats=stats))
    assert got == want
    assert stats["jobs_unique"] < stats["jobs_in"]


@pytest.mark.parametrize("ndev", DEVICE_COUNTS)
def test_fleet_reports_identical_across_device_counts(
        tmp_path, ndev):
    """End-to-end: the scheduled fleet scan over a mesh of each size
    produces reports byte-identical to the unsharded cpu-ref direct
    path (secrets + vulns + assembly)."""
    from trivy_tpu.db.compiled import CompiledDB
    from trivy_tpu.parallel import make_mesh
    from trivy_tpu.runtime import BatchScanRunner

    paths = make_fleet(tmp_path, 4)
    cdb = CompiledDB.compile(make_store())

    base_runner = BatchScanRunner(store=cdb, backend="cpu-ref")
    base = _norm(base_runner.scan_paths(paths))

    runner = BatchScanRunner(
        store=cdb, backend="tpu", mesh=make_mesh(ndev),
        sched=SchedConfig(flush_timeout_s=0.01, workers=4))
    try:
        got = _norm(runner.scan_paths(paths))
    finally:
        runner.close()
    assert got == base


def test_poison_image_with_resident_db(tmp_path, make_faults):
    """PR-2 quarantine path against device-resident tables: the
    poisoned slot completes on the exact host path with identical
    findings, healthy slots stay byte-identical, and the resident
    buffers survive (next dispatch reuses them — no re-upload)."""
    from trivy_tpu.db.compiled import CompiledDB
    from trivy_tpu.runtime import BatchScanRunner

    paths = make_fleet(tmp_path, 6, shared_secret=False)
    cdb = CompiledDB.compile(make_store())

    def run(injector=None):
        runner = BatchScanRunner(
            store=cdb, backend="tpu",
            sched=SchedConfig(flush_timeout_s=0.01, workers=4),
            fault_injector=injector)
        try:
            res = runner.scan_paths(paths)
            counters = runner.scheduler.metrics.snapshot()[
                "counters"]
        finally:
            runner.close()
        return res, counters

    baseline, _ = run()
    uploads_before = cdb.device_stats()["uploads"]
    inj = make_faults("poison-image:poison=img2.tar")
    faulted, counters = run(injector=inj)

    poisoned = [r for r in faulted if "img2.tar" in r.name]
    assert len(poisoned) == 1
    assert poisoned[0].status == "degraded" and not poisoned[0].error
    assert "quarantined" in [c.kind for c in poisoned[0].causes]
    healthy_f = [r for r in faulted if "img2.tar" not in r.name]
    healthy_b = [r for r in baseline if "img2.tar" not in r.name]
    assert _norm(healthy_f) == _norm(healthy_b)
    # the quarantined slot's findings match the healthy baseline's
    # (host fallback is the exact engine; only status metadata adds)
    base_p = [r for r in baseline if "img2.tar" in r.name][0]
    stripped = poisoned[0].report.to_dict()
    stripped.pop("Status", None)
    stripped.pop("FailureCauses", None)
    assert json.dumps(stripped, sort_keys=True) == \
        json.dumps(base_p.report.to_dict(), sort_keys=True)
    assert counters.get("quarantined", 0) >= 1
    # resident buffers were NOT re-uploaded by the fault handling;
    # only brand-new (device, mesh) keys add uploads
    assert cdb.device_stats()["uploads"] == uploads_before
