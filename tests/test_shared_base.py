"""A registry whose tags share base layers and come back, at test
size on the CPU: the blob cache, the findings memo, the base-layer
secret skip and the scheduler's short cut for a request that brings
nothing for the device, through ``BatchScanRunner(sched="on")`` as
the benchmark's ``fleet-shared-base`` cell drives them. The images
and the plain reference are the benchmark's own
(``benchmark/gen_shared.py``, ``benchmark/reference_shared.py``,
imported by path); the program is held to them request by request.

(a) a schedule of 3 bases and 8 tags with repeats equals the
    reference, with 1 and with 8 requests in flight;
(b) every report is, byte for byte, the one a new runner with an
    empty cache and no memo gives for the same tar;
(c) a base's planted secret is in no report, and is in the report
    of the same layers without the ``CMD`` boundary;
(d) after ``runtime.aot.warm_ladders`` a batch at every rung
    compiles nothing;
(e) ``layers_cached``, ``memo.hits`` and ``no_device_work`` read
    what the schedule implies, exactly.
"""

import dataclasses
import io
import json
import os
import sys
import tarfile

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

SIZES = {"files": 24, "os_pkgs": 16, "pip_pkgs": 8, "os_universe": 60,
         "ghsa_pkgs": 80, "db_seed": 5, "seg_len": 2048,
         "seg_overlap": 48,
         "file_size_quantiles": [[0.0, 10], [0.5, 2882], [0.9, 26621],
                                 [0.99, 184921]]}
TRAFFIC = {"tags": 8, "bases": 3, "tag_zipf_s": 1.0,
           "base_zipf_s": 1.0, "schedule": 24}
SEED = 2147483777
CHECKS = ["vuln", "secret"]


@pytest.fixture(scope="module")
def registry(tmp_path_factory):
    """The compiled table, the 8 tags on disk, the request order."""
    import gen
    import gen_shared
    from trivy_tpu.db import AdvisoryStore, CompiledDB
    store = AdvisoryStore()
    for bucket, pkg, vid, adv, detail in gen.advisory_rows(
            SIZES, SIZES["db_seed"]):
        store.put_advisory(bucket, pkg, vid, adv)
        if detail is not None:
            store.put_vulnerability(vid, detail)
    plan = gen_shared.plan(TRAFFIC)
    work = str(tmp_path_factory.mktemp("registry"))
    tags = gen_shared.build_tags(
        SIZES, list(range(TRAFFIC["tags"])),
        {str(t): b for t, b in enumerate(plan["base_of"])}, work, SEED)
    return {"cdb": CompiledDB.compile(store), "tags": tags,
            "plan": plan, "work": work,
            "table": gen.GhsaTable(SIZES["ghsa_pkgs"],
                                   SIZES["db_seed"])}


def new_runner(reg, memo: bool):
    from trivy_tpu.memo import make_findings_memo
    from trivy_tpu.runtime import BatchScanRunner
    runner = BatchScanRunner(store=reg["cdb"], backend="tpu",
                             sched="on")
    if memo:
        runner.memo = make_findings_memo(cache=runner.cache,
                                         backend="tpu")
    return runner


def options():
    from trivy_tpu.types import ScanOptions
    return ScanOptions(backend="tpu", security_checks=list(CHECKS))


def sweep(reg, in_flight: int) -> tuple:
    """The schedule through one runner with cache and memo, at most
    ``in_flight`` requests outstanding: the results in request order
    and the scheduler's counters."""
    runner = new_runner(reg, memo=True)
    opts, flying, out = options(), [], []
    try:
        for tag in reg["plan"]["schedule"]:
            if len(flying) == in_flight:
                out.append(flying.pop(0).result(timeout=300))
            flying.append(runner.submit_path(
                reg["tags"][tag]["path"], opts))
        out += [f.result(timeout=300) for f in flying]
        return out, runner.scheduler.stats()["counters"]
    finally:
        runner.close()


def rendered(result) -> str:
    import check
    assert result.status == "ok", (result.status, result.error)
    return json.dumps(check.render(result.report), sort_keys=True)


@pytest.fixture(scope="module")
def sequential(registry):
    """The sweep with one request in flight, and what it added to
    the process-wide counters."""
    from trivy_tpu.artifact.metrics import INGEST_METRICS
    from trivy_tpu.detect.metrics import DETECT_METRICS

    def counters():
        return dict(INGEST_METRICS.snapshot(),
                    **DETECT_METRICS.snapshot()["memo"])

    before = counters()
    results, sched = sweep(registry, 1)
    after = counters()
    return {"results": results, "sched": sched,
            "added": {k: after[k] - before[k] for k in after
                      if isinstance(after[k], int)}}


# (a) ---------------------------------------------------------------

@pytest.mark.parametrize("in_flight", [1, 8])
def test_schedule_equals_the_reference(registry, in_flight):
    import check
    import reference_shared
    results, _ = sweep(registry, in_flight)
    schedule = registry["plan"]["schedule"]
    assert len(results) == len(schedule) == TRAFFIC["schedule"]
    assert len(set(schedule)) == TRAFFIC["tags"]
    for k, (tag, res) in enumerate(zip(schedule, results)):
        assert res.status == "ok", (k, tag, res.error)
        got = check.findings(check.render(res.report))
        want = reference_shared.image_findings(
            registry["table"], registry["tags"][tag], CHECKS)
        assert got == want, (k, tag, registry["plan"]["classes"][k])
        assert len(want["secrets"]) == 6 and want["vulns"]


# (b) ---------------------------------------------------------------

@pytest.fixture(scope="module")
def cold_reports(registry):
    """Every tag through a runner of its own: empty cache, no memo."""
    out = []
    for facts in registry["tags"]:
        runner = new_runner(registry, memo=False)
        try:
            out.append(rendered(runner.submit_path(
                facts["path"], options()).result(timeout=300)))
        finally:
            runner.close()
    return out


@pytest.mark.parametrize("tag", range(TRAFFIC["tags"]))
def test_cached_report_is_the_cold_report(registry, sequential,
                                          cold_reports, tag):
    schedule = registry["plan"]["schedule"]
    sights = [k for k, t in enumerate(schedule) if t == tag]
    assert sights
    for k in sights:
        assert rendered(sequential["results"][k]) == \
            cold_reports[tag], (tag, k)


# (c) ---------------------------------------------------------------

def without_cmd_boundary(path: str, out: str) -> None:
    """The same layers as an image whose config has no history: no
    layer is the base's."""
    with tarfile.open(path) as src, \
            tarfile.open(out, mode="w") as dst:
        for member in src:
            data = src.extractfile(member).read()
            if member.name == "config.json":
                config = json.loads(data)
                del config["history"]
                data = json.dumps(config).encode()
                member.size = len(data)
            dst.addfile(member, io.BytesIO(data))


@pytest.mark.parametrize("base", range(TRAFFIC["bases"]))
def test_base_secrets_are_in_no_report(registry, sequential, base):
    import check
    planted = None
    for k, tag in enumerate(registry["plan"]["schedule"]):
        facts = registry["tags"][tag]
        if facts["base"] != base:
            continue
        planted = {tuple(s) for s in facts["base_secrets"]}
        got = check.findings(check.render(
            sequential["results"][k].report))["secrets"]
        assert len(planted) == 2 and not planted & got, (tag, k)
    assert planted, "the schedule asks for no tag of this base"
    # the same layers with no CMD entry to end the base: all eight
    facts = next(f for f in registry["tags"] if f["base"] == base)
    flat = os.path.join(registry["work"], f"flat{base}.tar")
    without_cmd_boundary(facts["path"], flat)
    runner = new_runner(registry, memo=False)
    try:
        res = runner.submit_path(flat, options()).result(timeout=300)
    finally:
        runner.close()
    got = check.findings(check.render(res.report))["secrets"]
    assert got == planted | {tuple(s) for s in facts["secrets"]}


# (d) ---------------------------------------------------------------

SEG_RUNGS = (256, 512)
JOB_RUNGS = (64, 128, 256)


@pytest.fixture(scope="module")
def warmed(registry, tmp_path_factory):
    """A scanner and the table after ``warm_ladders`` under batch
    budgets that end the ladders at 512 segment rows and 256 jobs."""
    from trivy_tpu.runtime import aot
    from trivy_tpu.sched import SchedConfig
    from trivy_tpu.secret.batch import BatchSecretScanner
    scanner = BatchSecretScanner(backend="tpu")
    cfg = SchedConfig(max_batch_bytes=256 << 10, max_batch_jobs=256)
    summary = aot.warm_ladders(
        scanner, registry["cdb"], cfg,
        cache_dir=str(tmp_path_factory.mktemp("jit")))
    yield {"scanner": scanner, "summary": summary}
    aot.configure_compile_cache()       # back where it was


def fresh_compiles() -> int:
    from trivy_tpu.runtime.aot import COMPILE_CACHE_METRICS
    cc = COMPILE_CACHE_METRICS.snapshot()
    return cc["persistent_requests"] - cc["persistent_hits"]


def test_warm_ladders_walks_both_ladders_whole(warmed):
    kernels = {k["kernel"]: k["shapes"]
               for k in warmed["summary"]["kernels"]}
    assert kernels == {"dfa_fused": list(SEG_RUNGS),
                       "interval_resident": list(JOB_RUNGS)}


@pytest.mark.parametrize("rung", SEG_RUNGS)
def test_no_compile_at_a_warmed_sieve_rung(warmed, rung):
    from trivy_tpu.ops.keywords import _bucket
    from trivy_tpu.ops.program import compiled_programs
    scanner = warmed["scanner"]
    step = scanner.seg_len - scanner.overlap
    rows = rung - 3
    body = (b"const handler = make_router(7);  // router helper\n"
            * (rows * step // 50 + 1))[:scanner.seg_len
                                       + (rows - 1) * step]
    assert scanner._n_segs(len(body)) == rows
    assert _bucket(rows) == rung
    before, programs = fresh_compiles(), compiled_programs()
    scanner.scan_files([("/srv/app/router.js", body)])
    assert fresh_compiles() == before
    assert compiled_programs() == programs


@pytest.mark.parametrize("rung", JOB_RUNGS)
def test_no_compile_at_a_warmed_interval_rung(registry, warmed, rung):
    from trivy_tpu.artifact.cache import MemoryCache
    from trivy_tpu.detect.batch import (_job_bucket, collect_dispatch,
                                        dispatch_jobs_async)
    from trivy_tpu.ops.program import compiled_programs
    from trivy_tpu.scan.local import LocalScanner, ScanTarget
    from trivy_tpu.artifact.artifact import ImageArtifact
    from trivy_tpu.artifact.image import load_image
    cache = MemoryCache()
    ref = ImageArtifact(load_image(registry["tags"][0]["path"]),
                        cache).inspect()
    jobs = LocalScanner(cache, registry["cdb"]).prepare(
        ScanTarget(name=ref.name, artifact_id=ref.id,
                   blob_ids=ref.blob_ids), options()).jobs
    job = next(j for j in jobs if j.payload[0] == "os")
    n = rung - 5
    many = [dataclasses.replace(
        job, pkg_version=f"1.{i % 10}.{i // 10 % 10}-r{i // 100}")
        for i in range(n)]
    assert _job_bucket(n) == rung
    before, programs = fresh_compiles(), compiled_programs()
    stats: dict = {}
    collect_dispatch(dispatch_jobs_async(many, backend="tpu",
                                         stats=stats))
    assert stats["jobs_unique"] == n
    assert fresh_compiles() == before
    assert compiled_programs() == programs


# (e) ---------------------------------------------------------------

def implied(registry) -> dict:
    """What one request in flight at a time implies, by class."""
    table = registry["table"]
    want = {"layers_seen": 0, "layers_cached": 0, "layers_analyzed": 0,
            "base_layers_skipped": 0, "hits": 0, "lookups": 0,
            "no_device_work": 0}
    cached = {"base_cold": 0, "tag_new": 2, "tag_seen": 3}
    for tag, cls in zip(registry["plan"]["schedule"],
                        registry["plan"]["classes"]):
        facts = registry["tags"][tag]
        os_q = len(facts["os_pkgs"])
        lib_q = sum(1 for i, _ in facts["pip_pkgs"]
                    if i < table.ghsa_pkgs)
        want["layers_seen"] += 3
        want["layers_cached"] += cached[cls]
        want["layers_analyzed"] += 3 - cached[cls]
        want["base_layers_skipped"] += 2
        want["lookups"] += os_q + lib_q
        want["hits"] += {"base_cold": 0, "tag_new": os_q,
                         "tag_seen": os_q + lib_q}[cls]
        want["no_device_work"] += cls == "tag_seen"
    return want


@pytest.mark.parametrize("counter", [
    "layers_seen", "layers_cached", "layers_analyzed",
    "base_layers_skipped", "lookups", "hits", "no_device_work"])
def test_counters_read_what_the_schedule_implies(registry, sequential,
                                                 counter):
    got = dict(sequential["added"], **sequential["sched"])
    want = implied(registry)
    assert want["no_device_work"] > 0 and want["hits"] > 0
    assert got[counter] == want[counter]


def test_bytes_analyzed_and_hit_wait_are_booked(registry, sequential):
    from trivy_tpu.obs.trace import phase_rows
    assert sequential["added"]["bytes_analyzed"] > 0
    row = phase_rows("sched")["hit_wait"]
    assert row["n"] >= sequential["sched"]["no_device_work"]
    assert row["busy_s"] > 0.0
