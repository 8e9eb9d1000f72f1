"""``trivy-tpu k8s`` on a mesh: the command sweeps a cluster's images
through the fleet runner (``k8s.build_runner``: the scheduler, and a
mesh over every visible device where there is more than one), on the
conftest's virtual CPU devices.

(a) a tiny cluster (3 namespaces, 10 workloads, 17 image refs, 6
    images of ``benchmark/gen.py``) reports the same through the
    command on every visible device, on a 4-device mesh, on one device
    and on the ``cpu-ref`` engine, byte for byte;
(b) the command builds a mesh only where more than one device is
    visible;
(c) after ``warm_ladders`` a sweep on the mesh compiles no new
    program;
(d) ``K8sScanner`` closes no runner it was handed, and closes the
    one it builds;
(e) the sweep's counters and spans, and the sieve's shard counters;
(f) the summary adds up every image of a workload.
"""

import contextlib
import io
import json
import os

import jax
import pytest

from trivy_tpu.k8s import K8sScanner, ManifestClient, build_runner
from trivy_tpu.k8s.report import render_summary, write_k8s_report

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEED = 2147483777
SIZES = {"files": 24, "os_pkgs": 16, "pip_pkgs": 8,
         "os_universe": 60, "ghsa_pkgs": 80}
CLUSTER = {"namespaces": 3, "kinds": [["Deployment", 2], ["CronJob", 1]],
           "daemonsets": ["node-exporter"], "app_images": 3,
           "sidecar_every": 2, "init_every": 4, "init_images": 1,
           "zipf_s": 1.0}
CHECKS = ["vuln", "secret"]


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    """The cluster's manifests and images, and the advisory table
    compiled and saved (``--compiled-db``)."""
    import sys
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    import gen
    import gen_k8s
    from trivy_tpu.db import AdvisoryStore, CompiledDB
    work = str(tmp_path_factory.mktemp("k8s"))
    with open(os.path.join(BENCH, "configs",
                           "k8s-cluster-4chip.json")) as f:
        sizes = dict(json.load(f)["sizes"], **SIZES)
    data = gen_k8s.make(sizes, CLUSTER, 1, work, SEED)
    store = AdvisoryStore()
    for bucket, pkg, vid, adv, detail in gen.advisory_rows(
            sizes, sizes["db_seed"]):
        store.put_advisory(bucket, pkg, vid, adv)
        if detail is not None:
            store.put_vulnerability(vid, detail)
    cdb = CompiledDB.compile(store)
    cdb.save(os.path.join(work, "cdb"))
    data.update(cdb=cdb, db=os.path.join(work, "cdb"))
    return data


def sweep(cluster, runner, backend="tpu") -> tuple:
    """The cluster's json report and summary through ``runner``."""
    scanner = K8sScanner(store=cluster["cdb"], backend=backend,
                         images_dir=cluster["images"],
                         security_checks=CHECKS, runner=runner)
    report = scanner.scan(ManifestClient(cluster["cluster"]))
    buf = io.StringIO()
    write_k8s_report(report, fmt="json", output=buf)
    return buf.getvalue(), render_summary(report)


def command(cluster, tmp_path, *extra) -> str:
    from trivy_tpu.cli import main
    out = tmp_path / "report.json"
    with contextlib.redirect_stdout(io.StringIO()):
        code = main(["k8s", cluster["cluster"], "--images-dir",
                     cluster["images"], "--compiled-db", cluster["db"],
                     "--format", "json", "--output", str(out),
                     "--cache-dir", str(tmp_path / "cache"), *extra])
    assert code == 0
    return out.read_text()


@pytest.fixture
def built(monkeypatch):
    """The meshes of the runners ``build_runner`` makes."""
    import trivy_tpu.k8s as k8s
    seen = []

    def spy(*args, **kwargs):
        runner = build_runner(*args, **kwargs)
        seen.append(runner.mesh)
        return runner

    monkeypatch.setattr(k8s, "build_runner", spy)
    return seen


# (a) ---------------------------------------------------------------

def test_a_mesh_reports_what_one_device_and_cpu_ref_report(cluster):
    from trivy_tpu.parallel.mesh import make_mesh
    got = {}
    for label, kwargs in (("mesh", {"mesh": make_mesh(4)}),
                          ("one", {"mesh": None}),
                          ("cpu-ref", {"backend": "cpu-ref"})):
        runner = build_runner(cluster["cdb"], **kwargs)
        try:
            got[label] = sweep(cluster, runner,
                               kwargs.get("backend", "tpu"))
        finally:
            runner.close()
    assert got["mesh"] == got["one"] == got["cpu-ref"]
    doc = json.loads(got["mesh"][0])
    entries = doc["Vulnerabilities"]
    assert len(entries) == 17 and not any("Error" in e
                                          for e in entries)
    assert sum(len(r.get("Secrets") or []) for e in entries
               for r in e.get("Results") or []) == 17 * 6


def test_the_command_sweeps_on_every_visible_device(cluster, tmp_path,
                                                    built):
    on_mesh = command(cluster, tmp_path)
    assert len(built) == 1 and built[0] is not None
    assert built[0].devices.size == len(jax.devices()) == 8
    assert on_mesh == command(cluster, tmp_path, "--backend",
                              "cpu-ref")
    assert built[1] is None
    assert on_mesh == command(cluster, tmp_path, "--sched", "off")


# (b) ---------------------------------------------------------------

@pytest.mark.parametrize("visible,shape", [
    (1, None), (2, {"data": 2, "rules": 1}),
    (4, {"data": 2, "rules": 2}), (8, {"data": 4, "rules": 2})])
def test_a_mesh_only_where_more_than_one_device_is_visible(
        monkeypatch, visible, shape):
    from trivy_tpu.runtime.device import visible_mesh
    devices = jax.devices()[:visible]
    monkeypatch.setattr(jax, "devices", lambda *a: devices)
    mesh = visible_mesh("tpu")
    assert (None if mesh is None else dict(mesh.shape)) == shape
    assert visible_mesh("cpu-ref") is None


def test_the_command_on_one_device_builds_no_mesh(cluster, tmp_path,
                                                  built, monkeypatch):
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    report = command(cluster, tmp_path)
    assert built == [None]
    monkeypatch.undo()
    assert report == command(cluster, tmp_path, "--backend",
                             "cpu-ref")


# (c) ---------------------------------------------------------------

def test_a_warmed_mesh_sweep_compiles_no_new_program(cluster):
    from trivy_tpu.ops.program import compiled_programs
    from trivy_tpu.parallel.mesh import make_mesh
    from trivy_tpu.runtime.aot import warm_ladders
    from trivy_tpu.sched import SchedConfig
    mesh = make_mesh(4)
    runner = build_runner(cluster["cdb"], mesh=mesh)
    try:
        summary = warm_ladders(runner.secret_scanner, cluster["cdb"],
                               SchedConfig(), mesh=mesh)
        kernels = {k["kernel"]: k["shapes"]
                   for k in summary["kernels"]}
        assert kernels["dfa_mesh"] == [256, 512, 1024, 2048, 4096,
                                       8192]
        assert kernels["interval_resident_mesh"] == [
            64, 128, 256, 512, 1024, 2048, 4096]
        warmed = set(compiled_programs())
        assert any(p.startswith("dfa_mesh_sieve(") for p in warmed)
        sweep(cluster, runner)
        assert set(compiled_programs()) == warmed
    finally:
        runner.close()


def test_the_mesh_programs_carry_their_names_into_a_trace():
    import numpy as np

    from trivy_tpu.parallel.interval_shard import _build_resident_hits
    from trivy_tpu.parallel.mesh import make_mesh
    from trivy_tpu.secret.batch import BatchSecretScanner
    mesh = make_mesh(4)
    sc = BatchSecretScanner(mesh=mesh)
    sieve = sc.table.mesh_sieve(mesh, tuple(sc.plan.run_specs), "cpu")
    text = sieve.lower(np.zeros((128, sc.seg_len), np.uint8),
                       *sc.table._resident_arrays()).as_text()
    assert "module @jit_sieve_mesh " in text
    rows = np.zeros(8, np.int32)
    table = np.zeros((4, 4), np.int32)
    text = _build_resident_hits(mesh).lower(
        rows, rows, table, table, table, table,
        np.zeros(4, np.int32)).as_text()
    assert "module @jit_interval_hits_resident_mesh " in text


# (d) ---------------------------------------------------------------

def test_the_scanner_closes_no_runner_it_was_handed(cluster,
                                                    monkeypatch):
    from trivy_tpu.runtime import BatchScanRunner
    closed = []
    real = BatchScanRunner.close
    monkeypatch.setattr(BatchScanRunner, "close",
                        lambda self: closed.append(self) or real(self))
    runner = build_runner(cluster["cdb"], mesh=None)
    try:
        sweep(cluster, runner)
        assert closed == []
        # a second sweep rides the same scheduler
        sched = runner.scheduler
        sweep(cluster, runner)
        assert runner.scheduler is sched and closed == []
    finally:
        runner.close()
    # without a runner a sweep builds one and closes it
    closed.clear()
    report = sweep(cluster, None)
    assert len(closed) == 1 and closed[0].sched == "on"
    assert closed[0].mesh is None
    assert json.loads(report[0])["Vulnerabilities"]


# (e) ---------------------------------------------------------------

def test_a_sweep_books_its_counters_spans_and_shards(cluster):
    from trivy_tpu.artifact.cache import MemoryCache
    from trivy_tpu.k8s.metrics import K8S_METRICS
    from trivy_tpu.parallel.mesh import make_mesh
    from trivy_tpu.secret.metrics import SECRET_METRICS
    runner = build_runner(cluster["cdb"], mesh=make_mesh(4))
    try:
        sweep(cluster, runner)
        before = runner.scheduler.stats()["k8s"]
        sec0 = SECRET_METRICS.snapshot()
        runner.cache = MemoryCache()      # every layer sieved again
        sweep(cluster, runner)
        after = runner.scheduler.stats()["k8s"]
        sec1 = SECRET_METRICS.snapshot()
    finally:
        runner.close()
    assert K8S_METRICS.snapshot()["sweeps"] >= 2
    got = {k: after[k] - before[k] for k in
           ("sweeps", "workloads", "image_refs", "images_distinct",
            "images_unresolved")}
    assert got == {"sweeps": 1, "workloads": 10, "image_refs": 17,
                   "images_distinct": 6, "images_unresolved": 0}
    for phase in ("enumerate", "report"):
        assert after["phase"][phase]["n"] > before["phase"][phase]["n"]
        assert after["phase"][phase]["busy_s"] > \
            before["phase"][phase]["busy_s"]
    shards = sec1["sieve_shards"] - sec0["sieve_shards"]
    rows = sec1["sieve_shard_rows"] - sec0["sieve_shard_rows"]
    fullest = sec1["sieve_shard_rows_max"] - sec0["sieve_shard_rows_max"]
    assert shards >= 4 and rows > 0
    assert rows / shards <= fullest <= rows


# (f) ---------------------------------------------------------------

def test_the_summary_adds_up_every_image_of_a_workload():
    from trivy_tpu.k8s import K8sReport, Resource
    from trivy_tpu.types import DetectedVulnerability, Result
    from trivy_tpu.types.report import Vulnerability

    def res(*sevs):
        return [Result(target="t", vulnerabilities=[
            DetectedVulnerability(
                vulnerability_id=f"CVE-{i}", pkg_name="p",
                vulnerability=Vulnerability(severity=s))
            for i, s in enumerate(sevs)])]

    report = K8sReport(cluster_name="c", vulnerabilities=[
        Resource(namespace="ns", kind="Deployment", name="web",
                 results=res("HIGH", "LOW")),
        Resource(namespace="ns", kind="Deployment", name="web",
                 results=res("HIGH", "CRITICAL", "UNKNOWN")),
        Resource(namespace="ns", kind="Job", name="once",
                 results=res("MEDIUM"))])
    rows = {line.split("|")[2].strip(): line.split("|")[3].strip()
            for line in render_summary(report).splitlines()
            if line.startswith("| ns")}
    assert rows == {"Deployment/web": "1/2/0/1/1",
                    "Job/once": "0/0/1/0/0"}
