"""The served path at test size on the CPU, as the benchmark's
``served-over-knee`` cell drives it: a server built by
``rpc.server.build_server`` (the function the ``server`` command
calls), the program's own client pushing builds over ``127.0.0.1``.
The builds and the plain reference are the benchmark's own
(``benchmark/gen_served.py``, ``benchmark/reference_served.py``,
imported by path).

(a) 40 builds on 3 bases from 8 threads at once: every answer equals
    the plain reference's and a ``--sched off`` server's;
(b) one build books ``n`` 1 in each ``rpc.*`` row it passes and the
    wire's counters read what was sent;
(c) a queue bound of 2 under 16 concurrent Scans sheds, ``shed_503``
    counts the 503s, and the client's retries bring every answer
    home;
(d) the first Scan after construction compiles nothing, with and
    without a compile cache directory, and ``health()`` says
    ``warming`` while the ladder warms;
(e) the ``server`` command builds its server through the same
    function, and closes the scheduler that function made.
"""

import json
import os
import sys
import threading
import time

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

SIZES = {"os_pkgs": 16, "pip_pkgs": 8, "os_universe": 60,
         "ghsa_pkgs": 80, "db_seed": 5, "base_layers": 2}
TRAFFIC = {"bases": 3, "base_zipf_s": 1.0, "rate_per_s": 100.0,
           "arrival_gamma_shape": 0.5}
BUILDS = 40
SEED = 2147483777
CHECKS = ["vuln"]


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """The compiled table and 40 builds, each analyzed once by the
    program's client side against a cache that keeps what it is
    given."""
    import gen
    import gen_served
    import reference_served
    from trivy_tpu.artifact import ImageArtifact, load_image
    from trivy_tpu.artifact.artifact import ArtifactOption
    from trivy_tpu.artifact.cache import MemoryCache
    from trivy_tpu.db import AdvisoryStore, CompiledDB
    store = AdvisoryStore()
    for bucket, pkg, vid, adv, detail in gen.advisory_rows(
            SIZES, SIZES["db_seed"]):
        store.put_advisory(bucket, pkg, vid, adv)
        if detail is not None:
            store.put_vulnerability(vid, detail)
    plan = gen_served.plan(TRAFFIC, BUILDS, SEED)
    work = str(tmp_path_factory.mktemp("builds"))
    table = gen.GhsaTable(SIZES["ghsa_pkgs"], SIZES["db_seed"])
    mine, bases, builds = MemoryCache(), {}, []
    for k in range(BUILDS):
        b = plan["base_of"][k]
        if b not in bases:
            bases[b] = gen_served.build_base(SIZES, b, SEED)
        facts = gen_served.build_top(
            SIZES, k, b, bases[b],
            os.path.join(work, f"build{k}.tar"), SEED)
        ref = ImageArtifact(
            load_image(facts["path"], name=f"build{k}"), mine,
            option=ArtifactOption(scan_secrets=False)).inspect()
        builds.append({
            "k": k, "first": plan["first"][k], "ref": ref,
            "want": reference_served.build_findings(
                table, facts, CHECKS)})
    assert sum(b["first"] for b in builds) == TRAFFIC["bases"]
    return {"cdb": CompiledDB.compile(store), "mine": mine,
            "builds": builds}


def serve_built(fleet, tmp_path, **kwargs):
    from trivy_tpu.rpc.server import build_server, serve
    kwargs.setdefault("cache_dir", str(tmp_path / "cache"))
    server = build_server(store=fleet["cdb"], **kwargs)
    httpd, _ = serve(port=0, server=server)
    return server, httpd, \
        f"http://127.0.0.1:{httpd.server_address[1]}"


def shut(server, httpd) -> None:
    httpd.shutdown()
    httpd.server_close()
    server.shutdown_gracefully(10.0)


def push(url: str, fleet, build, **client) -> dict:
    """One build through the four RPCs, in a ``--server`` client's
    order. Returns the findings of the answer in the reference's
    form, the answer as the wire gave it, and the clients' retry
    counts."""
    import check
    from trivy_tpu.rpc.client import RemoteCache, RemoteScanner
    from trivy_tpu.scan.local import ScanTarget
    from trivy_tpu.types import Metadata, Report, ScanOptions
    ref, mine = build["ref"], fleet["mine"]
    cache = RemoteCache(url, **client)
    scanner = RemoteScanner(url, **client)
    missing_artifact, missing = cache.missing_blobs(
        ref.id, ref.blob_ids)
    for b in ref.blob_ids:
        if b in missing:
            cache.put_blob(b, mine.blobs[b])
    if missing_artifact:
        cache.put_artifact(ref.id, mine.artifacts[ref.id])
    results, os_found = scanner.scan(
        ScanTarget(name=ref.name, artifact_id=ref.id,
                   blob_ids=ref.blob_ids),
        ScanOptions(backend="tpu", security_checks=list(CHECKS)))
    wire = json.dumps([r.to_dict() for r in results],
                      sort_keys=True)
    doc = check.render(Report(
        artifact_name=ref.name, artifact_type="container_image",
        metadata=Metadata(os=os_found), results=results))
    return {"got": check.findings(doc), "wire": wire,
            "missing": len(missing),
            "retries": cache.counters["retries"]
            + scanner.counters["retries"]}


def push_all(url: str, fleet, builds: list, threads: int,
             **client) -> list:
    out = [None] * len(builds)
    todo = list(enumerate(builds))
    lock = threading.Lock()

    def work():
        while True:
            with lock:
                if not todo:
                    return
                i, build = todo.pop(0)
            try:
                out[i] = push(url, fleet, build, **client)
            except Exception as e:      # noqa: BLE001
                out[i] = e

    pool = [threading.Thread(target=work) for _ in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join(300)
    return out


@pytest.fixture(scope="module")
def direct(fleet, tmp_path_factory):
    """Every build's answer from a ``--sched off`` server, one at a
    time."""
    server, httpd, url = serve_built(
        fleet, tmp_path_factory.mktemp("off"), sched="off")
    try:
        assert server.scheduler is None
        return [push(url, fleet, b) for b in fleet["builds"]]
    finally:
        shut(server, httpd)


@pytest.fixture(scope="module")
def served(fleet, tmp_path_factory):
    """The 40 builds from 8 threads at once against a scheduled
    server, and the scheduler's stats after them."""
    server, httpd, url = serve_built(
        fleet, tmp_path_factory.mktemp("on"), sched="on")
    try:
        out = push_all(url, fleet, fleet["builds"], threads=8)
        return out, server.scheduler.stats()
    finally:
        shut(server, httpd)


@pytest.mark.parametrize("k", range(0, BUILDS, 4))
def test_answers_equal_the_reference_and_sched_off(fleet, served,
                                                   direct, k):
    out, _ = served
    for got, want in zip(out[k:k + 4], fleet["builds"][k:k + 4]):
        assert not isinstance(got, Exception), got
        assert got["got"] == want["want"]
        assert want["want"]["vulns"], "a build with no finding"
        assert got["wire"] == direct[want["k"]]["wire"]


def test_forty_builds_rode_the_scheduler(fleet, served):
    _, stats = served
    c, rpc = stats["counters"], stats["rpc"]
    assert c["completed"] == BUILDS and c["failed"] == 0
    assert rpc["requests"]["Scan"] == BUILDS
    assert rpc["requests"]["MissingBlobs"] == BUILDS
    assert rpc["shed_503"] == 0 and rpc["retried"] == 0
    # every distinct layer was pushed at least once (bases of one
    # distribution share their release file's layer), and twice
    # where two threads met it together
    distinct = {b for build in fleet["builds"]
                for b in build["ref"].blob_ids}
    assert BUILDS + TRAFFIC["bases"] < len(distinct) \
        <= rpc["requests"]["PutBlob"]
    assert rpc["blobs_asked"] == 3 * BUILDS
    assert rpc["blobs_held"] == 3 * BUILDS - rpc["requests"]["PutBlob"]


def test_one_build_books_one_row_each(fleet, tmp_path):
    """A build on a base the server holds: the four RPCs book one
    run each in their own rows and four in ``decode`` and
    ``encode``."""
    server, httpd, url = serve_built(fleet, tmp_path)
    try:
        builds = fleet["builds"]
        first = next(b for b in builds if b["first"])
        later = next(b for b in builds if not b["first"]
                     and b["ref"].blob_ids[0]
                     == first["ref"].blob_ids[0])
        assert push(url, fleet, first)["missing"] == 3
        before = server.scheduler.stats()["rpc"]
        answer = push(url, fleet, later)
        after = server.scheduler.stats()["rpc"]
        assert answer["missing"] == 1 and answer["retries"] == 0
        runs = {ph: after["phase"][ph]["n"] - before["phase"][ph]["n"]
                for ph in after["phase"]}
        assert runs == {"decode": 4, "missing_blobs": 1,
                        "put_blob": 1, "put_artifact": 1,
                        "scan_wait": 1, "encode": 4}
        for ph, row in after["phase"].items():
            assert row["busy_s"] > before["phase"][ph]["busy_s"]
        calls = {m: after["requests"][m] - before["requests"][m]
                 for m in after["requests"]}
        assert calls == {"MissingBlobs": 1, "PutBlob": 1,
                         "PutArtifact": 1, "DeleteBlobs": 0,
                         "Scan": 1}
        assert after["blobs_asked"] - before["blobs_asked"] == 3
        assert after["blobs_held"] - before["blobs_held"] == 2
        # the top layer's 8 pins went in, the report came out
        assert after["bytes_in"] - before["bytes_in"] > 500
        assert after["bytes_out"] - before["bytes_out"] \
            > len(answer["wire"])
        # GET /metrics carries the same book
        assert server.metrics()["rpc"]["requests"]["Scan"] == 2
        from trivy_tpu.obs.prom import render_prometheus
        text = render_prometheus(server.metrics())
        assert 'trivy_tpu_rpc_requests_total{method="Scan"} 2' \
            in text
        # (the phase table is the process's, not this server's)
        assert 'trivy_tpu_phase_runs_total{pipeline="rpc",' \
            'phase="scan_wait"} ' in text
    finally:
        shut(server, httpd)


def test_sched_off_server_reports_the_book_too(fleet, tmp_path):
    server, httpd, url = serve_built(fleet, tmp_path, sched="off")
    try:
        push(url, fleet, fleet["builds"][0])
        rpc = server.metrics()["rpc"]
        assert rpc["requests"]["Scan"] == 1
        assert rpc["bytes_out"] > 0
    finally:
        shut(server, httpd)


def test_full_queue_sheds_and_retries_bring_every_answer_home(
        fleet, tmp_path, monkeypatch):
    """16 Scans at once against a bound of 2, with a join slow
    enough that the queue cannot drain between arrivals."""
    from trivy_tpu.scan.local import LocalScanner
    from trivy_tpu.sched import SchedConfig
    join = LocalScanner.join

    def slow_join(self, target, options):
        time.sleep(0.05)
        return join(self, target, options)

    monkeypatch.setattr(LocalScanner, "join", slow_join)
    server, httpd, url = serve_built(
        fleet, tmp_path, sched=SchedConfig(max_queue=2))
    try:
        builds = fleet["builds"][:16]
        out = push_all(url, fleet, builds, threads=16,
                       max_retries=200, backoff_base_s=0.01,
                       backoff_max_s=0.05)
        for got, want in zip(out, builds):
            assert not isinstance(got, Exception), got
            assert got["got"] == want["want"]
        stats = server.scheduler.stats()
        rpc = stats["rpc"]
        assert rpc["shed_503"] > 0
        # only a shed Scan was answered anything but 200, so every
        # retry a client made was one 503's, and came again under
        # its first attempt's idempotency key
        assert rpc["shed_503"] == sum(g["retries"] for g in out)
        assert rpc["retried"] == rpc["shed_503"]
        assert rpc["requests"]["Scan"] == 16 + rpc["shed_503"]
        assert stats["counters"]["rejected"] == rpc["shed_503"]
        assert stats["counters"]["completed"] == 16
        assert stats["counters"]["failed"] == 0
    finally:
        shut(server, httpd)


@pytest.mark.parametrize("compile_cache", [False, True])
def test_first_scan_compiles_nothing(fleet, tmp_path,
                                     compile_cache):
    """``server`` with and without ``--compile-cache``: the ladder
    is warm before the first Scan either way."""
    from trivy_tpu.ops.program import compiled_programs
    from trivy_tpu.runtime.aot import COMPILE_CACHE_METRICS
    server, httpd, url = serve_built(
        fleet, tmp_path, compile_cache_dir=str(
            tmp_path / "jit") if compile_cache else "")
    try:
        assert server.health()["status"] == "ok"
        assert server.compile_cache["kernels"][0]["shapes"]
        programs = set(compiled_programs())
        before = COMPILE_CACHE_METRICS.snapshot()
        answer = push(url, fleet, fleet["builds"][0])
        after = COMPILE_CACHE_METRICS.snapshot()
        assert answer["got"] == fleet["builds"][0]["want"]
        assert server.scheduler.stats()["batch"]["interval_jobs"] > 0
        assert after["persistent_requests"] \
            == before["persistent_requests"]
        assert set(compiled_programs()) == programs
    finally:
        shut(server, httpd)


def test_health_says_warming_while_the_ladder_warms(fleet,
                                                    monkeypatch):
    from trivy_tpu.rpc.server import ScanServer
    from trivy_tpu.runtime import aot
    server = ScanServer(store=fleet["cdb"], sched="on")
    seen = []

    def warm(**kwargs):
        seen.append((server.health()["status"], kwargs["store"]))
        return {"kernels": []}

    monkeypatch.setattr(aot, "warm_ladders", warm)
    try:
        assert server.health()["status"] == "ok"
        server.warm_ladder()
        assert seen == [("warming", server.store)]
        assert server.health()["status"] == "ok"
    finally:
        server.close()


def test_build_server_serves_over_a_scheduler_it_is_handed(fleet):
    """As ``watch --listen`` hands one: served over, warmed, and
    left to its owner to close."""
    from trivy_tpu.rpc.server import build_server
    from trivy_tpu.sched import ScanScheduler
    with ScanScheduler(backend="tpu") as sched:
        server = build_server(store=fleet["cdb"], sched=sched,
                              memo=False)
        assert server.scheduler is sched
        assert server.memo is None
        assert sched.stats()["rpc"]["requests"]["Scan"] == 0
        assert server.compile_cache["kernels"]
        server.close()
        assert sched.stats()["draining"] is False


def test_impact_index_needs_the_memo(fleet):
    from trivy_tpu.rpc.server import build_server
    with pytest.raises(ValueError, match="findings memo"):
        build_server(store=fleet["cdb"], sched="off", memo=False,
                     impact_index=True)


@pytest.mark.parametrize("argv, want", [
    ([], {"sched": "on", "memo": True, "impact_index": False,
          "compile_cache_dir": ""}),
    (["--sched", "off", "--no-memo", "--compile-cache", "/x/jit",
      "--memo-cache", "memory", "--replica-name", "r7"],
     {"sched": "off", "memo": False, "compile_cache_dir": "/x/jit",
      "memo_uri": "memory", "replica_name": "r7"}),
    (["--sched-queue", "7", "--sched-workers", "2",
      "--sched-flush-ms", "20", "--sched-deadline", "30s",
      "--token", "t", "--impact-index"],
     {"token": "t", "impact_index": True}),
])
def test_the_command_builds_through_the_same_function(
        tmp_path, monkeypatch, argv, want):
    from trivy_tpu import cli
    from trivy_tpu.rpc import server as rpc_server
    built, served_ = [], []

    class Stub:
        store = cache = memo = scheduler = None

        def close(self):
            built.append("closed")

    def build(**kwargs):
        built.append(kwargs)
        return Stub()

    monkeypatch.setattr(rpc_server, "build_server", build)
    monkeypatch.setattr(
        rpc_server, "serve_forever",
        lambda *a, **kw: served_.append((a, kw)) or 0)
    monkeypatch.setattr(cli, "_admission_controller",
                        lambda args, server: (None, None))
    rc = cli.main(["server", "--listen", "127.0.0.1:0",
                   "--cache-dir", str(tmp_path)] + argv)
    assert rc == 0 and len(served_) == 1
    kwargs = built[0]
    sched = kwargs.pop("sched")
    if want.get("sched", "on") == "off":
        assert sched == "off"
    else:
        want.pop("sched", None)
        assert sched.max_queue == (7 if "--sched-queue" in argv
                                   else 256)
        assert sched.workers == (2 if "--sched-workers" in argv
                                 else 4)
        assert sched.flush_timeout_s == pytest.approx(
            0.02 if "--sched-flush-ms" in argv else 0.05)
        assert sched.default_deadline_s == (
            30.0 if "--sched-deadline" in argv else 0.0)
    want.pop("sched", None)
    assert kwargs["cache_dir"] == str(tmp_path)
    for key, value in want.items():
        assert kwargs[key] == value, key


@pytest.mark.parametrize("flag", ["--impact-index",
                                  "--prewarm-members"])
def test_the_command_refuses_what_needs_the_memo(tmp_path, capsys,
                                                 flag):
    from trivy_tpu import cli
    argv = [flag] + (["a,b"] if flag == "--prewarm-members" else [])
    rc = cli.main(["server", "--listen", "127.0.0.1:0", "--no-memo",
                   "--cache-dir", str(tmp_path)] + argv)
    assert rc == 2
    assert f"{flag} needs the findings memo" in capsys.readouterr().err


def test_two_writers_of_one_entry_do_not_share_a_temp_file(
        tmp_path):
    """Two clients told that a base layer is missing push it at
    once, and two Scans that miss one layer store its verdict at
    once: each writes a file of its own and renames it."""
    from trivy_tpu.artifact.cache import FSCache
    from trivy_tpu.memo.store import FSMemoStore
    cache, memo = FSCache(str(tmp_path)), FSMemoStore(str(tmp_path))
    blob = {"schema_version": 2, "pad": "x" * 200_000}
    errors = []

    def write(i):
        try:
            for _ in range(20):
                cache.put_blob("sha256:one", blob)
                memo.put("abc123", json.dumps(blob).encode())
        except Exception as e:          # noqa: BLE001
            errors.append(e)

    pool = [threading.Thread(target=write, args=(i,))
            for i in range(8)]
    for t in pool:
        t.start()
    for t in pool:
        t.join(60)
    assert errors == []
    assert json.loads(memo.get("abc123")) == blob
    assert not [n for n in os.listdir(cache.dir + "/blob")
                + os.listdir(memo.dir) if n.endswith(".tmp")]
