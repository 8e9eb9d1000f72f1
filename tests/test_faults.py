"""Fault-injection suite (docs/robustness.md; `pytest -m faults`).

Exercises the failure-domain hardening under deterministic injected
faults: circuit-broken cache fallback, poison-image quarantine with
batch bisection, degraded-mode reports, idempotent RPC retries after
lost responses, deadline expiry while executing on device, and
graceful drain — asserting throughout that healthy targets produce
byte-identical results and no request is ever silently dropped.
"""

import json
import threading
import time

import pytest

from tests.test_sched import _norm, make_fleet, make_store
from trivy_tpu.faults import (CacheFault, DeviceFault, FaultInjector,
                              FaultyCache, parse_fault_spec)
from trivy_tpu.sched import (AnalyzedWork, DeadlineExceeded,
                             QueueFullError, ScanRequest,
                             ScanScheduler, SchedConfig)

pytestmark = pytest.mark.faults


# ---------------------------------------------------------------
# spec parsing
# ---------------------------------------------------------------

class TestSpec:
    def test_scenarios_and_overrides(self):
        s = parse_fault_spec("cache-outage")
        assert s.cache_fail_ops == 40 and s.wants_cache_faults()
        s = parse_fault_spec("poison-image:poison=a.tar;b.tar,seed=9")
        assert s.poison == ("a.tar", "b.tar") and s.seed == 9
        s = parse_fault_spec("device_fail_batches=3")
        assert s.device_fail_batches == 3 and s.scenario == ""

    def test_bad_specs_fail_up_front(self):
        with pytest.raises(ValueError):
            parse_fault_spec("no-such-scenario")
        with pytest.raises(ValueError):
            parse_fault_spec("cache-outage:bogus_key=1")
        with pytest.raises(ValueError):
            parse_fault_spec("seed=notanint")

    def test_determinism(self):
        a = FaultInjector(parse_fault_spec("cache-flaky:seed=5"))
        b = FaultInjector(parse_fault_spec("cache-flaky:seed=5"))

        def draws(inj):
            out = []
            for _ in range(50):
                try:
                    inj.on_cache_op("get_blob", "k")
                    out.append(0)
                except CacheFault:
                    out.append(1)
            return out

        assert draws(a) == draws(b)


# ---------------------------------------------------------------
# circuit breaker + resilient cache
# ---------------------------------------------------------------

class TestCircuitBreaker:
    def test_trip_halfopen_recover(self):
        from trivy_tpu.artifact.resilient import (CLOSED, HALF_OPEN,
                                                  OPEN,
                                                  CircuitBreaker)
        clock = [0.0]
        br = CircuitBreaker(fail_threshold=2, cooldown_s=5.0,
                            clock=lambda: clock[0])
        assert br.allow() and br.state == CLOSED
        br.record_failure()
        assert br.state == CLOSED          # below threshold
        br.record_failure()
        assert br.state == OPEN
        assert not br.allow()              # cooldown not elapsed
        clock[0] = 6.0
        assert br.allow()                  # the half-open probe
        assert br.state == HALF_OPEN
        assert not br.allow()              # only ONE probe at a time
        br.record_failure()                # probe failed: re-open
        assert br.state == OPEN
        clock[0] = 12.0
        assert br.allow()
        br.record_success()
        assert br.state == CLOSED
        st = br.stats()
        assert st["trips"] == 1
        assert st["recoveries"][0]["recovered_s"] > 0

    def test_resilient_cache_degrades_and_recovers(self, make_faults):
        from trivy_tpu.artifact.cache import MemoryCache
        from trivy_tpu.artifact.resilient import (CircuitBreaker,
                                                  ResilientCache)
        from trivy_tpu.types import BlobInfo

        primary = MemoryCache()
        inj = make_faults("cache_fail_ops=10")
        cache = ResilientCache(
            FaultyCache(primary, inj),
            breaker=CircuitBreaker(fail_threshold=2,
                                   cooldown_s=0.05))
        blob = BlobInfo(schema_version=2)
        # outage window: every op answers from the fallback, nothing
        # raises, writes stay readable
        for i in range(6):
            cache.put_blob(f"sha256:b{i}", blob)
            assert cache.get_blob(f"sha256:b{i}") is not None
        missing_artifact, missing = cache.missing_blobs(
            "sha256:a", ["sha256:b0", "sha256:zz"])
        assert missing == ["sha256:zz"]
        st = cache.breaker_stats()
        assert st["breaker"]["state"] == "open"
        assert st["fallback_ops"] > 0
        # outage ends (fail_ops exhausted) + cooldown passes → the
        # half-open probe closes the circuit again
        time.sleep(0.06)
        for _ in range(20):
            cache.put_blob("sha256:probe", blob)
            if cache.breaker_stats()["breaker"]["state"] == "closed":
                break
            time.sleep(0.06)
        st = cache.breaker_stats()
        assert st["breaker"]["state"] == "closed"
        assert st["breaker"]["recoveries"]
        # post-recovery writes reach the primary again
        assert primary.get_blob("sha256:probe") is not None
        # read-your-writes across the recovery boundary: a blob the
        # primary never received (written during the outage) still
        # resolves through the fallback, and the recovered primary's
        # missing_blobs must not force its re-analysis
        assert primary.get_blob("sha256:b0") is None
        assert cache.get_blob("sha256:b0") is not None
        _, missing = cache.missing_blobs("sha256:a", ["sha256:b0"])
        assert missing == []


    def test_read_through_mirror_is_bounded_writes_pinned(self):
        from trivy_tpu.artifact.cache import MemoryCache
        from trivy_tpu.artifact.resilient import ResilientCache
        from trivy_tpu.types import BlobInfo
        primary = MemoryCache()
        blob = BlobInfo(schema_version=2)
        for i in range(10):
            primary.put_blob(f"sha256:r{i}", blob)
        cache = ResilientCache(primary, mirror_cap=4)
        cache.put_blob("sha256:mine", blob)      # pinned local write
        for i in range(10):
            cache.get_blob(f"sha256:r{i}")       # mirrored reads
        # the mirror evicted down to the cap; the local write stayed
        assert len(cache.fallback.blobs) <= 4 + 1
        assert cache.fallback.get_blob("sha256:mine") is not None

    def test_integrity_errors_pass_through_the_breaker(self):
        """Cache INCONSISTENCY (S3IntegrityError) is not an outage:
        it must surface loudly, never trip the circuit."""
        from trivy_tpu.artifact.resilient import ResilientCache
        from trivy_tpu.artifact.s3_cache import S3IntegrityError

        class Inconsistent:
            def get_blob(self, blob_id):
                raise S3IntegrityError("index without body")

        cache = ResilientCache(Inconsistent())
        for _ in range(5):
            with pytest.raises(S3IntegrityError):
                cache.get_blob("sha256:x")
        assert cache.breaker_stats()["breaker"]["state"] == "closed"


def test_metrics_endpoint_honors_token():
    import urllib.error
    import urllib.request
    from trivy_tpu.rpc.server import ScanServer, serve
    srv = ScanServer(token="sekrit")
    httpd, _ = serve(port=0, server=srv)
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        assert urllib.request.urlopen(
            url + "/healthz", timeout=5).status == 200
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(url + "/metrics", timeout=5)
        assert e.value.code == 401
        req = urllib.request.Request(
            url + "/metrics", headers={"Trivy-Token": "sekrit"})
        assert urllib.request.urlopen(req, timeout=5).status == 200
    finally:
        httpd.shutdown()


# ---------------------------------------------------------------
# fleet scans under injected faults (the acceptance scenarios)
# ---------------------------------------------------------------

def _run_fleet(tmp_path, paths, injector=None, cache=None,
               options=None):
    from trivy_tpu.runtime import BatchScanRunner
    runner = BatchScanRunner(
        store=make_store(), backend="cpu", cache=cache,
        sched=SchedConfig(flush_timeout_s=0.01, workers=4),
        fault_injector=injector)
    try:
        results = runner.scan_paths(paths, options)
        sched_stats = runner.scheduler.metrics.snapshot()
    finally:
        runner.close()
    return results, sched_stats


class TestFleetUnderFaults:
    def test_cache_outage_costs_throughput_not_availability(
            self, tmp_path, make_faults):
        paths = make_fleet(tmp_path, 6, shared_secret=True)
        baseline, _ = _run_fleet(tmp_path, paths)

        inj = make_faults("cache-outage:cache_fail_ops=30")
        faulted, _ = _run_fleet(tmp_path, paths,
                                cache=inj.wrap_cache(
                                    __import__("trivy_tpu.artifact.cache",
                                               fromlist=["MemoryCache"])
                                    .MemoryCache()))
        # every target completes ok and byte-identical — the outage
        # cost re-analysis time only
        assert _norm(faulted) == _norm(baseline)
        assert all(r.status == "ok" for r in faulted)
        assert inj.counters["cache_faults"] > 0

    def test_poison_image_quarantined_rest_identical(
            self, tmp_path, make_faults):
        paths = make_fleet(tmp_path, 8, shared_secret=False)
        baseline, _ = _run_fleet(tmp_path, paths)

        inj = make_faults("poison-image:poison=img3.tar")
        faulted, stats = _run_fleet(tmp_path, paths, injector=inj)

        assert len(faulted) == 8
        by_name = {r.name: r for r in faulted}
        poisoned = [r for r in faulted if "img3.tar" in r.name]
        assert len(poisoned) == 1 and poisoned[0].status == "degraded"
        assert poisoned[0].error == ""
        kinds = [c.kind for c in poisoned[0].causes]
        assert "quarantined" in kinds
        assert poisoned[0].report.status == "degraded"
        # healthy targets: status ok and BYTE-IDENTICAL to fault-free
        healthy_f = [r for r in faulted if "img3.tar" not in r.name]
        healthy_b = [r for r in baseline if "img3.tar" not in r.name]
        assert all(r.status == "ok" for r in healthy_f)
        assert _norm(healthy_f) == _norm(healthy_b)
        # the quarantined slot's FINDINGS are also correct (the host
        # fallback is the exact engine) — only the status differs
        base_poisoned = [r for r in baseline if "img3.tar" in r.name]
        assert json.dumps(_strip_status(
            poisoned[0].report.to_dict()), sort_keys=True) == \
            json.dumps(base_poisoned[0].report.to_dict(),
                       sort_keys=True)
        c = stats["counters"]
        assert c.get("quarantined", 0) >= 1
        assert c.get("host_fallbacks", 0) >= 1

    def test_transient_device_error_heals_invisibly(
            self, tmp_path, make_faults):
        paths = make_fleet(tmp_path, 6, shared_secret=False)
        baseline, _ = _run_fleet(tmp_path, paths)
        inj = make_faults("device-transient:device_fail_batches=1")
        faulted, _ = _run_fleet(tmp_path, paths, injector=inj)
        assert _norm(faulted) == _norm(baseline)
        assert all(r.status == "ok" for r in faulted)
        assert inj.counters["device_faults"] >= 1

    def test_corrupt_layer_fails_its_slot_only(self, tmp_path,
                                               make_faults):
        paths = make_fleet(tmp_path, 5, shared_secret=False)
        baseline, _ = _run_fleet(tmp_path, paths)
        inj = make_faults("corrupt-layer:corrupt=img2.tar")
        faulted, _ = _run_fleet(tmp_path, paths, injector=inj)
        bad = [r for r in faulted if "img2.tar" in r.name]
        assert len(bad) == 1 and bad[0].status == "failed"
        assert "corrupt" in bad[0].error
        assert bad[0].causes and bad[0].causes[0].kind == \
            "load_failed"
        good_f = [r for r in faulted if "img2.tar" not in r.name]
        good_b = [r for r in baseline if "img2.tar" not in r.name]
        assert _norm(good_f) == _norm(good_b)


def _strip_status(d):
    d = dict(d)
    d.pop("Status", None)
    d.pop("FailureCauses", None)
    return d


# ---------------------------------------------------------------
# scheduler-level: in-flight deadline expiry + the race accounting
# satellite (every submit ends in exactly one typed outcome)
# ---------------------------------------------------------------

class TestSchedulerFaults:
    def test_deadline_fires_while_executing_on_device(
            self, make_faults):
        inj = make_faults("device_stall_s=0.3")
        sched = ScanScheduler(config=SchedConfig(
            workers=1, flush_timeout_s=0.01))
        sched.fault_injector = inj
        try:
            # a candidate, so that the request rides a batch (one
            # with nothing for the device never meets it)
            req = sched.submit(ScanRequest(
                "inflight", lambda r: AnalyzedWork(
                    candidates=[("/f", b"x")],
                    finish=lambda f, d: "late"),
                deadline_s=0.1))
            with pytest.raises(DeadlineExceeded):
                req.result()
            # the executor notices post-collect and abandons it
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                c = sched.metrics.snapshot()["counters"]
                if c.get("expired_inflight", 0) >= 1:
                    break
                time.sleep(0.05)
            c = sched.metrics.snapshot()["counters"]
            assert c.get("expired_inflight", 0) >= 1
            assert c["timed_out"] >= 1
        finally:
            sched.close()

    def test_concurrent_queue_full_deadline_device_failure_race(
            self, make_faults):
        """N concurrent submits racing a full admission queue plus
        injected device failures: every request must end in EXACTLY
        one of ok / degraded / 503 (QueueFullError) / 408
        (DeadlineExceeded) — nothing hangs, nothing double-resolves,
        nothing disappears."""
        inj = make_faults("device_fail_rate=0.5,seed=11")
        sched = ScanScheduler(config=SchedConfig(
            max_queue=4, workers=2, flush_timeout_s=0.005))
        sched.fault_injector = inj
        n = 32
        outcomes: dict = {}

        def one(i):
            def analyze(req):
                time.sleep(0.002)
                return AnalyzedWork(
                    finish=lambda f, d: f"r{i}")
            try:
                req = sched.submit(ScanRequest(
                    f"r{i}", analyze,
                    deadline_s=0.05 if i % 5 == 0 else 10.0))
            except QueueFullError:
                outcomes[i] = "503"
                return
            try:
                value = req.result(timeout=30)
            except DeadlineExceeded:
                outcomes[i] = "408"
                return
            except Exception as e:        # noqa: BLE001
                outcomes[i] = f"error:{type(e).__name__}"
                return
            outcomes[i] = "degraded" if req.faults else "ok"
            assert value == f"r{i}"

        try:
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            # exactly one outcome per submit, all of them typed
            assert len(outcomes) == n
            allowed = {"ok", "degraded", "503", "408"}
            assert set(outcomes.values()) <= allowed, outcomes
            # and the scheduler's own books balance: everything
            # admitted resolved exactly once
            c = sched.metrics.snapshot()["counters"]
            admitted = c["submitted"]
            resolved = (c["completed"] + c["failed"] +
                        c["timed_out"] + c["cancelled"])
            assert admitted == resolved
            assert c["rejected"] == \
                sum(1 for v in outcomes.values() if v == "503")
        finally:
            sched.close()

    def test_drain_completes_inflight_then_refuses(self):
        from trivy_tpu.sched import SchedulerClosed
        gate = threading.Event()

        def analyze(req):
            gate.wait(5)
            return AnalyzedWork(finish=lambda f, d: req.name)

        sched = ScanScheduler(config=SchedConfig(
            workers=2, flush_timeout_s=0.005))
        reqs = [sched.submit(ScanRequest(f"r{i}", analyze))
                for i in range(4)]
        done = {}

        def drainer():
            done["drained"] = sched.drain(timeout_s=10)

        t = threading.Thread(target=drainer)
        t.start()
        time.sleep(0.05)
        # draining: new work is refused with the typed error...
        with pytest.raises(SchedulerClosed):
            sched.submit(ScanRequest("late", analyze))
        # ...but everything already admitted completes
        gate.set()
        t.join(timeout=15)
        assert done.get("drained") is True
        for r in reqs:
            assert r.result(timeout=5) == r.name


# ---------------------------------------------------------------
# RPC: idempotent retry after a lost response + graceful drain
# ---------------------------------------------------------------

def _rpc_server(sched="off", injector=None):
    from trivy_tpu.db import AdvisoryStore
    from trivy_tpu.rpc.server import ScanServer, serve
    store = AdvisoryStore()
    store.put_advisory("alpine 3.9", "pkg0", "CVE-2020-1000",
                       {"FixedVersion": "2.0.0-r0"})
    store.put_vulnerability("CVE-2020-1000", {"Severity": "HIGH"})
    srv = ScanServer(store=store, sched=sched)
    srv.fault_injector = injector
    httpd, _ = serve(port=0, server=srv)
    return srv, httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


class TestRPCFaults:
    def test_lost_response_does_not_double_enqueue(self,
                                                   make_faults):
        """The server processes the Scan, the response is dropped,
        the client retries with the SAME idempotency key: the
        scheduler sees ONE submission and the client still gets the
        full result."""
        from trivy_tpu.rpc.client import RemoteCache, RemoteScanner
        from trivy_tpu.scan.local import ScanTarget
        from trivy_tpu.types import ScanOptions
        from trivy_tpu.types.artifact import (OS, BlobInfo, Package,
                                              PackageInfo)
        inj = make_faults("rpc-lost-response:rpc_drop_first=1")
        srv, httpd, url = _rpc_server(
            sched=SchedConfig(flush_timeout_s=0.01, workers=2))
        try:
            cache = RemoteCache(url, max_retries=3,
                                backoff_base_s=0.01)
            cache.put_blob("sha256:b0", BlobInfo(
                os=OS(family="alpine", name="3.9.4"),
                package_infos=[PackageInfo(packages=[
                    Package(name="pkg0", version="1.0.0",
                            release="r0", src_name="pkg0",
                            src_version="1.0.0",
                            src_release="r0")])]))
            # arm the injector only now: the cache pushes above must
            # not consume the dropped-response budget
            srv.fault_injector = inj
            scanner = RemoteScanner(url, max_retries=4,
                                    backoff_base_s=0.01)
            results, _ = scanner.scan(
                ScanTarget(name="img", artifact_id="sha256:a0",
                           blob_ids=["sha256:b0"]),
                ScanOptions(security_checks=["vuln"],
                            backend="cpu"))
            assert [v.vulnerability_id for r in results
                    for v in r.vulnerabilities] == ["CVE-2020-1000"]
            assert inj.counters["rpc_drops"] == 1
            # exactly one admission despite the client retry
            snap = srv.scheduler.stats()
            assert snap["counters"]["submitted"] == 1
            assert srv._idem.hits == 1
        finally:
            srv.close()
            httpd.shutdown()

    def test_injected_500_is_retried_transparently(self,
                                                   make_faults):
        from trivy_tpu.rpc.client import RemoteScanner
        from trivy_tpu.scan.local import ScanTarget
        from trivy_tpu.types import ScanOptions
        inj = make_faults("rpc_error_first=2")
        srv, httpd, url = _rpc_server(injector=inj)
        try:
            scanner = RemoteScanner(url, max_retries=5,
                                    backoff_base_s=0.01)
            results, _ = scanner.scan(
                ScanTarget(name="img", artifact_id="a",
                           blob_ids=[]),
                ScanOptions(security_checks=["vuln"],
                            backend="cpu"))
            assert results == []
            assert inj.counters["rpc_errors"] == 2
        finally:
            srv.close()
            httpd.shutdown()

    def test_transient_scan_error_is_not_replayed(self):
        """An idempotent Scan that FAILS must stay retryable: the
        next attempt with the same key re-runs instead of replaying
        the cached error (only success is worth replaying)."""
        from trivy_tpu.db import AdvisoryStore
        from trivy_tpu.rpc.server import ScanServer

        calls = {"n": 0}

        class FlakyOnce(ScanServer):
            def _scan(self, body):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise ConnectionError("transient backend blip")
                return {"os": None, "results": []}

        srv = FlakyOnce(store=AdvisoryStore())
        body = {"target": "t", "artifact_id": "a", "blob_ids": [],
                "idempotency_key": "k1"}
        with pytest.raises(ConnectionError):
            srv.scan(body)
        out = srv.scan(body)          # same key: re-runs, succeeds
        assert out == {"os": None, "results": []}
        assert calls["n"] == 2

    def test_graceful_drain_503s_new_work(self):
        import urllib.error
        import urllib.request
        from trivy_tpu.rpc.server import SCANNER_PREFIX
        srv, httpd, url = _rpc_server(
            sched=SchedConfig(flush_timeout_s=0.01, workers=2))
        try:
            body = json.dumps({
                "target": "t", "artifact_id": "a", "blob_ids": [],
                "options": {"backend": "cpu"}}).encode()

            def post():
                req = urllib.request.Request(
                    url + SCANNER_PREFIX + "Scan", data=body,
                    method="POST",
                    headers={"Content-Type": "application/json"})
                return urllib.request.urlopen(req, timeout=10)

            assert post().status == 200     # pre-drain: served
            srv.begin_drain()
            with pytest.raises(urllib.error.HTTPError) as e:
                post()
            assert e.value.code == 503
            assert json.loads(e.value.read())["code"] == \
                "unavailable"
            assert srv.shutdown_gracefully(timeout_s=5)
        finally:
            srv.close()
            httpd.shutdown()


# ---------------------------------------------------------------
# degraded-mode report formats
# ---------------------------------------------------------------

class TestDegradedReports:
    def _report(self, degraded: bool):
        from trivy_tpu.types import Metadata, Report
        r = Report(artifact_name="img.tar",
                   artifact_type="container_image",
                   metadata=Metadata())
        if degraded:
            r.mark_degraded([{"stage": "device",
                              "kind": "quarantined",
                              "message": "injected poison"}])
        return r

    def test_json_carries_status_only_when_faulted(self):
        clean = self._report(False).to_dict()
        assert "Status" not in clean and \
            "FailureCauses" not in clean
        d = self._report(True).to_dict()
        assert d["Status"] == "degraded"
        assert d["FailureCauses"] == [{
            "Stage": "device", "Kind": "quarantined",
            "Message": "injected poison"}]

    def test_table_banner(self):
        from trivy_tpu.report.writer import render_table
        out = render_table(self._report(True))
        assert "DEGRADED" in out and "device/quarantined" in out
        assert "DEGRADED" not in render_table(self._report(False))

    def test_sarif_and_github_and_sbom_annotations(self):
        import io
        from trivy_tpu.report.github import GithubWriter
        from trivy_tpu.report.sarif import SarifWriter
        from trivy_tpu.sbom.cyclonedx import Marshaler as CDX
        from trivy_tpu.sbom.spdx import Marshaler as SPDX

        buf = io.StringIO()
        SarifWriter(buf).write(self._report(True))
        sarif = json.loads(buf.getvalue())
        assert sarif["runs"][0]["properties"]["scanStatus"] == \
            "degraded"
        buf = io.StringIO()
        SarifWriter(buf).write(self._report(False))
        assert "properties" not in \
            json.loads(buf.getvalue())["runs"][0]

        buf = io.StringIO()
        GithubWriter(buf).write(self._report(True))
        gh = json.loads(buf.getvalue())
        assert gh["metadata"]["aquasecurity:trivy:ScanStatus"] == \
            "degraded"

        bom = CDX().marshal(self._report(True))
        assert bom["metadata"]["properties"][0]["value"] == \
            "degraded"
        assert "properties" not in \
            CDX().marshal(self._report(False))["metadata"]

        doc = SPDX().marshal(self._report(True))
        assert doc["creationInfo"]["comment"] == \
            "scan status: degraded"
        assert "comment" not in \
            SPDX().marshal(self._report(False))["creationInfo"]

    def test_cli_fault_spec_end_to_end(self, tmp_path, capsys):
        """`image a b c --fault-spec poison-image:...` completes the
        fleet, annotates the poisoned slot in the JSON array, and
        exits 0 (degraded is not a failure)."""
        from trivy_tpu import cli
        paths = make_fleet(tmp_path, 3, shared_secret=False)
        out = tmp_path / "report.json"
        rc = cli.main([
            "image", *paths, "--format", "json",
            "--output", str(out), "--backend", "cpu",
            "--no-cache", "--security-checks", "vuln,secret",
            "--fault-spec", "poison-image:poison=img1.tar"])
        assert rc == 0
        docs = json.loads(out.read_text())
        assert len(docs) == 3
        by_status = {d["ArtifactName"]: d.get("Status", "ok")
                     for d in docs}
        degraded = [n for n, s in by_status.items()
                    if s == "degraded"]
        assert len(degraded) == 1 and "img1.tar" in degraded[0]
        err = capsys.readouterr().err
        assert "degraded" in err


class TestSpecComposition:
    """The comma-composition grammar (ISSUE 17 satellite): a soak
    step asks for storms + kills + hostile trickle *simultaneously*
    by comma-combining scenario names. Sub-specs draw independently
    derived sub-seeds; conflicting scalar assignments fail up front
    naming the offending pair."""

    def test_multi_segment_parse(self):
        from trivy_tpu.faults.spec import parse_fault_specs
        specs = parse_fault_specs(
            "event-storm,replica-kill,hostile-ingest")
        assert [s.scenario for s in specs] == \
            ["event-storm", "replica-kill", "hostile-ingest"]
        assert specs[0].storm_events == 256
        assert specs[1].replica_kill_after == 32
        assert specs[2].hostile == ("all",)

    def test_params_bind_to_most_recent_segment(self):
        from trivy_tpu.faults.spec import parse_fault_specs
        specs = parse_fault_specs(
            "event-storm:storm_events=64,storm_malformed=4,"
            "replica-kill:replica_kill_after=8")
        assert len(specs) == 2
        assert specs[0].storm_events == 64
        assert specs[0].storm_malformed == 4
        assert specs[1].replica_kill_after == 8
        # the kill sub-spec never saw the storm's overrides
        assert specs[1].storm_events == 0

    def test_derived_subseeds_independent_and_stable(self):
        from trivy_tpu.faults.spec import (derive_subseed,
                                           parse_fault_specs)
        a = parse_fault_specs("event-storm,replica-kill")
        b = parse_fault_specs("event-storm,replica-kill")
        assert [s.seed for s in a] == [s.seed for s in b]
        assert a[0].seed != a[1].seed
        assert a[1].seed == derive_subseed(a[0].seed, 1,
                                           "replica-kill")
        # explicit seed= on a later segment wins over derivation
        c = parse_fault_specs(
            "event-storm,replica-kill:seed=99")
        assert c[1].seed == 99

    def test_base_seed_propagates_to_derivation(self):
        from trivy_tpu.faults.spec import parse_fault_specs
        a = parse_fault_specs("event-storm:seed=1,replica-kill")
        b = parse_fault_specs("event-storm:seed=2,replica-kill")
        assert a[1].seed != b[1].seed

    def test_combine_merges_domains(self):
        from trivy_tpu.faults.spec import parse_fault_spec
        spec = parse_fault_spec(
            "event-storm,replica-kill,cache-flaky")
        assert spec.scenario == \
            "event-storm+replica-kill+cache-flaky"
        assert spec.wants_event_storm()
        assert spec.wants_route_faults()
        assert spec.wants_cache_faults()

    def test_conflict_names_the_pair(self):
        from trivy_tpu.faults.spec import parse_fault_spec
        with pytest.raises(ValueError) as ei:
            parse_fault_spec("cache-outage,cache-down")
        msg = str(ei.value)
        assert "cache-outage" in msg and "cache-down" in msg
        assert "cache_fail_ops" in msg

    def test_same_value_is_not_a_conflict(self):
        from trivy_tpu.faults.spec import parse_fault_spec
        spec = parse_fault_spec(
            "cache-outage,standard-outage:cache_fail_ops=40")
        assert spec.cache_fail_ops == 40

    def test_tuple_fields_union_deduped(self):
        from trivy_tpu.faults.spec import parse_fault_spec
        spec = parse_fault_spec(
            "poison-image:poison=a.tar;b.tar,"
            "device-transient:poison=b.tar;c.tar")
        assert spec.poison == ("a.tar", "b.tar", "c.tar")

    def test_single_spec_back_compat(self):
        from trivy_tpu.faults.spec import (FaultSpec,
                                           parse_fault_specs)
        specs = parse_fault_specs("cache-outage:seed=7")
        assert len(specs) == 1 and specs[0].seed == 7
        # bare k=v legacy grammar forms one anonymous sub-spec
        specs = parse_fault_specs("cache_fail_ops=3,deadline_s=0.5")
        assert len(specs) == 1
        assert specs[0].cache_fail_ops == 3
        assert specs[0].deadline_s == 0.5
        # passthrough and empty
        assert parse_fault_specs(FaultSpec(seed=5))[0].seed == 5
        assert parse_fault_specs("")[0] == FaultSpec()

    def test_unknown_scenario_still_fails_fast(self):
        from trivy_tpu.faults.spec import parse_fault_specs
        with pytest.raises(ValueError, match="unknown fault"):
            parse_fault_specs("event-storm,not-a-scenario")
