"""SLO burn-rate engine (trivy_tpu/obs/slo.py): classification and
burn math on synthetic event streams, the multi-window AND rule,
tenant/priority scoping, config parsing, trip-transition trace
dumps through the flight recorder, scheduler wiring under a
deadline storm, ``GET /slo`` over HTTP, and the trivy_tpu_slo_*
gauges on the text exposition."""

from __future__ import annotations

import json

import pytest

from trivy_tpu.obs.slo import (SLO, SloEngine, default_slos,
                               parse_slo_config)
from trivy_tpu.utils.synth import tiny_fleet

pytestmark = pytest.mark.obs


class TestDeclarations:
    def test_validation(self):
        with pytest.raises(ValueError):
            SLO(name="x", kind="throughput")
        with pytest.raises(ValueError):
            SLO(name="x", objective=1.5)
        with pytest.raises(ValueError):
            SLO(name="x", kind="latency")       # no threshold
        SLO(name="ok", kind="latency", threshold_s=1.0)

    def test_classify(self):
        avail = SLO(name="a", kind="availability", objective=0.99)
        assert avail.classify("ok", 0.0) is True
        assert avail.classify("degraded", 0.0) is True
        assert avail.classify("failed", 0.0) is False
        assert avail.classify("timed_out", 0.0) is False
        assert avail.classify("cancelled", 0.0) is None
        lat = SLO(name="l", kind="latency", objective=0.9,
                  threshold_s=1.0)
        assert lat.classify("ok", 0.5) is True
        assert lat.classify("ok", 2.0) is False
        assert lat.classify("timed_out", 0.0) is False

    def test_scoping(self):
        t = SLO(name="t", tenant="alice")
        assert t.matches("alice", 0) and not t.matches("bob", 0)
        p = SLO(name="p", min_priority=10)
        assert p.matches("", 10) and not p.matches("", 9)

    def test_parse_config(self):
        slos = parse_slo_config(
            "avail:kind=availability,objective=0.999;"
            "lat:kind=latency,objective=0.95,threshold_s=2.5,"
            "tenant=alice")
        assert [s.name for s in slos] == ["avail", "lat"]
        assert slos[0].objective == 0.999
        assert slos[1].tenant == "alice"
        assert parse_slo_config("") == default_slos()
        with pytest.raises(ValueError):
            parse_slo_config("bad entry")
        with pytest.raises(ValueError):
            parse_slo_config("x:nope=1")
        with pytest.raises(ValueError):
            parse_slo_config("x:objective=banana")
        # duplicate names fail AT PARSE, so --slo-config typos hit
        # the CLI's clean error path, not server construction
        with pytest.raises(ValueError):
            parse_slo_config("a:objective=0.9;a:objective=0.99")

    def test_duplicate_names_rejected(self):
        with pytest.raises(ValueError):
            SloEngine([SLO(name="a"), SLO(name="a")])


class TestBurnMath:
    def test_burn_rate_values(self):
        e = SloEngine([SLO(name="a", objective=0.99)])
        for _ in range(90):
            e.record("ok")
        for _ in range(10):
            e.record("failed")
        v = e.verdicts()[0]
        # bad rate 0.1 over budget 0.01 -> burn 10 on every window
        assert v["burn"]["5m"] == pytest.approx(10.0)
        assert v["burn"]["6h"] == pytest.approx(10.0)
        # 10 < 14.4 fast threshold, but >= 6 slow threshold
        assert not v["fast_tripped"] and v["slow_tripped"]
        assert not v["ok"]

    def test_empty_window_burns_zero(self):
        e = SloEngine([SLO(name="a", objective=0.99)])
        v = e.verdicts()[0]
        assert v["burn"] == {"5m": 0.0, "1h": 0.0, "30m": 0.0,
                             "6h": 0.0}
        assert v["ok"]

    def test_multiwindow_and_rule(self):
        """Both windows of a pair must agree: old bad events inside
        the 1h window but outside 5m do not fast-trip on their
        own."""
        import time as _time

        e = SloEngine([SLO(name="a", objective=0.99)])
        now = _time.monotonic()
        from trivy_tpu.obs import slo as slo_mod
        old_bucket = int((now - 1200) / slo_mod._BUCKET_S)
        book = e._books["a"]
        book.ring[old_bucket] = [0, 100]    # 20 min ago: all bad
        book.bad += 100
        cur = int(now / slo_mod._BUCKET_S)
        book.ring[cur] = [100, 0]           # now: all good
        book.good += 100
        v = e.verdicts(now=now)[0]
        assert v["burn"]["5m"] == pytest.approx(0.0)
        assert v["burn"]["1h"] == pytest.approx(50.0)
        assert not v["fast_tripped"]

    def test_latency_slo_counts_slow_requests(self):
        e = SloEngine([SLO(name="lat", kind="latency",
                           objective=0.5, threshold_s=1.0)])
        for _ in range(10):
            e.record("ok", latency_s=0.1)
        for _ in range(10):
            e.record("ok", latency_s=5.0)
        v = e.verdicts()[0]
        assert v["good"] == 10 and v["bad"] == 10
        assert v["threshold_s"] == 1.0

    def test_tenant_scoped_engine_ignores_others(self):
        e = SloEngine([SLO(name="alice", tenant="alice")])
        e.record("failed", tenant="bob")
        e.record("ok", tenant="alice")
        v = e.verdicts()[0]
        assert v["good"] == 1 and v["bad"] == 0


class TestTripDumps:
    def _trip(self, recorder):
        e = SloEngine([SLO(name="a", objective=0.99)],
                      recorder=recorder)
        for i in range(5):
            e.record("ok")
        for i in range(20):
            e.record("failed", latency_s=float(i),
                     trace_id=f"{i:032x}")
        return e

    def test_trip_transition_dumps_worst_traces(self):
        dumped = []

        class FakeRecorder:
            def dump(self, trace_id, spans=None, epoch_mono=0.0):
                dumped.append(trace_id)

        e = self._trip(FakeRecorder())
        v = e.verdicts()[0]
        assert v["fast_tripped"] and v["trips"] >= 1
        assert dumped, "trip transition dumped nothing"
        # exemplars are worst-first (highest latency)
        assert v["exemplar_trace_ids"][0] == f"{19:032x}"
        assert e.dumps == len(dumped)
        # staying tripped does NOT re-dump
        n = len(dumped)
        e.verdicts()
        assert len(dumped) == n

    def test_missing_trace_in_ring_is_tolerated(self):
        from trivy_tpu.obs import FlightRecorder
        e = self._trip(FlightRecorder())   # ring has no such traces
        v = e.verdicts()[0]
        assert v["fast_tripped"]
        assert e.dumps == 0                # nothing dumped, no crash

    def test_trip_dump_shares_tracer_timebase(self, tmp_path):
        """An SLO-trip dump must land on the SAME timebase as the
        tracer's own failure dumps (us since tracer start), not raw
        monotonic-since-boot — the recorder remembers its tracer's
        epoch and dump() defaults to it."""
        from trivy_tpu.obs import FlightRecorder, Tracer
        recorder = FlightRecorder(dump_dir=str(tmp_path))
        tracer = Tracer(recorder=recorder)
        root = tracer.start_request("slo-victim")
        root.end()
        e = SloEngine([SLO(name="a", objective=0.99)],
                      recorder=recorder)
        for _ in range(5):
            e.record("ok")
        for _ in range(20):
            e.record("failed", latency_s=1.0,
                     trace_id=root.trace_id)
        assert e.verdicts()[0]["fast_tripped"]
        assert e.dumps == 1
        doc = json.loads(
            open(recorder.dump_path(root.trace_id)).read())
        ts = [ev["ts"] for ev in doc["traceEvents"]
              if "ts" in ev]
        # relative to the tracer epoch: a fresh trace sits within
        # seconds of 0, not hours of monotonic-since-boot
        assert ts and all(0 <= t < 60e6 for t in ts), ts


class TestSchedulerWiring:
    def test_deadline_storm_trips_fast_window_and_dumps(
            self, tmp_path, larger_fleet):
        """The acceptance drill end-to-end: a deadline storm mass-
        expires scheduled requests; the fast burn window trips,
        GET /slo reports the violation with exemplar trace ids, and
        the flight recorder dumps the offending traces."""
        import urllib.request

        from trivy_tpu.obs import FlightRecorder, Tracer
        from trivy_tpu.rpc.server import ScanServer, serve
        from trivy_tpu.runtime import BatchScanRunner
        from trivy_tpu.sched import SchedConfig
        from trivy_tpu.types import ScanOptions

        paths, store = larger_fleet(4)
        tracer = Tracer(recorder=FlightRecorder())
        tracer.recorder.dump_dir = str(tmp_path / "dumps")
        runner = BatchScanRunner(store=store, backend="cpu-ref",
                                 sched=SchedConfig(workers=2),
                                 tracer=tracer)
        try:
            options = ScanOptions(backend="cpu-ref")
            good = [runner.submit_path(p, options) for p in paths]
            for req in good:
                req.result()
            doomed = ScanOptions(backend="cpu-ref")
            doomed.deadline_s = 0.001
            storm = [runner.submit_path(paths[i % len(paths)],
                                        doomed)
                     for i in range(24)]
            timed_out = 0
            for req in storm:
                try:
                    req.result()
                except Exception:   # noqa: BLE001
                    timed_out += 1
            assert timed_out > 0
            server = ScanServer(sched=runner.scheduler,
                                tracer=tracer)
            httpd, _ = serve(port=0, server=server)
            try:
                base = \
                    f"http://127.0.0.1:{httpd.server_address[1]}"
                doc = json.load(
                    urllib.request.urlopen(base + "/slo"))
            finally:
                httpd.shutdown()
        finally:
            runner.close()
        avail = next(v for v in doc["slos"]
                     if v["name"] == "availability")
        assert avail["fast_tripped"] and not avail["ok"]
        assert avail["exemplar_trace_ids"]
        assert doc["dumps"] > 0
        import os
        dumped = [t for t in avail["exemplar_trace_ids"]
                  if os.path.exists(
                      tracer.recorder.dump_path(t))]
        assert dumped, "no exemplar trace reached the dump dir"

    def test_healthy_fleet_keeps_slo_ok(self, tmp_path):
        from trivy_tpu.runtime import BatchScanRunner
        from trivy_tpu.sched import SchedConfig

        paths, store = tiny_fleet(str(tmp_path), 3)
        runner = BatchScanRunner(store=store, backend="cpu-ref",
                                 sched=SchedConfig(workers=2))
        try:
            runner.scan_paths(paths)
            snap = runner.scheduler.stats()["slo"]
        finally:
            runner.close()
        by_name = {v["name"]: v for v in snap["slos"]}
        assert by_name["availability"]["ok"]
        assert by_name["availability"]["good"] == 3
        assert snap["dumps"] == 0

    def test_slo_gauges_on_text_exposition(self):
        from trivy_tpu.obs.prom import render_prometheus
        e = SloEngine()
        e.record("ok", latency_s=0.1)
        e.record("failed", latency_s=0.2)
        text = render_prometheus({"slo": e.snapshot()})
        assert 'trivy_tpu_slo_ok{slo="availability"}' in text
        assert ('trivy_tpu_slo_burn_rate{slo="availability",'
                'window="5m"}') in text
        assert ('trivy_tpu_slo_events_total{slo="availability",'
                'class="bad"} 1') in text
        assert "trivy_tpu_slo_trips_total" in text
        assert "trivy_tpu_slo_dumps_total 0" in text

    def test_sched_off_server_records_slo(self):
        from trivy_tpu.rpc.server import ScanServer
        server = ScanServer()            # sched off
        server.scan({"target": "t", "artifact_id": "a",
                     "blob_ids": []})
        v = server.slo_verdicts()["slos"]
        avail = next(x for x in v if x["name"] == "availability")
        assert avail["good"] >= 1

    def test_sched_config_slos_accepts_string_grammar(self):
        """SchedConfig.slos routes through parse_slo_config: the
        --slo-config string grammar works for embedders, and a typo
        fails with the parser's ValueError, not an AttributeError
        deep in SloEngine."""
        from trivy_tpu.sched import ScanScheduler, SchedConfig

        cfg = SchedConfig(workers=1,
                          slos="tight:kind=availability,"
                               "objective=0.5")
        sched = ScanScheduler(config=cfg)
        try:
            assert [s.name for s in sched.slo.slos] == ["tight"]
        finally:
            sched.close()
        with pytest.raises(ValueError):
            ScanScheduler(config=SchedConfig(
                workers=1, slos="bad:objective=nope"))

    def test_slo_config_overrides_engine(self):
        from trivy_tpu.rpc.server import ScanServer
        server = ScanServer(
            sched="on",
            slos=parse_slo_config("tight:kind=availability,"
                                  "objective=0.5"))
        try:
            names = [v["name"] for v in
                     server.slo_verdicts()["slos"]]
            assert names == ["tight"]
            assert server.slo is server.scheduler.slo
        finally:
            server.close()
