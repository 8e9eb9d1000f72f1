"""Elastic warm-state lifecycle suite (docs/serving.md "Elastic
lifecycle").

``pytest -m lifecycle`` — scale events as non-events:

* pre-join prewarm planning: ring placement is a pure cross-process
  function, so :func:`prewarm_ranges` computed by a replica that has
  NOT joined agrees exactly with the post-join ring's owner map, and
  :func:`plan_handoff` partitions a victim's hot set over the
  victim-less ring with nothing lost or duplicated;
* the :class:`HotSet` recency/refcount book (bounded, LRU-ordered,
  hottest-last export — the ``/handoff`` payload contract);
* :func:`range_walk` under deadline and memo-tier outage: partial,
  never an error — the caller degrades to a cold join;
* the warming ready-state machine on the router: a warming replica
  is on the ring but unroutable, the prober's ready flip admits it,
  and a RESTARTED replica re-announcing ``warming`` on /healthz is
  not re-admitted cold (the PR-18 fix);
* sim-replica prewarm/handoff/prefetch end-to-end over real HTTP,
  including the broken-memo-tier bounded cold join;
* :func:`run_handoff` orchestration books every published digest
  exactly once (prefetched or abandoned);
* autoscaler warming hysteresis: prewarming replicas don't count as
  capacity, no second scale-up while one is in flight, no shrink
  under a join;
* the AOT compile-cache manifest: key sensitivity, hit/miss
  accounting across boots, corrupt-manifest recovery, and
  ``warm_ladders`` surviving an unwritable cache dir (compile
  errors propagate — tests/test_device_runtime.py);
* the ScanServer lifecycle surface (warming /healthz, token-gated
  /handoff, /prefetch adoption, metrics sections) and the
  prewarm/handoff/compile-cache exposition on both planes.
"""

import hashlib
import json
import os
import time
import urllib.error
import urllib.request
import uuid

import pytest

from trivy_tpu.memo.store import MemoryMemoStore
from trivy_tpu.memo.warmth import DEFAULT_HOT_CAP, HotSet, range_walk
from trivy_tpu.router.core import SCAN_PATH, HealthProber, ScanRouter
from trivy_tpu.router.lifecycle import (HANDOFF_CAP,
                                        LIFECYCLE_METRICS,
                                        LifecycleMetrics,
                                        fetch_handoff, plan_handoff,
                                        prewarm_ranges, run_handoff)
from trivy_tpu.router.ring import Ring
from trivy_tpu.router.scaler import (Autoscaler, ScalerPolicy,
                                     SimReplicaController, decide)
from trivy_tpu.router.sim import SimReplica, _memo_fname

pytestmark = pytest.mark.lifecycle


# ---------------------------------------------------------------
# helpers
# ---------------------------------------------------------------

def _keys(n, seed="lifecycle"):
    return ["sha256:"
            + hashlib.sha256(f"{seed}:{i}".encode()).hexdigest()
            for i in range(n)]


def _scan_body(digest):
    return {"idempotency_key": uuid.uuid4().hex,
            "target": f"img:{digest[7:19]}",
            "artifact_id": "sha256:art-" + digest[-12:],
            "blob_ids": [digest]}


def _route_scan(router, digest):
    status, body, _ = router.route(
        SCAN_PATH, json.dumps(_scan_body(digest)).encode())
    return status, json.loads(body)


def _wait_ready(sim, timeout_s=10.0):
    t0 = time.monotonic()
    while sim.warming:
        assert time.monotonic() - t0 < timeout_s, \
            "sim replica wedged in the warming state"
        time.sleep(0.005)


def _seed_memo_dir(path, digests):
    os.makedirs(path, exist_ok=True)
    for d in digests:
        with open(os.path.join(path, _memo_fname(d)), "w",
                  encoding="utf-8") as f:
            f.write(d)


# ---------------------------------------------------------------
# pure prewarm / handoff planning
# ---------------------------------------------------------------

class TestPrewarmPlanning:
    def test_matches_post_join_ring_exactly(self):
        members = ["a", "b", "c"]
        keys = _keys(300)
        owned = prewarm_ranges(members, "d", keys)
        ring = Ring()
        for m in members + ["d"]:
            ring.add(m)
        expect = [k for k in keys if ring.owner(k) == "d"]
        assert owned == expect
        assert owned, "seeded population assigns the joiner nothing"

    def test_deterministic_across_calls(self):
        members = ["r0", "r1", "r2", "r3"]
        keys = _keys(200, "det")
        assert prewarm_ranges(members, "r4", keys) \
            == prewarm_ranges(members, "r4", keys)

    def test_preserves_input_order(self):
        """A recency-ordered listing prewarms hottest-first; the
        planner must not re-sort it."""
        keys = _keys(300, "order")
        owned = prewarm_ranges(["a", "b"], "c", keys)
        pos = {k: i for i, k in enumerate(keys)}
        assert [pos[k] for k in owned] \
            == sorted(pos[k] for k in owned)

    def test_joiners_partition_the_keyspace(self):
        """Every key lands on exactly one member of the post-join
        fleet — the union of each member's prewarm view over the
        same fleet covers the keyspace once."""
        fleet = ["a", "b", "c", "d"]
        keys = _keys(256, "part")
        seen = {}
        for joiner in fleet:
            others = [m for m in fleet if m != joiner]
            for k in prewarm_ranges(others, joiner, keys):
                assert k not in seen, \
                    f"{k} claimed by {seen[k]} and {joiner}"
                seen[k] = joiner
        assert len(seen) == len(keys)

    def test_plan_handoff_excludes_victim(self):
        plan = plan_handoff(["a", "b", "c"], "b", _keys(100, "ho"))
        assert "b" not in plan
        assert sum(len(v) for v in plan.values()) == 100

    def test_plan_handoff_matches_victimless_ring(self):
        members, victim = ["a", "b", "c", "d"], "c"
        digests = _keys(150, "vl")
        plan = plan_handoff(members, victim, digests)
        ring = Ring()
        for m in members:
            if m != victim:
                ring.add(m)
        for successor, batch in plan.items():
            for d in batch:
                assert ring.owner(d) == successor

    def test_plan_handoff_preserves_recency_order(self):
        digests = _keys(120, "rec")
        pos = {d: i for i, d in enumerate(digests)}
        plan = plan_handoff(["a", "b", "c"], "a", digests)
        for batch in plan.values():
            assert [pos[d] for d in batch] \
                == sorted(pos[d] for d in batch)


# ---------------------------------------------------------------
# HotSet
# ---------------------------------------------------------------

class TestHotSet:
    def test_bounded_drops_coldest(self):
        hs = HotSet(cap=3)
        for d in ["d1", "d2", "d3", "d4"]:
            hs.touch(d)
        assert len(hs) == 3
        assert "d1" not in hs and "d4" in hs

    def test_touch_refreshes_recency(self):
        hs = HotSet(cap=3)
        for d in ["d1", "d2", "d3"]:
            hs.touch(d)
        hs.touch("d1")          # d1 is now the hottest
        hs.touch("d4")          # evicts d2, the coldest
        assert "d2" not in hs and "d1" in hs
        assert hs.export() == ["d3", "d1", "d4"]

    def test_export_limit_keeps_hottest_tail(self):
        hs = HotSet(cap=10)
        for d in ["a", "b", "c", "d"]:
            hs.touch(d)
        assert hs.export(limit=2) == ["c", "d"]

    def test_discard_clear_snapshot(self):
        hs = HotSet(cap=10)
        hs.touch("x")
        hs.touch("x")
        hs.touch("y")
        snap = hs.snapshot()
        assert snap == {"entries": 2, "cap": 10, "touches": 3}
        hs.discard("x")
        assert "x" not in hs
        hs.clear()
        assert len(hs) == 0

    def test_empty_digest_ignored_and_default_cap(self):
        hs = HotSet()
        hs.touch("")
        assert len(hs) == 0
        assert hs.cap == DEFAULT_HOT_CAP


# ---------------------------------------------------------------
# range_walk
# ---------------------------------------------------------------

class TestRangeWalk:
    def _store(self, n=40):
        store = MemoryMemoStore()
        for i in range(n):
            store.put(f"k{i:03d}", b"v" * (i + 1))
        return store

    def test_stages_only_owned_keys(self):
        store = self._store()
        staged = {}
        res = range_walk(store,
                         lambda k: int(k[1:]) % 2 == 0,
                         deadline_s=5.0,
                         stage=lambda k, v: staged.setdefault(k, v))
        assert res["complete"] and not res["deadline_exceeded"]
        assert res["keys"] == 20 == len(staged)
        assert res["bytes"] == sum(len(v) for v in staged.values())
        assert all(int(k[1:]) % 2 == 0 for k in staged)

    def test_deadline_cuts_walk_partial(self):
        res = range_walk(self._store(), lambda k: True,
                         deadline_s=1e-9)
        assert res["deadline_exceeded"]
        assert not res["complete"]
        assert res["keys"] == 0

    def test_listing_outage_degrades_to_cold(self):
        class Broken:
            def scan_keys(self, prefix="", limit=0):
                raise OSError("tier down")

            def get(self, key):        # pragma: no cover
                raise OSError("tier down")

        res = range_walk(Broken(), lambda k: True, deadline_s=5.0)
        assert res == {"keys": 0, "bytes": 0,
                       "seconds": res["seconds"],
                       "complete": False,
                       "deadline_exceeded": False}

    def test_miss_mid_walk_is_partial_not_fatal(self):
        """A resilient store answers outage with a miss; the walk
        keeps going — later keys may live on a healthy shard."""
        store = self._store(10)

        class Flaky:
            def scan_keys(self, prefix="", limit=0):
                return store.scan_keys(prefix=prefix, limit=limit)

            def get(self, key):
                return None if key == "k003" else store.get(key)

        res = range_walk(Flaky(), lambda k: True, deadline_s=5.0)
        assert not res["complete"]
        assert res["keys"] == 9


# ---------------------------------------------------------------
# warming admission on the router
# ---------------------------------------------------------------

class TestWarmingAdmission:
    def test_warming_replica_on_ring_but_unroutable(self):
        ready = SimReplica(name="wa-ready", service_ms=0.0).start()
        warm = SimReplica(name="wa-warm", service_ms=0.0).start()
        try:
            router = ScanRouter([("wa-ready", ready.url)])
            router.add_replica("wa-warm", warm.url, warming=True)
            assert {h.name for h in router.replicas()} \
                == {"wa-ready", "wa-warm"}
            for d in _keys(20, "adm"):
                status, doc = _route_scan(router, d)
                assert status == 200
                assert doc["replica"] == "wa-ready"
            # the prober sees warming:false on /healthz -> admitted
            HealthProber(router, interval_s=60.0).probe_once()
            assert router.replica("wa-warm").warming is False
            served = {_route_scan(router, d)[1]["replica"]
                      for d in _keys(64, "adm2")}
            assert served == {"wa-ready", "wa-warm"}
        finally:
            ready.stop()
            warm.stop()

    def test_restarted_replica_not_readmitted_cold(self, tmp_path):
        """The PR-18 HealthProber fix: a replica that restarts and
        re-announces ``warming`` on /healthz is pulled OUT of the
        routable set until its prewarm completes, even though the
        router admitted it (non-warming) long ago."""
        memo = str(tmp_path / "memo")
        _seed_memo_dir(memo, _keys(60, "restart"))
        ready = SimReplica(name="rs-peer", service_ms=0.0).start()
        # stands in for a restarted replica: mid-prewarm at probe
        # time (the delay keeps the window open for the assertion)
        back = SimReplica(name="rs-back", service_ms=0.0,
                          memo_dir=memo,
                          ring_members=["rs-peer", "other"],
                          prewarm_delay_ms=20.0).start()
        try:
            router = ScanRouter([("rs-peer", ready.url)])
            # admitted WITHOUT the warming overlay — the pre-fix
            # world, where the router would route to it cold
            router.add_replica("rs-back", back.url)
            assert router.replica("rs-back").warming is False
            prober = HealthProber(router, interval_s=60.0)
            prober.probe_once()
            assert router.replica("rs-back").warming is True
            for d in _keys(16, "rs"):
                status, doc = _route_scan(router, d)
                assert status == 200
                assert doc["replica"] == "rs-peer"
            _wait_ready(back)
            prober.probe_once()
            assert router.replica("rs-back").warming is False
        finally:
            ready.stop()
            back.stop()

    def test_mark_warming_overlay(self):
        ready = SimReplica(name="mw-0", service_ms=0.0).start()
        try:
            router = ScanRouter([("mw-0", ready.url)])
            router.mark_warming("mw-0")
            assert "mw-0" in router._unroutable()
            router.mark_warming("mw-0", False)
            assert "mw-0" not in router._unroutable()
        finally:
            ready.stop()


# ---------------------------------------------------------------
# sim replica lifecycle end-to-end
# ---------------------------------------------------------------

class TestSimLifecycle:
    def test_prewarm_stages_owned_digests(self, tmp_path):
        memo = str(tmp_path / "memo")
        digests = _keys(80, "stage")
        _seed_memo_dir(memo, digests)
        members = ["p0", "p1"]
        sim = SimReplica(name="p2", service_ms=0.0, memo_dir=memo,
                         ring_members=members).start()
        try:
            _wait_ready(sim)
            owned = prewarm_ranges(members, "p2", digests)
            assert owned
            assert sim.counters["prewarm_keys"] == len(owned)
            assert sim.counters["prewarm_cold_joins"] == 0
            assert sim.counters["prewarm_deadline_exceeded"] == 0
            # a staged digest serves warm on its FIRST post-join
            # scan — the whole point of the prewarm
            router = ScanRouter([("p2", sim.url)])
            status, doc = _route_scan(router, owned[0])
            assert status == 200
            assert doc["memo_hit"] is True
        finally:
            sim.stop()

    def test_broken_memo_tier_bounded_cold_join(self, tmp_path):
        not_a_dir = tmp_path / "memo-tier"
        not_a_dir.write_text("outage stand-in")
        sim = SimReplica(name="cj", service_ms=0.0,
                         memo_dir=str(not_a_dir),
                         ring_members=["a", "b"],
                         prewarm_deadline_s=1.0).start()
        try:
            _wait_ready(sim, timeout_s=3.0)
            assert sim.counters["prewarm_cold_joins"] == 1
            assert sim.counters["prewarm_keys"] == 0
            router = ScanRouter([("cj", sim.url)])
            status, doc = _route_scan(router, _keys(1, "cj")[0])
            assert status == 200
            assert doc["memo_hit"] is False
        finally:
            sim.stop()

    def test_handoff_prefetch_http_roundtrip(self, tmp_path):
        src = SimReplica(name="ho-src", service_ms=0.0,
                         memo_dir=str(tmp_path / "memo")).start()
        dst = SimReplica(name="ho-dst", service_ms=0.0).start()
        try:
            digests = _keys(12, "round")
            router = ScanRouter([("ho-src", src.url)])
            for d in digests:
                assert _route_scan(router, d)[0] == 200
            with urllib.request.urlopen(src.url + "/handoff",
                                        timeout=5.0) as resp:
                doc = json.loads(resp.read())
            assert doc["name"] == "ho-src"
            assert set(doc["digests"]) == set(digests)
            req = urllib.request.Request(
                dst.url + "/prefetch",
                data=json.dumps({"digests": doc["digests"]}
                                ).encode(),
                method="POST")
            with urllib.request.urlopen(req, timeout=5.0) as resp:
                out = json.loads(resp.read())
            assert out["accepted"] == len(digests)
            # adopted digests serve warm on the successor
            router2 = ScanRouter([("ho-dst", dst.url)])
            status, body = _route_scan(router2, digests[0])
            assert status == 200 and body["memo_hit"] is True
        finally:
            src.stop()
            dst.stop()

    def test_run_handoff_books_every_digest_once(self, tmp_path):
        memo = str(tmp_path / "memo")
        sims = [SimReplica(name=f"rh{i}", service_ms=0.0,
                           memo_dir=memo).start() for i in range(3)]
        try:
            LIFECYCLE_METRICS.reset()
            router = ScanRouter([(s.name, s.url) for s in sims])
            keys = _keys(60, "books")
            for d in keys:
                assert _route_scan(router, d)[0] == 200
            router.mark_draining("rh2")
            summary = run_handoff(router, "rh2")
            assert summary["published"] > 0
            assert summary["abandoned"] == 0
            assert summary["prefetched"] == summary["published"]
            assert sum(summary["successors"].values()) \
                == summary["prefetched"]
            assert "rh2" not in summary["successors"]
            snap = LIFECYCLE_METRICS.snapshot()
            assert snap["handoff_published"] \
                == snap["handoff_prefetched"] \
                + snap["handoff_abandoned"]
            # the working set moved with the keys: once the victim
            # leaves the ring, every digest of the warmed set is a
            # memo hit on its new owner
            router.remove_replica("rh2")
            for d in keys:
                status, doc = _route_scan(router, d)
                assert status == 200
                assert doc["replica"] != "rh2"
                assert doc["memo_hit"] is True, d
        finally:
            LIFECYCLE_METRICS.reset()
            for s in sims:
                s.stop()

    def test_run_handoff_missing_victim_is_noop(self):
        router = ScanRouter([])
        summary = run_handoff(router, "ghost")
        assert summary == {"victim": "ghost", "published": 0,
                           "prefetched": 0, "abandoned": 0,
                           "successors": {}}

    def test_fetch_handoff_failure_returns_empty(self):
        assert fetch_handoff("http://127.0.0.1:9",
                             timeout_s=0.2) == []

    def test_handoff_cap_bounds_payload(self):
        assert HANDOFF_CAP == 4096
        digests = [f"sha256:{i:064d}" for i in range(10)]
        plan = plan_handoff(["a"], "b", digests)
        assert sum(len(v) for v in plan.values()) == 10


# ---------------------------------------------------------------
# autoscaler warming hysteresis
# ---------------------------------------------------------------

class TestScalerWarming:
    POLICY = ScalerPolicy(min_replicas=1, max_replicas=4,
                          cooldown_s=0.0, calm_ticks=1,
                          require_complete=False)

    def test_decide_holds_while_prewarming(self):
        action, reason = decide(False, True, 5.0, 2, 0,
                                self.POLICY, warming=1)
        assert action == "hold" and "prewarming" in reason

    def test_decide_never_shrinks_under_a_join(self):
        action, reason = decide(True, True, 0.0, 3, 5,
                                self.POLICY, warming=1)
        assert action == "hold" and "prewarming" in reason

    def test_decide_scales_up_when_none_warming(self):
        action, _ = decide(False, True, 5.0, 2, 0,
                           self.POLICY, warming=0)
        assert action == "up"

    def test_no_second_scale_up_in_flight(self, tmp_path):
        seed = SimReplica(name="hz-seed", service_ms=0.0).start()
        controller = SimReplicaController(
            prefix="hz", service_ms=0.0,
            memo_dir=str(tmp_path / "memo"))
        try:
            router = ScanRouter([("hz-seed", seed.url)])
            scaler = Autoscaler(router, controller,
                                policy=self.POLICY,
                                verdict_fn=lambda: {
                                    "slo_ok": False,
                                    "complete": True})
            burn = {"slo_ok": False, "complete": True}
            scaler.tick(burn)
            names = {h.name for h in router.replicas()}
            assert len(names) == 2
            joiner = (names - {"hz-seed"}).pop()
            # prewarm-enabled controller -> the joiner is admitted
            # to the ring warming; no prober runs, so it stays that
            # way for the duration of this test
            assert router.replica(joiner).warming is True
            # the burn continues, but a scale-up is in flight: hold
            for _ in range(3):
                verdict = scaler.tick(burn)
                assert verdict["action"] == "hold"
            assert len(router.replicas()) == 2
            # the prewarming replica is NOT capacity: the serving
            # count the decision saw stays at 1
            assert scaler._avg_inflight()[1:] == (1, 1)
            # ready flip -> the next burn tick may scale up again
            router.mark_warming(joiner, False)
            verdict = scaler.tick(burn)
            assert verdict["action"] == "up"
            assert len(router.replicas()) == 3
        finally:
            seed.stop()
            for name in list(controller.replicas):
                controller.stop(name)

    def test_controller_passes_ring_members(self, tmp_path):
        memo = str(tmp_path / "memo")
        _seed_memo_dir(memo, _keys(40, "ctrl"))
        controller = SimReplicaController(prefix="cm",
                                          service_ms=0.0,
                                          memo_dir=memo)
        assert controller.prewarm_enabled
        name, _url = controller.start(ring_members=["x", "y"])
        try:
            sim = controller.replicas[name]
            assert sim.ring_members == ["x", "y"]
            _wait_ready(sim)
            assert sim.counters["prewarm_runs"] == 1
        finally:
            controller.stop(name)


# ---------------------------------------------------------------
# AOT compile-cache manifest
# ---------------------------------------------------------------

class TestAotManifest:
    def test_cache_key_sensitivity(self):
        from trivy_tpu.runtime.aot import cache_key
        base = cache_key("interval", "P64xM8")
        assert base == cache_key("interval", "P64xM8")
        assert len(base) == 32
        assert base != cache_key("dfa_fused", "P64xM8")
        assert base != cache_key("interval", "P128xM8")
        assert base != cache_key("interval", "P64xM8", "rules-v2")

    def test_manifest_roundtrip_and_corruption(self, tmp_path):
        from trivy_tpu.runtime.aot import MANIFEST_NAME, _Manifest
        m = _Manifest(str(tmp_path))
        assert not m.seen("k1")
        m.note("k1", {"kernel": "interval", "P": 64})
        m2 = _Manifest(str(tmp_path))
        assert m2.seen("k1")
        assert m2.entries["k1"]["P"] == 64
        # corruption is a warning, not a boot failure
        (tmp_path / MANIFEST_NAME).write_text("{not json")
        m3 = _Manifest(str(tmp_path))
        assert m3.entries == {}
        m3.note("k2", {})       # and writes recover it
        assert _Manifest(str(tmp_path)).seen("k2")

    @staticmethod
    def _tiny_cdb():
        from trivy_tpu.db import AdvisoryStore, CompiledDB
        store = AdvisoryStore()
        store.put_advisory("alpine 3.16", "musl", "CVE-2026-0001",
                           {"FixedVersion": "1.2.3-r1"})
        return CompiledDB.compile(store)

    def test_precompile_books_miss_then_hit(self, tmp_path):
        from trivy_tpu.runtime.aot import (COMPILE_CACHE_METRICS,
                                           precompile_interval_shapes)
        cdb = self._tiny_cdb()
        COMPILE_CACHE_METRICS.reset()
        try:
            out = precompile_interval_shapes(
                cdb, buckets=(8,), cache_dir=str(tmp_path))
            assert out["kernel"] == "interval_resident"
            assert out["shapes"] == [8]
            snap = COMPILE_CACHE_METRICS.snapshot()
            assert snap["misses"] == 1 and snap["hits"] == 0
            assert snap["precompiled"] == 1
            # the next boot finds the keyed shape in the manifest
            precompile_interval_shapes(cdb, buckets=(8,),
                                       cache_dir=str(tmp_path))
            snap = COMPILE_CACHE_METRICS.snapshot()
            assert snap["hits"] == 1 and snap["misses"] == 1
            assert snap["seconds"] > 0.0
        finally:
            COMPILE_CACHE_METRICS.reset()

    def test_warm_ladders_survives_a_bad_cache_dir(self, tmp_path):
        from trivy_tpu.runtime.aot import warm_ladders
        from trivy_tpu.sched import SchedConfig
        blocker = tmp_path / "file"
        blocker.write_text("x")
        summary = warm_ladders(
            store=self._tiny_cdb(),
            config=SchedConfig(max_batch_jobs=64),
            cache_dir=str(blocker / "nested"))
        assert summary["persistent"] is False
        assert summary["seconds"] >= 0.0
        assert [k["shapes"] for k in summary["kernels"]] == [[64]]


# ---------------------------------------------------------------
# ScanServer lifecycle surface
# ---------------------------------------------------------------

class TestServerLifecycle:
    def _memo(self, n=60):
        from trivy_tpu.memo import FindingsMemo
        store = MemoryMemoStore()
        for i in range(n):
            store.put(f"memo:k{i:03d}", b"verdict" * 4)
        return FindingsMemo(store=store)

    def test_healthz_warming_until_prewarm_done(self):
        from trivy_tpu.rpc.server import ScanServer
        LIFECYCLE_METRICS.reset()
        try:
            srv = ScanServer(memo=self._memo(),
                             prewarm_members=["a", "b"],
                             prewarm_deadline_s=5.0)
            t0 = time.monotonic()
            while srv.health()["status"] == "warming":
                assert time.monotonic() - t0 < 10.0
                time.sleep(0.005)
            doc = srv.health()
            assert doc["status"] == "ok"
            assert doc["warming"] is False
            snap = LIFECYCLE_METRICS.snapshot()
            assert snap["prewarm_runs"] == 1
            assert snap["prewarm_keys"] > 0
            assert snap["prewarm_cold_joins"] == 0
            srv.close()
        finally:
            LIFECYCLE_METRICS.reset()

    def test_handoff_route_token_gated(self):
        from trivy_tpu.rpc.server import (DEFAULT_TOKEN_HEADER,
                                          ScanServer, serve)
        srv = ScanServer(token="hush")
        httpd, thread = serve(port=0, server=srv)
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            srv.prefetch({"digests": ["sha256:aa", "sha256:bb"]})
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(url + "/handoff",
                                       timeout=5.0)
            req = urllib.request.Request(
                url + "/handoff",
                headers={DEFAULT_TOKEN_HEADER: "hush"})
            with urllib.request.urlopen(req, timeout=5.0) as resp:
                doc = json.loads(resp.read())
            assert doc["digests"] == ["sha256:aa", "sha256:bb"]
            req = urllib.request.Request(
                url + "/prefetch",
                data=json.dumps({"digests": ["sha256:cc"]}).encode(),
                method="POST",
                headers={DEFAULT_TOKEN_HEADER: "hush"})
            with urllib.request.urlopen(req, timeout=5.0) as resp:
                assert json.loads(resp.read())["accepted"] == 1
            assert "sha256:cc" in srv.hot
        finally:
            httpd.shutdown()
            if thread is not None:
                thread.join(timeout=5.0)
            srv.close()

    def test_metrics_carries_lifecycle_sections(self):
        from trivy_tpu.rpc.server import ScanServer
        srv = ScanServer()
        try:
            out = srv.metrics()
            assert "lifecycle" in out and "compile_cache" in out
            assert out["lifecycle"]["warming"] is False
            assert out["lifecycle"]["hot"]["cap"] == DEFAULT_HOT_CAP
            for k in ("hits", "misses", "bytes"):
                assert k in out["compile_cache"]
        finally:
            srv.close()


# ---------------------------------------------------------------
# exposition
# ---------------------------------------------------------------

class TestLifecycleExposition:
    def test_replica_prom_families(self):
        from trivy_tpu.obs.prom import render_prometheus
        from trivy_tpu.rpc.server import ScanServer
        srv = ScanServer()
        try:
            srv.hot.touch("sha256:hot1")
            text = render_prometheus(srv.metrics())
        finally:
            srv.close()
        for family in ("trivy_tpu_prewarm_keys_total",
                       "trivy_tpu_prewarm_bytes_total",
                       "trivy_tpu_prewarm_seconds_total",
                       "trivy_tpu_prewarm_deadline_exceeded_total",
                       "trivy_tpu_handoff_published_total",
                       "trivy_tpu_handoff_prefetched_total",
                       "trivy_tpu_handoff_abandoned_total",
                       "trivy_tpu_warming",
                       "trivy_tpu_hot_digests",
                       "trivy_tpu_compile_cache_hits",
                       "trivy_tpu_compile_cache_misses",
                       "trivy_tpu_compile_cache_bytes",
                       "trivy_tpu_compile_cache_seconds_total"):
            assert family in text, family
        assert "trivy_tpu_hot_digests 1" in text

    def test_router_prom_families(self):
        from trivy_tpu.router.front import RouterServer
        sim = SimReplica(name="xp-0", service_ms=0.0).start()
        try:
            router = ScanRouter([("xp-0", sim.url)])
            router.add_replica("xp-warm", sim.url, warming=True)
            text = RouterServer(router).metrics_text()
        finally:
            sim.stop()
        assert 'trivy_tpu_router_replica_warming{' \
            'replica="xp-warm"} 1' in text
        assert 'trivy_tpu_router_replica_warming{' \
            'replica="xp-0"} 0' in text
        for family in ("trivy_tpu_handoff_published_total",
                       "trivy_tpu_handoff_prefetched_total",
                       "trivy_tpu_handoff_abandoned_total",
                       "trivy_tpu_prewarm_keys_total"):
            assert family in text, family

    def test_sim_metrics_text_families(self, tmp_path):
        memo = str(tmp_path / "memo")
        _seed_memo_dir(memo, _keys(30, "simexp"))
        sim = SimReplica(name="se-0", service_ms=0.0,
                         memo_dir=memo,
                         ring_members=["a"]).start()
        try:
            _wait_ready(sim)
            with urllib.request.urlopen(
                    sim.url + "/metrics/snapshot",
                    timeout=5.0) as resp:
                text = json.loads(resp.read())["prom"]
        finally:
            sim.stop()
        assert "trivy_tpu_prewarm_keys_total" in text
        assert "trivy_tpu_prewarm_seconds_total" in text
        assert "trivy_tpu_handoff_published_total" in text

    def test_lifecycle_metrics_snapshot_contract(self):
        m = LifecycleMetrics()
        m.inc("prewarm_keys", 7)
        m.add_seconds(0.25)
        snap = m.snapshot()
        assert snap["prewarm_keys"] == 7
        assert snap["prewarm_seconds"] == 0.25
        m.reset()
        assert m.snapshot()["prewarm_keys"] == 0
