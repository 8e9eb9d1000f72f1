"""Registry-scale soak harness tests (`pytest -m soak`;
docs/robustness.md "Soak & chaos testing").

Covers the synthetic registry (deterministic content-addressed
manifests, realistic layer reuse, envelope compatibility with the
watch source), scenario schedules (same seed => byte-identical),
the bounded-growth audit verdict, the sim replica's chaos surface,
the process self-stats gauges on every /metrics exposition, and a
seconds-scale end-to-end soak run gating the three fleet
invariants: books balance, designed-trip exactness with recorder
evidence, and a schema-stable report.
"""

import json
import urllib.request

import pytest

from trivy_tpu.soak import (RegistrySpec, Scenario, ScenarioSpec,
                            Step, SyntheticRegistry, load_scenario,
                            run_soak)
from trivy_tpu.soak.audit import ResourceAudit
from trivy_tpu.soak.registry import PATH_SCHEME
from trivy_tpu.soak.runner import stable_view
from trivy_tpu.soak.scenario import SCENARIOS
from trivy_tpu.watch.source import parse_notification

pytestmark = pytest.mark.soak


# ---------------------------------------------------------------
# synthetic registry
# ---------------------------------------------------------------

class TestSyntheticRegistry:
    def test_deterministic_manifests(self):
        a = SyntheticRegistry(RegistrySpec(seed=11))
        b = SyntheticRegistry(RegistrySpec(seed=11))
        for i in (0, 7, 19_999):
            assert a.manifest(i) == b.manifest(i)

    def test_seed_changes_identities(self):
        a = SyntheticRegistry(RegistrySpec(seed=1))
        b = SyntheticRegistry(RegistrySpec(seed=2))
        assert a.manifest(3)["digest"] != b.manifest(3)["digest"]

    def test_content_addressed_digest(self):
        reg = SyntheticRegistry(RegistrySpec(seed=5))
        m1, m2 = reg.manifest(42), reg.manifest(42)
        assert m1["digest"] == m2["digest"]
        assert m1["digest"].startswith("sha256:")
        assert reg.by_digest(m1["digest"]) == m1

    def test_layer_reuse_shape(self):
        """~reuse of layer slots come from the shared base pool —
        the PR-9 warm-fleet ratio, now index-bound."""
        reg = SyntheticRegistry(RegistrySpec(
            seed=3, layers=50_000, images=5_000, reuse=0.8))
        st = reg.stats()
        assert 0.6 <= st["sample_base_share"] <= 0.95, st
        # distinct layers scale well past the base pool
        assert st["sample_distinct_layers"] > reg.base_pool / 2

    def test_million_layer_registry_is_index_bound(self):
        """A 10^6-layer registry costs an integer, not a disk: any
        manifest materializes on demand."""
        reg = SyntheticRegistry(RegistrySpec(
            seed=9, layers=1_000_000, images=200_000))
        m = reg.manifest(123_456)
        assert all(d.startswith("sha256:") for d in m["layers"])
        assert len(reg._by_digest) == 1

    def test_no_duplicate_layers_in_manifest(self):
        reg = SyntheticRegistry(RegistrySpec(seed=13))
        for i in range(64):
            layers = reg.layers_for(i)
            assert len(layers) == len(set(layers))

    def test_tenant_mix(self):
        reg = SyntheticRegistry(RegistrySpec(seed=17))
        seen = {reg.tenant_for(i) for i in range(200)}
        assert seen == set(reg.spec.tenants)

    def test_notification_parses_through_watch_source(self):
        """The envelope is byte-compatible with the watch loop's
        webhook parser, and the resolver maps it to a soak://
        target."""
        reg = SyntheticRegistry(RegistrySpec(seed=23))
        env = reg.notification(5)
        events, malformed = parse_notification(
            env, resolver=reg.resolver())
        assert malformed == 0 and len(events) == 1
        ev = events[0]
        assert ev.digest == reg.manifest(5)["digest"]
        assert ev.path == PATH_SCHEME + ev.digest
        assert reg.resolve_path(ev.path)["index"] == 5

    def test_foreign_digest_unresolvable(self):
        reg = SyntheticRegistry(RegistrySpec(seed=29))
        assert reg.resolver()("repo:tag", "sha256:" + "0" * 64) == ""
        with pytest.raises(KeyError):
            reg.resolve_path(PATH_SCHEME + "sha256:" + "0" * 64)
        with pytest.raises(KeyError):
            reg.resolve_path("/not/a/soak/path")

    def test_scan_body_shape(self):
        reg = SyntheticRegistry(RegistrySpec(seed=31,
                                             hostile_rate=1.0))
        m = reg.manifest(4)
        body = reg.scan_body(m, idempotency_key="k1")
        assert body["idempotency_key"] == "k1"
        assert body["blob_ids"] == list(m["layers"])
        assert body["target"].startswith(m["tenant"] + "/")
        assert body["hostile"] is True

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            RegistrySpec(layers=0)
        with pytest.raises(ValueError):
            RegistrySpec(reuse=1.5)
        with pytest.raises(ValueError):
            RegistrySpec(tenants=("a",), tenant_weights=(1, 2))


# ---------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------

class TestScenario:
    def test_schedule_byte_identity(self):
        for name in SCENARIOS:
            a, b = load_scenario(name), load_scenario(name)
            assert a.to_json() == b.to_json()
            assert a.digest() == b.digest()

    def test_seed_override_changes_schedule(self):
        a = load_scenario("soak-smoke")
        b = load_scenario("soak-smoke", seed=99)
        assert a.digest() != b.digest()
        assert b.spec.registry.seed == 99

    def test_arrivals_sorted_and_bounded(self):
        sc = load_scenario("soak-smoke")
        arr = sc.arrivals()
        assert arr == sorted(arr)
        assert all(0 <= t < sc.spec.duration_s for t, _ in arr)
        assert all(0 <= i < sc.spec.registry.images
                   for _, i in arr)

    def test_diurnal_rate_swings(self):
        sc = load_scenario("soak-smoke")
        quarter = sc.spec.duration_s / 4
        assert sc.rate_at(quarter) > sc.rate_at(3 * quarter)

    def test_step_validation(self):
        with pytest.raises(ValueError):
            Step(t=1.0, kind="meteor-strike")
        with pytest.raises(ValueError):
            Step(t=-1.0, kind="kill")
        with pytest.raises(ValueError):
            ScenarioSpec(duration_s=10.0,
                         steps=(Step(t=99.0, kind="kill"),))

    def test_step_fault_spec_composition(self):
        st = Step(t=1.0, kind="storm",
                  fault="event-storm:storm_events=64,"
                        "storm_malformed=4")
        spec = st.fault_spec()
        assert spec.storm_events == 64
        assert spec.storm_malformed == 4
        assert Step(t=1.0, kind="kill").fault_spec() is None

    def test_load_scenario_from_file(self, tmp_path):
        doc = {"name": "filecase", "seed": 5, "duration_s": 10.0,
               "compression": 2.0, "base_rate": 5.0,
               "registry": {"seed": 5, "layers": 100, "images": 50},
               "steps": [{"t": 4.0, "kind": "kill"}]}
        p = tmp_path / "scenario.json"
        p.write_text(json.dumps(doc))
        sc = load_scenario(str(p))
        assert sc.spec.name == "filecase"
        assert sc.spec.steps[0].kind == "kill"
        assert sc.spec.registry.layers == 100

    def test_load_scenario_rejects_unknown(self, tmp_path):
        with pytest.raises(ValueError, match="unknown scenario"):
            load_scenario("no-such-preset")
        p = tmp_path / "bad.json"
        p.write_text(json.dumps(
            {"steps": [{"t": 1.0, "kind": "kill",
                        "blast_radius": 3}]}))
        with pytest.raises(ValueError, match="unknown step"):
            load_scenario(str(p))

    def test_presets_have_designed_trips(self):
        for name, spec in SCENARIOS.items():
            trips = [st for st in spec.steps if st.expect_trip]
            assert trips, f"{name} has no designed SLO trip"
            kinds = {st.kind for st in spec.steps}
            assert {"storm", "kill", "scale_up",
                    "hot_swap"} <= kinds


# ---------------------------------------------------------------
# bounded-growth audit
# ---------------------------------------------------------------

class TestResourceAudit:
    @staticmethod
    def _verdict(values, **kw):
        return ResourceAudit._bounded(
            values, kw.get("warmup_frac", 0.25),
            kw.get("tolerance", 0.10), kw.get("slack", 4.0))

    def test_flat_series_passes(self):
        assert self._verdict([100.0] * 30)["ok"]

    def test_noisy_plateau_passes(self):
        vals = [100.0 + (i % 7) for i in range(40)]
        assert self._verdict(vals)["ok"]

    def test_monotone_creep_fails(self):
        vals = [100.0 + 10.0 * i for i in range(40)]
        assert not self._verdict(vals)["ok"]

    def test_warmup_growth_forgiven(self):
        """A series that climbs during warm-up then flattens is the
        healthy shape — caches filling, pools spinning up."""
        vals = [10.0 * i for i in range(10)] + [100.0] * 30
        assert self._verdict(vals)["ok"]

    def test_sentinels_ignored(self):
        vals = [100.0, -1.0] * 20
        v = self._verdict(vals)
        assert v["ok"] and v["samples"] == 20

    def test_too_few_samples_passes(self):
        assert self._verdict([1.0, 2.0, 3.0])["ok"]

    def test_slack_absorbs_jitter(self):
        vals = [100.0] * 20 + [103.0] * 10
        assert self._verdict(vals, slack=4.0)["ok"]
        assert not self._verdict([100.0] * 20 + [200.0] * 10,
                                 slack=4.0)["ok"]

    def test_probe_errors_degrade(self):
        audit = ResourceAudit()

        def boom():
            raise RuntimeError("dead replica")
        audit.add_probe("broken", boom)
        row = audit.sample()
        assert row["broken"] == -1.0

    def test_gated_vs_informational(self):
        audit = ResourceAudit(warmup_frac=0.0)
        grow = iter(range(1000))
        audit.add_probe("leaky", lambda: 100 * next(grow))
        audit.add_probe("corpus", lambda: 100 * next(grow),
                        gate=False)
        for _ in range(30):
            audit.sample()
        v = audit.verdict()
        assert not v["ok"]
        assert not v["series"]["leaky"]["ok"]
        assert v["series"]["leaky"]["gated"]
        assert not v["series"]["corpus"]["gated"]
        # flip: only ungated series growing => verdict ok
        audit2 = ResourceAudit(warmup_frac=0.0)
        grow2 = iter(range(1000))
        audit2.add_probe("corpus", lambda: 100 * next(grow2),
                         gate=False)
        for _ in range(30):
            audit2.sample()
        assert audit2.verdict()["ok"]

    def test_process_stats_sampled(self):
        audit = ResourceAudit()
        row = audit.sample()
        assert {"rss_bytes", "open_fds", "threads"} <= set(row)


# ---------------------------------------------------------------
# process self-stats gauges (satellite: every exposition)
# ---------------------------------------------------------------

class TestProcessGauges:
    def test_procstats_shape(self):
        from trivy_tpu.obs.procstats import process_self_stats
        st = process_self_stats()
        assert set(st) == {"rss_bytes", "peak_rss_bytes",
                           "open_fds", "threads"}
        assert st["threads"] >= 1
        # on Linux /proc/self is live; elsewhere -1 sentinels
        assert st["rss_bytes"] == -1 or st["rss_bytes"] > 0
        # the peak ratchet never reads below the live gauge
        assert st["peak_rss_bytes"] >= st["rss_bytes"]

    def test_render_prometheus_carries_gauges(self):
        from trivy_tpu.obs.prom import render_prometheus
        text = render_prometheus({"process": {
            "rss_bytes": 1024, "open_fds": 12, "threads": 3}})
        assert "trivy_tpu_process_rss_bytes 1024" in text
        assert "trivy_tpu_process_open_fds 12" in text
        assert "trivy_tpu_process_threads 3" in text

    def test_render_prometheus_skips_sentinels(self):
        from trivy_tpu.obs.prom import render_prometheus
        text = render_prometheus({"process": {
            "rss_bytes": -1, "open_fds": 12, "threads": 3}})
        assert "trivy_tpu_process_rss_bytes" not in text
        assert "trivy_tpu_process_open_fds 12" in text

    def test_router_exposition_carries_gauges(self):
        from trivy_tpu.obs.prom import render_router
        from trivy_tpu.router.metrics import RouterMetrics
        m = RouterMetrics()
        text = render_router(
            {"router": m.snapshot(),
             "router_hists": m.hist_snapshot(),
             "process": {"rss_bytes": 2048, "open_fds": 7,
                         "threads": 2}})
        assert "trivy_tpu_process_rss_bytes 2048" in text


# ---------------------------------------------------------------
# sim replica chaos surface
# ---------------------------------------------------------------

@pytest.fixture()
def sim():
    from trivy_tpu.router.sim import SimReplica
    replica = SimReplica(name="chaos-sim", service_ms=1.0,
                        seed=77, slo_availability=0.995).start()
    yield replica
    replica.stop()


def _post(url, doc):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=5.0) as resp:
        return resp.status, json.loads(resp.read() or b"{}")


class TestSimChaos:
    def test_chaos_error_rate(self, sim):
        from trivy_tpu.router.sim import SCANNER_PREFIX
        status, state = _post(sim.url + "/chaos",
                              {"error_rate": 1.0})
        assert status == 200 and state["error_rate"] == 1.0
        try:
            _post(sim.url + SCANNER_PREFIX + "Scan",
                  {"idempotency_key": "k", "target": "t",
                   "artifact_id": "a", "blob_ids": ["b"]})
            raise AssertionError("expected 500")
        except urllib.error.HTTPError as e:
            assert e.code == 500
        assert sim.metrics()["chaos_errors"] == 1
        # knobs are read-modify-write: clearing restores service
        _post(sim.url + "/chaos", {"error_rate": 0.0})
        status, _ = _post(sim.url + SCANNER_PREFIX + "Scan",
                          {"idempotency_key": "k2", "target": "t",
                           "artifact_id": "a", "blob_ids": ["b"]})
        assert status == 200

    def test_db_generation_swap_clears_warm(self, sim):
        from trivy_tpu.router.sim import SCANNER_PREFIX
        _post(sim.url + SCANNER_PREFIX + "Scan",
              {"idempotency_key": "w1", "target": "t",
               "artifact_id": "a", "blob_ids": ["sha256:x"]})
        assert sim.metrics()["warm_digests"] == 1
        _post(sim.url + "/chaos", {"db_generation": 2})
        m = sim.metrics()
        assert m["warm_digests"] == 0
        assert m["db_swaps"] == 1
        assert m["db_generation"] == 2

    def test_chaos_rejects_non_dict(self, sim):
        try:
            _post(sim.url + "/chaos", ["not", "a", "dict"])
            raise AssertionError("expected 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400

    def test_metrics_snapshot_federation_contract(self, sim):
        with urllib.request.urlopen(
                sim.url + "/metrics/snapshot", timeout=5.0) as r:
            snap = json.loads(r.read())
        assert {"name", "build_info", "prom", "slo_export",
                "mono"} <= set(snap)
        assert snap["name"] == "chaos-sim"
        assert isinstance(snap["slo_export"], dict)

    def test_metrics_carry_process_stats(self, sim):
        m = sim.metrics()
        assert "process" in m
        assert m["process"]["threads"] >= 1


# ---------------------------------------------------------------
# end-to-end runner (seconds-scale)
# ---------------------------------------------------------------

def _tiny_scenario():
    return Scenario(ScenarioSpec(
        name="e2e-tiny", seed=7, duration_s=15.0, compression=3.0,
        base_rate=25.0,
        registry=RegistrySpec(seed=7, layers=5_000, images=800,
                              hostile_rate=0.02),
        steps=(
            Step(t=2.0, kind="storm",
                 fault="event-storm:storm_events=40,"
                       "storm_digests=4,storm_malformed=6"),
            Step(t=4.0, kind="kill"),
            Step(t=5.0, kind="scale_up"),
            Step(t=7.0, kind="hot_swap", duration=2.0),
            Step(t=10.0, kind="brownout", duration=4.0, value=1.0,
                 expect_trip=True),
        )))


def _run_tiny():
    return run_soak(_tiny_scenario(), replicas=2, epoch_s=0.3,
                    service_ms=2.0, slo_availability=0.995)


class TestSoakRunnerE2E:
    @pytest.fixture(scope="class")
    def report(self):
        return _run_tiny()

    def test_books_balance_fleet_wide(self, report):
        st = report["stable"]
        assert st["lost"] == 0
        assert st["books_balanced"]
        w = report["books"]["watch"]
        assert w["events"] == w["scans"] + w["deduped"] + w["shed"]

    def test_invoice_balances_through_chaos(self, report):
        # the per-tenant invoice rides the verdict: its totals
        # equal the fleet ledger, and the accounting identity
        # holds through the kill + scale_up + hot_swap chaos
        assert report["stable"]["invoice_totals_match"]
        inv = report["costs"]
        assert inv["balance"]["balanced"], inv["balance"]
        assert inv["tenants"], "no tenant was ever billed"
        tenant_sum = sum(v["device_s"]
                         for v in inv["tenants"].values())
        assert tenant_sum == pytest.approx(
            inv["attributed_device_s"], rel=1e-3)

    def test_peak_rss_in_verdict(self, report):
        assert report["fleet"]["peak_rss_bytes"] > 0
        series = report["audit"]["series"]
        assert "replica_peak_rss_bytes" in series
        assert not series["replica_peak_rss_bytes"]["gated"]

    def test_designed_trip_exact_with_evidence(self, report):
        trip = report["slo"]["trip"]
        assert trip["tripped"] and not trip["early_trip"]
        assert not trip["missed_trip"]
        assert trip["dumps"] > 0, \
            "designed trip left no flight-recorder dumps"
        # never before the designed window (late is allowed: one
        # epoch of federation staleness — the runner's grace rule)
        window = trip["expected"][0]
        assert trip["first_trip_t"] >= window["real_start"]

    def test_chaos_was_actually_injected(self, report):
        c = report["books"]["counters"]
        assert c["kills"] == 1
        assert c["scale_ups"] == 1
        assert c["hot_swaps"] == 1
        assert c["storm_envelopes"] > 0
        assert c["push_malformed"] == 6
        assert c["scans_failed"] + c["scans_shed"] > 0

    def test_report_schema_stable(self, report):
        # serializes canonically; wall-clock isolated under "wall"
        doc = json.dumps(report, sort_keys=True)
        assert json.loads(doc) == report
        assert set(report["wall"]) == {"started_unix",
                                       "duration_s"}
        sv = stable_view(report)
        assert "wall" not in sv
        for key in ("schedule_digest", "books_balanced", "lost",
                    "trips_exact", "audit_ok", "scenario", "seed",
                    "events_pushed", "malformed"):
            assert key in report["stable"], key

    def test_stable_view_matches_schedule(self, report):
        sc = _tiny_scenario()
        assert report["stable"]["schedule_digest"] == sc.digest()
        assert report["stable"]["arrivals"] == \
            len(sc.schedule()["arrivals"])

    def test_audit_sampled_and_gated(self, report):
        audit = report["audit"]
        assert audit["epochs"] >= 6
        gated = {k for k, v in audit["series"].items()
                 if v["gated"]}
        assert {"rss_bytes", "threads", "watch_backlog",
                "cursor_ack_window"} <= gated
        assert not audit["series"]["registry_index"]["gated"]
        assert report["stable"]["audit_ok"], audit

    def test_same_seed_run_is_byte_identical_in_the_stable_slice(
            self, report):
        assert stable_view(_run_tiny()) == stable_view(report)

    def test_cli_parser_accepts_soak(self):
        from trivy_tpu.cli import build_parser
        args = build_parser().parse_args(
            ["soak", "--scenario", "soak-smoke", "--replicas",
             "2", "--seed", "3", "--report", "/tmp/r.json"])
        assert args.command == "soak"
        assert args.replicas == 2 and args.seed == 3
