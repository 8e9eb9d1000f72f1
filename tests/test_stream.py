"""Streaming-ingest suite (docs/performance.md §9; ``pytest -m
stream``).

Scan-while-pulling: registry refs stream through
``BatchScanRunner.scan_registry_refs`` against the in-process
:class:`~trivy_tpu.artifact.localreg.LocalRegistry` and must produce
findings byte-identical to the materialize-first pull on both sched
modes, skip warm layers without a single blob GET, degrade a
cache-tier outage to a full pull, quarantine the entire hostile
corpus exactly like the tar path (cancelling — not draining — the
remaining fetch on a mid-stream budget trip), resume torn blob
streams with Range (rewriting from offset zero when the registry
rejects ranges), roll per-layer sub-budgets up to the per-target
cap, and keep pipelined fetch/decompress spans out of the idle
attribution's serialized causes.
"""

import dataclasses
import io
import json
import tarfile
import hashlib
import os
from collections import namedtuple

import numpy as np
import pytest

from tests.test_sched import _norm, make_fleet, make_store
from trivy_tpu.artifact.artifact import ArtifactOption
from trivy_tpu.artifact.localreg import LocalRegistry
from trivy_tpu.artifact.registry import DistributionClient
from trivy_tpu.artifact.stream import (INGEST_METRICS,
                                       clear_config_memo)
from trivy_tpu.faults import FaultInjector, parse_fault_spec
from trivy_tpu.faults.hostile import EXPECTED_STATUS
from trivy_tpu.guard import (ResourceBudget, ResourceBudgetExceeded,
                             ResourceLimits)
from trivy_tpu.guard.budget import LayerBudget
from trivy_tpu.obs.prom import render_prometheus
from trivy_tpu.obs.timeline import CAUSE_SPANS, CAUSES, Timeline
from trivy_tpu.runtime import BatchScanRunner
from trivy_tpu.types import ScanOptions

pytestmark = pytest.mark.stream

SCALE = 0.05


@pytest.fixture(autouse=True)
def _fresh_ingest_state():
    """Process-wide counters and the config-blob memo must not leak
    between tests — every assertion below is on deltas from zero."""
    INGEST_METRICS.reset()
    clear_config_memo()
    yield
    INGEST_METRICS.reset()
    clear_config_memo()


@pytest.fixture
def fleet_registry(tmp_path):
    """A 3-image fleet (tests/test_sched.py fixtures) served from an
    in-process distribution registry: → (registry, refs, tar paths)."""
    paths = make_fleet(tmp_path, 3)
    reg = LocalRegistry()
    for i, p in enumerate(paths):
        reg.add_image("fleet/img", str(i), p)
    reg.start()
    refs = [reg.ref("fleet/img", str(i)) for i in range(len(paths))]
    yield reg, refs, paths
    reg.stop()


def _runner(sched="off", limits=None, injector=None):
    opt = None
    if limits is not None:
        opt = ArtifactOption(ingest_guards=True, ingest_limits=limits)
    return BatchScanRunner(store=make_store(), backend="cpu-ref",
                           sched=sched, artifact_option=opt,
                           fault_injector=injector)


def _scan_refs(refs, sched="off", streaming=True, limits=None,
               injector=None, runner=None, client=None):
    own = runner is None
    if runner is None:
        runner = _runner(sched=sched, limits=limits,
                         injector=injector)
    try:
        return runner.scan_registry_refs(
            refs, client or DistributionClient(),
            ScanOptions(backend="cpu-ref"), streaming=streaming)
    finally:
        if own:
            runner.close()


def _image_tar(path, layer_blobs):
    """Minimal docker-save tar around raw layer blobs (the same
    framing tests/test_sched.make_fleet uses)."""
    diff_ids = ["sha256:" + hashlib.sha256(b).hexdigest()
                for b in layer_blobs]
    config = {"architecture": "amd64", "os": "linux",
              "rootfs": {"type": "layers", "diff_ids": diff_ids},
              "config": {}}
    manifest = [{"Config": "config.json",
                 "RepoTags": [f"big/{os.path.basename(path)}"],
                 "Layers": [f"l{j}.tar"
                            for j in range(len(layer_blobs))]}]
    with tarfile.open(path, "w") as tf:
        def add(name, data):
            ti = tarfile.TarInfo(name)
            ti.size = len(data)
            tf.addfile(ti, io.BytesIO(data))
        add("config.json", json.dumps(config).encode())
        add("manifest.json", json.dumps(manifest).encode())
        for j, b in enumerate(layer_blobs):
            add(f"l{j}.tar", b)
    return path


def _layer_tar(files):
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w") as tf:
        for name, content in files.items():
            ti = tarfile.TarInfo(name)
            ti.size = len(content)
            tf.addfile(ti, io.BytesIO(content))
    return buf.getvalue()


# ---------------------------------------------------------------
# byte-identity: streamed vs materialized, both sched modes
# ---------------------------------------------------------------

class TestStreamParity:
    @pytest.mark.parametrize("sched", ["off", "on"])
    def test_streamed_matches_materialized(self, fleet_registry,
                                           sched):
        reg, refs, _paths = fleet_registry
        streamed = _scan_refs(refs, sched=sched, streaming=True)
        cold = INGEST_METRICS.snapshot()
        pulled = _scan_refs(refs, sched=sched, streaming=False)
        assert _norm(streamed) == _norm(pulled)
        for r in streamed:
            assert r.status == "ok" and not r.error
        # the streaming path actually ran: one stream per ref, every
        # layer accounted for as fetched or warm-skipped
        assert cold["streams"] == len(refs)
        assert cold["layers_fetched"] >= 1
        assert cold["layers_fetched"] + cold["layers_skipped"] == 9
        # findings are real, not vacuously equal empties
        blob = json.dumps([r.report.to_dict() for r in streamed])
        assert "CVE-2099-0001" in blob

    def test_materialized_baseline_does_not_stream(
            self, fleet_registry):
        _reg, refs, _paths = fleet_registry
        pulled = _scan_refs(refs, streaming=False)
        assert all(r.status == "ok" for r in pulled)
        assert INGEST_METRICS.snapshot()["streams"] == 0


# ---------------------------------------------------------------
# warm-layer skip: zero blob GETs, metrics, outage degrade
# ---------------------------------------------------------------

class TestWarmSkip:
    @pytest.mark.parametrize("sched", ["off", "on"])
    def test_warm_repull_zero_blob_gets(self, fleet_registry, sched):
        reg, refs, _paths = fleet_registry
        runner = _runner(sched=sched)
        try:
            cold = runner.scan_registry_refs(
                refs, DistributionClient(),
                ScanOptions(backend="cpu-ref"))
            assert INGEST_METRICS.snapshot()["layers_fetched"] >= 1
            reg.reset_counters()
            warm = runner.scan_registry_refs(
                refs, DistributionClient(),
                ScanOptions(backend="cpu-ref"))
        finally:
            runner.close()
        snap = reg.snapshot()
        # the acceptance gate: a warm re-pull GETs manifests only —
        # not one blob (config blobs ride the digest-addressed memo)
        assert snap["blob_gets"] == 0, snap
        assert snap["manifest_gets"] >= len(refs)
        m = INGEST_METRICS.snapshot()
        assert m["layers_skipped"] >= 9
        assert m["bytes_skipped"] > 0
        assert m["config_memo_hits"] >= len(refs)
        assert _norm(cold) == _norm(warm)

    def test_cache_outage_degrades_to_full_pull(self, fleet_registry,
                                                monkeypatch):
        reg, refs, _paths = fleet_registry
        runner = _runner()

        # an outage of the cache tier the PROBE consults: the keyer
        # blows up before missing_blobs can answer
        def bad_keyer(_self, scan_secrets=True):
            def keyer(_img):
                raise RuntimeError("blob-cache tier down")
            return keyer

        monkeypatch.setattr(BatchScanRunner, "blob_keyer", bad_keyer)
        try:
            results = runner.scan_registry_refs(
                refs, DistributionClient(),
                ScanOptions(backend="cpu-ref"))
        finally:
            runner.close()
        # never an error: the probe outage degrades to a normal pull
        for r in results:
            assert r.status == "ok" and not r.error
        m = INGEST_METRICS.snapshot()
        assert m["warm_probe_outages"] == len(refs)
        assert m["layers_skipped"] == 0
        assert reg.snapshot()["blob_gets"] > 0

    def test_ingest_counters_render_prometheus(self):
        INGEST_METRICS.inc("layers_skipped", 5)
        INGEST_METRICS.inc("bytes_skipped", 1234)
        INGEST_METRICS.inc("range_resumes", 2)
        text = render_prometheus(
            {"ingest": INGEST_METRICS.snapshot()})
        assert "trivy_tpu_ingest_layers_skipped_total 5" in text
        assert "trivy_tpu_ingest_bytes_skipped_total 1234" in text
        assert "trivy_tpu_ingest_range_resumes_total 2" in text
        # every counter key has a family, with HELP/TYPE lines
        for key in INGEST_METRICS.counters:
            assert f"trivy_tpu_ingest_{key}_total" in text
            assert f"# TYPE trivy_tpu_ingest_{key}_total counter" \
                in text

    @pytest.mark.parametrize("sched", ["off", "on"])
    def test_ingest_section_in_server_metrics(self, sched):
        from trivy_tpu.rpc.server import ScanServer
        INGEST_METRICS.inc("layers_skipped", 3)
        srv = ScanServer(store=make_store(), sched=sched)
        try:
            out = srv.metrics()
        finally:
            srv.close()
        assert out["ingest"]["layers_skipped"] == 3
        assert set(out["ingest"]) == set(INGEST_METRICS.snapshot())


# ---------------------------------------------------------------
# hostile corpus through the streaming path
# ---------------------------------------------------------------

class TestHostileStreaming:
    @pytest.mark.parametrize("sched", ["off", "on"])
    def test_corpus_quarantine_parity(self, hostile_corpus,
                                      tmp_path, sched):
        corpus, limits = hostile_corpus(scale=SCALE)
        limits = dataclasses.replace(limits, ingest_deadline_s=30.0)
        reg = LocalRegistry()
        for name, path in corpus:
            reg.add_image(f"hostile/{name}", "latest", path)
        reg.start()
        try:
            refs = [reg.ref(f"hostile/{name}", "latest")
                    for name, _ in corpus]
            streamed = _scan_refs(refs, sched=sched, limits=limits)
        finally:
            reg.stop()
        # ground truth: the same corpus through the local-tar path
        direct_runner = _runner(sched=sched, limits=limits)
        try:
            direct = direct_runner.scan_paths(
                [p for _, p in corpus],
                ScanOptions(backend="cpu-ref"))
        finally:
            direct_runner.close()
        for (name, _), r, d in zip(corpus, streamed, direct):
            assert r.status == EXPECTED_STATUS[name], \
                f"{name}: {r.status} ({r.error})"
            assert r.status == d.status, name
            # identical quarantine verdicts: same typed causes,
            # ingest-stage first
            assert {(c.stage, c.kind) for c in r.causes} == \
                {(c.stage, c.kind) for c in d.causes}, name
            assert r.causes and r.causes[0].stage == "ingest"

    def test_midstream_trip_cancels_remaining_fetch(self, tmp_path):
        # one 16 MiB raw layer against a 256 KiB decompressed-byte
        # cap: the budget trips inside the first fetched chunk and
        # the write-side exception must CANCEL the rest of the blob
        # body, not drain it
        big = _layer_tar({"data.bin": b"\x00" * (16 << 20)})
        path = _image_tar(str(tmp_path / "big.tar"), [big])
        limits = dataclasses.replace(
            ResourceLimits(), max_decompressed_bytes=256 << 10)
        reg = LocalRegistry()
        reg.add_image("big/img", "latest", path)
        reg.start()
        try:
            (res,) = _scan_refs([reg.ref("big/img", "latest")],
                                limits=limits)
            snap = reg.snapshot()
        finally:
            reg.stop()
        assert res.status == "failed"
        assert ("ingest", "resource-budget") in \
            {(c.stage, c.kind) for c in res.causes}
        assert INGEST_METRICS.snapshot()["cancelled_fetches"] >= 1
        # well under the blob's size: the body was cut, not drained
        assert snap["bytes_served"] < len(big) // 2, snap


# ---------------------------------------------------------------
# resumable blob fetch: Range on torn streams
# ---------------------------------------------------------------

class TestRangeResume:
    def _fetch(self, reg, digest, drops=True, chunk=1 << 16):
        client = DistributionClient(backoff_s=0.01,
                                    backoff_max_s=0.05)
        if drops:
            client.fault_injector = FaultInjector(
                parse_fault_spec("registry-flaky"))
        buf = io.BytesIO()
        restarts = []

        def restart():
            restarts.append(buf.tell())
            buf.seek(0)
            buf.truncate()

        n = client.fetch_blob(reg.host, "blobs/unit", digest,
                              buf.write, restart, chunk=chunk)
        return n, buf.getvalue(), restarts

    def test_resume_after_midbody_drops(self):
        data = bytes(range(256)) * (8 << 10)          # 2 MiB
        reg = LocalRegistry()
        desc = reg.put_blob(data)
        reg.start()
        try:
            n, got, restarts = self._fetch(reg, desc["digest"])
            snap = reg.snapshot()
        finally:
            reg.stop()
        assert n == len(data) and got == data
        m = INGEST_METRICS.snapshot()
        # registry-flaky drops the stream twice mid-body; both
        # resumes must ride a 206, never an offset-0 rewrite
        assert m["range_resumes"] == 2
        assert m["full_restarts"] == 0
        assert restarts == []
        assert snap["range_requests"] == 2
        assert snap["range_rejected"] == 0

    def test_rejected_range_rewrites_from_zero(self):
        data = bytes(range(256)) * (8 << 10)
        reg = LocalRegistry(range_support=False)
        desc = reg.put_blob(data)
        reg.start()
        try:
            n, got, restarts = self._fetch(reg, desc["digest"])
            snap = reg.snapshot()
        finally:
            reg.stop()
        # the registry ignored every Range: the sink must have been
        # rewound and the digest still verifies end to end
        assert n == len(data) and got == data
        assert INGEST_METRICS.snapshot()["full_restarts"] >= 1
        assert len(restarts) >= 1
        assert snap["range_rejected"] >= 1

    def test_single_chunk_blob_never_dropped(self):
        # the injector only tears streams past offset 0 — a blob
        # read in one chunk has no mid-body to drop
        data = b"tiny blob"
        reg = LocalRegistry()
        desc = reg.put_blob(data)
        reg.start()
        try:
            n, got, restarts = self._fetch(reg, desc["digest"],
                                           chunk=1 << 20)
        finally:
            reg.stop()
        assert n == len(data) and got == data and restarts == []
        assert INGEST_METRICS.snapshot()["range_resumes"] == 0


# ---------------------------------------------------------------
# per-layer sub-budgets roll up to the per-target cap
# ---------------------------------------------------------------

class TestLayerBudget:
    LIM = ResourceLimits(max_decompressed_bytes=1000, max_files=10,
                         ratio_min_bytes=1 << 30)

    def test_charges_roll_up_to_parent(self):
        parent = ResourceBudget(self.LIM)
        a = LayerBudget(parent, "l0")
        b = LayerBudget(parent, "l1")
        a.charge_decompressed(400)
        b.charge_decompressed(300)
        assert parent.stats()["decompressed"] == 700
        a.charge_entries(3)
        b.charge_entries(4)
        assert parent.stats()["entries"] == 7

    def test_aggregate_trips_per_target_cap(self):
        # each layer is under the cap alone; the aggregate is not
        parent = ResourceBudget(self.LIM)
        a = LayerBudget(parent, "l0")
        b = LayerBudget(parent, "l1")
        a.charge_decompressed(600)
        with pytest.raises(ResourceBudgetExceeded):
            b.charge_decompressed(600)

    def test_layer_trips_same_as_materialized(self):
        # one layer alone past the cap trips on the CHILD check —
        # identical thresholds to a materialized scan of that layer
        parent = ResourceBudget(self.LIM)
        a = LayerBudget(parent, "l0")
        with pytest.raises(ResourceBudgetExceeded):
            a.charge_decompressed(1200)

    def test_entry_aggregate_trips(self):
        parent = ResourceBudget(self.LIM)
        a = LayerBudget(parent, "l0")
        b = LayerBudget(parent, "l1")
        a.charge_entries(6)
        with pytest.raises(ResourceBudgetExceeded):
            b.charge_entries(6)

    def test_ratio_tripwire_stays_with_child(self):
        lim = ResourceLimits(max_compression_ratio=2.0,
                             ratio_min_bytes=16)
        parent = ResourceBudget(lim)
        a = LayerBudget(parent, "l0")
        with pytest.raises(ResourceBudgetExceeded,
                           match="ratio"):
            a.charge_decompressed(100, compressed_total=10)
        # the child tripped before rolling up — the parent never
        # saw the bytes and holds no ratio state of its own
        assert parent.stats()["decompressed"] == 0

    def test_soft_faults_delegate_to_parent(self):
        parent = ResourceBudget(self.LIM)
        a = LayerBudget(parent, "l0")
        a.note("corrupt-rpmdb", "bad pages")
        assert parent.soft_faults == [("corrupt-rpmdb", "bad pages")]
        assert a.soft_faults == []


# ---------------------------------------------------------------
# idle taxonomy: pipelined fetches are not serialized staging
# ---------------------------------------------------------------

FakeSpan = namedtuple("FakeSpan", "name start_mono end_mono attrs",
                      defaults=({},))


def _attr(spans):
    tl = Timeline(spans)
    attr = tl.attribute()
    assert abs(sum(attr.values()) - tl.idle_s) < 1e-6
    return attr


class TestFetchTaxonomy:
    def test_cause_registered(self):
        assert "fetch_serialized" in CAUSES
        names = dict(CAUSE_SPANS)["fetch_serialized"]
        assert names == frozenset({"fetch", "decompress"})

    def test_overlapped_fetch_not_charged(self):
        # fetch [2,6] overlaps compute [0,4] → pipelined staging;
        # its idle tail falls through to the open device window
        spans = [
            FakeSpan("scan", 0.0, 8.0),
            FakeSpan("device", 0.0, 8.0),
            FakeSpan("device_compute", 0.0, 4.0),
            FakeSpan("fetch", 2.0, 6.0),
        ]
        attr = _attr(spans)
        assert attr["fetch_serialized"] == 0.0
        assert attr["dispatch_gap"] == pytest.approx(4.0)

    def test_serialized_fetch_still_charged(self):
        spans = [
            FakeSpan("scan", 0.0, 8.0),
            FakeSpan("device_compute", 0.0, 4.0),
            FakeSpan("decompress", 4.5, 6.0),
        ]
        attr = _attr(spans)
        assert attr["fetch_serialized"] == pytest.approx(1.5)

    def test_priority_below_uploads_above_pack(self):
        # covered idle [1,2]: upload beats fetch; [2,3]: fetch
        # beats pack; [3,4]: pack alone
        spans = [
            FakeSpan("scan", 0.0, 10.0),
            FakeSpan("device_compute", 0.0, 1.0),
            FakeSpan("device_compute", 9.0, 10.0),
            FakeSpan("h2d_upload", 1.0, 2.0),
            FakeSpan("fetch", 1.0, 3.0),
            FakeSpan("pack", 1.0, 4.0),
        ]
        attr = _attr(spans)
        assert attr["upload_serialized"] == pytest.approx(1.0)
        assert attr["fetch_serialized"] == pytest.approx(1.0)
        assert attr["host_pack_bound"] == pytest.approx(1.0)

    @pytest.mark.parametrize("seed", range(8))
    def test_partition_exact_with_overlapping_fetches(self, seed):
        """Seeded soups biased toward fetches overlapping compute:
        the partition stays exact and fetch_serialized equals an
        independent reference over only the never-overlapping
        fetch/decompress spans."""
        rng = np.random.default_rng(7000 + seed)
        spans = [FakeSpan("scan", 0.0, 60.0)]
        busy = []
        for _ in range(int(rng.integers(2, 10))):
            s = float(rng.uniform(0, 50))
            e = s + float(rng.uniform(0.5, 8))
            busy.append((s, e))
            spans.append(FakeSpan("device_compute", s, e))
        fetches = []
        for _ in range(int(rng.integers(2, 12))):
            if rng.random() < 0.5 and busy:
                b = busy[int(rng.integers(0, len(busy)))]
                s = float(rng.uniform(b[0], b[1]))
            else:
                s = float(rng.uniform(0, 55))
            e = s + float(rng.uniform(0.2, 6))
            fetches.append((s, e))
            name = "fetch" if rng.random() < 0.5 else "decompress"
            spans.append(FakeSpan(name, s, e))
        tl = Timeline(spans)
        attr = tl.attribute()
        assert abs(sum(attr.values()) - tl.idle_s) < 1e-6

        def olap(a, b):
            return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))

        serial = [f for f in fetches
                  if all(olap(f, b) <= 0.0 for b in busy)]
        expect = 0.0
        for lo, hi in tl.idle_intervals():
            covered = sorted(
                (max(s, lo), min(e, hi)) for s, e in serial
                if min(e, hi) > max(s, lo))
            cur = lo
            for s, e in covered:
                if e > cur:
                    expect += e - max(s, cur)
                    cur = max(cur, e)
        assert attr["fetch_serialized"] == pytest.approx(
            expect, abs=1e-6)
