"""Idle-attribution timeline (trivy_tpu/obs/timeline.py): the
partition invariants on seeded random span trees, the gap-cause
priority rules, per-batch breakdowns, clock discipline (monotonic
stamps only — a wall-clock step mid-batch moves nothing), and the
end-to-end reconstruction over a real fleet scan on both --sched
modes. The old grep-based ``time.time()`` lint that lived here moved
to the AST ``monotonic-clock`` rule in ``trivy_tpu/analysis`` —
tree-wide now, not just ``obs/`` (tests/test_analysis.py)."""

from __future__ import annotations

from collections import namedtuple

import numpy as np
import pytest

from trivy_tpu.obs.timeline import (CAUSE_SPANS, CAUSES,
                                    DEVICE_BUSY, Timeline,
                                    from_tracer)

pytestmark = pytest.mark.obs

FakeSpan = namedtuple(
    "FakeSpan", "name start_mono end_mono attrs",
    defaults=({},))

EPS = 1e-9


def _check_partition(tl: Timeline):
    """The load-bearing invariants: busy+idle tile the window, the
    attribution partitions idle exactly, nothing is negative."""
    attr = tl.attribute()
    assert set(attr) == set(CAUSES)
    for cause, v in attr.items():
        assert v >= 0.0, f"negative attribution for {cause}: {v}"
    assert abs(tl.busy_s + tl.idle_s - tl.window_s) < 1e-6
    assert abs(sum(attr.values()) - tl.idle_s) < 1e-6
    # intervals well-formed: sorted, disjoint, non-negative
    for ivs in (tl.busy_intervals(), tl.idle_intervals()):
        for (s0, e0), (s1, e1) in zip(ivs, ivs[1:]):
            assert e0 <= s1
        for s, e in ivs:
            assert e >= s
    return attr


class TestAttribution:
    def test_empty(self):
        tl = Timeline([])
        assert tl.window_s == 0.0
        assert tl.attribute() == {c: 0.0 for c in CAUSES}
        assert tl.report()["coverage"] == 1.0

    def test_fully_busy_no_idle(self):
        tl = Timeline([FakeSpan("device_compute", 0.0, 10.0)])
        attr = _check_partition(tl)
        assert tl.busy_s == 10.0
        assert sum(attr.values()) == 0.0

    def test_gap_causes_by_priority(self):
        # busy [0,1] and [9,10]; the gap [1,9] is covered by an
        # upload [1,2], a pack [1,3] (overlapping the upload), a
        # decode [3,4], a device window [1,8], and nothing at [8,9]
        spans = [
            FakeSpan("scan", 0.0, 10.0),
            FakeSpan("device_compute", 0.0, 1.0),
            FakeSpan("device_compute", 9.0, 10.0),
            FakeSpan("h2d_upload", 1.0, 2.0),
            FakeSpan("pack", 1.0, 3.0),
            FakeSpan("decode", 3.0, 4.0),
            FakeSpan("device", 1.0, 8.0),
        ]
        attr = _check_partition(Timeline(spans))
        # [1,2] upload wins over pack (priority), [2,3] pack,
        # [3,4] decode, [4,8] device window -> dispatch_gap,
        # [8,9] open scan span but nothing tracked -> unknown
        assert attr["upload_serialized"] == pytest.approx(1.0)
        assert attr["host_pack_bound"] == pytest.approx(1.0)
        assert attr["collect_bound"] == pytest.approx(1.0)
        assert attr["dispatch_gap"] == pytest.approx(4.0)
        assert attr["unknown"] == pytest.approx(1.0)
        assert attr["queue_empty"] == 0.0

    def test_queue_empty_vs_unknown(self):
        # no open root over [2,3] -> queue_empty; root open over
        # [4,5] with nothing tracked -> unknown
        spans = [
            FakeSpan("device_compute", 0.0, 2.0),
            FakeSpan("device_compute", 3.0, 4.0),
            FakeSpan("scan", 4.0, 5.0),
        ]
        attr = _check_partition(Timeline(spans))
        assert attr["queue_empty"] == pytest.approx(1.0)
        assert attr["unknown"] == pytest.approx(1.0)

    def test_overlapping_busy_spans_merge(self):
        spans = [FakeSpan("device_compute", 0.0, 5.0),
                 FakeSpan("dfa_scan", 3.0, 7.0),
                 FakeSpan("dfa_scan", 6.0, 6.5)]
        tl = Timeline(spans)
        assert tl.busy_s == pytest.approx(7.0)
        assert tl.idle_s == 0.0

    def test_explicit_window_clips(self):
        spans = [FakeSpan("device_compute", 2.0, 4.0)]
        tl = Timeline(spans, window=(0.0, 10.0))
        assert tl.window_s == 10.0
        assert tl.busy_s == pytest.approx(2.0)
        attr = _check_partition(tl)
        assert attr["queue_empty"] == pytest.approx(8.0)

    def test_unfinished_spans_ignored(self):
        spans = [FakeSpan("device_compute", 0.0, 1.0),
                 FakeSpan("device_compute", 2.0, None)]
        tl = Timeline(spans)
        assert tl.busy_s == pytest.approx(1.0)

    def test_per_batch_charges_next_dispatch(self):
        spans = [
            FakeSpan("scan", 0.0, 10.0),
            FakeSpan("device", 0.0, 3.0, {"batch": 1}),
            FakeSpan("device_compute", 1.0, 3.0),
            FakeSpan("device", 5.0, 8.0, {"batch": 2}),
            FakeSpan("device_compute", 6.0, 8.0),
        ]
        per = Timeline(spans).per_batch()
        by_batch = {b["batch"]: b for b in per}
        # [0,1] delayed batch 1, [3,6] delayed batch 2, [8,10] tail
        assert by_batch[1]["wait_s"] == pytest.approx(1.0)
        assert by_batch[2]["wait_s"] == pytest.approx(3.0)
        assert by_batch[None]["wait_s"] == pytest.approx(2.0)


class TestOverlappedUploads:
    """The async-runtime attribution rule: an upload span that ran
    concurrently with device compute is PIPELINED — it must not be
    charged as upload_serialized idle; only uploads that actually
    serialize against an idle device count."""

    def test_overlapped_upload_not_charged(self):
        # upload [2,6] overlaps busy [0,4] → pipelined; its idle
        # tail [4,6] must fall through (device window open →
        # dispatch_gap), NOT count as upload_serialized
        spans = [
            FakeSpan("scan", 0.0, 8.0),
            FakeSpan("device", 0.0, 8.0),
            FakeSpan("device_compute", 0.0, 4.0),
            FakeSpan("h2d_upload", 2.0, 6.0),
        ]
        attr = _check_partition(Timeline(spans))
        assert attr["upload_serialized"] == 0.0
        assert attr["dispatch_gap"] == pytest.approx(4.0)

    def test_serialized_upload_still_charged(self):
        # upload [4,6] touches no busy interval → it truly
        # serialized; the covered idle is upload_serialized
        spans = [
            FakeSpan("scan", 0.0, 8.0),
            FakeSpan("device_compute", 0.0, 4.0),
            FakeSpan("h2d_upload", 4.5, 6.0),
        ]
        attr = _check_partition(Timeline(spans))
        assert attr["upload_serialized"] == pytest.approx(1.5)

    def test_slot_wait_cause(self):
        # executor parked on a full ring [3,5] while the device sat
        # idle → typed slot_wait, higher priority than dispatch_gap
        spans = [
            FakeSpan("scan", 0.0, 6.0),
            FakeSpan("device", 0.0, 6.0),
            FakeSpan("device_compute", 0.0, 3.0),
            FakeSpan("slot_wait", 3.0, 5.0),
        ]
        attr = _check_partition(Timeline(spans))
        assert attr["slot_wait"] == pytest.approx(2.0)
        assert attr["dispatch_gap"] == pytest.approx(1.0)

    @pytest.mark.parametrize("seed", range(10))
    def test_partition_exact_with_overlapping_uploads(self, seed):
        """Seeded span soups BIASED toward uploads overlapping
        compute: the partition must stay exact (sum == idle) and
        upload_serialized must equal an independent reference
        computed from only the non-overlapping upload spans."""
        rng = np.random.default_rng(4000 + seed)
        spans = [FakeSpan("scan", 0.0, 60.0)]
        busy = []
        for _ in range(int(rng.integers(2, 10))):
            s = float(rng.uniform(0, 50))
            e = s + float(rng.uniform(0.5, 8))
            busy.append((s, e))
            spans.append(FakeSpan("device_compute", s, e))
        uploads = []
        for _ in range(int(rng.integers(2, 12))):
            if rng.random() < 0.5 and busy:
                # deliberately overlap a busy interval
                b = busy[int(rng.integers(0, len(busy)))]
                s = float(rng.uniform(b[0], b[1]))
            else:
                s = float(rng.uniform(0, 55))
            e = s + float(rng.uniform(0.2, 6))
            uploads.append((s, e))
            spans.append(FakeSpan("h2d_upload", s, e))
        tl = Timeline(spans)
        attr = _check_partition(tl)

        # reference: clip only never-overlapping uploads to idle
        def olap(a, b):
            return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))

        serial = [u for u in uploads
                  if all(olap(u, b) <= 0.0 for b in busy)]
        expect = 0.0
        for lo, hi in tl.idle_intervals():
            covered = []
            for s, e in serial:
                covered.append((max(s, lo), min(e, hi)))
            covered = sorted((s, e) for s, e in covered if e > s)
            cur = lo
            for s, e in covered:
                if e > cur:
                    expect += e - max(s, cur)
                    cur = max(cur, e)
        assert attr["upload_serialized"] == pytest.approx(
            expect, abs=1e-6)


class TestPropertyRandomTrees:
    """Seeded random span soups: the partition invariants must hold
    for ANY input — no overlap, no negative gap, full coverage of
    the device wall."""

    NAMES = tuple(DEVICE_BUSY) + tuple(
        n for _, names in CAUSE_SPANS for n in names) + (
        "scan", "bogus_phase")

    @pytest.mark.parametrize("seed", range(12))
    def test_partition_invariants(self, seed):
        rng = np.random.default_rng(1000 + seed)
        spans = []
        for _ in range(int(rng.integers(1, 80))):
            s = float(rng.uniform(0, 50))
            d = float(rng.uniform(0, 10))
            name = self.NAMES[int(rng.integers(0, len(self.NAMES)))]
            attrs = {"batch": int(rng.integers(1, 5))} \
                if name == "device" and rng.random() < 0.5 else {}
            spans.append(FakeSpan(name, s, s + d, attrs))
        tl = Timeline(spans)
        attr = _check_partition(tl)
        # per-batch totals re-partition the same idle wall
        per = tl.per_batch()
        assert abs(sum(b["wait_s"] for b in per) - tl.idle_s) < 1e-6
        for b in per:
            assert abs(sum(b["attribution"].values())
                       - b["wait_s"]) < 1e-6
        rep = tl.report()
        assert 0.0 <= rep["coverage"] <= 1.0
        assert rep["attribution"].keys() == attr.keys()

    @pytest.mark.parametrize("seed", range(4))
    def test_translation_invariance(self, seed):
        """Shifting every monotonic stamp by a constant must not
        change a single attributed duration — the math depends on
        relative time only."""
        rng = np.random.default_rng(2000 + seed)
        spans = []
        for _ in range(40):
            s = float(rng.uniform(0, 30))
            d = float(rng.uniform(0, 5))
            name = self.NAMES[int(rng.integers(0, len(self.NAMES)))]
            spans.append(FakeSpan(name, s, s + d))
        shift = 12345.678
        shifted = [FakeSpan(sp.name, sp.start_mono + shift,
                            sp.end_mono + shift) for sp in spans]
        a0 = Timeline(spans).attribute()
        a1 = Timeline(shifted).attribute()
        for c in CAUSES:
            assert a0[c] == pytest.approx(a1[c], abs=1e-6)


class TestClockDiscipline:
    """Wall time is labels-only: attribution must not move when the
    wall clock steps mid-batch."""

    def test_wall_step_mid_batch_does_not_move_attribution(
            self, monkeypatch):
        """Real spans through a real Tracer while time.time() jumps
        by hours between spans: the reconstruction must be identical
        to what the monotonic stamps alone dictate."""
        import time as _time

        from trivy_tpu.obs import FlightRecorder, Tracer

        walls = iter([1e9, 1e9 + 7200.0, 1e9 - 3600.0] * 50)
        real_time = _time.time
        monkeypatch.setattr(
            _time, "time",
            lambda: next(walls, None) or real_time())
        tracer = Tracer(recorder=FlightRecorder())
        root = tracer.start_request("clock-step")
        dev = tracer.child(root, "device")
        comp = tracer.child(dev, "device_compute")
        _time.sleep(0.01)
        comp.end()
        pack = tracer.child(dev, "pack")
        _time.sleep(0.01)
        pack.end()
        dev.end()
        root.end()
        tl = from_tracer(tracer)
        attr = _check_partition(tl)
        # busy == the device_compute wall, idle is pack + glue —
        # nothing resembling the (hours-long) wall steps appears
        assert tl.window_s < 5.0
        assert attr["host_pack_bound"] == pytest.approx(
            0.01, abs=0.05)
        assert tl.busy_s == pytest.approx(0.01, abs=0.05)

    # The grep-based monotonic-only lint that lived here was
    # superseded by the AST ``monotonic-clock`` rule
    # (trivy_tpu/analysis, tests/test_analysis.py): exact on the
    # syntax tree instead of regex-adjacent, and swept tree-wide —
    # sched/, watch/, memo/ now carry the same discipline obs/ did.


class TestEndToEnd:
    @pytest.mark.parametrize("sched", ["on", "off"])
    def test_fleet_reconstruction(self, larger_fleet, sched):
        from trivy_tpu.obs import FlightRecorder, Tracer
        from trivy_tpu.runtime import BatchScanRunner
        from trivy_tpu.sched import SchedConfig

        paths, store = larger_fleet(6)
        tracer = Tracer(recorder=FlightRecorder(capacity=64))
        kw = {"sched": SchedConfig(workers=2)} if sched == "on" \
            else {}
        runner = BatchScanRunner(store=store, backend="cpu-ref",
                                 tracer=tracer, **kw)
        try:
            results = runner.scan_paths(paths)
        finally:
            runner.close()
        assert all(r.error == "" for r in results)
        tl = from_tracer(tracer)
        attr = _check_partition(tl)
        rep = tl.report(per_batch=True)
        assert rep["window_s"] > 0
        # the known causes must explain the overwhelming share of
        # idle — this is the acceptance instrument, kept honest
        assert rep["coverage"] >= 0.9, rep
        # a fleet scan does real packing and collecting; those
        # causes must actually appear
        assert attr["host_pack_bound"] > 0
        if sched == "on":
            assert any(b["batch"] is not None
                       for b in rep["per_batch"])
