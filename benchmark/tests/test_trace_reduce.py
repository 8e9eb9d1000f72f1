"""The trace reduction: busy union, idle share, per-program sums, on
a hand-made trace whose numbers are worked out below, and on a small
trace recorded on the chip (``recorded_trace.json``: the first events
of a fleet-secrets window, through ``load_xplane``)."""

import json
import os

import pytest

import trace_reduce
from readers import trace_idle, trace_program_ms, trace_roofline

MS = 1_000_000
HAND = {
    "devices": {"/device:TPU:0": {
        # busy 10..30 (two overlapping ops), 50..60, 95..100 of 0..100
        "XLA Ops": [["fusion.1", 10 * MS, 15 * MS],
                    ["copy.2", 20 * MS, 10 * MS],
                    ["fusion.1", 50 * MS, 10 * MS],
                    ["gather.3", 95 * MS, 20 * MS]],   # clipped at 100
        "XLA Modules": [["jit_fused(17)", 10 * MS, 20 * MS],
                        ["jit_full(18)", 50 * MS, 10 * MS],
                        ["jit_interval_hits_resident_impl(19)",
                         95 * MS, 20 * MS]]}},
    "host": [["bench.window", 0, 100 * MS],
             ["bench.pass", 8 * MS, 40 * MS],
             ["bench.pool_wrap", 62 * MS, 30 * MS]],
}


def test_union_merges_overlaps():
    assert trace_reduce.union([[5, 7], [1, 3], [2, 4], [7, 9]]) == \
        [[1, 4], [5, 9]]


def test_hand_trace():
    r = trace_reduce.reduce(HAND)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.035)       # 20 + 10 + 5 ms
    assert r["programs"]["jit_fused"] == \
        {"count": 1, "seconds": pytest.approx(0.020)}
    assert r["programs"]["jit_interval_hits_resident_impl"][
        "seconds"] == pytest.approx(0.005)           # clipped
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.025)
    gaps = r["idle_gaps"]
    assert [round(s, 3) for _, s in gaps] == [0.035, 0.020, 0.010]
    assert [n for n, _ in gaps] == \
        ["pool_wrap", "host_in_pass", "before_first_dispatch"]


def test_readers_on_the_hand_trace():
    ctx = {"trace": trace_reduce.reduce(HAND),
           "stats": {"harness": {"units": 4},
                     "secret": {"device_bytes": 8192 * 2048}},
           "peaks": {"hbm_bytes_per_s": 819e9},
           "config": {"sizes": {"seg_len": 2048}, "patterns": 160}}
    assert trace_idle.read(ctx) == pytest.approx(65.0)
    assert trace_program_ms.read(
        ctx, "^jit_(fused|full)$", "harness.units") == \
        pytest.approx(7.5)                           # 30 ms / 4
    # 8192 x 2068 bytes at 819 GB/s is 20.68 us; over 30 ms
    assert trace_roofline.read(
        ctx, "^jit_(fused|full)$", "sieve_bytes") == \
        pytest.approx(100 * 8192 * 2068 / 819e9 / 0.030)


def test_nothing_to_read_reads_as_nothing():
    ctx = {"trace": {}, "stats": {}, "peaks": {}, "config": {}}
    assert trace_idle.read(ctx) is None
    assert trace_program_ms.read(ctx, "x", "harness.units") is None
    ctx["trace"] = trace_reduce.reduce(HAND)
    ctx["stats"] = {"harness": {"units": 4}}
    assert trace_program_ms.read(ctx, "^no_such$",
                                 "harness.units") is None
    assert trace_roofline.read(ctx, "^no_such$",
                               "sieve_bytes") is None


def test_recorded_trace():
    path = os.path.join(os.path.dirname(__file__),
                        "recorded_trace.json")
    with open(path, encoding="utf-8") as f:
        trace = json.load(f)
    r = trace_reduce.reduce(trace)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    # the busy union never exceeds the sum of the operations
    total = sum(d for evs in trace["devices"].values()
                for _, _, d in evs.get("XLA Ops", [])) / 1e9
    assert r["busy_s"] <= total + 1e-9
    assert any(n.startswith("jit_") for n in r["programs"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert sum(s for _, s in r["idle_gaps"]) <= \
        r["window_s"] - r["busy_s"] + 1e-9
