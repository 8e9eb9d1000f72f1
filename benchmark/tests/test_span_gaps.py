"""Idle gaps named by the program's phases: on a hand-made trace whose
numbers are worked out below, and on a small trace recorded on the
chip (``recorded_trace_spans.json``: the first seconds of a
fleet-secrets window through ``span_gaps.load``, device operations,
the ``bench.window`` span and the ``trivy.*`` spans)."""

import json
import os

import pytest

import span_gaps
import trace_reduce

MS = 1_000_000
HAND = {
    "devices": {"/device:TPU:0": {
        # busy 10..30 and 60..70 of the window 0..105
        "XLA Ops": [["%dfa_blockmask.1 = custom-call", 10 * MS, 20 * MS],
                    ["%fusion.2 = fusion", 60 * MS, 10 * MS]]}},
    "host": [["bench.window", 0, 105 * MS]],
    "phases": [
        # the thread that enqueues: pack 2..8, then the dispatch
        ["trivy.secret.pack", 2 * MS, 6 * MS, "exec#1"],
        ["trivy.dispatch.dfa_fused", 9 * MS, 1 * MS, "exec#1"],
        ["trivy.compile.dfa_fused", 8 * MS, 1 * MS, "exec#1"],
        # a worker's layer covers 0..50, under everything else
        ["trivy.ingest.layer_analyze", 0, 50 * MS, "worker#2"],
        # the drain thread: verify 30..45; decode nested 32..36
        ["trivy.secret.verify", 30 * MS, 15 * MS, "drain#3"],
        ["trivy.secret.decode", 32 * MS, 4 * MS, "drain#3"],
        # the enqueueing thread again, over part of verify
        ["trivy.detect.pack", 40 * MS, 12 * MS, "exec#1"],
        # nothing covers 52..60 and 70..105 but this short one
        ["trivy.detect.finish", 90 * MS, 5 * MS, "exec#1"],
    ],
}


def test_hand_trace_labels_and_sums():
    r = span_gaps.reduce(HAND)
    assert r["window_s"] == pytest.approx(0.105)
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["idle_s"] == pytest.approx(0.075)
    by = r["idle_by_phase"]
    # gap 0..10: pack 2..8 on the enqueueing thread, the layer under
    # the rest (the compile and dispatch spans name no phase)
    # gap 30..60: verify 30..32, decode 32..36, verify 36..40, the
    # enqueueing thread's pack 40..52 over verify, nothing 52..60
    # gap 70..105: finish 90..95, nothing else
    assert by["secret.pack"] == pytest.approx(0.006)
    assert by["ingest.layer_analyze"] == pytest.approx(0.004)
    assert by["secret.verify"] == pytest.approx(0.006)
    assert by["secret.decode"] == pytest.approx(0.004)
    assert by["detect.pack"] == pytest.approx(0.012)
    assert by["detect.finish"] == pytest.approx(0.005)
    assert by["no_phase"] == pytest.approx(0.038)
    assert sum(by.values()) == pytest.approx(r["idle_s"])
    assert r["labelled_share"] == pytest.approx(37 / 75)
    # trace_reduce's own shape, longest first, each gap under the
    # label that covers most of it
    assert [g[0] for g in r["idle_gaps"]] == \
        ["no_phase", "detect.pack", "secret.pack"]
    assert [g[1] for g in r["idle_gaps"]] == \
        pytest.approx([0.035, 0.030, 0.010])
    assert not any(k.startswith(("dispatch.", "compile.", "bench."))
                   for k in by)


def test_same_gaps_as_trace_reduce():
    """The gaps are the ones ``trace_reduce`` finds: same window, same
    busy union, same lengths; only the labels differ."""
    theirs = trace_reduce.reduce(
        {"devices": HAND["devices"], "host": HAND["host"]})
    ours = span_gaps.reduce(HAND)
    assert ours["busy_s"] == pytest.approx(theirs["busy_s"])
    assert sorted(s for _, s in ours["idle_gaps"]) == \
        pytest.approx(sorted(s for _, s in theirs["idle_gaps"]))


def test_no_window_no_phases_no_device():
    # an operator's trace has no bench.window: the device's extent
    t = {"devices": HAND["devices"], "host": [], "phases": []}
    r = span_gaps.reduce(t)
    assert r["window_s"] == pytest.approx(0.060)     # 10..70
    assert r["idle_by_phase"] == {"no_phase": pytest.approx(0.030)}
    assert r["labelled_share"] == 0.0
    assert span_gaps.reduce(
        {"devices": {}, "host": [], "phases": []}) == {}


def test_recorded_trace():
    path = os.path.join(os.path.dirname(__file__),
                        "recorded_trace_spans.json")
    with open(path, encoding="utf-8") as f:
        trace = json.load(f)
    names = {n for n, _, _, _ in trace["phases"]}
    assert all(n.startswith("trivy.") for n in names)
    assert {"trivy.secret.pack", "trivy.secret.dfa_scan",
            "trivy.secret.verify"} <= names
    assert any(n.startswith("trivy.dispatch.") for n in names)
    assert any("dfa_blockmask" in e[0] for evs in
               trace["devices"].values() for e in evs["XLA Ops"])
    r = span_gaps.reduce(trace)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    assert sum(r["idle_by_phase"].values()) == \
        pytest.approx(r["idle_s"])
    assert "no_phase" in r["idle_by_phase"]
    assert 0.5 < r["labelled_share"] < 1.0
    assert len(r["idle_gaps"]) <= 10
    labels = {g[0] for g in r["idle_gaps"]}
    assert labels <= set(r["idle_by_phase"])
    assert any(lab.startswith("secret.") for lab in labels)
