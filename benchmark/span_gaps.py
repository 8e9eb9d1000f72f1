"""The device's idle gaps, named by the program's own phases.

The program brackets every pipeline phase with one annotation on the
profiler's clock, ``trivy.<pipeline>.<phase>`` (``phase_span``), and
each device program's enqueue with ``trivy.dispatch.<name>``. From a
profiler directory (the program's ``--profile-out DIR``) this reduces:

``load(path)``: the ``.xplane.pb`` file to plain data, as
``trace_reduce.load_xplane`` does, plus the ``trivy.*`` spans with the
host thread each ran on::

    {"devices": {...}, "host": [[name, start_ns, dur_ns], ...],   # bench.*
     "phases": [[name, start_ns, dur_ns, thread], ...]}            # trivy.*

``reduce(trace)``: the device's idle gaps inside the window, found as
``trace_reduce`` finds them (the ``bench.window`` span where the trace
has one, else the extent of the device's events; the union of the
first device's ``XLA Ops``). Every instant of a gap goes to the phase
that covers it, else to ``no_phase``. Where phases on several threads
cover one instant, a thread that enqueues device programs (it has
``trivy.dispatch.*`` spans in this trace: the device waits for what
such a thread does next) goes before the others, and on one thread the
span that started last, the innermost. ``idle_by_phase`` sums those
pieces and adds up to ``idle_s``; ``idle_gaps`` lists the longest gaps
in ``trace_reduce``'s own shape ``[[label, seconds], ...]``, each under
the label that covers most of it.

Imports nothing of the program. Run as a script it prints the
reduction: ``python3 benchmark/span_gaps.py DIR``.
"""

from __future__ import annotations

import json
import sys

from trace_reduce import (DEVICE_PLANE, MIN_GAP_NS, OPS_LINE, TOP, _clip,
                          find_xplane, union)

PHASE_PREFIX = "trivy."
DISPATCH_PREFIX = "trivy.dispatch."
COMPILE_PREFIX = "trivy.compile."
WINDOW = "bench.window"
NO_PHASE = "no_phase"


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "phases": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = out["devices"].setdefault(plane.name, {})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    lines[line.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for n, line in enumerate(plane.lines):
                thread = f"{line.name}#{n}"
                for e in line.events:
                    if e.name.startswith(PHASE_PREFIX):
                        out["phases"].append(
                            [e.name, e.start_ns, e.duration_ns, thread])
                    elif e.name == WINDOW:
                        out["host"].append(
                            [e.name, e.start_ns, e.duration_ns])
    return out


def window_and_busy(trace: dict) -> tuple:
    """``(lo, hi, busy)``: the window and the merged intervals in
    which an operation ran on the first device, or None."""
    planes = sorted(trace["devices"])
    if not planes:
        return None
    ops = trace["devices"][planes[0]].get(OPS_LINE, [])
    win = [[s, s + d] for n, s, d in trace["host"] if n == WINDOW]
    if win:
        lo, hi = win[0]
    elif ops:
        lo = min(e[1] for e in ops)
        hi = max(e[1] + e[2] for e in ops)
    else:
        return None
    return lo, hi, union([[s, e] for _, s, e in _clip(ops, lo, hi)])


def gaps_of(lo: float, hi: float, busy: list) -> list:
    gaps, at = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > at:
            gaps.append([at, s])
        at = max(at, e)
    return gaps


def pieces(gap: list, spans: list) -> dict:
    """``{label: ns}`` of one gap: cut at every span boundary inside
    it, each piece to the best span covering it. ``spans`` are
    ``(rank, start, end, label)``; the highest rank wins, then the
    latest start."""
    lo, hi = gap
    inside = [sp for sp in spans if sp[1] < hi and sp[2] > lo]
    cuts = sorted({lo, hi} | {min(max(t, lo), hi)
                              for _, s, e, _ in inside for t in (s, e)})
    out: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        over = [sp for sp in inside if sp[1] <= a and sp[2] >= b]
        label = max(over, key=lambda sp: (sp[0], sp[1]))[3] \
            if over else NO_PHASE
        out[label] = out.get(label, 0) + (b - a)
    return out


def reduce(trace: dict) -> dict:
    found = window_and_busy(trace)
    if found is None:
        return {}
    lo, hi, busy = found
    dispatchers = {t for n, _, _, t in trace["phases"]
                   if n.startswith(DISPATCH_PREFIX)}
    spans = [(1 if t in dispatchers else 0, s, s + d,
              n[len(PHASE_PREFIX):])
             for n, s, d, t in trace["phases"]
             if not n.startswith((DISPATCH_PREFIX, COMPILE_PREFIX))]
    by_phase: dict = {}
    labelled = []
    for gap in gaps_of(lo, hi, busy):
        parts = pieces(gap, spans)
        for label, ns in parts.items():
            by_phase[label] = by_phase.get(label, 0) + ns
        if gap[1] - gap[0] >= MIN_GAP_NS:
            labelled.append([max(parts, key=parts.get),
                             (gap[1] - gap[0]) / 1e9])
    idle_ns = sum(by_phase.values())
    busy_ns = sum(e - s for s, e in busy)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_s": idle_ns / 1e9,
        "idle_gaps": sorted(labelled, key=lambda g: -g[1])[:TOP],
        "idle_by_phase": {k: v / 1e9 for k, v in sorted(
            by_phase.items(), key=lambda kv: -kv[1])},
        "labelled_share": 1.0 - by_phase.get(NO_PHASE, 0) / idle_ns
        if idle_ns else 0.0,
    }


if __name__ == "__main__":
    print(json.dumps(reduce(load(find_xplane(sys.argv[1]))), indent=1))
