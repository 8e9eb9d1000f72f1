"""From a ``jax.profiler`` trace to numbers. Two steps, so that the
second can be checked on a small recorded trace:

``load_xplane(path)``: the ``.xplane.pb`` file, read with nothing but
jax, to plain data::

    {"devices": {"/device:TPU:0": {"XLA Ops": [[name, start_ns, dur_ns], ...],
                                    "XLA Modules": [...]}},
     "host": [[name, start_ns, dur_ns], ...]}     # the bench.* spans only

``reduce(trace)``: the window (the harness's ``bench.window`` span, on
the profiler's clock), the union of the intervals in which an
operation ran on each device (``XLA Ops``), averaged over the devices;
device seconds by jitted program (``XLA Modules``; the trailing
``(id)`` of a module's name is dropped); the operations that took most
time; and the longest idle gaps, each named by what the harness knows
the host was doing: ``pool_wrap``, ``host_in_pass``, ``between_passes``
(a gap outside every ``bench.pass`` span where the run has such
spans), ``before_first_dispatch``, ``after_last_dispatch``, else
``host``. The program has no spans on the profiler's clock yet, so
``host`` is as fine as it gets.

Run as a script it prints the reduction of a trace directory:
``python benchmark/trace_reduce.py DIR``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."
TOP = 10
MIN_GAP_NS = 1000         # shorter is the device's own stride


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = out["devices"].setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns]
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return out


def union(intervals: list) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events: list, lo: float, hi: float) -> list:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def program_name(module_event: str) -> str:
    return re.sub(r"\(\d+\)$", "", module_event)


def op_name(event: str) -> str:
    """An operation's event is its whole HLO line; keep the result's
    name, the opcode and, for a custom call, its target."""
    head, _, rest = event.partition(" = ")
    if not rest:
        return event[:100]
    opcode = re.search(r"\s([a-z][a-z\-]*)\(", " " + rest)
    target = re.search(r'custom_call_target=\\?"([\w.\-]+)', rest)
    return " ".join(filter(None, [
        head, opcode.group(1) if opcode else "",
        target.group(1) if target else ""]))[:100]


def _label(gap: list, busy: list, spans: list) -> str:
    mid = (gap[0] + gap[1]) / 2
    inside = [n for n, s, d in spans if s <= mid <= s + d]
    if "bench.pool_wrap" in inside:
        return "pool_wrap"
    if "bench.pass" in inside:
        return "host_in_pass"
    if not busy or gap[1] <= busy[0][0]:
        return "before_first_dispatch"
    if gap[0] >= busy[-1][1]:
        return "after_last_dispatch"
    if any(n == "bench.pass" for n, _, _ in spans):
        return "between_passes"
    return "host"


def reduce(trace: dict) -> dict:
    spans = trace["host"]
    win = [[s, s + d] for n, s, d in spans if n == "bench.window"]
    every = [ev for lines in trace["devices"].values()
             for evs in lines.values() for ev in evs]
    if win:
        lo, hi = win[0]
    elif every:
        lo = min(e[1] for e in every)
        hi = max(e[1] + e[2] for e in every)
    else:
        return {}
    busy_ns, first_busy = [], []
    programs: dict = {}
    ops: dict = {}
    for plane in sorted(trace["devices"]):
        lines = trace["devices"][plane]
        clipped = _clip(lines.get(OPS_LINE, []), lo, hi)
        merged = union([[s, e] for _, s, e in clipped])
        busy_ns.append(sum(e - s for s, e in merged))
        if not first_busy:
            first_busy = merged
        for name, s, e in clipped:
            name = op_name(name)
            ops[name] = ops.get(name, 0.0) + (e - s)
        for name, s, e in _clip(lines.get(MODULES_LINE, []), lo, hi):
            p = programs.setdefault(program_name(name),
                                    {"count": 0, "seconds": 0.0})
            p["count"] += 1
            p["seconds"] += (e - s) / 1e9
    if not busy_ns:
        return {}
    n = len(busy_ns)
    for p in programs.values():         # per chip, like busy_s
        p["seconds"] /= n
    gaps, at = [], lo
    for s, e in first_busy + [[hi, hi]]:
        if s > at:
            gaps.append([at, s])
        at = max(at, e)
    gaps = sorted((g for g in gaps if g[1] - g[0] >= MIN_GAP_NS),
                  key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "devices": n,
        "programs": programs,
        "device_ops": [[k, v / n / 1e9] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(g, first_busy, spans),
                       (g[1] - g[0]) / 1e9] for g in gaps[:TOP]],
    }


def program_seconds(reduced: dict, pattern: str) -> tuple:
    """``(seconds, calls)`` of the programs whose name matches."""
    rx = re.compile(pattern)
    hit = [p for name, p in reduced.get("programs", {}).items()
           if rx.search(name)]
    return (sum(p["seconds"] for p in hit),
            sum(p["count"] for p in hit))


if __name__ == "__main__":
    print(json.dumps(reduce(load_xplane(find_xplane(sys.argv[1]))),
                     indent=1))
