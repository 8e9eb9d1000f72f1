"""Can this host's kernel tell a thread's computing from its calling
from its waiting, and what does asking cost (PERF.md section 6, PR 35)?

The phase clock (``trivy_tpu/obs/trace.phase_span``) splits a phase's
wall into ``user_s``, ``sys_s`` and ``wait_s`` from
``getrusage(RUSAGE_THREAD)``. Whether that split means anything is the
host's business, so this prints, with no JAX and on one thread:

(a) the split of three loops whole: 20,000 ``os.lstat`` of a path of
    seven components under ``TMPDIR``, a pure Python loop, a sleep;
(b) the same two working loops cut into spans of about 1 ms and of
    about 20 ms, raw and through ``phase_span``, their ``user`` and
    ``sys`` summed (and what would be left had each span's CPU been
    held to its wall: the kernel moves a thread's books at its tick,
    not at the call), beside how far three whole loops scatter;
(c) the two loops taking turns, a span of about 1 ms each, each kind
    summed apart: what two phases that alternate on one thread read;
(d) ``phase_span`` enter plus exit in ns, the clock calls it makes,
    and the ``gc.callbacks`` entry's cost at a young collection.

    python3 examples/phase_clock_probe.py

clocks the ``phase_span`` of the checkout it lies in; copied into an
older checkout's ``examples/`` it clocks that one's, which may lack
what (b) and (d) ask of it.

    python3 examples/phase_clock_probe.py --check CELL --seed N --trace 1

runs one cell of the benchmark on the chip through ``benchmark/run.py``
and prints, from the window's delta of the phase rows: each row's two
identities (``user_s + sys_s = cpu_s``, ``cpu_s + wait_s = busy_s``),
which phases ran inside another on their thread, the outermost phases'
CPU against the process row's, spans a unit, and collections a second.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import resource
import shutil
import sys
import tempfile
import threading
import time
import timeit

HERE = os.path.dirname(os.path.abspath(__file__))
LSTATS = 20000
SPAN_S = 1e-3
SPANS_S = (SPAN_S, 20e-3)       # a pack's length, a layer's


def rusage() -> tuple:
    r = resource.getrusage(resource.RUSAGE_THREAD)
    return r.ru_utime, r.ru_stime, time.perf_counter()


def clocked(fn, *args) -> dict:
    u0, s0, t0 = rusage()
    fn(*args)
    u1, s1, t1 = rusage()
    return {"user_s": u1 - u0, "sys_s": s1 - s0, "wall_s": t1 - t0}


def lstat_loop(path: str, n: int) -> None:
    lstat = os.lstat
    for _ in range(n):
        lstat(path)


def python_loop(n: int) -> int:
    x = 0
    for i in range(n):
        x = (x * 31 + i) & 0xffffff
    return x


def cut(fn, arg_of, total: int, chunk: int, span=None) -> dict:
    """``fn`` over ``total`` in spans of ``chunk``; the spans' sums.
    ``span`` is ``phase_span`` bound to a row, or nothing for the raw
    clock."""
    out = {"user_s": 0.0, "sys_s": 0.0, "wall_s": 0.0, "spans": 0,
           "clamped_cpu_s": 0.0}
    done = 0
    while done < total:
        k = min(chunk, total - done)
        if span is None:
            got = clocked(fn, *arg_of(k))
            for key in ("user_s", "sys_s", "wall_s"):
                out[key] += got[key]
            # what a clock that held each span's CPU to its wall
            # would keep of these books
            out["clamped_cpu_s"] += min(
                got["user_s"] + got["sys_s"], got["wall_s"])
        else:
            with span():
                fn(*arg_of(k))
        out["spans"] += 1
        done += k
    return out


def shares(row: dict) -> str:
    cpu = row["user_s"] + row["sys_s"]
    wall = row.get("wall_s", row.get("busy_s", 0.0))
    line = (f"user {row['user_s']:.4f} sys {row['sys_s']:.4f} "
            f"wall {wall:.4f} s")
    if cpu and wall:
        line += (f" (sys {100 * row['sys_s'] / cpu:.1f}% of cpu, "
                 f"cpu {100 * cpu / wall:.1f}% of wall)")
    return line


def probe(trace) -> None:
    """(a) to (c). ``trace`` is ``trivy_tpu.obs.trace``."""
    root = tempfile.mkdtemp(prefix="phase-probe-")
    try:
        deep = os.path.join(root, *"bcdef")
        os.makedirs(deep)
        path = os.path.join(deep, "file")
        with open(path, "w") as f:
            f.write("x")
        below = os.path.relpath(path, os.path.dirname(root))
        print(f"TMPDIR {tempfile.gettempdir()}: lstat of "
              f"{below.count(os.sep) + 1} components below it")
        lstat_loop(path, 200)
        python_loop(20000)
        # how much Python is half a second here
        t0 = time.perf_counter()
        python_loop(200000)
        loops = int(200000 * 0.5 / (time.perf_counter() - t0))

        # each working loop whole three times: what the host's books
        # read for the same work, and how far they scatter by
        # themselves
        work = {"lstat": (lstat_loop, lambda k: (path, k), LSTATS),
                "python": (python_loop, lambda k: (k,), loops)}
        print("(a) whole")
        wholes, whole = {}, {}
        for name, (fn, arg_of, total) in work.items():
            wholes[name] = [clocked(fn, *arg_of(total))
                            for _ in range(3)]
            for row in wholes[name]:
                print(f"  {name:7s} {shares(row)}")
            whole[name] = sorted(
                wholes[name],
                key=lambda r: r["user_s"] + r["sys_s"])[1]
        print(f"  sleep   {shares(clocked(time.sleep, 0.5))}")

        for span_s in SPANS_S:
            chunk = {name: max(1, round(
                span_s * work[name][2] / whole[name]["wall_s"]))
                for name in work}
            print(f"(b) cut into spans of about "
                  f"{span_s * 1e3:.0f} ms ({chunk['lstat']} lstat, "
                  f"{chunk['python']} turns), against the middle "
                  "whole loop")
            for name, (fn, arg_of, total) in work.items():
                raw = cut(fn, arg_of, total, chunk[name])
                line = {"raw": raw}
                print(f"  {name:7s} raw        {shares(raw)} in "
                      f"{raw['spans']} spans; each span's cpu held "
                      f"to its wall: {raw['clamped_cpu_s']:.4f}")
                if hasattr(trace, "ensure_phase"):      # PR 35 on
                    before = trace.phase_rows("probe").get(name)
                    cut(fn, arg_of, total, chunk[name],
                        span=lambda: trace.phase_span(
                            name, pipeline="probe"))
                    row = trace.phase_rows("probe")[name]
                    if before:
                        row = {k: row[k] - before[k] for k in row}
                    line["phase_span"] = row
                    print(f"  {name:7s} phase_span {shares(row)} "
                          f"wait {row['wait_s']:.4f}")
                cpu = whole[name]["user_s"] + whole[name]["sys_s"]
                for how, got in line.items():
                    print(f"    {how}: " + ", ".join(
                        f"{key} {got[key]:.4f} for "
                        f"{whole[name][key]:.4f} "
                        f"({100 * (got[key] - whole[name][key]) / cpu:+.1f}%"
                        " of the loop's cpu)"
                        for key in ("user_s", "sys_s")))
                scatter = [r["user_s"] + r["sys_s"]
                           for r in wholes[name]]
                print(f"    the whole loops' own cpu: "
                      f"{min(scatter):.4f} to {max(scatter):.4f} "
                      f"({100 * (max(scatter) - min(scatter)) / cpu:.1f}%)")

        chunk = {name: max(1, round(
            SPAN_S * work[name][2] / whole[name]["wall_s"]))
            for name in work}
        print("(c) the two loops taking turns, a span each")
        turns = {name: dict.fromkeys(("user_s", "sys_s", "wall_s"), 0.0)
                 for name in work}
        n = min(LSTATS // chunk["lstat"], loops // chunk["python"])
        for _ in range(n):
            for name, args in (
                    ("lstat", (path, chunk["lstat"])),
                    ("python", (chunk["python"],))):
                got = clocked(work[name][0], *args)
                for key in got:
                    turns[name][key] += got[key]
        for name, row in turns.items():
            print(f"  {name:7s} {shares(row)} in {n} spans")
    finally:
        shutil.rmtree(root, ignore_errors=True)


def cost(trace) -> None:
    """(d): ns a call."""
    out = {}
    n = 200000

    def per(fn, number=n):
        return min(timeit.repeat(fn, number=number, repeat=5)) \
            / number * 1e9

    rt = resource.RUSAGE_THREAD
    # how far the kernel's books move at a time, for a thread that
    # computes: its timer tick
    steps = []
    for _ in range(7):
        r0 = resource.getrusage(rt)
        while True:
            r = resource.getrusage(rt)
            if r[0] + r[1] != r0[0] + r0[1]:
                break
        steps.append(r[0] + r[1] - r0[0] - r0[1])
    out["rusage_step_ms"] = sorted(steps)[len(steps) // 2] * 1e3
    out["getrusage_thread_ns"] = per(lambda: resource.getrusage(rt))
    out["thread_time_ns"] = per(time.thread_time)
    out["monotonic_ns"] = per(time.monotonic)
    span = trace.phase_span

    def one():
        with span("cost", pipeline="probe"):
            pass
    out["phase_span_ns"] = per(one, 100000)
    on_gc = getattr(trace, "_on_gc", None)
    if on_gc is not None:
        young = {"generation": 0}
        out["gc_callback_young_ns"] = per(
            lambda: on_gc("start", young))
    print("(d) ns a call:", json.dumps(
        {k: round(v, 3) for k, v in out.items()}))


# ---------------------------------------------------------------------
# --check: one traced cell, the rows' identities
# ---------------------------------------------------------------------

def check(cell_name: str, seed: int, seconds: float,
          traced: bool) -> int:
    bench_dir = os.path.join(os.path.dirname(HERE), "benchmark")
    for p in (os.path.dirname(HERE), bench_dir):
        if p not in sys.path:
            sys.path.insert(0, p)
    import run as bench
    from trivy_tpu.obs import trace

    # which phases open inside another on their own thread
    local = threading.local()
    nested: dict = {}
    ctx = trace._PhaseSpanCtx
    enter, leave = ctx.__enter__, ctx.__exit__

    def enter_(self):
        stack = local.__dict__.setdefault("stack", [])
        if stack:
            nested.setdefault((self.pipeline, self.name),
                              set()).add(stack[-1])
        stack.append((self.pipeline, self.name))
        return enter(self)

    def leave_(self, *exc):
        local.stack.pop()
        return leave(self, *exc)

    ctx.__enter__, ctx.__exit__ = enter_, leave_

    cell = bench.Cell(bench.load_cell(cell_name, False), seed,
                      seconds, traced, require_chip=True)
    window: dict = {}
    drive = cell.mode.drive

    def drive_(*args, **kw):
        # the window's own: spans met before it (the warm-up's) say
        # nothing of which rows to add
        nested.clear()
        window["gc0"] = [g["collections"] for g in gc.get_stats()]
        try:
            return drive(*args, **kw)
        finally:
            window["gc1"] = [g["collections"]
                             for g in gc.get_stats()]

    cell.mode.drive = drive_
    try:
        result = cell.run()
    finally:
        cell.mode.drive = drive
    stats = cell.stats
    units = stats["harness"]["units"]
    wall = stats["harness"]["window_s"]
    print(f"check {cell_name} seed {seed} trace {int(traced)}: "
          f"{units} units in {wall:.3f} s, correct "
          f"{result['correct']}, failed {result['failed']}")
    # a parent from before PR 35 has no host rows
    host = stats["detect"].get("host", {})
    tables = {"sched": stats.get("phase", {}),
              "ingest": stats.get("ingest", {}).get("phase", {}),
              "secret": stats["secret"].get("phase", {}),
              "detect": stats["detect"].get("phase", {}),
              "host": host.get("phase", {})}
    worst = [0.0, 0.0]
    outer_cpu = outer_user = spans = 0.0
    for pl, rows in tables.items():
        for ph, r in sorted(rows.items()):
            if not r.get("n"):
                continue
            spans += r["n"]
            inside = sorted(".".join(p) for p in nested.get(
                (pl, ph), ()))
            if "user_s" in r:
                busy = r["busy_s"] or 1e-12
                worst[0] = max(worst[0], abs(
                    r["user_s"] + r["sys_s"] - r["cpu_s"]) / busy)
                worst[1] = max(worst[1], abs(
                    r["cpu_s"] + r["wait_s"] - r["busy_s"]) / busy)
            if not inside:
                outer_cpu += r["cpu_s"]
                outer_user += r.get("user_s", 0.0)
            print(f"  {pl}.{ph}: n {r['n']} busy {r['busy_s']:.4f} "
                  f"cpu {r['cpu_s']:.4f} user "
                  f"{r.get('user_s', float('nan')):.4f} sys "
                  f"{r.get('sys_s', float('nan')):.4f} wait "
                  f"{r.get('wait_s', float('nan')):.4f}"
                  + (f" (inside {', '.join(inside)})"
                     if inside else ""))
    print(f"  identities, worst row, as a share of its busy_s: "
          f"user+sys=cpu {100 * worst[0]:.4f}%, cpu+wait=busy "
          f"{100 * worst[1]:.4f}%")
    proc = host.get("process")
    if proc:
        total = proc["user_s"] + proc["sys_s"]
        print(f"  process: user {proc['user_s']:.4f} sys "
              f"{proc['sys_s']:.4f} s over {wall:.3f} s "
              f"({100 * proc['user_s'] / wall:.1f}% and "
              f"{100 * proc['sys_s'] / wall:.1f}% of one core)")
        print(f"  outermost phases: cpu {outer_cpu:.4f} s "
              f"({'no more than' if outer_cpu <= total else 'MORE THAN'}"
              f" the process's {total:.4f}), user {outer_user:.4f} s "
              f"= {100 * outer_user / wall:.1f}% of the window")
    young = [b - a for a, b in zip(window["gc0"], window["gc1"])]
    print(f"  spans a unit: {spans / max(units, 1):.1f} ({spans:.0f} "
          f"in the window); collections in the window by "
          f"generation {young}: {sum(young[:2]) / wall:.1f} young and "
          "middle ones a second")
    print("  metrics:", json.dumps(
        {k: v["value"] for k, v in result["metrics"].items()}))
    return 0 if result["correct"] and not result["failed"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--check", default="", metavar="CELL")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, default=1, choices=(0, 1))
    args = ap.parse_args(argv)
    if args.check:
        return check(args.check, args.seed, args.seconds,
                     bool(args.trace))
    sys.path.insert(0, os.path.dirname(HERE))
    from trivy_tpu.obs import trace
    probe(trace)
    cost(trace)
    return 0


if __name__ == "__main__":
    sys.exit(main())
