#!/usr/bin/env python3
"""The benchmark's one command (``BENCHMARK.json`` ``command``):

    python3 benchmark/run.py --workload NAME --seed N --seconds S --trace 0|1

One process, no child. Set-up (device, compile cache, advisory table,
data from the seed, runner, warm-up), then a window of ``--seconds``
driven by the cell's traffic file, then what decides ``correct``. The
last line of standard output is the result; everything else worth
reading goes on earlier lines and on standard error.

    python3 benchmark/run.py --check-manifest

checks ``BENCHMARK.json`` and the data files against the contract
(``manifest.py``) and touches no device.

Everything that belongs to one configuration, one traffic mix, one
traffic mode or one metric is a file of its own (``configs/``,
``workloads/``, ``modes/``, ``end_to_end/``, ``layer_metrics/``,
``readers/``), found by the name in ``BENCHMARK.json`` or in the
traffic file; see ``README.md``.
"""

from __future__ import annotations

import time

T0 = time.monotonic()           # set-up is timed from here

import argparse                                         # noqa: E402
import importlib                                        # noqa: E402
import json                                             # noqa: E402
import os                                               # noqa: E402
import shutil                                           # noqa: E402
import subprocess                                       # noqa: E402
import sys                                              # noqa: E402
import tempfile                                         # noqa: E402
import warnings                                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

class BenchFailure(Exception):
    """The run cannot measure: no chip, no program, a bad name."""


def say(*parts) -> None:
    print(*parts, flush=True)


def load_json(*path) -> dict:
    with open(os.path.join(*path), encoding="utf-8") as f:
        return json.load(f)


def load_cell(name: str, rehearsal: bool = False) -> dict:
    """The cell with its configuration, traffic and metrics, all
    found by the names in BENCHMARK.json."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json: "
                           f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    traffic = load_json(HERE, "workloads", cell["traffic"] + ".json")
    if rehearsal:
        tiny = load_json(HERE, "tests", "tiny.json")
        config["sizes"].update(tiny["sizes"])
        traffic.update(tiny["traffic"][traffic["mode"]])
    end_to_end = [load_json(HERE, "end_to_end", m["name"] + ".json")
                  for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    reported = {m["name"] for m in end_to_end}
    per_layer = []
    for m in bench["per_layer"]:
        if name in m.get("workloads", [name]) and \
                m["moves"] in reported:
            per_layer.append(load_json(
                HERE, "layer_metrics", m["name"] + ".json"))
    return {"name": name, "chips": cell["chips"], "config": config,
            "traffic": traffic, "end_to_end": end_to_end,
            "per_layer": per_layer}


# ---------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------

def find_device(chips: int, require_chip: bool) -> dict:
    try:
        from trivy_tpu.runtime.device import (DeviceUnavailable,
                                              resolve_device)
    except ImportError as e:
        raise BenchFailure(f"no trivy_tpu package beside "
                           f"benchmark/: {e}") from e
    try:
        info = resolve_device("tpu")
    except DeviceUnavailable as e:
        raise BenchFailure(str(e)) from e
    if require_chip and info.platform != "tpu":
        raise BenchFailure(
            f"jax is on {info.platform!r} ({info.device_kind}): the "
            "benchmark measures on a TPU and has no fallback "
            "(--rehearsal with JAX_PLATFORMS=cpu is the dry run)")
    if info.devices < chips:
        raise BenchFailure(f"the cell asks for {chips} chip(s), jax "
                           f"sees {info.devices}")
    return {"platform": info.platform, "kind": info.device_kind,
            "count": info.devices}


def db_cache_path(sizes: dict) -> str:
    return os.path.join(
        HERE, ".cache", f"db-{sizes['os_universe']}-"
        f"{sizes['ghsa_pkgs']}-seed{sizes['db_seed']}")


def build_db(sizes: dict, path: str) -> None:
    """``--build-db``: the table from ``gen.advisory_rows`` through
    the program's own AdvisoryStore and CompiledDB.compile, saved
    under ``path``."""
    import gen
    from trivy_tpu.db import AdvisoryStore, CompiledDB
    store = AdvisoryStore()
    for bucket, pkg, vid, adv, detail in gen.advisory_rows(
            sizes, sizes["db_seed"]):
        store.put_advisory(bucket, pkg, vid, adv)
        if detail is not None:
            store.put_vulnerability(vid, detail)
    tmp = f"{path}.tmp{os.getpid()}"
    os.makedirs(tmp, exist_ok=True)
    CompiledDB.compile(store).save(os.path.join(tmp, "cdb"))
    try:
        os.rename(tmp, path)
    except OSError:                 # another run got there first
        shutil.rmtree(tmp, ignore_errors=True)


def load_db(sizes: dict):
    """The compiled advisory table, one for every seed of a
    configuration (``db_seed``), loaded from the data cache. Where
    it is not there yet it is built first, in a
    child that never touches the chip (JAX_PLATFORMS=cpu): the store
    of a million rows would otherwise leave this process a heap that
    a run from the cache does not have, and its window read 4% to 5%
    slower for it."""
    from trivy_tpu.db import CompiledDB
    path = db_cache_path(sizes)
    built = not os.path.exists(os.path.join(path, "cdb.npz"))
    if built:
        done = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--build-db",
             json.dumps(sizes)],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
            stdout=subprocess.DEVNULL, timeout=1000)
        if done.returncode != 0:
            raise BenchFailure(f"--build-db exit {done.returncode}")
    return CompiledDB.load(os.path.join(path, "cdb")), built


# ---------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------

def snapshot(runner) -> dict:
    from trivy_tpu.detect.metrics import DETECT_METRICS
    from trivy_tpu.runtime.aot import COMPILE_CACHE_METRICS
    from trivy_tpu.secret.metrics import SECRET_METRICS
    out = runner.scheduler.stats() if runner.sched == "on" else {}
    out["detect"] = DETECT_METRICS.snapshot()
    out["secret"] = SECRET_METRICS.snapshot()
    cc = COMPILE_CACHE_METRICS.snapshot()
    out["compile_cache"] = cc
    cc["fresh_compiles"] = \
        cc["persistent_requests"] - cc["persistent_hits"]
    return out


def delta(after, before):
    """Numbers of ``after`` less those of ``before``, tree-wise."""
    if isinstance(after, dict):
        out = {}
        for k, v in after.items():
            d = delta(v, (before or {}).get(k)
                      if isinstance(before, dict) else None)
            if d is not None:
                out[k] = d
        return out
    if isinstance(after, bool) or not isinstance(after, (int, float)):
        return None
    return after - (before if isinstance(before, (int, float))
                    else 0)


class GcPauses:
    """Seconds the interpreter spent in full collections (every
    thread waits for one): what a slow window is checked against
    first."""

    def __init__(self):
        import gc
        self.each, self._t = [], 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if info["generation"] < 2:
            return
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.each.append(round(time.monotonic() - self._t, 2))

    def stop(self):
        import gc
        gc.callbacks.remove(self._on)


def memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ---------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------

class Cell:
    """One run of one cell; ``run()`` returns the result line. The
    traffic file's ``mode`` names the module under ``modes/`` that
    makes the data, warms up, drives the window and pairs what came
    back with the reference; metrics are read by the readers their
    files name."""

    def __init__(self, cell: dict, seed: int, seconds: float,
                 trace: bool, require_chip: bool = True,
                 control: bool = False):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.control = trace, control
        self.require_chip = require_chip
        self.traffic = cell["traffic"]
        self.sizes = cell["config"]["sizes"]
        self.say = say
        try:
            self.mode = importlib.import_module(
                "modes." + self.traffic["mode"])
        except ImportError as e:
            raise BenchFailure(
                f"traffic mode {self.traffic['mode']!r}: {e}") from e

    def fresh_cache(self) -> None:
        from trivy_tpu.artifact.cache import MemoryCache
        self.runner.cache = MemoryCache()

    def read(self, metrics: list, ctx: dict) -> dict:
        out = {}
        for m in metrics:
            reader = importlib.import_module("readers." + m["reader"])
            value = reader.read(ctx, **m.get("args", {}))
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def run(self) -> dict:
        import check
        import gen
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        cell, t, mode = self.cell, self.traffic, self.mode
        device = find_device(cell["chips"], self.require_chip)
        say(f"device: platform: {device['platform']} kind: "
            f"{device['kind']} count: {device['count']}")
        import jax
        from trivy_tpu.ops.program import compiled_programs
        from trivy_tpu.runtime import BatchScanRunner
        from trivy_tpu.types import ScanOptions
        work = tempfile.mkdtemp(prefix="trivy-bench-")
        try:
            t_db = time.monotonic()
            cdb, built = load_db(self.sizes)
            db_s = time.monotonic() - t_db
            say(f"advisory table: {cdb.stats['rows']} rows, "
                f"{'built' if built else 'from the data cache'} "
                f"in {db_s:.1f} s")
            t_data = time.monotonic()
            self.table = gen.GhsaTable(self.sizes["ghsa_pkgs"],
                                       self.sizes["db_seed"])
            data = mode.make_data(self, work)
            data_s = time.monotonic() - t_data
            self.opts = ScanOptions(
                backend="tpu",
                security_checks=list(t["security_checks"]))
            self.runner = BatchScanRunner(store=cdb, backend="tpu",
                                          sched=t["sched"])
            # every shape the window will use: the warm-up set goes
            # through the window's own entry, and must come back ok
            t_warm = time.monotonic()
            bad = mode.warm_up(self, data)
            if bad:
                raise BenchFailure(f"warm-up slots not ok: {bad[:3]}")
            self.fresh_cache()
            warm_s = time.monotonic() - t_warm
            before = snapshot(self.runner)
            programs_before = set(compiled_programs())
            seconds = self.seconds
            trace_dir = ""
            if self.trace:
                seconds = min(seconds, t.get("trace_seconds", seconds))
                trace_dir = os.path.join(work, "trace")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=opts)
            pauses = GcPauses()
            setup_s = time.monotonic() - T0
            rec = mode.drive(self, data, seconds)
            after = snapshot(self.runner)
            pauses.stop()
            if self.trace:
                jax.profiler.stop_trace()
            say(f"host: {len(pauses.each)} full garbage collections "
                f"took {sum(pauses.each):.2f} s of the window "
                f"{pauses.each}")
            for line in rec.get("lines", []):
                say(line)
            new_programs = sorted(set(compiled_programs())
                                  - programs_before)
            peak = memory_peak()
            stats = delta(after, before)
            t_check = time.monotonic()
            got = mode.answers(self, rec, data)
            since_warmup = delta(snapshot(self.runner), before)
            self.runner.close()
            reduced = {}
            if self.trace:
                import trace_reduce
                try:
                    reduced = trace_reduce.reduce(
                        trace_reduce.load_xplane(
                            trace_reduce.find_xplane(trace_dir)))
                except FileNotFoundError as e:
                    say(f"trace: {e}")
        finally:
            shutil.rmtree(work, ignore_errors=True)
        stats["harness"] = {
            "units": rec["units"], "window_s": rec["window_s"],
            "wraps": rec["wraps"], "db_load_s": db_s,
            "data_s": data_s, "warmup_s": warm_s,
            "setup_s": setup_s}

        fresh = stats["compile_cache"]["fresh_compiles"]
        say(f"set-up: {setup_s:.2f} s (table {db_s:.2f}, data "
            f"{data_s:.2f}, warm-up {warm_s:.2f}, jax compile "
            f"{before['compile_cache']['jax_compile_s']:.2f}); "
            f"window: {rec['window_s']:.3f} s, {rec['units']} "
            f"{t['unit']}, {rec['wraps']} pool wraps, "
            f"{len(rec['in_flight'])} in flight at its end")
        say(f"compiles in the window: {fresh} fresh of "
            f"{stats['compile_cache']['persistent_requests']} "
            f"requests; new programs: {new_programs}")
        compared = check.compare(got["answers"], got["never"])
        numbers = compared["numbers"]
        numbers.update(check.guarantees(
            stats, since_warmup, cdb.stats, got["expected_rows"],
            t["security_checks"]))
        correct = check.verdict(numbers)
        for line in compared["examples"]:
            say("differs:", line)
        say(f"reference: {compared['reference_vulns']} "
            f"vulnerabilities and {compared['reference_secrets']} "
            f"secrets in {numbers['reports_compared'][0]} reports, "
            f"{time.monotonic() - t_check:.1f} s after the window")

        attempted = rec["units"] + len(rec["in_flight"])
        failed = numbers["slots_not_ok"][0] + got["never"]
        device["memory_peak_bytes"] = peak
        ctx = {"stats": stats, "trace": reduced, "peaks": {},
               "config": cell["config"], "traffic": t}
        if not self.trace:
            metrics = self.read(cell["end_to_end"], ctx)
        else:
            peaks = load_json(HERE, "peaks.json")
            if device["kind"] not in peaks and self.require_chip:
                raise BenchFailure(f"no peaks for device kind "
                                   f"{device['kind']!r} in peaks.json")
            ctx["peaks"] = peaks.get(device["kind"], {})
            metrics = self.read(cell["per_layer"], ctx)
            device["busy_s"] = reduced.get("busy_s", 0.0)
            device["window_s"] = reduced.get("window_s",
                                             rec["window_s"])
        result = {"correct": bool(correct), "attempted": attempted,
                  "failed": failed, "metrics": metrics,
                  "device": device}
        if self.trace and reduced:
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"]}
            say("programs:", json.dumps(reduced["programs"]))
        result["compared"] = {k: [v, lim] for k, (v, lim, _kind)
                              in numbers.items()}
        self.stats = stats
        return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--check-manifest", action="store_true")
    ap.add_argument("--build-db", default="", help=argparse.SUPPRESS)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on an explicit JAX_PLATFORMS=cpu")
    ap.add_argument("--control", type=int, default=0, choices=(0, 1),
                    help="put the control's answers in the program's "
                         "place: the run has to come out not correct")
    args = ap.parse_args(argv)
    if args.check_manifest:
        import manifest
        errors = manifest.check(ROOT)
        for e in errors:
            print("manifest:", e, file=sys.stderr)
        say(f"manifest: {len(errors)} error(s)")
        return 1 if errors else 0
    if args.build_db:
        sizes = json.loads(args.build_db)
        build_db(sizes, db_cache_path(sizes))
        return 0
    if not args.workload:
        ap.error("--workload is required")
    if args.rehearsal and os.environ.get(
            "JAX_PLATFORMS", "").split(",")[0].strip() != "cpu":
        ap.error("--rehearsal needs JAX_PLATFORMS=cpu")
    try:
        cell = load_cell(args.workload, args.rehearsal)
        result = Cell(cell, args.seed, args.seconds, bool(args.trace),
                      require_chip=not args.rehearsal,
                      control=bool(args.control)).run()
    except (BenchFailure, FileNotFoundError) as e:
        print(f"benchmark: cannot run: {e}", file=sys.stderr)
        return 3
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value} limit {limit}",
              file=sys.stderr)
    sys.stderr.flush()
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
