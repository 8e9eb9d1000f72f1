"""AOT shape precompile + persistent compilation cache
(docs/serving.md "Elastic lifecycle").

PR 6 measured ~1.3 s × shapes × devices of first-hit kernel compile;
a scale-up pays that right in the middle of the SLO burn that
triggered it. This module makes the compile spike a boot cost, and a
cheap one:

* :func:`configure_compile_cache` turns on jax's persistent
  compilation cache for the process — where
  ``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed path inside
  the checkout — so an executable compiled by ANY earlier process
  of the same (jax version, backend) is deserialized instead of
  rebuilt. Every entry point calls it through
  ``runtime.device.resolve_device``.
* :func:`warm_ladders` walks the SAME shape ladders the scheduled
  path buckets into (``ops/keywords._bucket`` for segment buffers,
  ``detect/batch._job_bucket`` for a wave's pair rows), whole, up
  to what the scheduler's batch budgets can produce, and executes
  the fused sieve and the resident interval kernel once a rung on
  zero inputs — populating the in-process jit cache (the first
  real request never traces) AND the persistent cache (the next
  replica's boot never rebuilds).
* a JSON **manifest** in the cache dir, keyed by
  ``sha256(jax version | backend | kind | shape | table hash)``,
  records which keyed shapes earlier boots compiled — the
  ``trivy_tpu_compile_cache_{hits,misses}`` split. Any component of
  the key changing (jax upgrade, backend change, new rule set / DB
  ladder) misses cleanly into a fresh entry; stale entries are
  inert, never wrong.

Zero inputs are safe for every kernel here: pad rows are inert by
construction (flags=0 matches nothing, zero segments hit no
pattern), which is the same property the serving-path pad ladder
already relies on.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Iterable

from ..utils import get_logger

log = get_logger("runtime.aot")

MANIFEST_NAME = "trivy_tpu_aot_manifest.json"

class CompileCacheMetrics:
    """Cumulative compile-cache counters, one singleton per
    process. ``bytes`` is computed at snapshot time from the cache
    directory (the persistent cache is shared state on disk, not an
    in-process accumulator)."""

    # hits/misses/precompiled: the AOT manifest split (boot
    # precompile). persistent_requests/persistent_hits: jax's own
    # compile requests that consulted the persistent cache and those
    # it answered — their difference is this process's fresh compiles
    _KEYS = ("hits", "misses", "precompiled",
             "persistent_requests", "persistent_hits")

    def __init__(self):
        self._lock = threading.Lock()
        self._c = {k: 0 for k in self._KEYS}
        self._dir = ""
        self._seconds = 0.0
        self._jax_compile_s = 0.0

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self._c[name] += n

    def add_seconds(self, seconds: float) -> None:
        with self._lock:
            self._seconds += max(0.0, seconds)

    def add_jax_compile_seconds(self, seconds: float) -> None:
        with self._lock:
            self._jax_compile_s += max(0.0, seconds)

    def set_dir(self, path: str) -> bool:
        """True when ``path`` differs from the directory booked."""
        with self._lock:
            changed = self._dir != path
            self._dir = path
        return changed

    def reset(self) -> None:
        """Test hook — production code never calls this."""
        with self._lock:
            for k in self._c:
                self._c[k] = 0
            self._dir = ""
            self._seconds = 0.0
            self._jax_compile_s = 0.0

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self._c)
            out["dir"] = self._dir
            out["seconds"] = round(self._seconds, 6)
            out["jax_compile_s"] = round(self._jax_compile_s, 6)
        out["bytes"] = _dir_bytes(out["dir"])
        return out


COMPILE_CACHE_METRICS = CompileCacheMetrics()


def _dir_bytes(path: str) -> int:
    if not path or not os.path.isdir(path):
        return 0
    total = 0
    for root, _dirs, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(root, f))
            except OSError:
                # racing eviction/rewrite — a size gauge tolerates it
                continue
    return total


ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

# the one in-checkout location (git-ignored). The path is part of
# the cache's key on disk, so it must not move between processes:
# never a temp name, a pid or a timestamp.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_JAX_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache":
        "persistent_requests",
    "/jax/compilation_cache/cache_hits": "persistent_hits",
}
_listening = False


def _on_jax_event(event: str, **_kw) -> None:
    name = _JAX_EVENTS.get(event)
    if name is not None:
        COMPILE_CACHE_METRICS.inc(name)


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    # backend compile wall: a fresh compile, or the cache read that
    # replaced it
    if event == "/jax/core/compile/backend_compile_duration":
        COMPILE_CACHE_METRICS.add_jax_compile_seconds(seconds)


def configure_compile_cache(preferred: str = "") -> str:
    """Turn on jax's persistent compilation cache for this process
    and return its directory. ``JAX_COMPILATION_CACHE_DIR`` wins and
    is left to jax (no directory is set in code); otherwise
    ``preferred`` (the server's ``--compile-cache DIR``), otherwise
    :data:`DEFAULT_CACHE_DIR`. The size and compile-time thresholds
    are dropped so the small interval kernels qualify too. Raises
    ``OSError`` when the directory cannot be created."""
    global _listening
    import jax
    env_dir = os.environ.get(ENV_CACHE_DIR, "")
    cache_dir = env_dir or preferred or DEFAULT_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.0)
    if not _listening:
        _listening = True
        jax.monitoring.register_event_listener(_on_jax_event)
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
    if COMPILE_CACHE_METRICS.set_dir(cache_dir):
        log.info("persistent compile cache at %s", cache_dir)
    return cache_dir


def cache_key(kind: str, shape_sig: str, table_hash: str = "") -> str:
    """Manifest key: jax version × backend × kernel kind × shape ×
    rule-set/table hash — the invalidation domain. Any component
    changing misses into a fresh entry."""
    import jax
    backend = jax.default_backend()
    raw = f"{jax.__version__}|{backend}|{kind}|{shape_sig}|" \
          f"{table_hash}"
    return hashlib.sha256(raw.encode()).hexdigest()[:32]


class _Manifest:
    """The keyed-shape manifest beside the cache entries. Read once,
    appended per precompile, written atomically — two replicas
    racing a boot at worst both compile (correct, just not free)."""

    def __init__(self, cache_dir: str):
        self.path = os.path.join(cache_dir, MANIFEST_NAME) \
            if cache_dir else ""
        self.entries: dict = {}
        if self.path and os.path.exists(self.path):
            try:
                with open(self.path, encoding="utf-8") as f:
                    doc = json.load(f)
                if isinstance(doc, dict):
                    self.entries = doc
            except (OSError, ValueError) as e:
                log.warning("unreadable AOT manifest %s: %r",
                            self.path, e)

    def seen(self, key: str) -> bool:
        return key in self.entries

    def note(self, key: str, meta: dict) -> None:
        self.entries[key] = meta
        if not self.path:
            return
        tmp = self.path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(self.entries, f, sort_keys=True)
            os.replace(tmp, self.path)
        except OSError as e:
            log.warning("AOT manifest write failed: %r", e)


def _warm_call(fn, args, key: str, manifest: _Manifest,
               meta: dict) -> float:
    """Execute one jitted kernel on inert inputs, booking the
    manifest hit/miss and the compile wall. Returns seconds."""
    t0 = time.monotonic()
    if manifest.seen(key):
        COMPILE_CACHE_METRICS.inc("hits")
    else:
        COMPILE_CACHE_METRICS.inc("misses")
    out = fn(*args)
    # materialize: jit dispatch is async, and the point is to pay
    # the whole compile HERE, not on the first request
    try:
        import jax
        jax.block_until_ready(out)
    except (TypeError, ValueError):
        log.debug("non-blockable AOT output for %s", meta)
    dt = time.monotonic() - t0
    manifest.note(key, dict(meta, seconds=round(dt, 4)))
    COMPILE_CACHE_METRICS.inc("precompiled")
    COMPILE_CACHE_METRICS.add_seconds(dt)
    return dt


def sieve_rungs(max_batch_bytes: int, seg_len: int,
                overlap: int) -> tuple:
    """Every ``ops.keywords._bucket`` rung the segment buffer of one
    scheduled batch can take. The coalescer closes a batch within
    ``max_batch_bytes`` of candidates and gives a request over the
    budget a batch of its own; rows are bytes over the segment step
    plus one a file at the most, so twice the budget over the step
    bounds a full batch of small files and one image of up to about
    twice the budget alike. A larger image compiles its rung when it
    comes, as before. A streamed tree's parts are cut to
    ``ops.keywords.PART_ROWS`` rows, the top rung at the default
    budget; a scheduler configured with a smaller one leaves a
    tree's full parts to compile when they come."""
    from ..ops.keywords import _bucket
    top = _bucket(2 * max_batch_bytes // (seg_len - overlap))
    return _rungs(_bucket, top)


def interval_rungs(max_batch_jobs: int) -> tuple:
    """Every ``detect.batch._job_bucket`` rung one wave of a
    scheduled batch can take: a batch holds ``max_batch_jobs`` at
    the most and is launched in waves of ``_WAVE_ROWS``."""
    from ..detect.batch import _WAVE_ROWS, _job_bucket
    return _rungs(_job_bucket,
                  _job_bucket(min(max_batch_jobs, _WAVE_ROWS)))


def _rungs(bucket, top: int) -> tuple:
    out, b = [], bucket(1)
    while b <= top:
        out.append(b)
        b = bucket(b + 1)
    return tuple(out)


def precompile_interval_shapes(cdb, buckets: Iterable[int],
                               cache_dir: str = "") -> dict:
    """Warm the resident interval kernel, the one the scheduler's
    waves and ``scan_boms`` run, over the pair-row ladder
    (``detect/batch._job_bucket`` rungs) against ``cdb``'s resident
    tables, staging them as a side effect. The gather operands are
    uploaded as a wave uploads them, so the program's signature is
    the wave's; zero rows gather the table's first row and the
    result is dropped."""
    import jax
    import numpy as np

    from ..ops.intervals import interval_hits_resident_donated
    manifest = _Manifest(cache_dir)
    out = {"kernel": "interval_resident", "shapes": [],
           "seconds": 0.0}
    tables = cdb.device_tables()
    sig = "x".join(str(d) for d in tables[0].shape)
    for p in sorted(set(int(b) for b in buckets if int(b) > 0)):
        # the kernel donates its gather operands: fresh ones a rung
        args = (jax.device_put(np.zeros(p, np.int32)),
                jax.device_put(np.zeros(p, np.int32))) + tuple(tables)
        key = cache_key("interval_resident", f"P{p}xT{sig}")
        dt = _warm_call(interval_hits_resident_donated, args, key,
                        manifest,
                        {"kernel": "interval_resident", "P": p})
        out["shapes"].append(p)
        out["seconds"] += dt
    out["seconds"] = round(out["seconds"], 4)
    return out


def precompile_dfa_shapes(scanner, buckets: Iterable[int],
                          cache_dir: str = "") -> dict:
    """Warm ``scanner``'s (a ``BatchSecretScanner``) fused sieve over
    the segment-buffer ladder (``ops/keywords._bucket`` rungs × its
    ``seg_len`` columns), staging the table's resident arrays as a
    side effect — exactly the prewarm staging order a joining
    replica wants. Keyed on the table's ``rules_hash`` so a custom
    rule set misses into its own entries."""
    import jax
    import numpy as np

    table, seg_len = scanner.table, scanner.seg_len
    manifest = _Manifest(cache_dir)
    out = {"kernel": "dfa_fused", "shapes": [], "full_shapes": [],
           "seconds": 0.0}
    tbl = table.device_tables()
    fn = table.fused_sieve(tuple(scanner.plan.run_specs),
                           jax.default_backend())
    for b in sorted(set(int(x) for x in buckets if int(x) > 0)):
        # the sieve donates its segment buffer; hand it a fresh one
        seg = jax.device_put(np.zeros((b, seg_len), np.uint8))
        key = cache_key("dfa_fused", f"B{b}xL{seg_len}",
                        table.rules_hash)
        dt = _warm_call(fn, (seg,) + tuple(tbl), key, manifest,
                        {"kernel": "dfa_fused", "B": b,
                         "rules_hash": table.rules_hash})
        out["shapes"].append(b)
        out["seconds"] += dt
    # a rung above SIEVE_CAP can bring more hit rows than the
    # compacted fetch holds, and the whole mask then comes back by
    # the full variant (secret/batch._decode): of a real source
    # tree's parts most do (PERF.md section 4), so it is warmed too
    from ..ops.dfa import SIEVE_CAP
    full = table.full_sieve((), jax.default_backend())
    for b in out["shapes"]:
        if b <= SIEVE_CAP:
            continue
        seg = jax.device_put(np.zeros((b, seg_len), np.uint8))
        key = cache_key("dfa_full", f"B{b}xL{seg_len}",
                        table.rules_hash)
        out["seconds"] += _warm_call(
            full, (seg,) + tuple(tbl), key, manifest,
            {"kernel": "dfa_full", "B": b,
             "rules_hash": table.rules_hash})
        out["full_shapes"].append(b)
    out["seconds"] = round(out["seconds"], 4)
    return out


def precompile_mesh_sieve_shapes(scanner, buckets: Iterable[int],
                                 cache_dir: str = "") -> dict:
    """:func:`precompile_dfa_shapes` for a scanner with a mesh: its
    sharded sieve (``DfaTable.mesh_sieve``, the program a mesh
    dispatch of ``secret/batch.py`` calls) over the segment ladder,
    each rung padded as a mesh dispatch pads it
    (``secret_shard.sieve_rows``) and uploaded as it uploads it (a
    committed row-sharded array), staging the table on every chip
    as a side effect; and its whole-mask twin
    (``DfaTable.mesh_full_sieve``) at every rung where a chip's rows
    are more than its compacted capacity, so can overflow it."""
    import jax
    import numpy as np

    from ..ops.dfa import SIEVE_CAP
    from ..parallel.secret_shard import row_sharding, sieve_rows
    table, seg_len, mesh = scanner.table, scanner.seg_len, scanner.mesh
    n_flat = int(mesh.devices.size)
    rows = row_sharding(mesh)
    manifest = _Manifest(cache_dir)
    out = {"kernel": "dfa_mesh", "shapes": [], "full_shapes": [],
           "seconds": 0.0}
    tbl = table.device_tables(mesh)
    platform = jax.default_backend()
    fn = table.mesh_sieve(mesh, tuple(scanner.plan.run_specs),
                          platform)
    full = table.mesh_full_sieve(mesh, (), platform)
    for b in sorted({sieve_rows(int(x), n_flat) for x in buckets
                     if int(x) > 0}):
        warm = [("dfa_mesh", fn, "shapes")]
        if b // n_flat > SIEVE_CAP // n_flat:
            warm.append(("dfa_mesh_full", full, "full_shapes"))
        for kernel, prog, shapes in warm:
            # the sieve donates its segment buffer: a fresh one a call
            seg = jax.device_put(np.zeros((b, seg_len), np.uint8), rows)
            key = cache_key(kernel, f"B{b}xL{seg_len}xD{n_flat}",
                            table.rules_hash)
            out["seconds"] += _warm_call(
                prog, (seg,) + tuple(tbl), key, manifest,
                {"kernel": kernel, "B": b, "devices": n_flat,
                 "rules_hash": table.rules_hash})
            out[shapes].append(b)
    out["seconds"] = round(out["seconds"], 4)
    return out


def precompile_mesh_interval_shapes(cdb, mesh, buckets: Iterable[int],
                                    cache_dir: str = "") -> dict:
    """:func:`precompile_interval_shapes` on a mesh: the resident
    interval kernel a wave runs there
    (``parallel/interval_shard.sharded_interval_hits_resident``),
    its gather operands placed as a wave places them
    (``shard_rows``), against ``cdb``'s tables replicated on every
    chip, which this stages."""
    import numpy as np

    from ..parallel.interval_shard import (
        shard_rows, sharded_interval_hits_resident)
    n_flat = int(mesh.devices.size)
    manifest = _Manifest(cache_dir)
    out = {"kernel": "interval_resident_mesh", "shapes": [],
           "seconds": 0.0}
    tables = cdb.device_tables(mesh=mesh)
    sig = "x".join(str(d) for d in tables[0].shape)
    for p in sorted(set(int(b) for b in buckets if int(b) > 0)):
        args = shard_rows(mesh, np.zeros(p, np.int32),
                          np.zeros(p, np.int32))
        key = cache_key("interval_resident_mesh",
                        f"P{p}xT{sig}xD{n_flat}")
        out["seconds"] += _warm_call(
            lambda *dev: sharded_interval_hits_resident(
                mesh, *dev, tables), args, key, manifest,
            {"kernel": "interval_resident_mesh", "P": p,
             "devices": n_flat})
        out["shapes"].append(p)
    out["seconds"] = round(out["seconds"], 4)
    return out


def warm_ladders(secret_scanner=None, store=None, config=None,
                 cache_dir: str = "", mesh=None) -> dict:
    """Run every program a scheduled batch can meet once, on inert
    inputs, before the first request: the fused sieve at every
    :func:`sieve_rungs` rung of ``secret_scanner`` and the resident
    interval kernel at every :func:`interval_rungs` rung against
    ``store`` (a ``CompiledDB``, or a holder whose ``acquire`` gives
    one), with ``config``'s (a ``SchedConfig``) batch budgets. Which
    rungs a run meets depends on how requests fall into batches, so
    the ladders are warmed whole: a fleet whose images are mostly
    cached closes batches of one to several small images, and each
    rung met cold would cost a compile in the middle of the scan.
    Called where a scheduler is built for an accelerator
    (``BatchScanRunner``, ``ScanServer``) and by ``server
    --compile-cache DIR``. The persistent cache is placed first
    (``cache_dir``, see :func:`configure_compile_cache`); a
    directory that cannot be written costs compile time, not the
    boot; a kernel that does not compile is a program fault and
    propagates (``ops.program.DeviceProgramError``). A scanner with
    a mesh has its sharded sieve warmed instead, and with ``mesh``
    the interval rungs are the mesh's; the cpu-ref engine has no
    program here and is left alone."""
    from ..db import CompiledDB
    if config is None:
        from ..sched import SchedConfig
        config = SchedConfig()
    t0 = time.monotonic()
    summary = {"cache_dir": cache_dir, "persistent": False,
               "kernels": []}
    try:
        cache_dir = summary["cache_dir"] = \
            configure_compile_cache(cache_dir)
        summary["persistent"] = True
    except OSError as e:
        log.warning("persistent compile cache unavailable: %r", e)
        summary["error"] = repr(e)
        cache_dir = ""
    if secret_scanner is not None \
            and getattr(secret_scanner, "backend", "") != "cpu-ref":
        rungs = sieve_rungs(config.max_batch_bytes,
                            secret_scanner.seg_len,
                            secret_scanner.overlap)
        warm = precompile_dfa_shapes \
            if getattr(secret_scanner, "mesh", None) is None \
            else precompile_mesh_sieve_shapes
        summary["kernels"].append(warm(secret_scanner, rungs,
                                       cache_dir))
    release = None
    if hasattr(store, "acquire") and hasattr(store, "release"):
        store, release = store.acquire(), store.release
    try:
        if isinstance(store, CompiledDB):
            rungs = interval_rungs(config.max_batch_jobs)
            summary["kernels"].append(
                precompile_interval_shapes(store, rungs, cache_dir)
                if mesh is None else precompile_mesh_interval_shapes(
                    store, mesh, rungs, cache_dir))
    finally:
        if release is not None:
            release()
    summary["seconds"] = round(time.monotonic() - t0, 4)
    log.info("warm ladders: %s in %.2fs (persistent=%s)",
             ", ".join(f"{k['kernel']} {k['shapes']}"
                       for k in summary["kernels"]) or "nothing",
             summary["seconds"], summary["persistent"])
    return summary
