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
* :func:`precompile_interval_shapes` / :func:`precompile_dfa_shapes`
  walk the SAME shape ladders the serving path buckets into
  (``ops/keywords._bucket`` for segment buffers,
  ``detect/batch._job_bucket`` for pair rows) and execute each
  jitted kernel once on zero inputs — populating the in-process jit
  cache (the first real request never traces) AND the persistent
  cache (the next replica's boot never rebuilds).
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
from typing import Iterable, Optional, Tuple

from ..utils import get_logger

log = get_logger("runtime.aot")

MANIFEST_NAME = "trivy_tpu_aot_manifest.json"

# default ladder rungs warmed at boot: the small end, where first
# requests actually land (a cold fleet's first scans are small
# batches; the big rungs amortize their own compile once traffic
# exists to fill them)
DEFAULT_PAIR_BUCKETS = (64, 128, 256)
DEFAULT_SEG_BUCKETS = (256, 512)


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


def precompile_interval_shapes(
        buckets: Iterable[int] = DEFAULT_PAIR_BUCKETS,
        cache_dir: str = "") -> dict:
    """Warm the classic interval kernel over the pair-row ladder
    (``detect/batch._job_bucket`` rungs). Zero rows are inert
    (flags=0 ⇒ not vulnerable), so execution is a no-op
    semantically; the value is the populated jit + persistent
    caches."""
    import numpy as np

    from ..ops.intervals import MAX_INTERVALS, interval_hits
    manifest = _Manifest(cache_dir)
    out = {"kernel": "interval", "shapes": [], "seconds": 0.0}
    for p in sorted(set(int(b) for b in buckets if int(b) > 0)):
        rank = np.zeros(p, np.int32)
        iv = np.zeros((p, MAX_INTERVALS), np.int32)
        flags = np.zeros(p, np.int32)
        key = cache_key("interval", f"P{p}xM{MAX_INTERVALS}")
        dt = _warm_call(interval_hits,
                        (rank, iv, iv, iv, iv, flags),
                        key, manifest,
                        {"kernel": "interval", "P": p})
        out["shapes"].append(p)
        out["seconds"] += dt
    out["seconds"] = round(out["seconds"], 4)
    return out


def precompile_dfa_shapes(table, run_specs: tuple = (),
                          buckets: Iterable[int] =
                          DEFAULT_SEG_BUCKETS,
                          cache_dir: str = "",
                          platform: str = "") -> dict:
    """Warm the DFA fused sieve over the segment-buffer ladder
    (``ops/keywords._bucket`` rungs × SEG_LEN columns), staging the
    table's resident arrays as a side effect — exactly the prewarm
    staging order a joining replica wants. Keyed on the table's
    ``rules_hash`` so a custom rule set misses into its own
    entries."""
    import jax
    import numpy as np

    from ..secret.batch import SEG_LEN
    platform = platform or jax.default_backend()
    manifest = _Manifest(cache_dir)
    out = {"kernel": "dfa_fused", "shapes": [], "seconds": 0.0}
    tbl = table.device_tables()
    fn = table.fused_sieve(tuple(run_specs), platform)
    for b in sorted(set(int(x) for x in buckets if int(x) > 0)):
        # the sieve donates its segment buffer; hand it a fresh one
        seg = jax.device_put(np.zeros((b, SEG_LEN), np.uint8))
        key = cache_key("dfa_fused", f"B{b}xL{SEG_LEN}",
                        table.rules_hash)
        dt = _warm_call(fn, (seg,) + tuple(tbl), key, manifest,
                        {"kernel": "dfa_fused", "B": b,
                         "rules_hash": table.rules_hash})
        out["shapes"].append(b)
        out["seconds"] += dt
    out["seconds"] = round(out["seconds"], 4)
    return out


def boot_precompile(cache_dir: str = "",
                    dfa_table=None,
                    run_specs: tuple = (),
                    pair_buckets: Optional[Tuple[int, ...]] = None,
                    seg_buckets: Optional[Tuple[int, ...]] = None,
                    ) -> dict:
    """The boot-time glue the server calls once (``--compile-cache``):
    place the persistent cache, then warm the interval and (when a
    table is supplied) DFA ladders, the manifest beside the cache
    entries. A cache directory that cannot be written costs compile
    time, not the boot; a kernel that does not compile is a program
    fault and propagates (``ops.program.DeviceProgramError``)."""
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
    summary["kernels"].append(precompile_interval_shapes(
        pair_buckets or DEFAULT_PAIR_BUCKETS, cache_dir))
    if dfa_table is not None:
        summary["kernels"].append(precompile_dfa_shapes(
            dfa_table, run_specs,
            seg_buckets or DEFAULT_SEG_BUCKETS, cache_dir))
    summary["seconds"] = round(time.monotonic() - t0, 4)
    log.info("boot precompile: %d kernels in %.2fs "
             "(persistent=%s)", len(summary["kernels"]),
             summary["seconds"], summary["persistent"])
    return summary
