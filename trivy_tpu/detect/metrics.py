"""Dispatch-path metrics: dedup ratios, compile/parse cache hit
rates, device-resident DB upload amortization (docs/performance.md).

Process-wide by design, like ``guard.budget.GUARD_METRICS``: the
constraint-interval cache and the purl parse cache are process
singletons, DB uploads happen once per (generation, mesh), and the
numbers an operator watches on ``/metrics`` are the cumulative
totals. Counter updates take one short lock; nothing here sits on a
per-byte hot path (per-job costs are batched by the dispatchers
before they land here).
"""

from __future__ import annotations

import threading


class DetectMetrics:
    """Cumulative counters for the interval-dispatch hot path."""

    _KEYS = (
        # dispatch_jobs: jobs submitted vs unique after dedup
        "jobs_in", "jobs_unique",
        # interval kernel launches on the device and the (unpadded)
        # pair rows they carried — cpu-ref evaluations add nothing
        "device_waves", "device_rows",
        # constraint-interval compile cache (detect/ccache.py)
        "interval_cache_hits", "interval_cache_misses",
        # purl parse cache (purl.from_string)
        "purl_cache_hits", "purl_cache_misses",
        # device-resident advisory tables (db/compiled.py)
        "db_uploads", "db_upload_bytes", "db_invalidations",
        "resident_dispatches",
        # host packing pool (runtime/hostpool.py)
        "pack_tasks",
    )

    def __init__(self):
        self._lock = threading.Lock()
        self._c = {k: 0 for k in self._KEYS}

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            # lint: disable=unbounded-label-cardinality -- counter
            # names are code-literal call sites, never
            # request-derived strings
            self._c[name] = self._c.get(name, 0) + n

    def note_dispatch(self, jobs_in: int, jobs_unique: int) -> None:
        with self._lock:
            self._c["jobs_in"] += jobs_in
            self._c["jobs_unique"] += jobs_unique

    def note_wave(self, rows: int) -> None:
        with self._lock:
            self._c["device_waves"] += 1
            self._c["device_rows"] += rows

    def note_db_upload(self, nbytes: int) -> None:
        with self._lock:
            self._c["db_uploads"] += 1
            self._c["db_upload_bytes"] += nbytes

    def reset(self) -> None:
        """Test hook — production code never calls this."""
        with self._lock:
            for k in self._c:
                self._c[k] = 0

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self._c)
        jobs_in = out["jobs_in"]
        out["dedup_ratio"] = round(
            1.0 - out["jobs_unique"] / jobs_in, 4) if jobs_in else 0.0
        ic = out["interval_cache_hits"] + out["interval_cache_misses"]
        out["interval_cache_hit_rate"] = round(
            out["interval_cache_hits"] / ic, 4) if ic else 0.0
        pc = out["purl_cache_hits"] + out["purl_cache_misses"]
        out["purl_cache_hit_rate"] = round(
            out["purl_cache_hits"] / pc, 4) if pc else 0.0
        out["upload_amortization"] = round(
            out["resident_dispatches"] / out["db_uploads"], 2) \
            if out["db_uploads"] else 0.0
        # the interval and SBOM rows of the phase clock
        # (obs/trace.phase_span: decode, decode_task, join, pack,
        # h2d_upload, device_compute, finish, gc), cumulative
        from ..obs.trace import phase_rows
        out["phase"] = phase_rows("detect")
        # the findings memo's outcomes (memo/metrics.py), beside the
        # memo_lookup and memo_store rows above: queries asked, those
        # answered, and layers served whole
        from ..memo.metrics import MEMO_METRICS
        memo = MEMO_METRICS.snapshot()
        out["memo"] = {"lookups": memo["hits"] + memo["misses"],
                       "hits": memo["hits"],
                       "layer_hits": memo["layer_hits"]}
        return out


DETECT_METRICS = DetectMetrics()
