"""Dispatch-path metrics: dedup ratios, compile/parse cache hit
rates, device-resident DB upload amortization (docs/performance.md).

Process-wide by design, like ``guard.budget.GUARD_METRICS``: the
constraint-interval cache and the purl parse cache are process
singletons, DB uploads happen once per (generation, mesh), and the
numbers an operator watches on ``/metrics`` are the cumulative
totals. Counter updates take no lock: the purl memo counts a lookup
a component (80,000 a pass of 2,000 SBOMs; from eight pool threads
until PR 33, from every scheduler worker still), and one shared lock
there convoyed the whole decode (docs/performance.md "SBOM decode and
the lock convoy").
"""

from __future__ import annotations

import threading


class DetectMetrics:
    """Cumulative counters for the interval-dispatch hot path.

    Every thread adds into a cell of its own, keyed by its ident, and
    ``snapshot`` folds the cells: a cell is written only by the one
    live thread that has its ident (a later thread that is handed a
    dead one's ident carries its cell on), so no add is lost and none
    takes a lock. Rests on the interpreter lock making ``dict.get``,
    ``dict.setdefault``, ``d[k] = v`` and ``dict.copy`` each atomic
    for ``int`` and ``str`` keys. Totals are exact once the adding
    threads are done; a snapshot taken while they run may be one add
    a thread behind, and sees the two counters of a ``note_*`` pair
    one after the other."""

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
        # times each memo turned its two generations (ccache.KeyedMemo)
        "purl_cache_turns", "constraint_cache_turns",
        # documents and components a scan_boms call decoded
        "sbom_docs", "sbom_components",
        # device-resident advisory tables (db/compiled.py)
        "db_uploads", "db_upload_bytes", "db_invalidations",
        "resident_dispatches",
        # advisory rows the name join built from the table's columns
        # (scan/local._vuln_jobs, added once a join)
        "table_rows_decoded",
        # host packing pool (runtime/hostpool.py)
        "pack_tasks",
    )

    def __init__(self):
        self._cells: dict = {}

    def inc(self, name: str, n: int = 1) -> None:
        ident = threading.get_ident()
        cell = self._cells.get(ident)
        if cell is None:
            cell = self._cells.setdefault(ident, {})
        # lint: disable=unbounded-label-cardinality -- counter
        # names are code-literal call sites, never
        # request-derived strings
        cell[name] = cell.get(name, 0) + n

    def note_dispatch(self, jobs_in: int, jobs_unique: int) -> None:
        self.inc("jobs_in", jobs_in)
        self.inc("jobs_unique", jobs_unique)

    def note_wave(self, rows: int) -> None:
        self.inc("device_waves")
        self.inc("device_rows", rows)

    def note_db_upload(self, nbytes: int) -> None:
        self.inc("db_uploads")
        self.inc("db_upload_bytes", nbytes)

    def reset(self) -> None:
        """Test hook — production code never calls this."""
        self._cells.clear()

    def snapshot(self) -> dict:
        out = dict.fromkeys(self._KEYS, 0)
        for cell in list(self._cells.values()):
            for k, v in cell.copy().items():
                out[k] = out.get(k, 0) + v
        jobs_in = out["jobs_in"]
        out["dedup_ratio"] = round(
            1.0 - out["jobs_unique"] / jobs_in, 4) if jobs_in else 0.0
        # how often each memo engages, and how often it pays: a hit
        # share near 0 under some traffic says the memo is pure cost
        # there (detect/ccache.py)
        for memo in ("interval_cache", "purl_cache"):
            hits = out[f"{memo}_hits"]
            lookups = hits + out[f"{memo}_misses"]
            out[f"{memo}_lookups"] = lookups
            out[f"{memo}_hit_rate"] = round(
                hits / lookups, 4) if lookups else 0.0
        out["upload_amortization"] = round(
            out["resident_dispatches"] / out["db_uploads"], 2) \
            if out["db_uploads"] else 0.0
        # the interval and SBOM rows of the phase clock
        # (obs/trace.phase_span: decode, decode_task, join, pack,
        # h2d_upload, device_compute, finish, gc), cumulative
        from ..obs.trace import host_snapshot, phase_rows
        out["phase"] = phase_rows("detect")
        # the process's CPU and the full collections, for a run
        # with no scheduler to carry them (``scan_boms``)
        out["host"] = host_snapshot()
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
