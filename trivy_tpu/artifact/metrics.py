"""Ingest metrics: what the streaming path fetched and skipped, what
the blob cache answered, and what the walker and analyzers had to do
instead (docs/observability.md "The phase clock",
docs/performance.md §9).

Process-wide by design, mirroring ``detect.metrics.DETECT_METRICS``:
the numbers an operator watches are cumulative totals over every
image inspected, whichever runner or cache inspected it. One short
lock an event; nothing here sits on a per-file path.
"""

from __future__ import annotations

import threading


class IngestMetrics:
    """Process-wide ingest counters (thread-safe); snapshotted into
    ``scheduler.stats()`` and ``GET /metrics`` on both sched modes
    and rendered as ``trivy_tpu_ingest_*_total`` Prometheus
    families."""

    _KEYS = (
        # the streaming path (artifact/stream.py)
        "streams", "layers_fetched", "bytes_fetched",
        "layers_skipped", "bytes_skipped", "range_resumes",
        "full_restarts", "warm_probe_outages",
        "cancelled_fetches", "config_memo_hits",
        # every HTTP request DistributionClient sends (manifest,
        # config, blob, token, each retry), the bytes its layer
        # inflaters wrote to their spools, and the bytes read back
        # whole from them as their layers were opened
        "registry_requests", "bytes_inflated", "spool_bytes_read",
        # ImageArtifact.inspect: layers an image asked the cache
        # for, those it held, and those walked and analyzed instead
        # (seen = cached + analyzed), and of those analyzed the
        # layers walked from index_tar's one pass over their bytes
        # (the rest fell back to tarfile: guard/safetar.LayerTar)
        "layers_seen", "layers_cached", "layers_analyzed",
        "layers_indexed",
        # file bytes the walker handed the analyzers in those layers
        "bytes_analyzed",
        # layers whose secrets the base-image rule leaves out of the
        # image's report (image.guess_base_layers), cached or not
        "base_layers_skipped",
        # LocalFSArtifact's streamed walk (a tree through
        # runtime/batch.submit_tree): regular files the walk met,
        # those of them that are no secret candidate (the secret
        # analyzer's skips: directory, name, extension, size, a NUL
        # in the head), and the bytes of those that are
        "tree_files", "tree_files_skipped", "tree_bytes",
        # AnalyzerGroup's gate, image layers and trees alike: files
        # it was asked about and the analyzers' ``required`` calls
        # that took (its index answers most files with none or one)
        "gate_files", "gate_probes",
    )

    def __init__(self):
        self._lock = threading.Lock()
        self.counters = {k: 0 for k in self._KEYS}

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            # lint: disable=unbounded-label-cardinality -- counter
            # names are code-literal call sites, never
            # request-derived strings
            self.counters[name] = self.counters.get(name, 0) + n

    def note_inspect(self, layers: int, analyzed: int,
                     nbytes: int, base: int,
                     gates: tuple = (0, 0), indexed: int = 0) -> None:
        with self._lock:
            c = self.counters
            c["gate_files"] += gates[0]
            c["gate_probes"] += gates[1]
            c["layers_seen"] += layers
            c["layers_cached"] += layers - analyzed
            c["layers_analyzed"] += analyzed
            c["layers_indexed"] += indexed
            c["bytes_analyzed"] += nbytes
            c["base_layers_skipped"] += base

    def note_tree(self, files: int, candidates: int,
                  nbytes: int, gates: tuple = (0, 0)) -> None:
        with self._lock:
            c = self.counters
            c["gate_files"] += gates[0]
            c["gate_probes"] += gates[1]
            c["tree_files"] += files
            c["tree_files_skipped"] += files - candidates
            c["tree_bytes"] += nbytes

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self.counters)
        # the walkers', analyzers' and pull's rows of the phase clock
        # (obs/trace.phase_span: layer_analyze, tree_walk,
        # layer_fetch, layer_inflate, layer_ready_wait), cumulative
        from ..obs.trace import phase_rows
        out["phase"] = phase_rows("ingest")
        return out

    def reset(self) -> None:
        with self._lock:
            self.counters = {k: 0 for k in self._KEYS}


INGEST_METRICS = IngestMetrics()
