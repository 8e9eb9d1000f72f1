"""RPC metrics: what a served request costs before and after the
scheduler has it (docs/observability.md "The phase clock",
docs/serving.md).

One book a ``ScanServer``. The counters are the wire's: requests by
method, body bytes in and out, Scans the admission queue shed (a 503
the client retries), Scans that came again under an idempotency key
the server had met, and what ``MissingBlobs`` was asked and held (a
thin client analyzes, so the server's layer cache answers here and
not in ``ImageArtifact.inspect``). The seconds are the ``rpc`` rows of
the phase clock (``obs/trace.phase_span``), process-wide like every
pipeline's: ``decode``, ``missing_blobs``, ``put_blob``,
``put_artifact``, ``scan_wait``, ``encode``. One short lock a call;
nothing here sits inside a loop over a request's blobs.
"""

from __future__ import annotations

import threading

PHASES = ("decode", "missing_blobs", "put_blob", "put_artifact",
          "scan_wait", "encode")


class RpcMetrics:
    """Thread-safe; snapshotted into ``ScanScheduler.stats()["rpc"]``
    of the scheduler the server rides and into ``GET /metrics`` on
    both sched modes (``trivy_tpu_rpc_*_total``)."""

    _KEYS = ("bytes_in", "bytes_out", "shed_503", "retried",
             "blobs_asked", "blobs_held")
    METHODS = ("MissingBlobs", "PutBlob", "PutArtifact",
               "DeleteBlobs", "Scan")

    def __init__(self):
        self._lock = threading.Lock()
        self.counters = {k: 0 for k in self._KEYS}
        self.requests = {m: 0 for m in self.METHODS}
        # the rows are there at zero from the server's construction:
        # a reader for which a missing row means "not measured" reads
        # 0 for a method nobody has called yet
        from ..obs.trace import ensure_phase
        for phase in PHASES:
            ensure_phase("rpc", phase)

    def inc(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] += n

    def note_request(self, method: str, bytes_in: int) -> None:
        with self._lock:
            self.requests[method] += 1      # a name of METHODS
            self.counters["bytes_in"] += bytes_in

    def note_missing(self, asked: int, missing: int) -> None:
        with self._lock:
            self.counters["blobs_asked"] += asked
            self.counters["blobs_held"] += asked - missing

    def snapshot(self) -> dict:
        from ..obs.trace import phase_rows
        with self._lock:
            out = dict(self.counters)
            out["requests"] = dict(self.requests)
        out["phase"] = phase_rows("rpc")
        return out
