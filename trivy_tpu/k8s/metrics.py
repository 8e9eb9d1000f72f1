"""Cluster-sweep metrics: what ``K8sScanner.scan`` found in the
cluster, and the ``k8s`` rows of the phase clock
(``obs/trace.phase_span``: ``enumerate``, ``report``).

Process-wide, like ``secret.metrics.SECRET_METRICS``: one book a
process, one short lock a sweep. A scheduler the sweep rides carries
the snapshot as ``stats()["k8s"]`` (``K8sScanner`` seats it there),
as it carries a server's as ``stats()["rpc"]``.
"""

from __future__ import annotations

import threading

PHASES = ("enumerate", "report")


class K8sMetrics:
    """Cumulative counters of the cluster sweeps: sweeps, workload
    objects with a pod spec, container image refs, the distinct
    images those resolved to, and refs with no image to scan."""

    _KEYS = ("sweeps", "workloads", "image_refs", "images_distinct",
             "images_unresolved")

    def __init__(self):
        self._lock = threading.Lock()
        self._c = dict.fromkeys(self._KEYS, 0)
        from ..obs.trace import ensure_phase
        for phase in PHASES:
            ensure_phase("k8s", phase)

    def note_sweep(self, workloads: int, image_refs: int,
                   images_distinct: int,
                   images_unresolved: int) -> None:
        with self._lock:
            c = self._c
            c["sweeps"] += 1
            c["workloads"] += workloads
            c["image_refs"] += image_refs
            c["images_distinct"] += images_distinct
            c["images_unresolved"] += images_unresolved

    def snapshot(self) -> dict:
        from ..obs.trace import phase_rows
        with self._lock:
            out = dict(self._c)
        out["phase"] = phase_rows("k8s")
        return out


K8S_METRICS = K8sMetrics()
