"""Multi-host mesh seam: one v5e-16+ pod as one logical scanner
(docs/performance.md §8, docs/serving.md "Multi-host deployment").

A single process sees at most one host's chips. ``jax.distributed``
joins N processes (one per host) into one runtime whose
``jax.devices()`` is the GLOBAL device set, after which the existing
mesh/sharding machinery — ``make_mesh`` over all devices, LPT shard
layout over the global device count, resident advisory/DFA tables
staged per host through the ``ResidentTables`` generation machinery
(each process stages to its addressable slice, same generation key)
— makes the pod one batch-scan backend.

The contract has three pieces, each testable without TPU hardware:

* :func:`topology_from_env` — the env/flag seam. A pod slice is
  described by ``TRIVY_TPU_COORDINATOR`` (host:port of process 0),
  ``TRIVY_TPU_NUM_PROCESSES`` and ``TRIVY_TPU_PROCESS_ID`` (CLI:
  ``--coordinator`` / ``--num-processes`` / ``--process-id``).
  Absent env = single host, everything degenerates to the
  single-process paths.
* :func:`initialize` — the idempotent ``jax.distributed.initialize``
  call, made BEFORE any backend touch; on a single host it is a
  no-op.
* :func:`host_shard_layout` / :func:`local_indices` — the
  work-placement function: greedy LPT (parallel/balance.py) of
  per-item byte volumes over the process set. It is a PURE function
  of (volumes, num_processes), so every host computes the identical
  global layout from the same inputs with no coordination traffic —
  shard-layout parity is a testable invariant, and the union of the
  per-host scans is byte-identical to a single-host scan of the
  whole fleet.

CI cannot reach a pod, so the contract ships with a multi-process
*simulation* mode (``trivy_tpu/parallel/simhost.py``): N spawned
subprocesses on the CPU backend, each believing it is process k of
P, each scanning exactly its layout slice — ``pytest -m async_rt``
gates layout parity and findings byte-identity through it.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Optional

from ..utils import get_logger

log = get_logger("parallel.multihost")

ENV_COORDINATOR = "TRIVY_TPU_COORDINATOR"
ENV_NUM_PROCESSES = "TRIVY_TPU_NUM_PROCESSES"
ENV_PROCESS_ID = "TRIVY_TPU_PROCESS_ID"
ENV_LOCAL_DEVICES = "TRIVY_TPU_LOCAL_DEVICES"


@dataclass(frozen=True)
class HostTopology:
    """One process's view of the pod."""

    num_processes: int = 1
    process_id: int = 0
    coordinator: str = ""       # "host:port" of process 0
    local_devices: int = 0      # 0 = let the backend decide

    @property
    def multi_host(self) -> bool:
        return self.num_processes > 1

    def validate(self) -> "HostTopology":
        if self.num_processes < 1:
            raise ValueError(
                f"num_processes must be >= 1, got "
                f"{self.num_processes}")
        if not 0 <= self.process_id < self.num_processes:
            raise ValueError(
                f"process_id {self.process_id} outside "
                f"[0, {self.num_processes})")
        if self.multi_host and not self.coordinator:
            raise ValueError(
                "multi-host topology needs a coordinator "
                f"address ({ENV_COORDINATOR} or --coordinator)")
        return self


def topology_from_env(env=None, coordinator: str = "",
                      num_processes: int = 0,
                      process_id: int = -1) -> HostTopology:
    """Resolve the topology: explicit args (CLI flags) win over the
    ``TRIVY_TPU_*`` env contract; a typo'd value fails the run up
    front with ValueError instead of silently scanning a partial
    fleet on one host."""
    env = os.environ if env is None else env

    def _env_int(key, default):
        raw = env.get(key, "")
        if not raw:
            return default
        try:
            return int(raw)
        except ValueError:
            raise ValueError(f"bad {key}={raw!r}: not an integer")

    topo = HostTopology(
        num_processes=int(num_processes) if num_processes > 0
        else _env_int(ENV_NUM_PROCESSES, 1),
        process_id=int(process_id) if process_id >= 0
        else _env_int(ENV_PROCESS_ID, 0),
        coordinator=coordinator or env.get(ENV_COORDINATOR, ""),
        local_devices=_env_int(ENV_LOCAL_DEVICES, 0),
    )
    return topo.validate()


_INIT_LOCK = threading.Lock()
_INITIALIZED: dict = {}


def initialize(topo: Optional[HostTopology] = None) -> bool:
    """The ``jax.distributed.initialize`` seam: joins this process
    into the pod runtime, AFTER which ``jax.devices()`` is global.
    Idempotent per topology; single-host topologies are a no-op.
    Returns True when the distributed runtime was (or already had
    been) initialized."""
    topo = topology_from_env() if topo is None else topo.validate()
    if not topo.multi_host:
        return False
    key = (topo.coordinator, topo.num_processes, topo.process_id)
    with _INIT_LOCK:
        if _INITIALIZED.get(key):
            return True
        if _INITIALIZED:
            raise RuntimeError(
                f"jax.distributed already initialized with "
                f"{next(iter(_INITIALIZED))}, cannot re-join as "
                f"{key}")
        import jax
        kwargs = {}
        if topo.local_devices:
            kwargs["local_device_ids"] = list(
                range(topo.local_devices))
        log.info("joining pod: coordinator=%s process %d/%d",
                 topo.coordinator, topo.process_id,
                 topo.num_processes)
        jax.distributed.initialize(
            coordinator_address=topo.coordinator,
            num_processes=topo.num_processes,
            process_id=topo.process_id, **kwargs)
        _INITIALIZED[key] = True
    return True


def global_mesh(topo: Optional[HostTopology] = None,
                rules_shards: Optional[int] = None):
    """Mesh over the GLOBAL device set (every host's chips). Call
    after :func:`initialize`; on a single host this is exactly
    ``make_mesh()``."""
    from .mesh import make_mesh
    if topo is not None:
        initialize(topo)
    return make_mesh(rules_shards=rules_shards)


# --- deterministic cross-host work placement ---

def host_shard_layout(volumes: list, num_processes: int) -> list:
    """``volumes[i]`` (bytes of work item i) → owning process id,
    greedy LPT over the process set (parallel/balance.py — the same
    packer that balances rows over chips, one level up). Pure and
    deterministic: every host derives the identical global layout
    from the shared fleet spec, which is what makes "no coordinator
    traffic per item" safe. Layout parity across processes is gated
    by ``pytest -m async_rt``'s two simulated hosts."""
    from .balance import balance_by_volume
    return balance_by_volume([int(v) for v in volumes],
                             max(1, int(num_processes)))


def local_indices(volumes: list, topo: HostTopology) -> list:
    """The work items THIS process owns under the global layout,
    in input order."""
    assign = host_shard_layout(volumes, topo.num_processes)
    return [i for i, p in enumerate(assign)
            if p == topo.process_id]
