"""Bounded host worker pool (docs/performance.md "host/device
overlap").

One process-wide pool, sized to the host's spare cores, reserved for
tasks that NEVER block on scheduler events. Its one user is the
direct path's sieve enqueue (runtime/batch.py), which runs a batch's
``dispatch_files`` here while the calling thread enqueues the
interval waves. The secret sieve's pack and decode are NOT split
into tasks here, on one device or on a mesh: they run on the thread
that calls ``dispatch_files`` or ``collect`` without letting go of
the interpreter (secret/batch.py ``_pack``), because a task a file
or a shard made those threads wait for the lock hundreds of times a
batch. Keeping the pool
separate from the scheduler's worker pool is load-bearing, not
stylistic — the scheduler pool runs
``finish`` tasks that wait on patch events only the device thread
resolves, so routing a pack task there while the device thread
blocks on its future could deadlock the pipeline. Tasks here are
pure compute with no cross-task waits, so the pool can be saturated
safely from any thread.

The pool's threads share one interpreter, so they overlap only what
lets go of it (hashing, numpy copies), and what a task touches once
an ITEM must take no lock of its own: the purl parse memo did,
80,000 times a pass of 2,000 SBOMs, and eight decode tasks convoyed
on it for two thirds of the pass (docs/performance.md "SBOM decode
and the lock convoy"). Without that lock the SBOM decode still gained
nothing here, a json parse never lets go of the interpreter, and on
the chip's host it was a sixth slower and unsteady: since PR 33 it
runs on the thread that calls ``scan_boms`` (PERF.md section 6).
"""

from __future__ import annotations

import os
import threading
from concurrent.futures import ThreadPoolExecutor

from ..utils import get_logger

log = get_logger("runtime.hostpool")

_POOL = None
_LOCK = threading.Lock()


def pool_size() -> int:
    """Bounded: the spare cores past the two the device thread and
    main loop keep busy, capped at 8 — which disables the pool
    entirely on 1-2 core hosts, where extra threads only add GIL
    contention. ``TRIVY_TPU_HOST_POOL`` overrides (0 disables)."""
    env = os.environ.get("TRIVY_TPU_HOST_POOL", "")
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            log.warning("bad TRIVY_TPU_HOST_POOL=%r ignored", env)
    return min(8, max(0, (os.cpu_count() or 1) - 2))


def get_host_pool():
    """The shared pool, or None when disabled."""
    global _POOL
    if _POOL is None:
        with _LOCK:
            if _POOL is None:
                n = pool_size()
                if n == 0:
                    return None
                _POOL = ThreadPoolExecutor(
                    max_workers=n,
                    thread_name_prefix="trivy-hostpool")
    return _POOL
