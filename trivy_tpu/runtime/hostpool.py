"""Bounded host worker pool for packing and decode work
(docs/performance.md "host/device overlap").

One process-wide pool, sized to the host's spare cores, reserved for
tasks that NEVER block on scheduler events: the mesh sieve's
per-shard segment packing and block decode
(parallel/secret_shard.py), interval wave packing (detect/batch.py)
and the direct path's sieve enqueue.
The one-device secret pack is NOT here: it runs on the thread that
calls ``dispatch_files`` without letting go of the interpreter
(secret/batch.py ``_pack``), because a task a file made that thread
wait for the lock hundreds of times a batch. Keeping the pool
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
    """The shared packing/decode pool, or None when disabled."""
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


def map_in_pool(fn, items: list, chunk: int = 1) -> list:
    """``[fn(x) for x in items]`` spread over the pool (input order
    preserved). Falls back to the inline loop when the pool is
    disabled, the batch is too small to amortize the hops, or the
    CALLER is itself a pool worker — a task that blocks on
    ``pool.map`` of its own pool deadlocks the moment every worker
    is such a task (the direct path's sieve enqueue runs here and
    then packs segments through here again). ``fn`` must capture
    its own errors — a raising task would abandon the batch.

    ``chunk > 1`` batches that many items per pool task, for
    items whose work is less than a hop to a worker costs."""
    from ..detect.metrics import DETECT_METRICS
    on_pool_thread = threading.current_thread().name.startswith(
        "trivy-hostpool")
    pool = get_host_pool() \
        if len(items) > max(8, chunk) and not on_pool_thread \
        else None
    def task(slab: list) -> list:
        return [fn(x) for x in slab]

    if pool is None:
        return task(items)
    if chunk > 1:
        slabs = [items[i:i + chunk]
                 for i in range(0, len(items), chunk)]
        DETECT_METRICS.inc("pack_tasks", len(slabs))
        out: list = []
        for part in pool.map(task, slabs):
            out.extend(part)
        return out
    DETECT_METRICS.inc("pack_tasks", len(items))
    return list(pool.map(fn, items))
