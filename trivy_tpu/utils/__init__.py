import contextlib
import gc
import threading

from .log import get_logger, set_format, set_level


@contextlib.contextmanager
def defer_gc():
    """Suspend generational GC around allocation-heavy fleet loops.

    A fleet-scale SBOM decode allocates hundreds of thousands of
    objects that live until the batch is reported; left on, the
    collector would walk them again at every threshold it crosses.
    Objects created inside the block are collected by the explicit
    collect() on exit, so cycles cannot accumulate across batches.
    That collection walks the whole heap while every thread waits
    (the batch's own documents, blobs and reports, still alive, are
    most of it: the compiled advisory table keeps its rows in arrays
    the collector does not walk, db/compiled._RowTable): it is
    booked as the ``gc`` phase of the detect pipeline."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            from ..obs.trace import phase_span
            # the phase opens BEFORE gc.enable(): the first tracked
            # allocation after it would set off a young collection
            # over everything the block allocated (0.4 s a pass of
            # 2,000 SBOMs, measured), ahead of the full one
            with phase_span("gc", pipeline="detect"):
                gc.enable()
                gc.collect()

# middle collections between two full ones while a scheduler runs
# (the interpreter's default is 10). 300 is the spacing a heap of a
# million advisory rows gave by the interpreter's own rule: a quarter
# of its 8.6 million objects is 2.15 million promotions, and a middle
# collection of a fleet scan promotes about 7,000 (PERF.md section 6,
# PR 30)
_FULL_GC_EVERY = 300
_sparse_lock = threading.Lock()
_sparse = {"holders": 0, "found": None}     # thresholds, process-wide


def sparse_full_gc():
    """Space out the interpreter's automatic FULL collections for as
    long as the caller runs; returns the function that ends it.

    The interpreter considers a full collection every 10 middle
    ones and runs it once the objects promoted since the last pass a
    quarter of those that survived it. A scan loop keeps what it
    makes (reports until they are written, the blob cache, the
    findings memo), so its heap only grows and next to none of it is
    cyclic garbage: on a small heap that rule walked the growing
    heap 17 times in 30 s of a fleet scan, a fifth of the run, where
    one that held a million advisory rows as Python objects was
    collected twice (PERF.md section 6, PR 30). Held, a full
    collection is considered every ``_FULL_GC_EVERY`` middle ones;
    young and middle collections, which find a request's short-lived
    cycles, run as before. Each full collection that does run stops
    every thread, so while this is held it is booked on the phase
    clock as ``host.gc_full`` (``obs/trace.watch_full_gc``).
    Counted: the first holder raises the threshold and the last one
    puts back what the first found."""
    from ..obs.trace import watch_full_gc
    with _sparse_lock:
        if not _sparse["holders"]:
            found = _sparse["found"] = gc.get_threshold()
            gc.set_threshold(found[0], found[1],
                             max(found[2], _FULL_GC_EVERY))
            watch_full_gc(True)
        _sparse["holders"] += 1

    def release() -> None:
        with _sparse_lock:
            _sparse["holders"] -= 1
            if not _sparse["holders"]:
                gc.set_threshold(*_sparse["found"])
                watch_full_gc(False)

    return release


__all__ = ["get_logger", "set_format", "set_level"]
