import contextlib
import gc

from .log import get_logger, set_format, set_level


@contextlib.contextmanager
def defer_gc():
    """Suspend generational GC around allocation-heavy fleet loops.

    With the compiled advisory DB resident (48k+ Python row tuples),
    every young-generation collection walks that long-lived heap
    while a fleet-scale SBOM decode allocates.
    Objects created inside the block are collected by the explicit
    collect() on exit, so cycles cannot accumulate across batches.
    That collection walks the whole heap while every thread waits:
    it is booked as the ``gc`` phase of the detect pipeline."""
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

__all__ = ["get_logger", "set_format", "set_level"]
