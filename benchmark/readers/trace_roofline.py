"""A kernel's share of its roofline, in percent: the least time the
chip could take for the bytes ``work.<work>`` counts from the window's
deltas (bytes over the peak HBM bandwidth of ``peaks.json``), over the
device time of the programs matching ``pattern``. Nothing to read, no
number: never 0."""

import work as work_module
from trace_reduce import program_seconds


def read(ctx, pattern, work):
    if not ctx["trace"]:
        return None
    seconds, calls = program_seconds(ctx["trace"], pattern)
    if not calls or seconds <= 0:
        return None
    nbytes = getattr(work_module, work)(ctx["stats"], ctx["config"])
    if nbytes <= 0:
        return None
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / seconds
