"""A sharded kernel's share of its roofline over every chip it ran
on, in percent: the least time the chips could take for the bytes
``work.<work>`` counts from the window's deltas, spread over all of
them (bytes over the peak HBM bandwidth of ``peaks.json`` times
``trace["devices"]``), over the device time of the programs matching
``pattern`` on one chip. ``trace_reduce.reduce`` gives a program's
seconds as the average over the chips, so ``trace_roofline``'s
one-chip peak reads a program that ran on every chip of a mesh as
many times too high as there are chips; on one chip both read alike.
Nothing to read, no number: never 0."""

import work as work_module
from trace_reduce import program_seconds


def read(ctx, pattern, work):
    t = ctx["trace"]
    if not t or not t.get("devices"):
        return None
    seconds, calls = program_seconds(t, pattern)
    if not calls or seconds <= 0:
        return None
    nbytes = getattr(work_module, work)(ctx["stats"], ctx["config"])
    if nbytes <= 0:
        return None
    peak = ctx["peaks"]["hbm_bytes_per_s"] * t["devices"]
    return 100.0 * nbytes / peak / seconds
