"""Device milliseconds of the XLA programs whose name in the trace
matches ``pattern``, over ``per`` (a dotted path into the window's
deltas, see ``stats_ratio``) times ``per_scale``. No such program in
the trace reads as nothing."""

from readers.stats_ratio import lookup
from trace_reduce import program_seconds


def read(ctx, pattern, per, per_scale=1.0):
    if not ctx["trace"]:
        return None
    seconds, calls = program_seconds(ctx["trace"], pattern)
    d = lookup(ctx["stats"], per)
    if not calls or not d:
        return None
    return seconds * 1e3 / (d * per_scale)
