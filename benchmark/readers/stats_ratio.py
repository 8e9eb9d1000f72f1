"""A counter, or the ratio of two, out of the window's deltas.

``num`` and ``den`` are dotted paths into ``ctx["stats"]``: the delta
over the window of ``scheduler.stats()`` (its own keys at the top:
``counters.completed``, ``batch.count``, ``host_busy_s``) and of the
process-wide snapshots under ``detect``, ``secret`` and
``compile_cache``, plus what the harness itself clocked and counted
under ``harness`` (``units``, ``db_load_s``). Without ``den`` the
value is ``num * scale``. A path that is not there, or a ``den`` of
0, reads as nothing.
"""


def lookup(tree, path):
    for key in path.split("."):
        if not isinstance(tree, dict) or key not in tree:
            return None
        tree = tree[key]
    return tree


def read(ctx, num, den="", scale=1.0):
    n = lookup(ctx["stats"], num)
    if n is None:
        return None
    if not den:
        return n * scale
    d = lookup(ctx["stats"], den)
    if not d:
        return None
    return n / d * scale
