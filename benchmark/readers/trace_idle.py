"""One minus the union of the intervals in which an operation ran on
the device over the traced window, averaged over the chips, in
percent."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
