"""Device: share of the traced window in which no operation ran
(1 - union of busy intervals / window)."""


def read(ctx):
    if ctx.trace is None or ctx.trace.window_s <= 0 or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share
