"""Scheduler: mean time from due to admission into a bucket, over the
window's requests that were admitted."""


def read(ctx):
    waits = [r.t_admit - r.arrival for r in ctx.window.due()
             if r.t_admit is not None]
    return 1e3 * sum(waits) / len(waits) if waits else None
