"""Scheduler: share of the bucket slots that carried a request, over the
forwards run in the window (slot_steps / (steps x slots))."""


def read(ctx):
    if not ctx.steps:
        return None
    return 100.0 * ctx.slot_steps / (ctx.steps * ctx.slots_per_bucket)
