"""MoE and kernels: device time of the paged MoE layer's programs (route,
wave, finish and the batched slot writes) in the traced window, per
prediction answered in it.

Single-expert slot writes run as an unnamed jitted lambda, which the
trace cannot tell from the head and final-norm programs, and are left
out (about 10 us each on a TPU v5e)."""

PROGRAMS = ("route", "wave", "finish", "_write_many", "_write_full")


def read(ctx):
    if ctx.trace is None:
        return None
    answered = sum(1 for r in ctx.window.due() if r.t_done is not None)
    seconds = sum(ctx.trace.program_s.get(p, 0.0) for p in PROGRAMS)
    if not answered or seconds <= 0:
        return None
    return 1e3 * seconds / answered
