"""MoE and kernels: the jitted dense block's share of its roofline.  The
least time the chip could take for one call (operations and bytes from
the shapes it is called with, one bucket's batch) over its mean device
time per call in the trace."""


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    calls = ctx.trace.program_calls.get("dense_block", 0)
    if not calls:
        return None
    per_call = ctx.trace.program_s["dense_block"] / calls
    ops, nbytes = ctx.flops.dense_block_cost(ctx.cfg, ctx.slots_per_bucket)
    least, _ = ctx.flops.roofline_s(ops, nbytes, ctx.peak)
    return 100.0 * least / per_call
