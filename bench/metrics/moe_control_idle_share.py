"""Paged MoE host control: share of the traced window in which the device
idled while the innermost span the program had open was the paged MoE
layer's own (``repro.moe.*``: the router launch, the blocking readback of
its counts, wave planning, wave launches, the finish), not its paging."""

from harness import program_spans


def read(ctx):
    ps = program_spans.read(ctx)
    return None if ps is None else ps.idle_share("repro.moe.")
