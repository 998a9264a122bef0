"""Expert paging: share of the traced window in which the device idled
while the innermost span the program had open was one of the expert
cache's (``repro.paging.*``: ensure, page_in, device_put, slot_write)."""

from harness import program_spans


def read(ctx):
    ps = program_spans.read(ctx)
    return None if ps is None else ps.idle_share("repro.paging.")
