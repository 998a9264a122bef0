"""Expert paging: host time of the expert cache's page-ins in the traced
window (the program's ``repro.paging.page_in`` spans; synchronous paging
stalls the serving thread for all of it), per prediction answered in the
window."""

from harness import program_spans


def read(ctx):
    ps = program_spans.read(ctx)
    if ps is None or not ctx.completed_in_window:
        return None
    seconds = ps.program_span_s.get("repro.paging.page_in", 0.0)
    return 1e3 * seconds / ctx.completed_in_window
