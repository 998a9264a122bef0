"""Expert paging: bytes copied into the expert slots inside the window,
per prediction completed in it, in MB (1e6 bytes)."""


def read(ctx):
    n = ctx.completed_in_window
    return ctx.cache["bytes_paged"] / 1e6 / n if n else None
