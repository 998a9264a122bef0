"""Expert paging: demand hits over demand accesses of the expert cache,
all MoE layers, inside the window."""


def read(ctx):
    n = ctx.cache["hits"] + ctx.cache["misses"]
    return 100.0 * ctx.cache["hits"] / n if n else None
