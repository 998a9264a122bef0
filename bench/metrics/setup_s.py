"""Process start to window start: imports, device check, weights and
frames, the entry built, every shape of the window run once."""


def read(ctx):
    return ctx.setup_s
