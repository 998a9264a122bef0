"""Task predictions completed inside the window, per second of it."""


def read(ctx):
    return ctx.completed_in_window / ctx.seconds
