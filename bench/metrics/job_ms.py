"""Milliseconds per job over the whole window: the window's length over
the jobs completed in it."""


def read(ctx):
    return ctx.window_s * 1e3 / len(ctx.answers) if ctx.answers else None
