"""Inferences completed in the window over the window's seconds."""


def read(ctx):
    return ctx.completed / ctx.window_s if ctx.completed else None
