"""Device time per inference: the union of the busiest chip's op intervals
in the traced window over the inferences completed in it."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_completed:
        return None
    return ctx.trace["busiest_busy_s"] / ctx.traced_completed * 1e3
