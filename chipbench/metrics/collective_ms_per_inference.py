"""Collective time per inference: the union of the busiest chip's
``collective-permute`` ops in the traced window over the inferences
completed in it (the trace reduction's ``busiest_collective_s``)."""


def read(ctx):
    if ctx.trace is None or not ctx.traced_completed:
        return None
    return ctx.trace["busiest_collective_s"] / ctx.traced_completed * 1e3
