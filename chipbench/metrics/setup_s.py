"""Process start to the first timed request: weights, plan, compile or
cache load, and warm-up."""


def read(ctx):
    return ctx.setup_s
