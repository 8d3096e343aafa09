"""Share of the traced window in which no op ran, averaged over the cell's
chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace["idle_share"] * 100.0
