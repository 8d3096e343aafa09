"""95th percentile (nearest rank) of the window's request latencies."""
from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.latencies, 95) * 1e3 if ctx.latencies else None
