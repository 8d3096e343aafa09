"""Median wall time of the window's requests, send to output on the host."""
from chipbench.stats import percentile


def read(ctx):
    return percentile(ctx.latencies, 50) * 1e3 if ctx.latencies else None
