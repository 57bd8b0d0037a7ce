"""Median served-path latency over every query completed in the window:
the client's clock, request write to the answer's last byte."""
from perfbench.lib import stats

UNIT = "ms"


def read(ctx):
    return stats.median([s["ms"] for s in ctx.samples]) if ctx.samples \
        else None
