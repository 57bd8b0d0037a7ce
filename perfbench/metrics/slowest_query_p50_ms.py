"""The largest per-template median: the north star's own shape ("every
query under 500 ms p50", BASELINE.json)."""
from perfbench.lib import stats

UNIT = "ms"


def read(ctx):
    return stats.slowest_template(ctx.samples)[1] if ctx.samples else None
