"""The tail of all completed queries. Reported only where the window holds
at least 200 completions: ten samples beyond the percentile."""
from perfbench.lib import stats

UNIT = "ms"
MIN_SAMPLES = 200


def read(ctx):
    if len(ctx.samples) < MIN_SAMPLES:
        return None
    return stats.percentile([s["ms"] for s in ctx.samples], 95.0)
