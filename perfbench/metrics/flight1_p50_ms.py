"""Dispatch (executor/runner.py): the client's median over Q1.1-Q1.3 only.
They are time-pruned with sub-millisecond device work, so this is the host
cost that every query pays."""
from perfbench.lib import stats

UNIT = "ms"
FLIGHT1 = ("q1.1", "q1.2", "q1.3")


def read(ctx):
    d = [s["ms"] for s in ctx.samples if s["template"] in FLIGHT1]
    return stats.median(d) if d else None
