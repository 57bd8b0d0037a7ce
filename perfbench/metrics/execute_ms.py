"""Dispatch (executor/runner.py): median of the record's execute_ms, the
device call and the fetch of its packed answer."""
from perfbench.lib import stats

UNIT = "ms"


def read(ctx):
    d = [rec["execute_ms"]
         for rec in (ctx.records.get(s["qid"]) for s in ctx.samples)
         if rec and rec.get("execute_ms") is not None]
    return stats.median(d) if d else None
