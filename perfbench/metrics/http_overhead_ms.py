"""HTTP edge (api/server.py): median of the client's wall time less the
record's total_ms for the same query, joined on X-Query-Id."""
from perfbench.lib import stats

UNIT = "ms"


def read(ctx):
    d = [s["ms"] - ctx.records[s["qid"]]["total_ms"] for s in ctx.samples
         if s["qid"] in ctx.records
         and ctx.records[s["qid"]].get("total_ms") is not None]
    return stats.median(d) if d else None
