"""Stage graph (executor/stages.py): median per query of the summed waits
for a slot of each stage, from the record's `stages`."""
from perfbench.lib import stats

UNIT = "ms"


def read(ctx):
    d = [sum(st.get("wait_ms", 0.0) for st in rec["stages"])
         for rec in (ctx.records.get(s["qid"]) for s in ctx.samples)
         if rec and rec.get("stages")]
    return stats.median(d) if d else None
