"""SQL to plan (planner/, api/engine.py): median over the window's queries
of the span tree's `parse` + `plan` spans plus the record's lower_ms (the
lowering holds the stage graph's `plan` stage)."""
from perfbench.lib import stats

UNIT = "ms"


def _span_ms(tree, names):
    total = 0.0
    for child in tree.get("children", []):
        if child["name"] in names:
            total += child["duration_ms"]
    return total


def read(ctx):
    d = []
    for s in ctx.samples:
        rec, tree = ctx.records.get(s["qid"]), ctx.traces.get(s["qid"])
        if rec is None or tree is None or rec.get("lower_ms") is None:
            continue
        d.append(_span_ms(tree, ("parse", "plan")) + rec["lower_ms"])
    return stats.median(d) if d else None
