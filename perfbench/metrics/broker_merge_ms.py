"""Mesh (executor/sharding.py): median `broker-merge` span, the host's fold
of the chips' partial tables. Only a mesh cell has the span."""
from perfbench.lib import stats

UNIT = "ms"


def _find(tree, name, out):
    if tree.get("name") == name:
        out.append(tree["duration_ms"])
    for child in tree.get("children", []):
        _find(child, name, out)


def read(ctx):
    d = []
    for s in ctx.samples:
        tree = ctx.traces.get(s["qid"])
        if tree is not None:
            _find(tree, "broker-merge", d)
    return stats.median(d) if d else None
