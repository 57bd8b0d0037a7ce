"""Dispatch (executor/runner.py): host time from the end of the last
`device-call` span to the root span closing, over the sparse-served
queries, of the template where it is longest (the largest per-template
median): finalize, the ordered LIMIT over every present group (span
`ordered-limit`), the rows, render and serialize. The device is idle
throughout."""
from perfbench.lib import timeline
from perfbench.lib import reduce_path as rp

UNIT = "ms"


def read(ctx):
    pairs = []
    for s, _rec in rp.served_by(ctx, "sparse"):
        tree = ctx.traces.get(s["qid"])
        v = timeline.after_dispatch_ms(tree) if tree is not None else None
        if v is not None:
            pairs.append((s["template"], v))
    return timeline.worst_of(pairs)
