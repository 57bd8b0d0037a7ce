"""Dispatch (executor/runner.py): host time from the end of the last
`device-call` span to the root span closing, over the window's TopN queries
(records with `topn_group_space`), of the template where it is longest (the
largest per-template median): finalize, the `topn-threshold` span (the
ranking of every fetched group row where its `where` says `host`, of the
`threshold` rows the device kept where it says `device`), the rows, render
and serialize. The device is idle throughout."""
from perfbench.lib import timeline, topn

UNIT = "ms"


def read(ctx):
    pairs = []
    for s, _rec in topn.served(ctx):
        tree = ctx.traces.get(s["qid"])
        v = timeline.after_dispatch_ms(tree) if tree is not None else None
        if v is not None:
            pairs.append((s["template"], v))
    return timeline.worst_of(pairs)
