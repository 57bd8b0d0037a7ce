"""Dispatch (executor/runner.py): host time from the end of the last
`device-call` span to the root span closing, over the window's queries with
a HAVING (records with `having_groups_in`), of the template where it is
longest (the largest per-template median): finalize, the decode of the
fetched groups, the `having` span where its `where` says `host` (the
predicate over every fetched group), the ordered LIMIT, the rows, render
and serialize. The device is idle throughout."""
from perfbench.lib import having, timeline

UNIT = "ms"


def read(ctx):
    pairs = []
    for s, _rec in having.served(ctx):
        tree = ctx.traces.get(s["qid"])
        v = timeline.after_dispatch_ms(tree) if tree is not None else None
        if v is not None:
            pairs.append((s["template"], v))
    return timeline.worst_of(pairs)
