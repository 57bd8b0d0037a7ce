"""Dispatch (executor/runner.py): host time from the end of the last
`device-call` span to the root span closing, over the window's queries
whose sparse key took more than one int64 word (records with `key_words`
>= 2), of the template where it is longest (the largest per-template
median): the fetch of a `_keys` table a word, finalize, the decode of each
dimension's ids from its word, the ordered LIMIT, the rows, render and
serialize. The device is idle throughout."""
from perfbench.lib import timeline, widekey

UNIT = "ms"


def read(ctx):
    pairs = []
    for s, _rec in widekey.served(ctx):
        tree = ctx.traces.get(s["qid"])
        v = timeline.after_dispatch_ms(tree) if tree is not None else None
        if v is not None:
            pairs.append((s["template"], v))
    return timeline.worst_of(pairs)
