"""Mesh (executor/sharding.py): the `broker-merge` span of a sparse query,
the merge of the chips' compact tables by key (in numpy at the broker, or
the wait for the chips' own merge program), of the sparse
template where it is longest (the largest per-template median). Only the
queries whose history record says `reduce_path: sparse` are read: a dense
query's `broker-merge` folds [K] tables and is `broker_merge_ms`'s."""
from perfbench.lib import timeline
from perfbench.lib import reduce_path as rp

UNIT = "ms"


def read(ctx):
    pairs = []
    for s, _rec in rp.served_by(ctx, "sparse"):
        tree = ctx.traces.get(s["qid"])
        v = timeline.span_ms(tree, "broker-merge") if tree is not None \
            else None
        if v is not None:
            pairs.append((s["template"], v))
    return timeline.worst_of(pairs)
