"""Mesh (executor/sharding.py): the `sparse-shard-fetch` span, the fetch of
compact tables to the host in a mesh's sparse dispatch (the D chips' tables
where the broker merges them, one copy of the merged table where the chips
do), of the
sparse template where it is longest (the largest per-template median of a
query's summed spans). A program without the span (an older commit, a cell
on one chip) gives nothing to read."""
from perfbench.lib import timeline

UNIT = "ms"


def read(ctx):
    return timeline.worst_of(timeline.per_query(
        ctx, lambda tree: timeline.span_ms(tree, "sparse-shard-fetch")))
