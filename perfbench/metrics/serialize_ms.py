"""HTTP edge (api/server.py): the `serialize` span (result frame -> rows ->
JSON bytes) of the template where it is longest: the largest per-template
median, as `slowest_query_p50_ms` is built."""
from perfbench.lib import timeline

UNIT = "ms"


def read(ctx):
    return timeline.worst_of(timeline.per_query(
        ctx, lambda tree: timeline.span_ms(tree, "serialize")))
