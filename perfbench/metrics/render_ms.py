"""SQL to plan (planner/, api/engine.py): the `render` span (device result
-> the answer's frame: labels, ORDER BY, LIMIT) of the template where it is
longest: the largest per-template median."""
from perfbench.lib import timeline

UNIT = "ms"


def read(ctx):
    return timeline.worst_of(timeline.per_query(
        ctx, lambda tree: timeline.span_ms(tree, "render")))
