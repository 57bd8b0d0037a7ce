"""HTTP edge (api/server.py): median over the window's queries of what the
handler itself does inside the request's root span: `http-read` (body read
and JSON decode) + `serialize` (frame -> rows -> JSON bytes) + `http-write`
(status line, headers, body). A program without those spans gives nothing."""
from perfbench.lib import timeline

UNIT = "ms"


def _edge_ms(tree):
    found = [timeline.span_ms(tree, name) for name in timeline.EDGE_SPANS]
    return None if None in found else sum(found)


def read(ctx):
    return timeline.median_of(timeline.per_query(ctx, _edge_ms))
