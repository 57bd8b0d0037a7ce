"""HTTP edge (api/server.py): median time from one request's root span
closing (the last byte written) to the next one's opening (headers parsed,
before the body is read), on the roots' shared `t0_ns` axis. With the one
closed-loop client that reads it this is both socket transits plus the load
generator; no change to the program shortens it."""
from perfbench.lib import timeline

UNIT = "ms"


def read(ctx):
    gaps = timeline.between_requests(ctx)
    return timeline.median_of(gaps) if gaps else None
