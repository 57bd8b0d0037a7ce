"""Dispatch (executor/runner.py): host time from the end of the request's
last `device-call` span to its root span closing (finalize, assemble,
record, render, serialize, write), of the template where it is longest: the
largest per-template median. The device is idle throughout."""
from perfbench.lib import timeline

UNIT = "ms"


def read(ctx):
    return timeline.worst_of(
        timeline.per_query(ctx, timeline.after_dispatch_ms))
