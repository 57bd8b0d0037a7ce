"""Dispatch (executor/runner.py): median host time from the request's root
span opening to its first `device-call` span: body read, parse, plan,
lowering and admission, before the device is asked for anything."""
from perfbench.lib import timeline

UNIT = "ms"


def read(ctx):
    return timeline.median_of(
        timeline.per_query(ctx, timeline.before_dispatch_ms))
