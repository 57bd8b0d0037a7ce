"""Dispatch (executor/runner.py): median over the traced window's whole
queries of the device call's extent (the program's
`TraceAnnotation(query_id)`) less the device's busy time for that query:
the part of prepare, launch and fetch during which the chip waits."""
from perfbench.lib import stats

UNIT = "ms"


def read(ctx):
    if ctx.trace is None:
        return None
    d = [(q["end_s"] - q["start_s"] - q["device_s"]) * 1000.0
         for q in ctx.trace["queries"] if q["whole"]]
    return stats.median(d) if d else None
