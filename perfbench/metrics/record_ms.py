"""Dispatch (executor/runner.py): median `record` span, QueryRunner.record()
on the serving thread: workload profiler, metric registry, SLO, event log
and sentinel, once per query."""
from perfbench.lib import timeline

UNIT = "ms"


def read(ctx):
    return timeline.median_of(timeline.per_query(
        ctx, lambda tree: timeline.span_ms(tree, "record")))
