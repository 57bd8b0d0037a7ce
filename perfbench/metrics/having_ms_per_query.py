"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged):
device time per query of the traced window's queries with a HAVING: those
whose history record carries `having_groups_in`, whoever decided the
predicate (the device's program, or the host over the fetched table).
Device time is the union of the operations' intervals under the query's
annotation. A program whose records lack the counter gives nothing to
read."""
from perfbench.lib import having

UNIT = "ms"


def read(ctx):
    busy = [q["device_s"] for q, _rec in having.traced(ctx)]
    return 1000.0 * sum(busy) / len(busy) if busy else None
