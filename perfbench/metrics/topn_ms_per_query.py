"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged):
device time per query of the traced window's TopN queries: those whose
history record carries `topn_group_space`, whatever path served them (the
sparse sort with the threshold on the device, or a dense [K] table ranked
on the host). Device time is the union of the operations' intervals under
the query's annotation. A program whose records lack the counter gives
nothing to read."""
from perfbench.lib import topn

UNIT = "ms"


def read(ctx):
    busy = [q["device_s"] for q, _rec in topn.traced(ctx)]
    return 1000.0 * sum(busy) / len(busy) if busy else None
