"""Kernels (kernels/groupby.py): device time per query of the traced
window's queries that XLA's scatter served: those whose history record says
`reduce_path: scatter` (the plan `pallas_reduce.eligible` turned down; the
record carries the reason as `pallas_reason`). Device time is the union of
the operations' intervals under the query's annotation. A program whose
records lack `reduce_path` gives nothing to read."""
from perfbench.lib import reduce_path as rp

UNIT = "ms"


def read(ctx):
    return rp.ms_per_query(ctx, "scatter")
