"""Sparse group-by (kernels/sparse_groupby.py,
runner._run_sparse_staged): device time per query of the traced
window's queries that the sort-based sparse group-by served: those whose
history record says `reduce_path: sparse`. Device time is the union of the
operations' intervals under the query's annotation (every cap attempt's
sort and segment reduces). A program whose records lack `reduce_path`
gives nothing to read."""
from perfbench.lib import reduce_path as rp

UNIT = "ms"


def read(ctx):
    return rp.ms_per_query(ctx, "sparse")
