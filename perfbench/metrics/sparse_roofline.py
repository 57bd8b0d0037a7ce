"""Sparse group-by (kernels/sparse_groupby.py,
runner._run_sparse_staged): the sparse-served queries' share of
their memory roofline over the traced window: the bytes those templates
have to read (the dataset's bytes function) over the chip's peak HBM
bandwidth, divided by the device time of the same queries. The sort's
passes over the key and the carried operands are what it pays beyond one
read; they count against the share, not into the numerator."""
from perfbench.lib import reduce_path as rp

UNIT = "%"


def read(ctx):
    return rp.roofline(ctx, "sparse")
