"""Kernels (kernels/groupby.py): the scatter-served queries' share of their
memory roofline over the traced window. Least time = the bytes those
templates have to read (the dataset's bytes function: narrowest width of
the referenced columns, times the rows the time filter leaves, never more
rows than the record's rows_scanned) over the chip's peak HBM bandwidth;
divided by the device time of the same queries. Memory-bound by
construction: a filter, a key and a few adds per row."""
from perfbench.lib import reduce_path as rp

UNIT = "%"


def read(ctx):
    return rp.roofline(ctx, "scatter")
