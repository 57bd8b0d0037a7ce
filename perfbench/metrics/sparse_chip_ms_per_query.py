"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged),
on a mesh: device busy time per query under the annotations of the traced
window's queries whose history record says `reduce_path: sparse`, the mean
over the cell's chips (`lib/xplane.py` divides every query's device time by
the number of device planes): every chip's sort of its own rows, the [cap]
tables read at the runs' boundaries and whatever the program runs on the
device to merge them or to hand the broker its rows. A program whose records lack
`reduce_path` gives nothing to read."""
from perfbench.lib import reduce_path as rp

UNIT = "ms"


def read(ctx):
    return rp.ms_per_query(ctx, "sparse")
