"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged),
on a mesh: the sparse-served queries' share of their memory roofline over
the traced window. The numerator is the dataset's bytes function over those
templates (the columns each has to read, once, over the rows its time
filter leaves: the same work whatever implements it) over the peak HBM
bandwidth of all the cell's chips; the denominator is the busiest chip's
time under the same queries. The reduction keeps a query's device time as
the mean of the chips, so the busiest chip's is taken as that mean times
the window's busiest chip's busy time over the chips' mean busy time (1
where the chips are level, which interleaved segments make them). The
sort's passes, the prefix sums, the gathers and the rows handed to the
broker count against the share, not into the numerator."""
from perfbench.lib import reduce_path as rp

UNIT = "%"


def read(ctx):
    share = rp.roofline(ctx, "sparse")
    if share is None:
        return None
    busy = list(ctx.trace["busy_s_by_device"].values())
    mean = sum(busy) / len(busy) if busy else 0.0
    return share * mean / max(busy) if mean > 0 else share
