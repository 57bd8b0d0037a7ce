"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged):
the TopN queries' share of their memory roofline over the traced window:
the bytes those templates have to read (the dataset's bytes function: key
and aggregated columns once, the same whatever path serves the query) over
the chip's peak HBM bandwidth, divided by the device time of the same
queries. The sort's passes over the key and the carried operands, a
segment reduce a min / max, the [cap] tables and the threshold are what a
TopN pays beyond one read; they count against the share, not into the
numerator."""
from perfbench.lib import topn

UNIT = "%"


def read(ctx):
    need, busy = 0, 0.0
    for q, rec in topn.traced(ctx):
        need += ctx.dataset.needed_bytes(q["template"], ctx.reference,
                                         rec.get("rows_scanned"))
        busy += q["device_s"]
    if busy <= 0:
        return None
    least_s = need / (ctx.peaks["hbm_bytes_per_s"] * ctx.chips)
    return 100.0 * least_s / busy
