"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged):
the wide-key queries' share of their memory roofline over the traced
window: the bytes those templates have to read (the dataset's bytes
function: the group columns, the filters' and the aggregated columns once
over the rows scanned, the same work whatever implements it) over the
chip's peak HBM bandwidth, divided by the device time of the same queries
(records with `key_words` >= 2). A key of two words, the sort's passes, the
prefix sums, the [cap] tables and a HAVING's cut are what such a query pays
beyond one read; they count against the share, not into the numerator."""
from perfbench.lib import widekey

UNIT = "%"


def read(ctx):
    need, busy = 0, 0.0
    for q, rec in widekey.traced(ctx):
        need += ctx.dataset.needed_bytes(q["template"], ctx.reference,
                                         rec.get("rows_scanned"))
        busy += q["device_s"]
    if busy <= 0:
        return None
    least_s = need / (ctx.peaks["hbm_bytes_per_s"] * ctx.chips)
    return 100.0 * least_s / busy
