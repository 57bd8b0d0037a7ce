"""Kernels: the scan's share of its memory roofline over the grouped queries
of the traced window. Least time = the bytes those queries have to read
(the dataset's bytes function: narrowest stored width of the referenced
columns, times the rows the time filter leaves, never more rows than the
record's rows_scanned) over the chips' peak HBM bandwidth; divided by the
device time of the same queries. Memory-bound by construction."""
import re

UNIT = "%"
GROUPED = re.compile(r"^q[234]\.")


def read(ctx):
    if ctx.trace is None:
        return None
    need, busy = 0, 0.0
    for q in ctx.trace["queries"]:
        rec = ctx.records.get(q["qid"])
        if not (q["whole"] and GROUPED.match(q["template"]) and rec):
            continue
        need += ctx.dataset.needed_bytes(q["template"], ctx.reference,
                                         rec.get("rows_scanned"))
        busy += q["device_s"]  # mean over the chips
    if busy <= 0:
        return None
    least_s = need / (ctx.peaks["hbm_bytes_per_s"] * ctx.chips)
    return 100.0 * least_s / busy
