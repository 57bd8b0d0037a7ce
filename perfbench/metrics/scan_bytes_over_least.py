"""Kernels: bytes the program hands its kernels over the bytes the answer
needs, for the grouped whole queries of the traced window (the set
`scan_roofline` uses). Numerator: the records' `bytes_scanned` (every array
of the kernel's env and the validity mask at its resident width, over the
padded rows of the scanned segments). Denominator: the dataset's
`needed_bytes`. Both are counts that repeat exactly for a template."""
import re

UNIT = "x"
GROUPED = re.compile(r"^q[234]\.")


def read(ctx):
    if ctx.trace is None:
        return None
    handed, need = 0, 0
    for q in ctx.trace["queries"]:
        rec = ctx.records.get(q["qid"])
        if not (q["whole"] and GROUPED.match(q["template"]) and rec):
            continue
        if rec.get("bytes_scanned") is None:
            return None  # a program without the counter
        handed += rec["bytes_scanned"]
        need += ctx.dataset.needed_bytes(q["template"], ctx.reference,
                                         rec.get("rows_scanned"))
    return handed / need if need > 0 else None
