"""Kernels (kernels/pallas_reduce.py, kernels/groupby.py): device self time
of the ops whose `hlo_category` is `data formatting` (the compiler's layout
copies: a column into the Pallas kernel's [1, n] layout, a table into the
packed buffer's) per grouped query (q2.x-q4.x) of the traced window, from
the stat of the capture's event metadata (`lib/stages.py`). A program
without the stage vocabulary, or a run without a capture, gives nothing to
read."""
import re

from perfbench.lib import stages

UNIT = "ms"
GROUPED = re.compile(r"^q[234]\.")


def read(ctx):
    out = stages.reduce(ctx)
    if out is None:
        return None
    took = [q["layout_copy_s"] for q in out["queries"]
            if q["whole"] and GROUPED.match(q["template"])]
    return 1000.0 * sum(took) / len(took) if took else None
