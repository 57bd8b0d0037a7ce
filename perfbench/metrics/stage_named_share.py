"""Device: the share of the traced window's device self time (the mean of
the chips) whose op carries a stage of the program's vocabulary
(`tpu_olap/kernels/groupby.py::STAGES`): in its own op_name, which the
capture keeps as `tf_op`, or, for an op the compiler made without a name (a
rewritten reduce-window, a layout copy), its consumers' (`lib/stages.py`,
which prints the two parts apart, and the largest ops with neither). The
soundness of the join of device time with the program's names, as
`idle_named_share` is of the host's. At most 100 by construction. A program
without the vocabulary, or a run without a capture, gives nothing to read."""
from perfbench.lib import stages

UNIT = "%"


def read(ctx):
    out = stages.reduce(ctx)
    if out is None:
        return None
    how = out["how_s"]
    total = sum(how.values())
    if total <= 0:
        return None
    return 100.0 * (how[stages.OWN] + how[stages.INHERITED]) / total
