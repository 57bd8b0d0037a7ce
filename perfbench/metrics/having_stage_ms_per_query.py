"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged):
device self time under the stage `having` per query of the traced window's
whole queries whose history record says `reduce_path: sparse`: the
predicate over the tested aggregates' [cap] tables and the one-operand sort
that compacts the passing slots into the kept bucket. The stage is the
program's own `jax.named_scope`, read from the capture's `tf_op`
(`lib/stages.py`; an op the compiler made without a name takes its
consumers' stage); the mean over the cell's chips. A program without the
stage in its vocabulary, or a run without a capture, gives nothing to
read."""
from perfbench.lib import stages

UNIT = "ms"


def read(ctx):
    return stages.sparse_ms_per_query(ctx, "having")
