"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged):
device self time under the stage `sort` per query of the traced window's
whole queries whose history record says `reduce_path: sparse`, in the cell
whose every such query sorts by a key of two int64 words: the multi-operand
`lax.sort` (`num_keys` = the words) and the selects that build its
operands, the first of the two stages a key word makes dearer. The stage is
the program's own `jax.named_scope`, read from the capture's `tf_op`
(`lib/stages.py`; an op the compiler made without a name takes its
consumers' stage). A program without the stage vocabulary, or a run without
a capture, gives nothing to read."""
from perfbench.lib import stages

UNIT = "ms"


def read(ctx):
    return stages.sparse_ms_per_query(ctx, "sort")
