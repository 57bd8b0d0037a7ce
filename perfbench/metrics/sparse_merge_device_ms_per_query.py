"""Mesh (executor/sharding.py): device self time under the stage `merge` per
query of the traced window's whole queries whose history record says
`reduce_path: sparse`, the mean over the cell's chips: the all_gather of the
chips' compact tables and the two sorts of `merge_device`
(`sharding.mesh_merge_kernel`), without the host's wait for them, which
`sparse_merge_ms` spans. Read from the capture's `tf_op`
(`lib/stages.py`). A program without the stage vocabulary, or a run without
a capture, gives nothing to read."""
from perfbench.lib import stages

UNIT = "ms"


def read(ctx):
    return stages.sparse_ms_per_query(ctx, "merge")
