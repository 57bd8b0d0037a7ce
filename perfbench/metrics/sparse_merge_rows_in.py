"""Mesh (executor/sharding.py): median of the counter `sparse_merge_rows_in`
over the window's sparse-served queries: the rows of the chips' compact
tables that the merge was handed, on the device or at the broker (D whole
[cap] tables where the program fetches them whole, the chips' present rows
where it slices them first). A program without the counter gives nothing to read."""
from perfbench.lib import stats
from perfbench.lib import reduce_path as rp

UNIT = "count"


def read(ctx):
    seen = [rec["sparse_merge_rows_in"]
            for _s, rec in rp.served_by(ctx, "sparse")
            if rec.get("sparse_merge_rows_in") is not None]
    return stats.median(seen) if seen else None
