"""Dispatch (executor/runner.py): median of the counter `sparse_attempts`
over the window's sparse-served queries: how many times the compact table's
cap was tried (each a run of the sort; a new cap compiles). 1 when the
template's cap hint is warm, which warm-up leaves it."""
from perfbench.lib import stats
from perfbench.lib import reduce_path as rp

UNIT = "count"


def read(ctx):
    seen = [rec["sparse_attempts"] for _s, rec in rp.served_by(ctx, "sparse")
            if rec.get("sparse_attempts") is not None]
    return stats.median(seen) if seen else None
