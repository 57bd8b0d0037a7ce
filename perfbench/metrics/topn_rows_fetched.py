"""Dispatch (executor/runner.py): median of the counter `topn_rows_fetched`
over the window's TopN queries: group rows brought to the host to be
ranked. `threshold` (100) where the device applied the threshold, the dense
group space K where a [K] table was fetched and argsorted on the host, the
compact table's cap where a sparse table was."""
from perfbench.lib import stats, topn

UNIT = "count"


def read(ctx):
    seen = [rec["topn_rows_fetched"] for _s, rec in topn.served(ctx)
            if rec.get("topn_rows_fetched") is not None]
    return stats.median(seen) if seen else None
