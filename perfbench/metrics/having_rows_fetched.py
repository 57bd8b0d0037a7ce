"""Dispatch (executor/runner.py): median of the counter
`having_rows_fetched` over the window's queries with a HAVING: rows a table
brought to the host. The kept bucket (a power of two that holds the groups
that passed) where the device decided the predicate, the compact table's
cap where the host did over a sparse table, the dense group space K where
it did over a [K] table."""
from perfbench.lib import having, stats

UNIT = "count"


def read(ctx):
    seen = [rec["having_rows_fetched"] for _s, rec in having.served(ctx)
            if rec.get("having_rows_fetched") is not None]
    return stats.median(seen) if seen else None
