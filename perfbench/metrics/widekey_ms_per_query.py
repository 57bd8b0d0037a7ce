"""Sparse group-by (kernels/sparse_groupby.py, runner._run_sparse_staged):
device time per query of the traced window's queries whose sparse key took
more than one int64 word: those whose history record says `key_words` >= 2.
Device time is the union of the operations' intervals under the query's
annotation. A program whose records lack the counter gives nothing to
read."""
from perfbench.lib import widekey

UNIT = "ms"


def read(ctx):
    busy = [q["device_s"] for q, _rec in widekey.traced(ctx)]
    return 1000.0 * sum(busy) / len(busy) if busy else None
