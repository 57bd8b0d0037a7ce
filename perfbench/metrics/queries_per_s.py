"""Queries completed and correct per second of window: all the window's
work over all its time (window start to the last answer)."""

UNIT = "queries/s"


def read(ctx):
    if not ctx.samples or ctx.elapsed_s <= 0:
        return None
    return (len(ctx.samples) - ctx.failed) / ctx.elapsed_s
