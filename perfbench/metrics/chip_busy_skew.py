"""Mesh (executor/sharding.py): (max - min) / max of the chips' busy time
in the traced window. Only a cell on several chips reads it."""

UNIT = "%"


def read(ctx):
    if ctx.trace is None:
        return None
    busy = list(ctx.trace["busy_s_by_device"].values())
    if len(busy) < 2 or max(busy) <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / max(busy)
