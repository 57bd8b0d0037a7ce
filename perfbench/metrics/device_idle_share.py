"""Device: 1 - (union of the device operations' intervals / traced window),
the mean over the chips."""

UNIT = "%"


def read(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
