"""Device: soundness of the join between the program's spans and the device
trace. Every device call gives one estimate of the offset between the two
clocks (its `device-call` span on the process axis against its annotation
in the trace); this is the 95th percentile of the estimates' distance from
their median. Everything that places a span against a device operation is
good to about this much."""
from perfbench.lib import stats, timeline

UNIT = "us"


def read(ctx):
    est = timeline.clock_offsets_us(ctx)
    if len(est) < 2:
        return None
    mid = stats.median(est)
    return stats.percentile([abs(e - mid) for e in est], 95.0)
