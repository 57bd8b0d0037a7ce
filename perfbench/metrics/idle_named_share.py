"""Device: the share of the device's idle time in the traced window that lies
under a named leaf span of the program or between two requests; the rest
is self time of spans with children (the root, `execute`): host work with
no name yet. At most 100 by construction (`lib/timeline.py::idle_account`).
Prints, on the lines before the result, the idle seconds by name, what
follows the device call of the templates with most of it, and the check
that the timeline's parts add up to the query period."""
from perfbench.lib import stats, timeline

UNIT = "%"


def _say(msg):
    print(f"[perfbench] {msg}", flush=True)


def read(ctx):
    acc = timeline.idle_account(ctx)
    if acc is None or acc["named_ms"] + acc["unnamed_ms"] <= 0:
        return None
    total = acc["named_ms"] + acc["unnamed_ms"]
    n = acc["requests"]
    parts = {k: v / n for k, v in acc["parts_ms"].items()}
    by_trace = 1000 * ctx.trace["window_s"] / max(1, len(ctx.trace["queries"]))
    by_client = 1000 * ctx.elapsed_s / max(1, len(ctx.samples))
    _say(f"timeline: {n} requests; mean ms per request: "
         + " + ".join(f"{k} {v:.3f}" for k, v in parts.items())
         + f" = {sum(parts.values()):.3f}; the trace's window_s / annotated "
         f"queries = {by_trace:.3f}; the client's elapsed_s / completed = "
         f"{by_client:.3f}")
    _say(f"timeline: idle by the spans {total / 1000:.3f} s, by the trace "
         f"{ctx.trace['window_s'] - ctx.trace['busy_s']:.3f} s; by name (s): "
         + ", ".join(f"{k} {v / 1000:.3f}" for k, v in sorted(
             acc["by_name"].items(), key=lambda kv: -kv[1])[:16]))
    after = {t: {k: stats.median(v) for k, v in names.items()}
             for t, names in acc["after_by_template"].items()}
    for t in sorted(after, key=lambda t: -sum(after[t].values()))[:4]:
        _say(f"timeline: after the device call of {t} (median ms): "
             + ", ".join(f"{k} {v:.3f}" for k, v in sorted(
                 after[t].items(), key=lambda kv: -kv[1])[:10]))
    return 100.0 * acc["named_ms"] / total
