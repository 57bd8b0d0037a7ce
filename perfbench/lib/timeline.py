"""One request timeline from the program's span trees, and its join with the
device trace.

What it takes from the program (`tpu_olap/obs/trace.py`):

- a root's `t0_ns` (`time.perf_counter_ns()` at entry): `t0_ns` / 1e6 plus a
  span's `start_ms` is that span's place, in milliseconds, on one axis for
  the whole process, so spans of different requests can be laid end to end;
- the root opened by the HTTP handler before it reads the body and closed
  after the last byte is written, with the children `http-read`, `serialize`
  and `http-write` around the engine's own;
- `device-call`: the span of exactly the extent of the
  `TraceAnnotation(query_id)` that `lib/xplane.py` finds in the device
  trace, the one instant both clocks see.

A program without them (an older commit) gives nothing to read: every
function here then returns None or an empty list, and never raises.

The period of one closed-loop client then splits, request by request, into
root start -> first device call (`before`), the device calls (`call`: the
device's busy time plus its idle time inside them), last device call ->
root end (`after`) and root end -> next root start (`between`). Inside a
root, a LEAF span is named host work; what a span with children does
between them is its self time, which has no name of its own.
"""

from __future__ import annotations

from perfbench.lib import stats

DEVICE_CALL = "device-call"
_EPS_MS = 1e-6  # a nanosecond: under the spans' own grain, over float error
EDGE_SPANS = ("http-read", "serialize", "http-write")


def walk(tree: dict):
    yield tree
    for child in tree.get("children", []):
        yield from walk(child)


def spans_named(tree: dict, name: str) -> list:
    """Every finished span of that name, in start order."""
    out = [s for s in walk(tree) if s.get("name") == name
           and s.get("start_ms") is not None
           and s.get("duration_ms") is not None]
    return sorted(out, key=lambda s: s["start_ms"])


def span_ms(tree: dict, name: str):
    """Summed duration of the spans of that name, or None without one."""
    found = spans_named(tree, name)
    return sum(s["duration_ms"] for s in found) if found else None


def per_query(ctx, value) -> list:
    """[(template, value(tree))] over the window's queries whose span tree
    is at hand and for which `value` finds something."""
    out = []
    for s in ctx.samples:
        tree = ctx.traces.get(s["qid"])
        v = value(tree) if tree is not None else None
        if v is not None:
            out.append((s["template"], v))
    return out


def median_of(pairs: list):
    return stats.median([v for _t, v in pairs]) if pairs else None


def worst_of(pairs: list):
    """The largest per-template median, as `slowest_query_p50_ms` is built."""
    by: dict = {}
    for t, v in pairs:
        by.setdefault(t, []).append(v)
    return max(stats.median(v) for v in by.values()) if by else None


def before_dispatch_ms(tree: dict):
    calls = spans_named(tree, DEVICE_CALL)
    return calls[0]["start_ms"] if calls else None


def after_dispatch_ms(tree: dict):
    calls = spans_named(tree, DEVICE_CALL)
    if not calls or tree.get("duration_ms") is None:
        return None
    return tree["duration_ms"] - (calls[-1]["start_ms"]
                                  + calls[-1]["duration_ms"])


# ------------------------------------------------------- the process axis

def roots_in_order(ctx) -> list:
    """[(sample, tree, start, end)] of the window's queries on the process
    axis (ms), in start order; empty where a tree lacks `t0_ns`."""
    out = []
    for s in ctx.samples:
        tree = ctx.traces.get(s["qid"])
        if tree is None or tree.get("t0_ns") is None \
                or tree.get("duration_ms") is None:
            continue
        start = tree["t0_ns"] / 1e6
        out.append((s, tree, start, start + tree["duration_ms"]))
    return sorted(out, key=lambda r: r[2])


def between_requests(ctx):
    """[(template of the earlier request, ms)]: root end -> next root
    start. None where the roots cannot be laid end to end: a tree missing,
    no `t0_ns`, or two requests in flight at once."""
    roots = roots_in_order(ctx)
    if len(roots) < 2 or len(roots) != len(ctx.samples):
        return None
    gaps = [(a[0]["template"], b[2] - a[3])
            for a, b in zip(roots, roots[1:])]
    return gaps if all(g >= 0 for _t, g in gaps) else None


# ------------------------------------------ the join with the device trace

def matched_calls(ctx) -> list:
    """[(sample, tree, start, device-call span, annotated entry of the
    reduced trace)]: the k-th `device-call` of a query with the k-th
    annotation of its id; queries whose counts differ are left out."""
    if ctx.trace is None:
        return []
    by_qid: dict = {}
    for q in ctx.trace["queries"]:
        by_qid.setdefault(q["qid"], []).append(q)
    out = []
    for s, tree, start, _end in roots_in_order(ctx):
        calls = spans_named(tree, DEVICE_CALL)
        anns = sorted(by_qid.get(s["qid"], []), key=lambda q: q["start_s"])
        if calls and len(calls) == len(anns):
            out.extend((s, tree, start, c, a) for c, a in zip(calls, anns))
    return out


def clock_offsets_us(ctx) -> list:
    """One estimate per device call of (trace clock - process axis), in
    microseconds: the annotation's midpoint less the span's. The
    annotation nests inside the span by about a microsecond on either
    side, which the midpoints cancel."""
    out = []
    for _s, _tree, start, call, ann in matched_calls(ctx):
        span_mid = start + call["start_ms"] + call["duration_ms"] / 2.0
        ann_mid = (ann["start_s"] + ann["end_s"]) * 500.0
        out.append((ann_mid - span_mid) * 1000.0)
    return out


# ---------------------------------------------------- naming the idle time

def _pieces(span: dict, origin: float, out: list) -> None:
    """Cut a span's interval into named pieces: a leaf is one piece under
    its own name; a span with children gives its children's pieces and,
    for what lies between them, pieces named `self:<its name>`."""
    if span.get("start_ms") is None or span.get("duration_ms") is None:
        return
    start = origin + span["start_ms"]
    end = start + span["duration_ms"]
    children = sorted((c for c in span.get("children", [])
                       if c.get("start_ms") is not None
                       and c.get("duration_ms") is not None),
                      key=lambda c: c["start_ms"])
    if not children:
        out.append((span["name"], start, end))
        return
    cursor = start
    for c in children:
        c_start = origin + c["start_ms"]
        c_end = c_start + c["duration_ms"]
        if c_start - cursor > _EPS_MS:
            out.append(("self:" + span["name"], cursor, min(c_start, end)))
        _pieces(c, origin, out)
        cursor = max(cursor, c_end)
    if end - cursor > _EPS_MS:
        out.append(("self:" + span["name"], cursor, end))


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def idle_account(ctx):
    """Where the device's idle time of the window lies, by the name of what
    the host was doing. Returns None where the program's spans cannot say
    (no `t0_ns`, no `device-call`, requests that overlap), else

        {"named_ms", "unnamed_ms", "by_name": {name: ms},
         "after_by_template": {template: {name: [ms per request]}},
         "parts_ms": {"before", "busy", "dispatch_idle", "after",
                      "between"},   # sums over the requests counted
         "requests": n}

    Outside the device calls the device is idle throughout (the call ends
    when the answer has been fetched), so every piece of the span tree
    there is idle time under that piece's name, and the time from a root's
    end to the next root's start is `between_requests`. Inside a device
    call the idle time is the call's extent less the device's busy time
    (from the trace); the busy time is taken to lie under the call's leaf
    spans (the host sits in the launch or in the fetch while the device
    works), so the call's own self time counts as idle in full: the named
    share is, if anything, understated."""
    calls = matched_calls(ctx)
    gaps = between_requests(ctx)
    if not calls or gaps is None:
        return None
    by_tree: dict = {}
    for _s, tree, _start, call, ann in calls:
        by_tree.setdefault(id(tree), []).append((call, ann))
    by_name: dict = {}
    after: dict = {}
    parts = dict.fromkeys(("before", "busy", "dispatch_idle", "after",
                           "between"), 0.0)
    n = 0

    def add(name, ms):
        if ms > 0:
            by_name[name] = by_name.get(name, 0.0) + ms

    roots = roots_in_order(ctx)
    for (s, tree, start, end), gap in zip(roots, gaps + [(None, None)]):
        pairs = by_tree.get(id(tree))
        if not pairs or not all(a["whole"] for _c, a in pairs):
            continue  # unmatched, or the trace's last query (cut short)
        n += 1
        pieces: list = []
        _pieces(tree, start, pieces)
        call_iv = [(start + c["start_ms"],
                    start + c["start_ms"] + c["duration_ms"])
                   for c, _a in pairs]
        first, last = call_iv[0][0], call_iv[-1][1]
        per_after = after.setdefault(s["template"], {})
        seen_after: dict = {}
        for name, p0, p1 in pieces:
            inside = sum(_overlap(p0, p1, c0, c1) for c0, c1 in call_iv)
            outside = (p1 - p0) - inside
            add(name, outside)
            tail = _overlap(p0, p1, last, end)
            if tail > 0:
                seen_after[name] = seen_after.get(name, 0.0) + tail
        for name, ms in seen_after.items():
            per_after.setdefault(name, []).append(ms)
        for (c0, c1), (call, ann) in zip(call_iv, pairs):
            busy = ann["device_s"] * 1000.0
            idle = max(0.0, (c1 - c0) - busy)
            self_ms = sum(p1 - p0 for name, p0, p1 in pieces
                          if name.startswith("self:")
                          and p0 >= c0 - _EPS_MS and p1 <= c1 + _EPS_MS)
            unnamed = min(self_ms, idle)
            add("self:in-device-call", unnamed)
            add("in-device-call (prepare, launch, fetch)", idle - unnamed)
            parts["busy"] += busy
            parts["dispatch_idle"] += (c1 - c0) - busy
        parts["before"] += first - start
        parts["after"] += end - last
        if gap[1] is not None:
            add("between_requests", gap[1])
            parts["between"] += gap[1]
    # pieces inside a call were counted as outside=0 above and their idle
    # share was added from the trace; `self:` pieces outside are unnamed
    unnamed_ms = sum(ms for name, ms in by_name.items()
                     if name.startswith("self:"))
    named_ms = sum(by_name.values()) - unnamed_ms
    return {"named_ms": named_ms, "unnamed_ms": unnamed_ms,
            "by_name": by_name, "after_by_template": after,
            "parts_ms": parts, "requests": n}
