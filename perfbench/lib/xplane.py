"""From the profiler's `.xplane.pb` to numbers: the trace reduction.

Read with `jax.profiler.ProfileData` and nothing else. What it takes from
the trace:

- device operations: the events of the line "XLA Ops" on each plane
  "/device:TPU:<n>". (A trace recorded on the CPU backend has no such
  plane; there the events that carry an `hlo_op` stat on the host's
  threads stand in as device 0, so that the reduction can be rehearsed and
  tested without a chip. Its numbers are never a device's.)
- the program's own `TraceAnnotation(query_id)` around each device call
  (`tpu_olap/obs/profile.py::annotate_dispatch`): host events whose name is
  a query id the window's client saw.

What it gives: the traced window (first annotation to the end of the last
query's work), per device the union of the operations'
intervals (busy seconds), self time per operation name, each annotated
query's device time (an operation belongs to the query whose annotation
began last before it: right for one client in a closed loop, the only
traffic that reads it), and the idle gaps of device 0 named by what the
host was doing: inside a query's annotation (dispatch and fetch) or between
two (HTTP, plan, assemble, client).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_OP_KIND = re.compile(r"\s([a-z][a-z0-9_.\-]*)\(")
_CC_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_CUSTOM_FUSION = "kind=kCustom"  # XLA:TPU's own emitters: scatter, sort


def short_name(name: str) -> str:
    """The trace prints a TPU operation as its whole HLO line. Keep the
    result's name, the operation's kind and, of a custom call, its target:
    `%fn.1 custom-call:tpu_custom_call`; of a fusion that XLA emits with a
    custom emitter (its scatter), the kind: `%fusion.1 fusion:kCustom`."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    kind = _OP_KIND.search(" " + rhs)
    out = f"{lhs} {kind.group(1)}" if kind else lhs
    target = _CC_TARGET.search(rhs)
    if target:
        out += ":" + target.group(1)
    elif _CUSTOM_FUSION in rhs:
        out += ":kCustom"
    return out[:120]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events: list) -> list:
    """[(name, start, self seconds)]: an event's duration less the part its
    nested events on the same line cover."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    out, stack = [], []  # stack of [name, start, end, child_time]
    for name, s, e in ev:
        while stack and stack[-1][2] <= s:
            n, s0, e0, child = stack.pop()
            out.append((n, s0, (e0 - s0) - child))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        n, s0, e0, child = stack.pop()
        out.append((n, s0, (e0 - s0) - child))
    return out


def read_planes(path: str) -> dict:
    """{"devices": {index: [(name, start_s, end_s)]}, "host": [(name,
    start_s, end_s)], "extent": (first_s, last_s)} from one xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    host, stand_in = [], []
    first, last = float("inf"), 0.0
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns / 1e9
                end = s + e.duration_ns / 1e9
                if m or plane.name.startswith("/host:"):
                    first, last = min(first, s), max(last, end)
                if m:
                    if line.name == OPS_LINE:
                        devices.setdefault(int(m.group(1)), []).append(
                            (short_name(e.name), s, end))
                elif plane.name.startswith("/host:"):
                    host.append((e.name, s, end))
                    if e.duration_ns and any(k == "hlo_op"
                                             for k, _v in e.stats):
                        stand_in.append((e.name, s, end))
    if not devices and stand_in:
        devices[0] = stand_in
    return {"devices": devices, "host": host, "extent": (first, last)}


def reduce_planes(planes: dict, qid_template: dict) -> dict:
    """The reduction proper, on what read_planes returns. `qid_template`
    maps the query ids the client saw to their template names."""
    ann = sorted((s, e, name) for name, s, e in planes["host"]
                 if name in qid_template)
    starts = [a[0] for a in ann]
    # the traced window is the part of the capture under load: from the
    # first query's annotation to the end of the last one's work, not the
    # profiler's own start and stop
    first, last = planes["extent"]
    if ann:
        first = ann[0][0]
        ends = [ann[-1][1]] + [e for ev in planes["devices"].values()
                               for _n, _s, e in ev]
        last = max(ends)
    window_s = max(0.0, last - first)
    planes = dict(planes, devices={
        d: [(n, max(s, first), e) for n, s, e in ev if e > first]
        for d, ev in planes["devices"].items()})
    queries = [{"qid": name, "template": qid_template[name],
                "start_s": s - first, "end_s": e - first,
                "device_s": 0.0, "op_s": {}} for s, e, name in ann]
    n_dev = max(1, len(planes["devices"]))
    busy, op_s = {}, {}
    for dev, events in sorted(planes["devices"].items()):
        merged = _union([(s, e) for _n, s, e in events])
        busy[dev] = sum(e - s for s, e in merged)
        for name, s, self_s in _self_times(events):
            op_s[name] = op_s.get(name, 0.0) + self_s / n_dev
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0:
                q = queries[i]
                q["op_s"][name] = q["op_s"].get(name, 0.0) + self_s / n_dev
        for s, e in merged:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0:
                queries[i]["device_s"] += (e - s) / n_dev
    # a query is whole when the next annotation is in the trace too: the
    # last one's operations may run on after the capture stopped
    for i, q in enumerate(queries):
        q["whole"] = i + 1 < len(queries)
    gaps: dict = {}
    dev0 = min(planes["devices"]) if planes["devices"] else None
    if dev0 is not None:
        merged = _union([(s, e) for _n, s, e in planes["devices"][dev0]])
        edges = [first] + [x for iv in merged for x in iv] + [last]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge <= gs:
                continue
            i = bisect.bisect_right(starts, (gs + ge) / 2) - 1
            if i < 0:
                name = "before_first_query"
            elif (gs + ge) / 2 < ann[i][1]:
                name = f"in_dispatch:{qid_template[ann[i][2]]}"
            else:
                name = f"between_queries:after_{qid_template[ann[i][2]]}"
            gaps[name] = gaps.get(name, 0.0) + (ge - gs)
    return {
        "window_s": window_s,
        "busy_s_by_device": busy,
        "busy_s": sum(busy.values()) / n_dev if busy else 0.0,
        "op_s": op_s,
        "queries": queries,
        "idle_gap_s": gaps,
    }


def reduce_file(path: str, qid_template: dict) -> dict:
    return reduce_planes(read_planes(path), qid_template)


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
