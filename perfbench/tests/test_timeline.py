"""The request timeline and the readers built on it: on hand-made span trees
with known answers, on trees shaped like an older program's (nothing to
read, nothing raised), and on trees laid over the annotations of the trace
recorded on the chip (`data/tpu_600k.xplane.pb.gz`, see test_xplane.py).
"""

import gzip
import os
import types

import pytest

from perfbench.lib import harness, timeline, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "perfbench")
NEW_READERS = (
    "http_server_ms", "serialize_ms", "between_requests_ms", "render_ms",
    "host_before_dispatch_ms", "host_after_dispatch_ms", "dispatch_idle_ms",
    "record_ms", "scan_bytes_over_least", "idle_named_share",
    "trace_clock_skew_us")


def _read(name, ctx):
    return harness.load_reader(BENCH, name).read(ctx)


def _span(name, start, dur, *children):
    out = {"name": name, "start_ms": start, "duration_ms": dur}
    if children:
        out["children"] = list(children)
    return out


def _hand_made():
    """Three requests of one closed-loop client, 1000 ms into the process.

    A (q2.1), 20 ms: http-read 0-1, parse 1-2, [root's own 2-3], execute
    3-15 {[own 3-4], device-call 4-12 {prepare 4-5, [own 5-5.5], dispatch
    5.5-11.5, [own 11.5-12]}, [own 12-13], record 13-14, [own 14-15]},
    serialize 15-18, http-write 18-20. The device is busy 5 of the call's
    8 ms. B and C (q1.1), 10 ms each, 5 ms after the one before: execute
    0-10 {device-call 2-6, a leaf}; busy 1 ms. The trace's clock is the
    process axis less 1004 ms; C's annotation sits 10 us late, and C is
    the trace's last query, so not whole."""
    a = _span("sql", 0.0, 20.0,
              _span("http-read", 0.0, 1.0), _span("parse", 1.0, 1.0),
              _span("execute", 3.0, 12.0,
                    _span("device-call", 4.0, 8.0,
                          _span("prepare", 4.0, 1.0),
                          _span("dispatch", 5.5, 6.0)),
                    _span("record", 13.0, 1.0)),
              _span("serialize", 15.0, 3.0), _span("http-write", 18.0, 2.0))
    a["t0_ns"] = 1_000_000_000

    def short(t0_ns):
        t = _span("sql", 0.0, 10.0,
                  _span("execute", 0.0, 10.0, _span("device-call", 2.0, 4.0)))
        t["t0_ns"] = t0_ns
        return t

    traces = {"A": a, "B": short(1_025_000_000), "C": short(1_040_000_000)}
    samples = [{"qid": q, "template": t}
               for q, t in (("A", "q2.1"), ("B", "q1.1"), ("C", "q1.1"))]

    def ann(qid, template, start_s, end_s, device_s, whole):
        return {"qid": qid, "template": template, "start_s": start_s,
                "end_s": end_s, "device_s": device_s, "op_s": {},
                "whole": whole}

    trace = {"window_s": 0.042, "busy_s": 0.007, "queries": [
        ann("A", "q2.1", 0.0, 0.008, 0.005, True),
        ann("B", "q1.1", 0.023, 0.027, 0.001, True),
        ann("C", "q1.1", 0.03801, 0.04201, 0.001, False)]}
    dataset = types.SimpleNamespace(
        needed_bytes=lambda t, ref, rows: 1_000_000_000)
    return types.SimpleNamespace(
        samples=samples, traces=traces, trace=trace, elapsed_s=0.05,
        records={"A": {"bytes_scanned": 3_000_000_000, "rows_scanned": 7},
                 "B": {"bytes_scanned": 5}, "C": {"bytes_scanned": 5}},
        dataset=dataset, reference=None)


def test_idle_account_of_hand_made_trees():
    acc = timeline.idle_account(_hand_made())
    assert acc["requests"] == 2  # C is the trace's last query: left out
    # the parts lay the two requests end to end: 1000 -> 1040 ms
    assert acc["parts_ms"] == pytest.approx(
        {"before": 6.0, "busy": 6.0, "dispatch_idle": 6.0, "after": 12.0,
         "between": 10.0})
    assert sum(acc["parts_ms"].values()) == pytest.approx(40.0)
    assert acc["by_name"] == pytest.approx({
        "http-read": 1.0, "parse": 1.0, "record": 1.0, "serialize": 3.0,
        "http-write": 2.0, "between_requests": 10.0,
        "in-device-call (prepare, launch, fetch)": 5.0,  # A 3 - 1, B 3
        "self:sql": 1.0, "self:execute": 9.0,            # A 3, B 2 + 4
        "self:in-device-call": 1.0})                     # A's 0.5 + 0.5
    assert acc["named_ms"] == pytest.approx(23.0)
    assert acc["unnamed_ms"] == pytest.approx(11.0)
    # what follows A's device call, by name
    assert acc["after_by_template"]["q2.1"] == pytest.approx(
        {"self:execute": [2.0], "record": [1.0], "serialize": [3.0],
         "http-write": [2.0]})


def test_readers_on_hand_made_trees(capsys):
    ctx = _hand_made()
    assert _read("http_server_ms", ctx) == pytest.approx(6.0)  # A alone
    assert _read("serialize_ms", ctx) == pytest.approx(3.0)
    assert _read("between_requests_ms", ctx) == pytest.approx(5.0)
    assert _read("render_ms", ctx) is None  # no tree has the span
    assert _read("host_before_dispatch_ms", ctx) == pytest.approx(2.0)
    assert _read("host_after_dispatch_ms", ctx) == pytest.approx(8.0)
    assert _read("dispatch_idle_ms", ctx) == pytest.approx(3.0)
    assert _read("record_ms", ctx) == pytest.approx(1.0)
    assert _read("scan_bytes_over_least", ctx) == pytest.approx(3.0)
    assert _read("idle_named_share", ctx) == pytest.approx(100 * 23 / 34)
    assert "between_requests 0.010" in capsys.readouterr().out
    # offsets -1004000, -1004000, -1003990 us: distances 0, 0, 10
    assert timeline.clock_offsets_us(ctx) == pytest.approx(
        [-1_004_000.0, -1_004_000.0, -1_003_990.0])
    assert _read("trace_clock_skew_us", ctx) == pytest.approx(9.0)


def test_requests_in_flight_together_have_no_timeline():
    ctx = _hand_made()
    ctx.traces["B"]["t0_ns"] = 1_010_000_000  # B opens inside A
    assert timeline.between_requests(ctx) is None
    assert _read("between_requests_ms", ctx) is None
    assert _read("idle_named_share", ctx) is None


@pytest.mark.parametrize("traced", [True, False])
def test_an_older_program_gives_nothing_to_read(traced):
    """The parent commit's trees: no `t0_ns`, no edge spans, no
    `device-call`, no `record`; its records have no `bytes_scanned`. Every
    new reader returns None or a number, and none raises."""
    ctx = _hand_made()
    for tree in ctx.traces.values():
        del tree["t0_ns"]
        tree["children"] = [
            _span("parse", 0.0, 0.1), _span("plan", 0.1, 0.2),
            _span("execute", 0.5, 5.0, _span("dispatch", 1.0, 3.0)),
            _span("render", 6.0, 1.5)]
    ctx.records = {q: {"rows_scanned": 7} for q in ctx.records}
    if not traced:
        ctx.trace = None
    got = {name: _read(name, ctx) for name in NEW_READERS}
    assert got.pop("render_ms") == pytest.approx(1.5)
    assert got.pop("dispatch_idle_ms") == (pytest.approx(3.0) if traced
                                           else None)
    assert set(got.values()) == {None}


def test_every_new_reader_is_in_the_manifest_for_both_cells():
    for cell in ("ssb-sf100-chip.ssb13-c1", "ssb-sf4-mesh4.ssb13-c1"):
        per_layer = {m["name"]: m
                     for m in harness.load_cell(ROOT, cell)["per_layer"]}
        for name in NEW_READERS:
            assert harness.load_reader(BENCH, name).UNIT == \
                per_layer[name]["unit"]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tpu_600k.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "tpu_600k.xplane.pb.gz")) as g:
        path.write_bytes(g.read())
    spec = harness.load_cell(ROOT, "ssb-sf100-chip.ssb13-c1")
    names = [f"q{a}.{b}" for a, n in ((1, 3), (2, 3), (3, 4), (4, 3))
             for b in range(1, n + 1)]
    seq = traffic.plan(spec["traffic"], names, 1010, 1.0)["sequences"][0]
    qids = {f"q041d5b0-{58 + i:06d}": t for i, t in enumerate(seq[:154])}
    return xplane.reduce_file(str(path), qids)


def test_trees_laid_over_the_recorded_trace(recorded, capsys):
    """Span trees made to fit the recorded annotations, on a process axis
    7.5 s off the trace's clock: each gap between two device calls is cut
    into render (half, after the earlier call), the client's turn (a
    fifth) and plan (before the later call). Every piece has a name, so the
    named share is 100, the clocks agree to float error, and the spans'
    idle time is the trace's less the cut-short last query's part."""
    off_ms = 7_500.0
    qs = recorded["queries"]
    gaps = [(b["start_s"] - a["end_s"]) * 1000 for a, b in zip(qs, qs[1:])]
    assert min(gaps) > 0
    samples, traces = [], {}
    for i, q in enumerate(qs):
        before = 0.3 * (gaps[i - 1] if i else 1.0)
        after = 0.5 * (gaps[i] if i < len(gaps) else 1.0)
        extent = (q["end_s"] - q["start_s"]) * 1000
        tree = _span("sql", 0.0, before + extent + after,
                     _span("plan", 0.0, before),
                     _span("device-call", before, extent),
                     _span("render", before + extent, after))
        tree["t0_ns"] = round((q["start_s"] * 1000 + off_ms - before) * 1e6)
        traces[q["qid"]] = tree
        samples.append({"qid": q["qid"], "template": q["template"]})
    ctx = types.SimpleNamespace(samples=samples, traces=traces,
                                trace=recorded, elapsed_s=1.0, records={})
    assert _read("idle_named_share", ctx) == pytest.approx(100.0)
    assert _read("trace_clock_skew_us", ctx) < 0.01
    assert _read("between_requests_ms", ctx) == pytest.approx(
        0.2 * sorted(gaps)[len(gaps) // 2], rel=0.02)
    acc = timeline.idle_account(ctx)
    assert acc["requests"] == 153 and acc["unnamed_ms"] < 1e-6
    last = qs[-1]
    busy_less_last = recorded["busy_s"] - last["device_s"]
    # (the first tree starts 0.3 ms before the window; the last whole
    # one's successor takes 0.3 of the last gap for its `plan`)
    assert (acc["named_ms"] - 0.3 * 1.0 + 0.3 * gaps[-1]) / 1000 == \
        pytest.approx(last["start_s"] - busy_less_last, rel=1e-6)
    # the reader on the recorded trace alone, as the run would print it
    assert _read("dispatch_idle_ms", ctx) == pytest.approx(
        sorted((q["end_s"] - q["start_s"] - q["device_s"]) * 1000
               for q in qs[:-1])[76], rel=1e-9)
    assert "timeline: 153 requests" in capsys.readouterr().out
