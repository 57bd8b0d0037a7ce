"""One request timeline, socket to device and back (ISSUE 25): the root span
the HTTP edge opens and the engine adopts, `device-call` / `record`, the
process-wide `t0_ns` axis, span annotations under a live device capture,
the one capture entry point, and the `bytes_scanned` counter.
"""

import glob
import http.client
import json
import os
import time

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.api.server import QueryServer
from tpu_olap.executor import EngineConfig
from tpu_olap.obs import profile as profile_mod

BLOCK_ROWS = 2048
GROUP_SQL = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY g"
AGG_SQL = "SELECT sum(v) AS s, count(*) AS n FROM t"


def _df(n=6000, seed=5):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "ts": pd.to_datetime("2023-03-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 30, n), unit="s"),
        "g": rng.choice(["a", "b", "c"], n),       # 3 codes: int8 resident
        "v": rng.integers(0, 1000, n).astype(np.int64),  # < 2^15: int16
    })


def _engine(**kw):
    eng = Engine(EngineConfig(**kw))
    eng.register_table("t", _df(), time_column="ts", block_rows=BLOCK_ROWS)
    return eng


@pytest.fixture()
def served():
    eng = _engine()
    srv = QueryServer(eng).start()
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=60)

    def post(path, payload):
        conn.request("POST", path, json.dumps(payload),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
        # the root closes AFTER the last byte is written: the client can
        # hold the answer a moment before the trace is in the ring
        qid = (resp.getheader("X-Query-Id") or "").split(",")[0]
        deadline = time.monotonic() + 10
        while qid and qid != "-" and time.monotonic() < deadline and not (
                eng.tracer.last is not None
                and qid in (eng.tracer.last.query_id,
                            *_batch_ids(eng.tracer.last))):
            time.sleep(0.001)
        return resp, body

    try:
        yield eng, post
    finally:
        conn.close()
        srv.stop()


def _batch_ids(trace):
    """The statement ids a `sql_batch` root's `plan` spans carry."""
    return [c.attrs.get("query_id") for c in trace.children
            if c.name == "plan"]


def _children(span):
    return [c.name for c in span.children]


def _named(trace, name):
    return [s for _d, s in trace.walk() if s.name == name]


# ----------------------------------------------------- the root, socket to socket


def test_http_sql_root_children_in_order(served):
    eng, post = served
    resp, body = post("/sql", {"query": GROUP_SQL})
    assert resp.status == 200
    root = eng.tracer.last
    assert root.name == "sql"
    assert _children(root) == ["http-read", "parse", "plan", "execute",
                               "render", "serialize", "http-write"]
    # one id: the header's, the root's, the record's
    assert resp.getheader("X-Query-Id") == root.query_id \
        == eng.history[-1]["query_id"]
    read, ser, write = root.children[0], root.children[5], root.children[6]
    assert read.attrs["bytes"] == len(json.dumps({"query": GROUP_SQL}))
    assert ser.attrs == {"rows": 3, "bytes": len(body)}
    # the root closes after the last byte is written, so it holds every
    # child, and the record's total_ms (the runner's part) is inside it
    assert write.start_ms + write.duration_ms <= root.duration_ms
    assert eng.history[-1]["total_ms"] < root.duration_ms


def test_http_sql_batch_root_edge_spans(served):
    eng, post = served
    resp, body = post("/sql/batch", {"queries": [GROUP_SQL, AGG_SQL]})
    assert resp.status == 200
    root = eng.tracer.last
    assert root.name == "sql_batch"
    names = _children(root)
    assert names[0] == "http-read"
    assert names[-2:] == ["serialize", "http-write"]
    assert names.count("plan") == 2  # the engine's spans, between them
    assert len(resp.getheader("X-Query-Id").split(",")) == 2
    assert root.children[-2].attrs == {"rows": 4, "bytes": len(body)}
    assert len(json.loads(body)["results"]) == 2


@pytest.mark.parametrize("over_http", [True, False])
def test_parse_and_plan_are_direct_children_of_the_root(served, over_http):
    """What the benchmark's `plan_ms` reads: `parse` and `plan` among the
    root's DIRECT children, whoever opened the root."""
    eng, post = served
    if over_http:
        post("/sql", {"query": AGG_SQL})
    else:
        eng.sql(AGG_SQL)
    tree = eng.tracer.last.to_json()
    direct = [c["name"] for c in tree["children"]]
    assert direct.count("parse") == 1 and direct.count("plan") == 1
    assert direct.index("parse") < direct.index("plan") \
        < direct.index("execute")


def test_engine_sql_called_directly_roots_its_own_trace(served):
    eng, _post = served
    before = len(eng.tracer.recent_traces())
    eng.sql(GROUP_SQL)
    root = eng.tracer.last
    assert len(eng.tracer.recent_traces()) == before + 1
    assert root.name == "sql"
    assert _children(root) == ["parse", "plan", "execute", "render"]


def test_statements_that_leave_no_trace_discard_the_edge_root(served):
    """A statement verb or a sys.* statement over HTTP: the root the edge
    opened is dropped, no X-Query-Id, and EXPLAIN ANALYZE's inner
    statement roots a trace of its own (not the edge's)."""
    eng, post = served
    post("/sql", {"query": GROUP_SQL})
    n0 = len(eng.tracer.recent_traces())
    resp, _ = post("/sql", {"query": "SELECT count(*) AS n FROM sys.queries"})
    assert resp.status == 200 and resp.getheader("X-Query-Id") is None
    assert len(eng.tracer.recent_traces()) == n0
    resp, body = post("/sql", {"query": "EXPLAIN ANALYZE " + GROUP_SQL})
    assert resp.status == 200 and resp.getheader("X-Query-Id") is None
    traces = eng.tracer.recent_traces()
    assert len(traces) == n0 + 1  # the inner statement's, nothing else
    assert _children(traces[-1]) == ["parse", "plan", "execute", "render"]
    assert traces[-1].duration_ms is not None


# --------------------------------------------------------- device-call, record


class _FailFirst:
    def __init__(self):
        self.calls = 0

    def __call__(self, stage, attempt):
        self.calls += 1
        if self.calls == 1:
            raise RuntimeError("injected device fault")


def test_device_call_once_per_attempt_and_record_span():
    eng = _engine(dispatch_retries=1, fault_injector=_FailFirst())
    eng.sql(GROUP_SQL)
    assert eng.history[-1]["retries"] == 1
    root = eng.tracer.last
    calls = _named(root, "device-call")
    assert [c.attrs["attempt"] for c in calls] == [0, 1]
    assert "injected device fault" in calls[0].attrs["error"]
    assert "error" not in calls[1].attrs
    # the dispatch's own spans hang under the attempt that ran them
    assert _named(calls[1], "prepare") and not _named(calls[0], "prepare")
    # record(): once per query, under `execute`, after the device call
    (rec,) = _named(root, "record")
    execute = _named(root, "execute")[0]
    assert rec in execute.children
    assert rec.start_ms >= calls[1].start_ms + calls[1].duration_ms


# ------------------------------------------------------------- the shared axis


def test_t0_ns_places_consecutive_requests_without_overlap(served):
    eng, post = served
    for sql in (GROUP_SQL, AGG_SQL, GROUP_SQL):
        post("/sql", {"query": sql})
    trees = [t.to_json() for t in eng.tracer.recent_traces()[-3:]]
    for a, b in zip(trees, trees[1:]):
        assert isinstance(a["t0_ns"], int)
        end_a = a["t0_ns"] / 1e6 + a["duration_ms"]
        assert end_a <= b["t0_ns"] / 1e6  # a's last byte before b's first
        # ... and by less than a closed-loop client could ever take
        assert b["t0_ns"] / 1e6 - end_a < 5_000
    # a child's place on the axis is t0_ns + start_ms, inside its root
    t = trees[-1]
    for c in t["children"]:
        assert 0 <= c["start_ms"] <= t["duration_ms"]


# ------------------------------------------------------------ bytes_scanned


def test_bytes_scanned_is_the_hand_count_and_repeats():
    eng = _engine()
    eng.sql(GROUP_SQL)
    rec = eng.history[-1]
    # 6000 rows in blocks of 2048 = 3 segments, all scanned, PADDED rows;
    # g resident as int8 codes, v as int16, the validity mask 1 byte
    assert rec["segments_scanned"] == 3 and rec["rows_scanned"] == 6000
    assert rec["bytes_scanned"] == 3 * BLOCK_ROWS * (1 + 2 + 1)
    eng.sql(GROUP_SQL)
    assert eng.history[-1]["bytes_scanned"] == rec["bytes_scanned"]
    # one column less: v and the mask only
    eng.sql(AGG_SQL)
    assert eng.history[-1]["bytes_scanned"] == 3 * BLOCK_ROWS * (2 + 1)


# ----------------------------------------------------------------- the capture


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                names.setdefault(e.name, []).append(dict(e.stats))
    return names


def test_spans_annotate_only_while_a_capture_is_live(served, tmp_path,
                                                     monkeypatch):
    eng, post = served
    post("/sql", {"query": GROUP_SQL})  # compiled before the capture
    made = []
    real = profile_mod.annotate_span
    monkeypatch.setattr(profile_mod, "annotate_span",
                        lambda name, qid: made.append(name) or real(name, qid))
    post("/sql", {"query": GROUP_SQL})
    assert made == []  # off capture: the flag probe and nothing else
    stop = profile_mod.start_capture(str(tmp_path))
    try:
        resp, _ = post("/sql", {"query": GROUP_SQL})
    finally:
        stop()
    qid = resp.getheader("X-Query-Id")
    assert {"sql", "http-read", "device-call", "serialize"} <= set(made)
    post("/sql", {"query": GROUP_SQL})
    assert made.count("serialize") == 1  # and off again
    names = _host_event_names(str(tmp_path))
    for span_name in ("sql", "http-read", "parse", "plan", "execute",
                      "device-call", "prepare", "record", "render",
                      "serialize", "http-write"):
        assert span_name in names, span_name
        assert names[span_name][0].get("query_id") == qid
    # the device call itself is still the event NAMED by the query id
    assert len(names[qid]) == 1


def test_start_capture_runs_with_the_python_tracer_off(tmp_path,
                                                       monkeypatch):
    import jax
    seen = {}
    real_start = jax.profiler.start_trace

    def start_trace(log_dir, *a, **kw):
        seen["level"] = kw["profiler_options"].python_tracer_level
        return real_start(log_dir, *a, **kw)

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    assert not profile_mod.capture_active()
    stop = profile_mod.start_capture(str(tmp_path))
    try:
        assert profile_mod.capture_active()
        assert seen["level"] == 0
        sorted(range(1000), key=lambda i: -i)  # Python work to (not) trace
        # one capture at a time, through either entry point
        with pytest.raises(RuntimeError, match="already in progress"):
            profile_mod.start_capture(str(tmp_path / "second"))
        out = profile_mod.capture_device_profile(5)
        assert out == {"ok": False, "reason": "capture already in progress"}
    finally:
        stop()
    assert not profile_mod.capture_active()
    assert not profile_mod._capture_lock.locked()
    # the Python tracer names its events "$<file>:<line> <function>"
    assert not [n for n in _host_event_names(str(tmp_path))
                if n.startswith("$")]
    # and the hook behind POST /debug/profile is a sleep between the two
    out = profile_mod.capture_device_profile(5, str(tmp_path / "hook"))
    assert out["ok"] and seen["level"] == 0
    assert not profile_mod._capture_lock.locked()
