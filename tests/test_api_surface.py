"""L7 surface tests: statement verbs, counters, and the HTTP query server
(the ThriftServer-wrapper analog, SURVEY.md §3.1/§4.5)."""

import json
import urllib.request

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.api.server import QueryServer


@pytest.fixture()
def engine():
    rng = np.random.default_rng(5)
    n = 5000
    df = pd.DataFrame({
        "ts": pd.to_datetime("2021-06-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 60, n), unit="s"),
        "shop": rng.choice(["a", "b", "c"], n),
        "amount": rng.integers(1, 500, n).astype(np.int64),
    })
    eng = Engine()
    eng.register_table("sales", df, time_column="ts")
    return eng


def test_clear_cache_verb(engine):
    engine.sql("SELECT shop, sum(amount) AS s FROM sales GROUP BY shop")
    assert engine.runner._datasets
    out = engine.sql("CLEAR DRUID CACHE")
    assert out.status[0] == "cleared cache"
    assert not engine.runner._datasets
    out = engine.sql("CLEAR DRUID CACHE sales")
    assert "sales" in out.status[0]


def test_explain_rewrite_verb(engine):
    out = engine.sql(
        "EXPLAIN DRUID REWRITE SELECT shop, sum(amount) AS s "
        "FROM sales GROUP BY shop")
    text = "\n".join(out.plan)
    info = json.loads(text)
    assert info["rewritten"] is True
    assert info["query"]["queryType"] == "groupBy"


def test_passthrough_verb(engine):
    spec = json.dumps({
        "queryType": "timeseries",
        "granularity": "all",
        "aggregations": [{"type": "longSum", "name": "s",
                          "fieldName": "amount"}],
    })
    out = engine.sql(
        f"ON DRUID DATASOURCE sales EXECUTE QUERY '{spec}'")
    ref = engine.sql("SELECT sum(amount) AS s FROM sales")
    assert int(out.s[0]) == int(ref.s[0])


def test_counters(engine):
    engine.sql("SELECT shop, sum(amount) AS s FROM sales GROUP BY shop")
    engine.sql("SELECT sum(amount) AS s FROM sales")
    c = engine.counters()
    assert c["queries"] == 2
    assert c["rows_scanned"] > 0
    assert c["by_query_type"] == {"groupBy": 1, "timeseries": 1}


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def test_http_server(engine):
    srv = QueryServer(engine).start()
    try:
        out = _post(srv.url + "/sql", {
            "query": "SELECT shop, sum(amount) AS s FROM sales "
                     "GROUP BY shop ORDER BY shop"})
        assert out["columns"] == ["shop", "s"]
        assert [r["shop"] for r in out["rows"]] == ["a", "b", "c"]

        druid = _post(srv.url + "/druid/v2", {
            "queryType": "timeseries",
            "dataSource": "sales",
            "granularity": "all",
            "aggregations": [{"type": "longSum", "name": "s",
                              "fieldName": "amount"}]})
        assert druid[0]["result"]["s"] == sum(r["s"] for r in out["rows"])

        status = _get(srv.url + "/status")
        assert status["tables"]["sales"]["accelerated"] is True
        assert status["counters"]["queries"] >= 2

        meta = _get(srv.url + "/status/metadata/sales")
        assert meta["columns"]["amount"]["type"] == "LONG"

        # bad SQL -> 400 with an error body, server stays up
        try:
            _post(srv.url + "/sql", {"query": "SELEKT nope"})
            raise AssertionError("expected HTTPError")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            assert "error" in json.loads(e.read())
        out2 = _get(srv.url + "/status")
        assert out2["engine"] == "tpu_olap"
    finally:
        srv.stop()


def test_jsonable_pandas_nulls():
    from tpu_olap.api.server import _jsonable
    assert _jsonable(pd.NaT) is None
    assert _jsonable(pd.NA) is None
    assert _jsonable(float("nan")) is None
    assert _jsonable({"a": [pd.NaT, 1, "x"]}) == {"a": [None, 1, "x"]}
    assert _jsonable(np.float64("inf")) is None


def test_status_does_not_force_lazy_frame(engine, tmp_path):
    df = pd.DataFrame({"k": [1, 2], "v": ["x", "y"]})
    path = str(tmp_path / "dim.parquet")
    df.to_parquet(path)
    engine.register_table("dim", path, accelerate=False)
    srv = QueryServer(engine).start()
    try:
        status = _get(srv.url + "/status")
        assert status["tables"]["dim"]["numRows"] is None
        assert engine.catalog.get("dim")._frame is None  # not materialized
        engine.sql("SELECT k FROM dim")  # fallback loads it
        status = _get(srv.url + "/status")
        assert status["tables"]["dim"]["numRows"] == 2
    finally:
        srv.stop()


def test_concurrent_fallback_not_wedged_behind_device_query(engine):
    """A slow device dispatch must not block fallback queries or status
    pings (VERDICT r1 missing #6: one pathological query wedged the
    endpoint behind a global lock)."""
    import threading
    import time

    engine.register_table(
        "dim", pd.DataFrame({"k": [1, 2, 3]}), accelerate=False)
    release = threading.Event()

    def stall(stage, attempt):
        release.wait(timeout=20)

    engine.config.fault_injector = stall
    engine.clear_cache()  # force the next device query through dispatch
    srv = QueryServer(engine).start()
    try:
        t = threading.Thread(target=_post, args=(
            srv.url + "/sql",
            {"query": "SELECT sum(amount) AS s FROM sales"}))
        t.start()
        time.sleep(0.2)  # let the device query take the lock
        t0 = time.perf_counter()
        out = _post(srv.url + "/sql",
                    {"query": "SELECT k FROM dim ORDER BY k"})
        status = _get(srv.url + "/status")
        elapsed = time.perf_counter() - t0
        assert [r["k"] for r in out["rows"]] == [1, 2, 3]
        assert status["engine"] == "tpu_olap"
        assert elapsed < 5.0  # answered while the device query stalled
    finally:
        release.set()
        t.join(timeout=30)
        engine.config.fault_injector = None
        srv.stop()
