"""Semantic result caching (executor.resultcache; docs/CACHING.md):
tier-2 full-result serving, tier-1 per-segment partial reuse across
moving windows (with bucket-layout rebase), the generational
invalidation contract (ingest bumps, CLEAR DRUID CACHE, DROP), byte-
budget LRU eviction, batch-executor tier sharing, and observability
(tier-labeled counters, /debug/cache)."""

import json
import urllib.request

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.api.server import QueryServer
from tpu_olap.bench.parity import check_query
from tpu_olap.executor import EngineConfig

N_ROWS = 40_000


def _df(n=N_ROWS, seed=7, days=60):
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "ts": pd.to_datetime("2023-01-01")
        + pd.to_timedelta(np.sort(rng.integers(0, 86400 * days, n)),
                          unit="s"),
        "g": rng.choice([f"g{i}" for i in range(10)], n),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    })


def _engine(df=None, **kw):
    cfg = dict(result_cache_enabled=True, segment_cache_enabled=True,
               segment_cache_min_rows=0)
    cfg.update(kw)
    eng = Engine(EngineConfig(**cfg))
    eng.register_table("t", df if df is not None else _df(),
                       time_column="ts", block_rows=1 << 11,
                       time_partition="day")
    return eng


GROUP_SQL = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY g"
AGG_SQL = "SELECT sum(v) AS s, count(*) AS n FROM t"


def _win_sql(lo, hi):
    return ("SELECT g, sum(v) AS s FROM t WHERE "
            f"ts >= TIMESTAMP '{lo}' AND ts < TIMESTAMP '{hi}' "
            "GROUP BY g ORDER BY g")


# ------------------------------------------------------- tier 2 (full)


def test_full_cache_serves_repeat_with_real_cache_hit():
    eng = _engine()
    a = eng.sql(GROUP_SQL)
    first = dict(eng.history[-1])
    b = eng.sql(GROUP_SQL)
    hit = dict(eng.history[-1])
    assert a.equals(b)
    assert first["cache_hit"] is False
    assert hit["cache_hit"] is True
    assert hit["cache_tier"] == "full"
    assert hit["path"] == "cache"
    assert hit["rows_scanned"] == 0 and hit["segments_scanned"] == 0
    # tier-labeled counters are live in the registry (and /metrics)
    req = eng.metrics.counter("result_cache_requests_total")
    assert req.value(tier="full", result="hit") >= 1
    assert req.value(tier="full", result="miss") >= 1


def test_ingest_bumps_generation_and_invalidates_both_tiers():
    df = _df()
    eng = _engine(df)
    gen0 = eng.catalog.get("t").segments.generation
    a = eng.sql(GROUP_SQL)
    eng.sql(GROUP_SQL)  # tier-2 primed
    eng.sql(_win_sql("2023-01-01", "2023-02-01"))  # tier-1 primed
    # fresh ingest with DIFFERENT data: any stale entry would now give
    # a provably wrong answer
    eng.register_table("t", df.iloc[: N_ROWS // 2], time_column="ts",
                       block_rows=1 << 11, time_partition="day")
    gen1 = eng.catalog.get("t").segments.generation
    assert gen1 > gen0
    # tier 1 first (before anything repopulates entries under the new
    # generation): every lookup must miss
    w = eng.sql(_win_sql("2023-01-01", "2023-02-01"))
    rec = dict(eng.history[-1])
    assert not rec.get("segments_cached")  # tier 1 invalidated too
    check_query(eng, _win_sql("2023-01-01", "2023-02-01"),
                label="post-ingest-window")
    assert len(w) > 0
    b = eng.sql(GROUP_SQL)
    rec = dict(eng.history[-1])
    # the old generation's full result is never served (fresh gen-1
    # tier-1 entries stored by the window query above MAY serve — that
    # is the feature, and the frame + parity checks prove freshness)
    assert rec.get("cache_tier") != "full"
    assert not b.equals(a)
    check_query(eng, GROUP_SQL, label="post-ingest")
    # the eager purge dropped the stale bytes and logged the event
    snap = eng.runner.result_cache.snapshot()
    assert snap["full"]["entries"] <= 2  # only post-ingest entries
    assert any(e["event"] == "cache_invalidate"
               for e in eng.runner.events.snapshot())


def test_clear_druid_cache_clears_both_tiers():
    eng = _engine()
    eng.sql(GROUP_SQL)
    eng.sql(_win_sql("2023-01-01", "2023-02-01"))
    snap = eng.runner.result_cache.snapshot()
    assert snap["full"]["entries"] >= 1
    assert snap["segment"]["entries"] >= 1
    eng.sql("CLEAR DRUID CACHE t")
    snap = eng.runner.result_cache.snapshot()
    assert snap["full"]["entries"] == 0
    assert snap["segment"]["entries"] == 0
    eng.sql(GROUP_SQL)
    assert dict(eng.history[-1])["cache_hit"] is False
    # unscoped clear works too
    eng.sql("CLEAR DRUID CACHE")
    assert eng.runner.result_cache.snapshot()["full"]["entries"] == 0


def test_drop_table_purges_cache_entries():
    eng = _engine()
    eng.sql(GROUP_SQL)
    eng.drop_table("t")
    assert eng.runner.result_cache.snapshot()["full"]["entries"] == 0
    with pytest.raises(Exception):
        eng.sql(GROUP_SQL)  # table gone


def test_byte_budget_lru_eviction():
    eng = _engine(result_cache_max_bytes=20_000)
    # distinct queries -> distinct entries; tiny budget forces eviction
    for lo in range(1, 20):
        eng.sql(_win_sql(f"2023-01-{lo:02d}", "2023-02-01"))
    snap = eng.runner.result_cache.snapshot()
    assert snap["full"]["bytes"] <= 20_000
    assert snap["full"]["evict"] >= 1
    ev = eng.metrics.counter("result_cache_evictions_total")
    assert ev.value(tier="full") >= 1


# ---------------------------------------------------- tier 1 (segment)


def test_moving_window_recomputes_only_uncached_segments():
    eng = _engine(result_cache_enabled=False)  # isolate tier 1
    eng.sql(_win_sql("2023-01-01", "2023-02-01"))
    cold = dict(eng.history[-1])
    assert cold["cache_hit"] is False
    assert cold["segments_computed"] >= 28
    eng.sql(_win_sql("2023-01-08", "2023-02-15"))
    warm = dict(eng.history[-1])
    assert warm["cache_hit"] is True
    assert warm["cache_tier"] == "segment"
    assert warm["segments_cached"] >= 20   # Jan 8..Feb 1 reused
    assert warm["segments_computed"] <= 18  # only the new tail
    assert warm["rows_scanned"] < cold["rows_scanned"]
    check_query(eng, _win_sql("2023-01-08", "2023-02-15"),
                label="moving-window")
    # identical repeat: full tier-1 coverage, zero segments computed
    eng.sql(_win_sql("2023-01-08", "2023-02-15"))
    full = dict(eng.history[-1])
    assert full["segments_computed"] == 0
    assert full["rows_scanned"] == 0


def test_bucketed_layout_rebases_across_shifted_windows():
    eng = _engine(result_cache_enabled=False)
    sql1 = ("SELECT DATE_TRUNC('day', ts) AS d, sum(v) AS s, "
            "min(v) AS mn, max(v) AS mx FROM t WHERE "
            "ts < TIMESTAMP '2023-02-01' GROUP BY d ORDER BY d")
    sql2 = ("SELECT DATE_TRUNC('day', ts) AS d, sum(v) AS s, "
            "min(v) AS mn, max(v) AS mx FROM t WHERE "
            "ts >= TIMESTAMP '2023-01-05' AND "
            "ts < TIMESTAMP '2023-02-20' GROUP BY d ORDER BY d")
    eng.sql(sql1)
    eng.sql(sql2)
    rec = dict(eng.history[-1])
    # the shifted window's bucket grid differs, but cached per-segment
    # rows re-anchor by bucket start timestamp (resultcache._rebase)
    assert rec["cache_tier"] == "segment"
    assert rec["segments_cached"] >= 20
    check_query(eng, sql2, label="rebase")


def test_straddling_interval_segments_always_recompute():
    eng = _engine(result_cache_enabled=False)
    # mid-day boundaries: the edge segments' partials are interval-
    # dependent, so they must recompute (and never be stored)
    sql = ("SELECT g, sum(v) AS s FROM t WHERE "
           "ts >= TIMESTAMP '2023-01-03 12:00:00' AND "
           "ts < TIMESTAMP '2023-01-20 06:30:00' "
           "GROUP BY g ORDER BY g")
    eng.sql(sql)
    eng.sql(sql)
    rec = dict(eng.history[-1])
    assert rec.get("segments_computed", 0) >= 1  # the straddlers
    check_query(eng, sql, label="straddle")


def test_sketches_merge_exactly_through_segment_cache():
    eng = _engine(result_cache_enabled=False)
    sql1 = ("SELECT count(DISTINCT g) AS n, sum(v) AS s FROM t "
            "WHERE ts < TIMESTAMP '2023-02-01'")
    sql2 = ("SELECT count(DISTINCT g) AS n, sum(v) AS s FROM t "
            "WHERE ts >= TIMESTAMP '2023-01-10' AND "
            "ts < TIMESTAMP '2023-02-20'")
    eng.sql(sql1)
    eng.sql(sql2)
    rec = dict(eng.history[-1])
    assert rec.get("segments_cached", 0) >= 1
    check_query(eng, sql2, approx_cols=("n",), label="hll-merge")


def test_state_budget_bypass_falls_through_to_plain_path():
    eng = _engine(result_cache_enabled=False,
                  segment_cache_state_budget=1)
    out = eng.sql(GROUP_SQL)
    rec = dict(eng.history[-1])
    assert str(rec.get("segment_cache", "")).startswith("bypass")
    assert "segments_cached" not in rec
    assert len(out) == 10  # plain path served it
    req = eng.metrics.counter("result_cache_requests_total")
    assert req.value(tier="segment", result="bypass") >= 1


# ----------------------------------------------------- batch executor


def test_batch_legs_share_tiers_with_single_query_dispatch():
    eng = _engine()
    eng.sql(GROUP_SQL)  # single-query dispatch populates tier 2
    outs = eng.sql_batch([GROUP_SQL, AGG_SQL])
    assert outs[0].equals(eng.sql(GROUP_SQL))
    recs = list(eng.history)
    # the batch leg for GROUP_SQL served from the cache the single
    # path populated...
    assert any(r.get("cache_tier") == "full" for r in recs)
    # ...and the batch-computed AGG_SQL populated the tier the single
    # path now serves from
    eng.sql(AGG_SQL)
    assert dict(eng.history[-1])["cache_hit"] is True


# -------------------------------------------------------- LRU satellite


def test_runner_caches_are_lru_not_fifo():
    eng = _engine(result_cache_enabled=False,
                  segment_cache_enabled=False)
    r = eng.runner
    eng.sql(GROUP_SQL)
    eng.sql(AGG_SQL)
    k_group = next(iter(r._plan_cache))  # oldest = GROUP_SQL's plan
    eng.sql(GROUP_SQL)  # hit moves it to the end
    keys = list(r._plan_cache)
    assert keys[-1] == k_group, "plan-cache hit did not move-to-end"
    assert keys[0] != k_group


# ------------------------------------------------------- observability


def test_debug_cache_endpoint_and_metrics_exposition():
    eng = _engine()
    eng.sql(GROUP_SQL)
    eng.sql(GROUP_SQL)
    srv = QueryServer(eng).start()
    try:
        with urllib.request.urlopen(srv.url + "/debug/cache") as r:
            snap = json.loads(r.read())
        assert snap["enabled"] == {"full": True, "segment": True}
        assert snap["full"]["hit"] >= 1
        assert snap["generations"]["t"] >= 1
        with urllib.request.urlopen(srv.url + "/metrics") as r:
            text = r.read().decode()
        assert 'tpu_olap_result_cache_requests_total' \
               '{tier="full",result="hit"}' in text
        assert 'tpu_olap_result_cache_bytes{tier="full"}' in text
    finally:
        srv.stop()


def test_explain_analyze_shows_cache_decision():
    eng = _engine(result_cache_enabled=False)
    eng.sql(_win_sql("2023-01-01", "2023-02-01"))
    out = eng.sql("EXPLAIN ANALYZE "
                  + _win_sql("2023-01-05", "2023-02-10"))
    spans = {s.strip(): d for s, d in zip(out["span"], out["detail"])}
    assert "segment-cache" in spans
    d = json.loads(spans["segment-cache"])
    assert d["segments_cached"] >= 1
    assert "segments_computed" in d


@pytest.mark.parametrize("days,form", [(60, "compare"), (400, "scatter")])
def test_segment_partials_record_the_form_of_the_extended_key(days, form):
    """The segment-cache partials program reduces over W*K groups (the
    key extended by the window's segment), so that number, not the plan's
    K of 10, is what the generic kernel branches on: the record and the
    `dispatch` span name the program that ran, and the answer is the
    uncached one either way."""
    from tpu_olap.kernels.groupby import COMPARE_MAX_GROUPS, reduce_form
    df = _df(days=days)
    eng = _engine(df, result_cache_enabled=False)
    got = eng.sql(GROUP_SQL)
    rec = eng.runner.history[-1]
    W = 1 << (rec["segments_computed"] - 1).bit_length()
    assert reduce_form(10) == "compare" and rec["reduce_path"] == "scatter"
    assert (W * 10 > COMPARE_MAX_GROUPS) == (form == "scatter")
    assert rec["reduce_form"] == form

    def walk(t):
        yield t
        for c in t.get("children", []):
            yield from walk(c)
    assert [s["attrs"]["reduce_form"]
            for s in walk(eng.tracer.last.to_json())
            if s["name"] == "dispatch" and s["attrs"].get("segcache")] \
        == [form]
    want = df.groupby("g")["v"].sum().sort_index()
    assert list(got["s"]) == list(want)
