"""Plan-level tests (SURVEY.md §5: 'sql -> expected query IR, no device
needed') + Engine-level parity between the device path and the pandas
fallback on identical data."""

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.executor import EngineConfig
from tpu_olap.ir.query import (GroupByQuerySpec, ScanQuerySpec,
                               TimeseriesQuerySpec, TopNQuerySpec)
from tpu_olap.utils import timeutil as tu


def build_engine():
    rng = np.random.default_rng(23)
    n = 6000
    t0 = tu.date_to_millis(1993, 1, 1)
    lineorder = pd.DataFrame({
        "lo_orderdate": rng.integers(0, 2000, n) + 19930000,  # date FK
        "ts": pd.to_datetime(
            t0 + rng.integers(0, 3 * 365 * 86_400_000, n), unit="ms"),
        "lo_discount": rng.integers(0, 11, n).astype(np.int64),
        "lo_quantity": rng.integers(1, 51, n).astype(np.int64),
        "lo_extendedprice": rng.integers(100, 10_000, n).astype(np.int64),
        "lo_revenue": rng.integers(100, 100_000, n).astype(np.int64),
        "lo_supplycost": rng.integers(10, 1000, n).astype(np.int64),
        "p_brand": rng.choice([f"MFGR#{i:02d}" for i in range(12)], n),
        "p_category": rng.choice(["MFGR#12", "MFGR#13", "MFGR#14"], n),
        "s_region": rng.choice(["AMERICA", "ASIA", "EUROPE"], n),
        "c_nation": rng.choice(["US", "CN", "DE", "FR"], n),
    })
    # denormalized d_year must agree with the dimension row it joins to
    lineorder["d_year"] = (1993
                           + (lineorder.lo_orderdate - 19930000) % 3
                           ).astype(np.int64)
    date_dim = pd.DataFrame({
        "d_datekey": np.arange(19930000, 19935000),
        "d_year2": 1993 + (np.arange(5000) % 3),
    })
    eng = Engine(EngineConfig())
    eng.register_table(
        "lineorder", lineorder, time_column="ts",
        star_schema={
            "fact": "lineorder",
            "dimensions": [{"table": "date_dim", "factKey": "lo_orderdate",
                            "dimKey": "d_datekey",
                            "columnMap": {"d_year2": "d_year"}}],
        })
    eng.register_table("date_dim", date_dim, accelerate=False)
    return eng, lineorder, date_dim


ENG, LO, DD = build_engine()


# ---------------------------------------------------------- plan assertions

def test_q11_star_join_rewrites_to_timeseries():
    sql = """SELECT sum(lo_extendedprice * lo_discount) AS revenue
             FROM lineorder, date_dim
             WHERE lo_orderdate = d_datekey AND d_year2 = 1993
               AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25"""
    plan = ENG.planner.plan(sql)
    assert plan.rewritten, plan.fallback_reason
    q = plan.query
    assert isinstance(q, TimeseriesQuerySpec)
    assert q.data_source == "lineorder"
    assert len(q.virtual_columns) == 1
    assert q.aggregations[0].to_json()["type"] == "longSum"
    # d_year2 remapped onto the denormalized fact column d_year
    assert "d_year" in q.filter.columns()


def test_year_filter_becomes_interval():
    sql = "SELECT count() AS n FROM lineorder WHERE year(ts) = 1993"
    plan = ENG.planner.plan(sql)
    assert plan.rewritten
    (iv,) = plan.query.intervals
    assert iv.start == tu.date_to_millis(1993)
    assert iv.end == tu.date_to_millis(1994)
    assert plan.query.filter is None


def test_time_literal_bounds_become_interval():
    sql = ("SELECT count() AS n FROM lineorder "
           "WHERE ts >= '1993-06-01' AND ts < '1993-09-01'")
    plan = ENG.planner.plan(sql)
    assert plan.rewritten
    (iv,) = plan.query.intervals
    assert iv.start == tu.date_to_millis(1993, 6, 1)
    assert iv.end == tu.date_to_millis(1993, 9, 1)


def test_groupby_with_year_extraction():
    sql = """SELECT d_year, year(ts) AS yr, sum(lo_revenue) AS rev
             FROM lineorder GROUP BY d_year, year(ts)"""
    plan = ENG.planner.plan(sql)
    assert plan.rewritten
    q = plan.query
    assert isinstance(q, GroupByQuerySpec)
    assert q.dimensions[0].to_json()["type"] == "default"
    assert q.dimensions[1].to_json()["extractionFn"]["format"] == "YYYY"
    assert plan.outputs[1].cast == "int"


def test_date_trunc_becomes_granularity():
    sql = """SELECT date_trunc('month', ts) AS m, count() AS n
             FROM lineorder GROUP BY date_trunc('month', ts)"""
    plan = ENG.planner.plan(sql)
    assert plan.rewritten
    q = plan.query
    assert isinstance(q, TimeseriesQuerySpec)
    assert q.granularity.to_json()["period"] == "P1M"
    assert plan.outputs[0].source == "timestamp"


def test_avg_becomes_postagg():
    plan = ENG.planner.plan(
        "SELECT avg(lo_quantity) AS aq FROM lineorder")
    assert plan.rewritten
    q = plan.query
    assert q.post_aggregations[0].to_json()["fn"] == "quotient"
    assert {a.to_json()["type"] for a in q.aggregations} == \
        {"longSum", "count"}


def test_count_distinct_becomes_cardinality():
    plan = ENG.planner.plan(
        "SELECT count(DISTINCT p_brand) AS u FROM lineorder")
    assert plan.rewritten
    assert plan.query.aggregations[0].to_json()["type"] == "cardinality"
    # and falls back when disallowed
    eng2 = Engine(EngineConfig(allow_count_distinct=False))
    eng2.catalog = ENG.catalog
    from tpu_olap.planner import DruidPlanner
    eng2.planner = DruidPlanner(eng2.catalog, eng2.config)
    plan2 = eng2.planner.plan(
        "SELECT count(DISTINCT p_brand) AS u FROM lineorder")
    assert not plan2.rewritten


def test_topn_selection_and_threshold():
    sql = """SELECT p_brand, sum(lo_revenue) AS rev FROM lineorder
             GROUP BY p_brand ORDER BY rev DESC LIMIT 5"""
    plan = ENG.planner.plan(sql)
    assert isinstance(plan.query, TopNQuerySpec)
    assert plan.query.threshold == 5 and not plan.query.inverted
    # ascending -> bottom-N (inverted)
    plan2 = ENG.planner.plan(sql.replace("DESC", "ASC"))
    assert isinstance(plan2.query, TopNQuerySpec) and plan2.query.inverted
    # multi-dim group: stays groupBy
    sql3 = """SELECT p_brand, d_year, sum(lo_revenue) AS rev FROM lineorder
              GROUP BY p_brand, d_year ORDER BY rev DESC LIMIT 5"""
    plan3 = ENG.planner.plan(sql3)
    assert isinstance(plan3.query, GroupByQuerySpec)


def test_scan_plan():
    plan = ENG.planner.plan(
        "SELECT p_brand, lo_revenue FROM lineorder "
        "WHERE s_region = 'ASIA' LIMIT 7")
    assert isinstance(plan.query, ScanQuerySpec)
    assert plan.query.limit == 7


def test_fallbacks():
    # left join is not collapsible
    plan = ENG.planner.plan(
        "SELECT count() AS n FROM lineorder LEFT JOIN date_dim "
        "ON lo_orderdate = d_datekey")
    assert not plan.rewritten and "left" in plan.fallback_reason
    # join with no star edge
    plan = ENG.planner.plan(
        "SELECT count() AS n FROM lineorder, date_dim "
        "WHERE d_year = d_year2")
    assert not plan.rewritten
    # query on a non-accelerated table
    plan = ENG.planner.plan("SELECT count() AS n FROM date_dim")
    assert not plan.rewritten and "not" in plan.fallback_reason


def test_explain_shapes():
    exp = ENG.explain("SELECT count() AS n FROM lineorder")
    assert exp["rewritten"] and exp["query"]["queryType"] == "timeseries"
    exp2 = ENG.explain("SELECT count() AS n FROM date_dim")
    assert not exp2["rewritten"] and "reason" in exp2


# ------------------------------------------------------------ parity: device
# path vs pandas fallback on identical SQL (SURVEY.md §5 implication #3)

PARITY_QUERIES = [
    """SELECT sum(lo_extendedprice * lo_discount) AS revenue
       FROM lineorder, date_dim
       WHERE lo_orderdate = d_datekey AND d_year2 = 1993
         AND lo_discount BETWEEN 1 AND 3 AND lo_quantity < 25""",
    """SELECT d_year, sum(lo_revenue) AS rev, count() AS n
       FROM lineorder WHERE s_region = 'ASIA' GROUP BY d_year""",
    """SELECT p_brand, sum(lo_revenue) AS rev FROM lineorder
       WHERE p_category = 'MFGR#12' GROUP BY p_brand
       ORDER BY rev DESC LIMIT 4""",
    """SELECT year(ts) AS yr, avg(lo_quantity) AS aq
       FROM lineorder GROUP BY year(ts)""",
    """SELECT date_trunc('month', ts) AS m, count() AS n FROM lineorder
       WHERE year(ts) = 1994 GROUP BY date_trunc('month', ts)""",
    """SELECT c_nation, d_year, sum(lo_revenue - lo_supplycost) AS profit
       FROM lineorder GROUP BY c_nation, d_year
       HAVING sum(lo_revenue - lo_supplycost) > 100000""",
    """SELECT s_region, min(lo_revenue) AS mn, max(lo_revenue) AS mx
       FROM lineorder GROUP BY s_region""",
    """SELECT p_brand FROM lineorder WHERE lo_quantity = 50
       AND p_category = 'MFGR#13' LIMIT 6""",
    """SELECT DISTINCT s_region FROM lineorder""",
    """SELECT count() AS n FROM lineorder WHERE p_brand LIKE 'MFGR#0%'""",
    """SELECT count() AS n FROM lineorder
       WHERE c_nation IN ('US', 'DE') AND NOT (lo_discount = 0)""",
]


@pytest.mark.parametrize("idx", range(len(PARITY_QUERIES)))
def test_parity_device_vs_fallback(idx):
    sql = PARITY_QUERIES[idx]
    dev = ENG.sql(sql)
    assert ENG.last_plan.rewritten, ENG.last_plan.fallback_reason
    from tpu_olap.planner.fallback import execute_fallback
    fb = execute_fallback(ENG.last_plan.stmt, ENG.catalog, ENG.config)
    fb.columns = list(dev.columns)[:len(fb.columns)]
    a = dev.sort_values(list(dev.columns)).reset_index(drop=True)
    b = fb.sort_values(list(fb.columns)).reset_index(drop=True)
    assert len(a) == len(b), (sql, len(a), len(b))
    for col in a.columns:
        av, bv = a[col].to_numpy(), b[col].to_numpy()
        if av.dtype.kind in "fc" or bv.dtype.kind in "fc":
            assert np.allclose(av.astype(float), bv.astype(float),
                               rtol=1e-9, equal_nan=True), (sql, col)
        else:
            assert (av == bv).all(), (sql, col, av[:5], bv[:5])


# --- ExprUtil simplification (SURVEY.md §3.2; round 3) -------------------

def test_simplify_constant_folding():
    from tpu_olap.ir.expr import BinOp, Col, FuncCall, Lit
    from tpu_olap.planner.exprutil import simplify
    assert simplify(BinOp("+", Lit(2), Lit(3))) == Lit(5)
    assert simplify(BinOp("*", Lit(4), Lit(2.5))) == Lit(10.0)
    assert simplify(BinOp("<", Lit(1), Lit(2))) == Lit(True)
    assert simplify(BinOp("+", Col("x"), Lit(0))) == Col("x")
    assert simplify(BinOp("*", Lit(1), Col("x"))) == Col("x")
    # x*0 must NOT fold (NULL*0 is NULL)
    z = simplify(BinOp("*", Col("x"), Lit(0)))
    assert isinstance(z, BinOp)
    # NULL arithmetic propagates
    assert simplify(BinOp("+", Lit(None), Lit(3))) == Lit(None)
    # NOT NOT x -> x; casts of literals fold
    assert simplify(FuncCall("not", (FuncCall("not", (Col("b"),)),))) \
        == Col("b")
    assert simplify(FuncCall("cast_long", (Lit(3.9),))) == Lit(3)
    assert simplify(FuncCall("cast_double", (Lit("1.5"),))) == Lit(1.5)
    # boolean identities prune branches
    t = BinOp("&&", BinOp(">", Lit(2), Lit(1)), Col("p"))
    assert simplify(t) == Col("p")
    f = BinOp("||", Col("p"), BinOp(">", Lit(1), Lit(2)))
    assert simplify(f) == Col("p")


def test_simplified_where_enables_rewrite():
    """A tautological conjunct (1 < 2) would previously force fallback
    as an unsupported literal predicate; simplification prunes it."""
    plan = ENG.planner.plan(
        "SELECT p_brand, sum(lo_revenue) AS s FROM lineorder "
        "WHERE 1 < 2 AND lo_quantity > 0 GROUP BY p_brand")
    assert plan.rewritten, plan.fallback_reason


def test_simplify_review_regressions():
    from tpu_olap.ir.expr import BinOp, Col, Lit
    from tpu_olap.planner.exprutil import simplify
    # non-numeric '/' literals must not crash planning
    assert isinstance(simplify(BinOp("/", Lit("a"), Lit(2))), BinOp)
    # float/bool identity elements must NOT fold (dtype coercion)
    assert isinstance(simplify(BinOp("+", Col("q"), Lit(0.0))), BinOp)
    assert isinstance(simplify(BinOp("*", Col("q"), Lit(1.0))), BinOp)
    assert isinstance(simplify(BinOp("*", Col("q"), Lit(True))), BinOp)
    # standalone tautological WHERE is dropped -> still rewrites
    plan = ENG.planner.plan(
        "SELECT p_brand, sum(lo_revenue) AS s FROM lineorder "
        "WHERE 1 < 2 GROUP BY p_brand")
    assert plan.rewritten, plan.fallback_reason
    assert plan.stmt.where is None


def test_group_by_integer_expression_rewrites():
    """GROUP BY <integer expr> lowers as a virtual numeric dimension
    (histogram bucketing) with numeric ORDER BY semantics."""
    import numpy as np
    import pandas as pd

    from tpu_olap import Engine
    from tpu_olap.bench.parity import assert_frame_parity
    from tpu_olap.executor import EngineConfig
    from tpu_olap.planner.fallback import execute_fallback
    rng = np.random.default_rng(4)
    n = 4000
    df = pd.DataFrame({
        "ts": pd.to_datetime("2024-02-01"),
        "g": rng.choice(["a", "b"], n),
        "v": rng.integers(0, 120, n).astype(np.int64),
    })
    eng = Engine(EngineConfig(fallback_on_device_failure=False))
    eng.register_table("t", df, time_column="ts")
    for sql in (
        "SELECT v + 1 AS w, count(*) AS n FROM t GROUP BY v + 1 "
        "ORDER BY w LIMIT 7",
        "SELECT g, v - 60 AS c, sum(v) AS s FROM t GROUP BY g, v - 60 "
        "ORDER BY g, c LIMIT 9",
    ):
        dev = eng.sql(sql)
        assert eng.last_plan.rewritten, eng.last_plan.fallback_reason
        fb = execute_fallback(eng.planner.plan(sql).stmt, eng.catalog,
                              eng.config)
        assert_frame_parity(dev, fb, ordered=True)
    # float-typed expressions reject into the fallback, still answered
    r = eng.sql("SELECT v / 10 AS d, count(*) AS n FROM t GROUP BY v / 10")
    assert not eng.last_plan.rewritten
    assert len(r) > 0


def test_group_by_modulo_and_modulo_sum():
    """Floored-modulo expressions are integer-bounded ([0, m-1] for a
    positive constant modulus) and ride the device path both as a
    grouping dimension and as a Pallas-eligible sum input."""
    import numpy as np
    import pandas as pd

    from tpu_olap import Engine
    from tpu_olap.bench.parity import assert_frame_parity
    from tpu_olap.executor import EngineConfig
    from tpu_olap.planner.fallback import execute_fallback
    rng = np.random.default_rng(6)
    n = 3000
    df = pd.DataFrame({
        "ts": pd.to_datetime("2024-03-01"),
        "g": rng.choice(["a", "b"], n),
        "v": rng.integers(-80, 200, n).astype(np.int64),  # negatives too
    })
    eng = Engine(EngineConfig(fallback_on_device_failure=False))
    eng.register_table("t", df, time_column="ts")
    for sql in (
        "SELECT v % 7 AS m, count(*) AS n FROM t GROUP BY v % 7 "
        "ORDER BY m",
        "SELECT g, sum(v % 10) AS s FROM t GROUP BY g ORDER BY g",
    ):
        dev = eng.sql(sql)
        assert eng.last_plan.rewritten, eng.last_plan.fallback_reason
        fb = execute_fallback(eng.planner.plan(sql).stmt, eng.catalog,
                              eng.config)
        assert_frame_parity(dev, fb, ordered=True)


def test_virtual_numeric_dim_with_nulls():
    """Null inputs to an expression dimension land in the null group on
    BOTH paths (device slot 0 -> None label; pandas NA group)."""
    import numpy as np
    import pandas as pd

    from tpu_olap import Engine
    from tpu_olap.bench.parity import assert_frame_parity
    from tpu_olap.executor import EngineConfig
    from tpu_olap.planner.fallback import execute_fallback
    rng = np.random.default_rng(8)
    n = 2000
    v = rng.integers(0, 40, n).astype(np.float64)
    v[rng.random(n) < 0.1] = np.nan
    df = pd.DataFrame({
        "ts": pd.to_datetime("2024-04-01"),
        "v": pd.array(v, dtype="Int64"),
    })
    eng = Engine(EngineConfig(fallback_on_device_failure=False))
    eng.register_table("t", df, time_column="ts")
    for sql in (
        "SELECT v + 1 AS w, count(*) AS n FROM t GROUP BY v + 1 "
        "ORDER BY w",
        "SELECT v % 7 AS m, count(*) AS n FROM t GROUP BY v % 7 "
        "ORDER BY m",
    ):
        dev = eng.sql(sql)
        assert eng.last_plan.rewritten, eng.last_plan.fallback_reason
        fb = execute_fallback(eng.planner.plan(sql).stmt, eng.catalog,
                              eng.config)
        assert_frame_parity(dev, fb, ordered=True)


def test_having_over_time_bucket_group():
    """GROUP BY date_trunc(...) HAVING ... must not lower to a
    timeseries query (which has no having clause — the filter would be
    silently dropped; fuzz seed 1300)."""
    import numpy as np
    import pandas as pd

    from tpu_olap import Engine
    from tpu_olap.bench.parity import assert_frame_parity
    from tpu_olap.planner.fallback import execute_fallback
    rng = np.random.default_rng(9)
    n = 3000
    df = pd.DataFrame({
        "ts": pd.to_datetime("2024-01-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 40, n), unit="s"),
        "v": rng.integers(0, 100, n).astype(np.int64),
    })
    eng = Engine()
    eng.register_table("t", df, time_column="ts")
    # the filtered sum is 0 for most days, so a dropped HAVING is visible
    sql = ("SELECT date_trunc('day', ts) AS d, "
           "sum(v) FILTER (WHERE v > 98) AS hi FROM t "
           "GROUP BY date_trunc('day', ts) HAVING hi > 0")
    dev = eng.sql(sql)
    assert eng.last_plan.rewritten
    assert eng.planner.plan(sql).query.query_type == "groupBy"
    assert (dev["hi"] > 0).all()
    fb = execute_fallback(eng.planner.plan(sql).stmt, eng.catalog,
                          eng.config)
    assert_frame_parity(dev, fb)
