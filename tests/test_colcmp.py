"""columnComparison filter: row-vs-row equality across columns
(SURVEY.md §3.3 filter family; the TPC-H Q5/Q7 shape).

Semantics under test (kernels/filtereval._colcmp_pair): a NULL operand
never matches at the leaf; NOT inversion makes NULL rows match `<>` —
exactly the pandas fallback's object-dtype behavior, so parity holds by
construction. String pairs translate codes across dictionaries via a
derived stream (one elementwise compare per dispatch, no gather).
"""

import json

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.bench.parity import assert_frame_parity, run_both
from tpu_olap.executor import EngineConfig
from tpu_olap.ir.filters import ColumnComparisonFilter, filter_from_json


def _frame(n=4000, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 5, n).astype(float)
    x[rng.random(n) < 0.1] = np.nan
    y = rng.integers(0, 5, n).astype(float)
    y[rng.random(n) < 0.1] = np.nan
    return pd.DataFrame({
        "ts": pd.to_datetime("2024-03-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 30, n), unit="s"),
        # overlapping-but-distinct vocabularies: "kiev" only on the left,
        # "bern" only on the right — exercises absent-value translation
        "city": rng.choice(["rome", "oslo", "lima", "kiev", None], n),
        "dest": rng.choice(["rome", "oslo", "lima", "bern", None], n),
        "x": x, "y": y,
        "v": rng.integers(0, 100, n).astype(np.int64),
    })


@pytest.fixture(scope="module")
def eng():
    e = Engine()
    e.register_table("t", _frame(), time_column="ts")
    return e


PARITY_SQL = [
    "SELECT count(*) AS n, sum(v) AS s FROM t WHERE city = dest",
    "SELECT count(*) AS n FROM t WHERE city <> dest",
    "SELECT count(*) AS n FROM t WHERE NOT (city = dest)",
    "SELECT city, count(*) AS n FROM t WHERE city = dest GROUP BY city",
    "SELECT count(*) AS n FROM t WHERE x = y",
    # <> with NULL operands: NOT(==) matches the fallback's NaN != x;
    # a bare ExpressionFilter(!=) would exclude them (regression lock
    # for the round-4 lowering fix in planner/plan.py::_to_filter)
    "SELECT count(*) AS n FROM t WHERE x <> y",
    "SELECT count(*) AS n FROM t WHERE x + 1 <> y + 1",
    "SELECT count(*) AS n FROM t WHERE city = dest AND x = y",
]


@pytest.mark.parametrize("sql", PARITY_SQL)
def test_device_parity(eng, sql):
    dev, fb, _ = run_both(eng, sql)  # raises ParityError on fallback
    assert_frame_parity(dev, fb, ordered=False, label=sql)


def test_null_semantics_exact(eng):
    """Pin the counts, not just parity: nulls never match `=`; every
    null-operand row matches `<>` (NOT inversion)."""
    f = _frame()
    both = (f.city.notna() & f.dest.notna())
    eq = int((both & (f.city == f.dest)).sum())
    got = eng.sql("SELECT count(*) AS n FROM t WHERE city = dest")
    assert int(got.iloc[0]["n"]) == eq
    got = eng.sql("SELECT count(*) AS n FROM t WHERE city <> dest")
    assert int(got.iloc[0]["n"]) == len(f) - eq


def test_mesh_and_pallas_force():
    frame = _frame(seed=11)
    for cfg, tag in [(EngineConfig(num_shards=8), "mesh8"),
                     (EngineConfig(use_pallas="force"), "pallas-force")]:
        e = Engine(cfg)
        e.register_table("t", frame, time_column="ts")
        sql = ("SELECT city, sum(v) AS s FROM t WHERE city = dest "
               "GROUP BY city")
        dev, fb, _ = run_both(e, sql)
        assert_frame_parity(dev, fb, ordered=False, label=tag)
        if tag == "pallas-force":
            # columnComparison IS Pallas-whitelisted: the translation
            # stream enters the kernel as an int32 row (no in-kernel
            # gather), so the fused kernel must be active for this plan
            from tpu_olap.executor.lowering import lower
            plan = e.planner.plan(sql)
            phys = lower(plan.query, plan.entry.segments, e.config)
            assert phys.pallas_reason is None, phys.pallas_reason


def test_scan_path(eng):
    got = eng.sql("SELECT city, dest, v FROM t WHERE city = dest "
                  "ORDER BY v DESC LIMIT 5")
    assert len(got) == 5
    assert (got["city"] == got["dest"]).all()


def test_raw_ir_passthrough(eng):
    body = json.dumps({
        "queryType": "timeseries", "granularity": "all",
        "aggregations": [{"type": "count", "name": "n"}],
        "filter": {"type": "columnComparison",
                   "dimensions": ["city", "dest"]},
        "intervals": ["1000-01-01/3000-01-01"],
    })
    out = eng.sql(f"ON DRUID DATASOURCE t EXECUTE QUERY '{body}'")
    f = _frame()
    exp = int((f.city.notna() & (f.city == f.dest)).sum())
    assert int(out.iloc[0]["n"]) == exp


def test_serde_roundtrip():
    f = ColumnComparisonFilter(("a", "b", "c"))
    assert filter_from_json(f.to_json()) == f
    with pytest.raises(ValueError):
        filter_from_json({"type": "columnComparison", "dimensions": ["a"]})


def test_mixed_types_fall_back(eng):
    """String-vs-numeric comparison is outside the filter algebra — the
    fallback must answer it (correct-but-slow, never an error)."""
    from tpu_olap.bench.parity import ParityError
    with pytest.raises(ParityError):
        run_both(eng, "SELECT count(*) AS n FROM t WHERE city = v")


@pytest.mark.parametrize("op", ["<", "<=", ">", ">="])
def test_ordered_string_comparison_on_device(eng, op):
    """Row-vs-row ORDER of two string columns (TPC-H Q12's `l_commitdate <
    l_receiptdate`): ranks in the merged dictionary, served by the device
    (a structural fallback before), NULL operands never matching."""
    out, fb, _plan = run_both(
        eng, f"SELECT count(*) AS n FROM t WHERE city {op} dest")
    f = _frame()
    both = f[f.city.notna() & f.dest.notna()]
    exp = int(eval(f"(both.city {op} both.dest).sum()"))
    assert 0 < exp < len(both)
    assert int(out.iloc[0]["n"]) == int(fb.iloc[0]["n"]) == exp


def test_ordered_serde_roundtrip():
    f = ColumnComparisonFilter(("a", "b"), "<=")
    assert f.to_json()["op"] == "<=" and filter_from_json(f.to_json()) == f
    assert "op" not in ColumnComparisonFilter(("a", "b")).to_json()
    with pytest.raises(ValueError):
        ColumnComparisonFilter(("a", "b", "c"), "<")


def test_derived_stream_cached(eng):
    """The translation stream is built once per content token and reused
    across dispatches (the round-4 no-per-dispatch-gather rule)."""
    ds = eng.runner._datasets.get("t")
    if ds is None:
        eng.sql("SELECT count(*) AS n FROM t WHERE city = dest")
        ds = eng.runner._datasets["t"]
    eng.sql("SELECT count(*) AS n FROM t WHERE city = dest")
    n0 = len(ds._derived)
    eng.sql("SELECT sum(v) AS s FROM t WHERE city = dest")
    assert len(ds._derived) == n0  # same pair -> same token, no rebuild
