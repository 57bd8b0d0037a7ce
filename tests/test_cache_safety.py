"""Compile-cache safety: queries that share a stripped template but differ
in trace-baked structure must NOT share a jitted program (regressions for
the silent-wrong-answer cache collisions)."""

import numpy as np
import pandas as pd

from tpu_olap import Engine
from tpu_olap.executor import EngineConfig


def make_engine():
    eng = Engine(EngineConfig())
    df = pd.DataFrame({
        "x": [10, 20, 30, None],
        "g": ["a", "a", "b", "b"],
    })
    eng.register_table("f", df)
    return eng


def test_virtual_column_literals_not_aliased():
    eng = make_engine()
    a = eng.sql("SELECT sum(x * 2) AS s FROM f")
    b = eng.sql("SELECT sum(x * 3) AS s FROM f")
    assert a.s[0] == 120
    assert b.s[0] == 180


def test_selector_value_vs_is_null_not_aliased():
    eng = make_engine()
    a = eng.sql("SELECT count() AS n FROM f WHERE x = 30")
    b = eng.sql("SELECT count() AS n FROM f WHERE x IS NULL")
    assert a.n[0] == 1
    assert b.n[0] == 1


def test_in_list_with_and_without_null():
    eng = make_engine()
    a = eng.sql("SELECT count() AS n FROM f WHERE x IN (10, 20)")
    b = eng.sql("SELECT count() AS n FROM f WHERE x IN (10, NULL)")
    assert a.n[0] == 2
    assert b.n[0] == 2  # 10 and the null row


def test_unparseable_selector_after_parseable():
    eng = make_engine()
    a = eng.sql("SELECT count() AS n FROM f WHERE x = 10")
    b = eng.sql("SELECT count() AS n FROM f WHERE x = 'abc'")
    assert a.n[0] == 1
    assert b.n[0] == 0


def test_order_by_date_trunc_alias():
    eng = Engine(EngineConfig())
    df = pd.DataFrame({
        "t": pd.to_datetime(["1993-01-05", "1993-01-07", "1993-02-01",
                             "1993-03-02"]),
        "x": [1, 2, 3, 4],
    })
    eng.register_table("f", df, time_column="t")
    out = eng.sql("SELECT date_trunc('month', t) AS m, sum(x) AS s FROM f "
                  "GROUP BY date_trunc('month', t) ORDER BY m DESC LIMIT 2")
    assert eng.last_plan.rewritten, eng.last_plan.fallback_reason
    assert out.s.tolist() == [4, 3]


def test_zero_division_parity():
    eng = Engine(EngineConfig())
    df = pd.DataFrame({"x": [1, 2], "y": [0, 0], "g": ["a", "b"]})
    eng.register_table("f", df)
    dev = eng.sql("SELECT g, sum(x) / sum(y) AS r FROM f GROUP BY g")
    assert eng.last_plan.rewritten
    from tpu_olap.planner.fallback import execute_fallback
    fb = execute_fallback(eng.last_plan.stmt, eng.catalog, eng.config)
    assert dev.r.tolist() == [0.0, 0.0]
    assert fb.r.tolist() == [0.0, 0.0]
