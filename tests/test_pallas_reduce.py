"""Pallas fused one-hot reduce: parity vs the XLA scatter path.

Runs the kernel in interpret mode on the CPU backend (the conftest forces
the virtual-CPU platform), mirroring the reference's plan-level testing
philosophy (SURVEY.md §5): same engine, two physical execution strategies,
identical results required.
"""

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.executor import EngineConfig
from tpu_olap.executor.lowering import lower
from tpu_olap.kernels.pallas_reduce import expr_int_bounds
from tpu_olap.ir.expr import BinOp, Col, Lit


def _table(n=4096, seed=3):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "ts": pd.to_datetime("2020-01-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 200, n), unit="s"),
        "color": rng.choice(["red", "green", "blue", None], n),
        "region": rng.choice([f"r{i}" for i in range(12)], n),
        "qty": rng.integers(0, 50, n).astype(np.int64),
        "price": rng.integers(0, 10_000, n).astype(np.int64),
    })
    df.loc[rng.random(n) < 0.05, "qty"] = np.nan  # nullable numeric
    df["qty"] = df["qty"].astype("Int64")
    # columnComparison pairs, derived WITHOUT rng draws (keeps every
    # other column's per-seed values stable): same-vocabulary roll plus
    # deterministic out-of-vocabulary injections so the cross-dictionary
    # translation map carries absent values
    df["dest"] = np.roll(df["region"].to_numpy(), 5)
    df.loc[df.index[::97], "dest"] = "zX"
    df["color2"] = np.roll(df["color"].to_numpy(), 3)  # nullable pair
    return df


def _engines():
    plain = Engine(EngineConfig(use_pallas="never"))
    forced = Engine(EngineConfig(use_pallas="force"))
    df = _table()
    for e in (plain, forced):
        e.register_table("t", df, time_column="ts", block_rows=512)
    return plain, forced


QUERIES = [
    # single-group total with arithmetic projection + filters (Q1.1 shape)
    """SELECT sum(price * qty) AS rev, count(*) AS n FROM t
       WHERE qty BETWEEN 1 AND 25 AND price < 5000""",
    # group by string dim
    """SELECT color, sum(price) AS s, count(*) AS n FROM t
       GROUP BY color ORDER BY color""",
    # two dims incl. numeric-range dim + IN filter
    """SELECT region, qty, sum(price) AS s FROM t
       WHERE region IN ('r1','r2','r3') GROUP BY region, qty
       ORDER BY region, qty""",
    # filtered aggregator via CASE-less SQL: WHERE-free filtered sums
    """SELECT color, count(*) AS n FROM t
       WHERE NOT (region = 'r5' OR region = 'r6')
       GROUP BY color ORDER BY color""",
    # negative-capable sum (biased half-plane path, the SSB Q4.x profit
    # shape: revenue - cost can go below zero)
    """SELECT color, sum(price - qty * 300) AS profit FROM t
       GROUP BY color ORDER BY color""",
]


def _assert_parity(sql, check_eligible=False):
    plain, forced = _engines()
    a = plain.sql(sql)
    assert plain.last_plan.rewritten
    b = forced.sql(sql)
    assert forced.last_plan.rewritten
    if check_eligible:
        plan = forced.planner.plan(sql)
        phys = lower(plan.query, plan.entry.segments, forced.config)
        assert phys.pallas_reason is None, phys.pallas_reason
    pd.testing.assert_frame_equal(a, b)


@pytest.mark.parametrize("sql", QUERIES)
def test_pallas_parity(sql):
    _assert_parity(sql)


def test_pallas_kernel_is_active():
    _, forced = _engines()
    q = "SELECT color, sum(price) AS s FROM t GROUP BY color"
    plan = forced.planner.plan(q)
    phys = lower(plan.query, plan.entry.segments, forced.config)
    assert phys.pallas_reason is None
    assert "pallas" in phys.statics


def test_pallas_ineligible_falls_back():
    _, forced = _engines()
    # division makes the sum input DOUBLE-typed: outside the int32 kernel
    q = "SELECT color, sum(price / 2) AS m FROM t GROUP BY color"
    plan = forced.planner.plan(q)
    phys = lower(plan.query, plan.entry.segments, forced.config)
    assert phys.pallas_reason is not None
    assert "pallas" not in phys.statics
    # still correct via the generic kernel
    plain, _ = _engines()
    pd.testing.assert_frame_equal(plain.sql(q), forced.sql(q))


MINMAX_QUERIES = [
    # min/max ride a second VPU-accumulated output buffer (round 3);
    # max rides negated so one minimum-accumulate serves both
    """SELECT color, min(price) AS mn, max(price) AS mx, sum(price) AS s
       FROM t GROUP BY color ORDER BY color""",
    # with filters, a nullable input, and a filtered aggregator
    """SELECT region, min(qty) AS mn, max(qty) AS mx,
              min(price) FILTER (WHERE qty > 25) AS mf, count(*) AS n
       FROM t WHERE price < 8000 GROUP BY region ORDER BY region""",
    # global (single group): empty-filter max must render NULL
    """SELECT max(price) FILTER (WHERE qty > 9999) AS none_mx,
       min(price) AS mn FROM t""",
    # negative-capable expression input
    """SELECT color, min(price - 5000) AS mn, max(price - 5000) AS mx
       FROM t GROUP BY color ORDER BY color""",
]


@pytest.mark.parametrize("sql", MINMAX_QUERIES)
def test_pallas_minmax_parity(sql):
    _assert_parity(sql, check_eligible=True)


def test_pallas_group_cap_guard():
    plain = Engine(EngineConfig(use_pallas="never"))
    forced = Engine(EngineConfig(use_pallas="force", pallas_group_cap=4,
                                 pallas_group_cap_factorized=4))
    df = _table()
    for e in (plain, forced):
        e.register_table("t", df, time_column="ts", block_rows=512)
    q = "SELECT region, count(*) AS n FROM t GROUP BY region ORDER BY region"
    phys_plan = forced.planner.plan(q)
    phys = lower(phys_plan.query, phys_plan.entry.segments, forced.config)
    assert "exceeds pallas cap" in phys.pallas_reason
    pd.testing.assert_frame_equal(plain.sql(q), forced.sql(q))


def test_expr_int_bounds():
    b = {"x": (0, 10), "y": (-5, 5)}
    assert expr_int_bounds(Col("x"), b) == (0, 10)
    assert expr_int_bounds(BinOp("*", Col("x"), Col("y")), b) == (-50, 50)
    assert expr_int_bounds(BinOp("+", Col("x"), Lit(7)), b) == (7, 17)
    assert expr_int_bounds(BinOp("-", Col("x"), Col("y")), b) == (-5, 15)
    assert expr_int_bounds(BinOp("/", Col("x"), Lit(2)), b) is None
    assert expr_int_bounds(Col("z"), b) is None
    assert expr_int_bounds(Lit(1.5), b) is None


WIDENED_QUERIES = [
    # granularity buckets folded into the key (round-3 widening): monthly
    # timeseries — bucket ids computed outside the kernel on int64 time
    """SELECT date_trunc('month', ts) AS m, sum(price) AS s,
              count(*) AS n FROM t GROUP BY date_trunc('month', ts)
       ORDER BY m""",
    # bucket + string dim mixed-radix key
    """SELECT date_trunc('month', ts) AS m, color, sum(price) AS s FROM t
       GROUP BY date_trunc('month', ts), color ORDER BY m, color""",
    # interval mask (time-range predicate) ANDed into the validity mask
    # outside the kernel
    """SELECT color, sum(price) AS s FROM t
       WHERE ts >= '2020-02-01' AND ts < '2020-05-01'
       GROUP BY color ORDER BY color""",
    # interval mask + buckets together (mid-month edges so the mask is
    # not subsumed by bucket pruning)
    """SELECT date_trunc('month', ts) AS m, sum(qty) AS q FROM t
       WHERE ts >= '2020-02-15' AND ts < '2020-06-20'
       GROUP BY date_trunc('month', ts) ORDER BY m""",
]


@pytest.mark.parametrize("sql", WIDENED_QUERIES)
def test_pallas_widened_parity(sql):
    _assert_parity(sql, check_eligible=True)


def test_pallas_k_tiling_parity():
    """Group space wider than pallas_k_per_block tiles over grid axis 0."""
    plain = Engine(EngineConfig(use_pallas="never"))
    forced = Engine(EngineConfig(use_pallas="force", pallas_k_per_block=16))
    df = _table()
    for e in (plain, forced):
        e.register_table("t", df, time_column="ts", block_rows=512)
    # region(13) x color(4) = 52 groups -> 4 K-blocks of 16
    q = """SELECT region, color, sum(price) AS s, count(*) AS n FROM t
           GROUP BY region, color ORDER BY region, color"""
    plan = forced.planner.plan(q)
    phys = lower(plan.query, plan.entry.segments, forced.config)
    assert phys.pallas_reason is None, phys.pallas_reason
    pd.testing.assert_frame_equal(plain.sql(q), forced.sql(q))


def test_pallas_time_in_kernel_ineligible():
    """A filter on raw __time (not expressible as intervals) must reject."""
    _, forced = _engines()
    q = "SELECT color, sum(ts * 0 + price) AS s FROM t GROUP BY color"
    plan = forced.planner.plan(q)
    if not plan.rewritten:
        return  # planner may refuse the shape entirely — equally safe
    phys = lower(plan.query, plan.entry.segments, forced.config)
    assert phys.pallas_reason is not None


def test_pallas_multichip_parity():
    """Pallas plans under the 8-device virtual mesh: the mesh dispatch
    maps the single-chip kernel over the chips (sharding.
    mesh_agg_kernel), so the Pallas kernel itself runs per chip — in
    interpret mode here — and stays parity-exact sharded."""
    plain = Engine(EngineConfig(use_pallas="never"))
    forced = Engine(EngineConfig(use_pallas="force", num_shards=8))
    df = _table()
    for e in (plain, forced):
        e.register_table("t", df, time_column="ts", block_rows=256)
    q = """SELECT color, sum(price) AS s, count(*) AS n FROM t
           WHERE qty < 30 GROUP BY color ORDER BY color"""
    a = plain.sql(q)
    b = forced.sql(q)
    pd.testing.assert_frame_equal(a, b)


PRECOMPUTED_DIM_QUERIES = [
    # IN-constrained string dim -> remap kind: ids are gathered on the
    # host side (Mosaic cannot lower 1-D dynamic gathers) and streamed
    # into the kernel as an int32 row input
    """SELECT region, sum(price) AS s FROM t
       WHERE region IN ('r1','r2','r3') GROUP BY region ORDER BY region""",
    # substring extraction dim -> remap
    """SELECT substr(region, 1, 2) AS r2, sum(price) AS s, count(*) AS n
       FROM t GROUP BY substr(region, 1, 2) ORDER BY r2""",
    # two timeformat dims (year + month) -> both precomputed
    """SELECT year(ts) AS y, month(ts) AS mo, sum(price) AS s FROM t
       GROUP BY year(ts), month(ts) ORDER BY y, mo""",
    # mixed in-kernel (codes) + precomputed (timeformat) digits in one
    # mixed-radix key — the SSB q2.1 shape that first failed on hardware
    """SELECT year(ts) AS y, color, sum(price) AS s FROM t
       GROUP BY year(ts), color ORDER BY y, color""",
    # remap + codes + filter together
    """SELECT substr(region, 1, 2) AS r2, color, sum(price) AS s FROM t
       WHERE qty < 40 GROUP BY substr(region, 1, 2), color
       ORDER BY r2, color""",
]


@pytest.mark.parametrize("sql", PRECOMPUTED_DIM_QUERIES)
def test_pallas_precomputed_dim_parity(sql):
    _assert_parity(sql, check_eligible=True)


COLCMP_QUERIES = [
    # string pair via the translation stream (incl. absent-vocab values)
    """SELECT color, sum(price) AS s, count(*) AS n FROM t
       WHERE region = dest GROUP BY color ORDER BY color""",
    # NOT composition: NULL rows match <>
    """SELECT region, count(*) AS n FROM t
       WHERE color <> color2 GROUP BY region ORDER BY region""",
    # nullable string pair + second filter + numeric dim
    """SELECT qty, sum(price) AS s FROM t
       WHERE color = color2 AND qty BETWEEN 0 AND 30
       GROUP BY qty ORDER BY qty""",
    # numeric pair (nullable Int64 vs int64) inside an AND tree
    """SELECT color, count(*) AS n FROM t
       WHERE qty = price OR region = dest GROUP BY color ORDER BY color""",
]


@pytest.mark.parametrize("sql", COLCMP_QUERIES)
def test_pallas_colcmp_parity(sql):
    """columnComparison inside the Pallas kernel: the translation stream
    enters as an ordinary int32 row and the compare is elementwise (no
    in-kernel gather — Mosaic only lowers 2-D gathers)."""
    _assert_parity(sql, check_eligible=True)


def test_pallas_precomputed_dim_kinds():
    """The remap/timeformat dims really take the precomputed path (guards
    against the planner silently reclassifying them as in-kernel)."""
    _, forced = _engines()
    q = """SELECT year(ts) AS y, color, sum(price) AS s FROM t
           GROUP BY year(ts), color"""
    plan = forced.planner.plan(q)
    phys = lower(plan.query, plan.entry.segments, forced.config)
    kinds = [dp.kind for dp in phys.dim_plans]
    assert "timeformat" in kinds and "codes" in kinds, kinds
    assert phys.pallas_reason is None


def test_pallas_large_value_sums():
    """Values spanning the full int32 range exercise every 4-bit plane and
    the f64 half-sum recombination (the int64-shift recombination was
    miscompiled on real hardware; interpret mode guards the math)."""
    rng = np.random.default_rng(11)
    n = 2048
    df = pd.DataFrame({
        "ts": pd.to_datetime("2021-01-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 30, n), unit="s"),
        "g": rng.choice([f"g{i}" for i in range(7)], n),
        "big": rng.integers(0, 2**31 - 1, n).astype(np.int64),
        "neg": rng.integers(-(2**30), 2**30, n).astype(np.int64),
    })
    plain = Engine(EngineConfig(use_pallas="never"))
    forced = Engine(EngineConfig(use_pallas="force"))
    for e in (plain, forced):
        e.register_table("big_t", df, time_column="ts", block_rows=512)
    for q in (
        "SELECT g, sum(big) AS s FROM big_t GROUP BY g ORDER BY g",
        # negative values ride the biased half-plane path with a bias
        # whose magnitude needs both 16-bit halves of the un-shift
        "SELECT g, sum(neg) AS s FROM big_t GROUP BY g ORDER BY g",
    ):
        a = plain.sql(q)
        b = forced.sql(q)
        assert forced.last_plan.rewritten
        plan = forced.planner.plan(q)
        phys = lower(plan.query, plan.entry.segments, forced.config)
        assert phys.pallas_reason is None, phys.pallas_reason
        pd.testing.assert_frame_equal(a, b)


def test_pallas_factorized_boundary_sweep():
    """The factorized lane packing (Factorization: key -> (k1, k2v), k2
    groups per lane tile) must be value-identical to the direct one-hot
    across group counts spanning the direct/factorized decision boundary
    and the K % k2 != 0 tail-slice cases — including biased (negative)
    sums, filtered aggs, and NULL inputs."""
    from tpu_olap.kernels.pallas_reduce import factorization

    rng = np.random.default_rng(23)
    n = 4096
    for card in (2, 9, 16, 63, 200, 1001):
        df = pd.DataFrame({
            "ts": pd.to_datetime("2022-01-01")
            + pd.to_timedelta(rng.integers(0, 86400 * 10, n), unit="s"),
            "g": rng.integers(0, card, n).astype(np.int64),
            "v": rng.integers(-500, 500, n).astype(np.int64),
        })
        df.loc[rng.random(n) < 0.03, "v"] = np.nan
        df["v"] = df["v"].astype("Int64")
        plain = Engine(EngineConfig(use_pallas="never"))
        forced = Engine(EngineConfig(use_pallas="force"))
        for e in (plain, forced):
            e.register_table("f_t", df, time_column="ts", block_rows=512)
        q = ("SELECT g, sum(v) AS s, count(*) AS n, "
             "sum(v) FILTER (WHERE v > 0) AS sp "
             "FROM f_t GROUP BY g ORDER BY g")
        a = plain.sql(q)
        b = forced.sql(q)
        assert forced.last_plan.rewritten
        plan = forced.planner.plan(q)
        phys = lower(plan.query, plan.entry.segments, forced.config)
        assert phys.pallas_reason is None, phys.pallas_reason
        pd.testing.assert_frame_equal(a, b)
    # sanity: the sweep covered both layouts
    cfg = EngineConfig()
    assert factorization(2, 9, 0, cfg) is None
    assert factorization(1001, 9, 0, cfg) is not None


def test_pallas_plane_sizing():
    """Round-5 roofline fix: byte planes sized by the column value span,
    not a fixed 32 bits. A 14-bit span costs 2 planes; a negative span
    biases; a wide positive lo biases only when it saves net columns."""
    from tpu_olap.kernels.pallas_reduce import _sum_plane_spec

    assert _sum_plane_spec(0, 10_000) == (2, 0)
    assert _sum_plane_spec(0, 255) == (1, 0)
    assert _sum_plane_spec(0, 2**31 - 1) == (4, 0)
    # mandatory bias for negative lo
    n, bias = _sum_plane_spec(-500, 500)
    assert bias == -500 and n == 2
    # lo = 2**24: unbiased needs 4 planes, biased needs 1 + the extra
    # row-count column = cheaper
    n, bias = _sum_plane_spec(2**24, 2**24 + 100)
    assert (n, bias) == (1, 2**24)
    # narrow saving: biasing 0..255 span at lo=256 would cost 1+1 vs 2
    assert _sum_plane_spec(256, 511) == (2, 0)


def test_pallas_chunked_accumulator():
    """Grid runs longer than steps_per_chunk flush one accumulator chunk
    per run; the host recombines chunks in f64. Forced here by shrinking
    MAX_VALUE so spc drops to 2 grid steps (production: ~8M rows)."""
    from tpu_olap.kernels import pallas_reduce

    old = pallas_reduce.MAX_VALUE
    pallas_reduce.MAX_VALUE = 256 * 255 * 2 + 1  # spc = 2 at rb = 256
    try:
        rng = np.random.default_rng(47)
        n = 8192
        df = pd.DataFrame({
            "ts": pd.to_datetime("2023-01-01")
            + pd.to_timedelta(rng.integers(0, 86400 * 20, n), unit="s"),
            "gch": rng.choice([f"c{i}" for i in range(11)], n),
            "v": rng.integers(-200, 200, n).astype(np.int64),
            "w": rng.integers(0, 101, n).astype(np.int64),
        })
        df.loc[rng.random(n) < 0.04, "w"] = np.nan
        df["w"] = df["w"].astype("Int64")
        plain = Engine(EngineConfig(use_pallas="never"))
        forced = Engine(EngineConfig(use_pallas="force"))
        for e in (plain, forced):
            e.register_table("ch_t", df, time_column="ts", block_rows=256)
        # 8192 rows / rb 256 = 32 grid steps = 16 chunks; cover biased
        # sums, nullable inputs, filtered aggs, counts, and min/max
        # (unchunked second buffer) in one layout
        for q in (
            """SELECT gch, sum(v) AS s, count(*) AS n,
                      sum(w) FILTER (WHERE v > 0) AS sw
               FROM ch_t GROUP BY gch ORDER BY gch""",
            """SELECT gch, min(v) AS mn, max(v) AS mx, sum(w) AS sw
               FROM ch_t GROUP BY gch ORDER BY gch""",
            "SELECT sum(v * w) AS sv FROM ch_t",
        ):
            a, b = plain.sql(q), forced.sql(q)
            plan = forced.planner.plan(q)
            phys = lower(plan.query, plan.entry.segments, forced.config)
            assert phys.pallas_reason is None, phys.pallas_reason
            pd.testing.assert_frame_equal(a, b)
    finally:
        pallas_reduce.MAX_VALUE = old


def test_pallas_factorized_beyond_direct_cap():
    """Group spaces past pallas_group_cap stay on the kernel when the
    layout factorizes (pallas_group_cap_factorized); min/max layouts
    (no factorization) still reject legibly."""
    rng = np.random.default_rng(31)
    n = 4096
    df = pd.DataFrame({
        "ts": pd.to_datetime("2022-01-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 5, n), unit="s"),
        "g": rng.integers(0, 20000, n).astype(np.int64),
        "v": rng.integers(0, 1000, n).astype(np.int64),
    })
    plain = Engine(EngineConfig(use_pallas="never"))
    forced = Engine(EngineConfig(use_pallas="force"))
    for e in (plain, forced):
        e.register_table("big_k", df, time_column="ts", block_rows=512)
    q = ("SELECT g, sum(v) AS s, count(*) AS n FROM big_k "
         "GROUP BY g ORDER BY g")
    a, b = plain.sql(q), forced.sql(q)
    plan = forced.planner.plan(q)
    phys = lower(plan.query, plan.entry.segments, forced.config)
    assert phys.total_groups > forced.config.pallas_group_cap
    assert phys.pallas_reason is None, phys.pallas_reason
    pd.testing.assert_frame_equal(a, b)
    # a min/max agg blocks factorization -> legible decline past the cap
    q2 = "SELECT g, min(v) AS m FROM big_k GROUP BY g ORDER BY g"
    plan2 = forced.planner.plan(q2)
    phys2 = lower(plan2.query, plan2.entry.segments, forced.config)
    assert phys2.pallas_reason is not None
    assert "does not factorize" in phys2.pallas_reason
    a2, b2 = plain.sql(q2), forced.sql(q2)
    pd.testing.assert_frame_equal(a2, b2)
