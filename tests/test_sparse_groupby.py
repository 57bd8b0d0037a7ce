"""Sort-based sparse group-by (kernels.sparse_groupby): high-cardinality
GROUP BY beyond the dense mixed-radix budget (SURVEY.md §8.4 hard part #1).

dense_group_budget is forced tiny so ordinary-size tables exercise the
sparse path; parity versus the pandas fallback is the oracle throughout.
"""

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.bench.parity import check_query
from tpu_olap.executor import EngineConfig, sparse_dispatch
from tpu_olap.executor.lowering import lower
from tpu_olap.kernels.sparse_groupby import SparseProgram, sparse_group_reduce


def _reduce(key, mask, env, plans, cap, top=None, having=None, narrow=False,
            boundary=None):
    """`sparse_group_reduce`'s tables of the program a case's cut and
    spellings name; `having`: (test, names, kept)."""
    return sparse_group_reduce(
        key, mask, env, plans, {},
        SparseProgram(cap, top, having and having[2], narrow, boundary),
        having and having[:2])


def _df(n=6000, seed=23):
    rng = np.random.default_rng(seed)
    df = pd.DataFrame({
        "ts": pd.to_datetime("2022-01-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 100, n), unit="s"),
        "a": rng.choice([f"a{i}" for i in range(150)], n),
        "b": rng.choice([f"b{i}" for i in range(90)], n),
        "c": rng.choice(["x", "y", None], n),
        "v": rng.integers(-100, 1000, n).astype(np.int64),
        "w": np.round(rng.random(n) * 50, 4),
    })
    df.loc[rng.random(n) < 0.03, "v"] = np.nan
    df["v"] = df["v"].astype("Int64")
    return df


def _engine(**kw):
    cfg = EngineConfig(dense_group_budget=64, **kw)
    eng = Engine(cfg)
    eng.register_table("t", _df(), time_column="ts", block_rows=512)
    return eng


SQL = ("SELECT a, b, sum(v) AS sv, count(*) AS n, min(w) AS mw, "
       "max(v) AS xv FROM t GROUP BY a, b")


def test_sparse_plan_selected():
    eng = _engine()
    plan = eng.planner.plan(SQL)
    phys = lower(plan.query, plan.entry.segments, eng.config)
    assert phys.sparse
    assert phys.total_groups > 64


def test_sparse_parity():
    check_query(_engine(), SQL)


def test_sparse_parity_with_filter_and_having():
    check_query(_engine(),
                "SELECT a, b, sum(v) AS sv, count(*) AS n FROM t "
                "WHERE w < 40 AND c = 'x' GROUP BY a, b "
                "HAVING count(*) > 1")


def test_sparse_count_distinct():
    check_query(_engine(),
                "SELECT a, approx_count_distinct(b) AS d FROM t GROUP BY a",
                approx_cols=("d",))


def test_sparse_order_limit():
    check_query(_engine(),
                "SELECT a, b, sum(v) AS sv FROM t GROUP BY a, b "
                "ORDER BY sv DESC LIMIT 17")


def test_sparse_multichip_parity():
    check_query(_engine(num_shards=8), SQL)


def test_sparse_cap_adapts():
    eng = _engine(sparse_group_cap=64)
    res = eng.sql(SQL)
    h = eng.history[-1]
    assert h["sparse"] and h["result_groups"] > 64
    assert h["result_cap"] >= h["result_groups"]
    assert len(res) == h["result_groups"]


def test_sparse_budget_exceeded_falls_back():
    eng = _engine(sparse_group_budget=64)
    res = eng.sql(SQL)
    assert "sparse budget" in (eng.last_plan.fallback_reason or "")
    # fallback still answers correctly
    ref = _engine().sql(SQL)
    assert len(res) == len(ref)


def test_merge_propagates_local_overflow():
    """A chip whose LOCAL compact table overflowed dropped groups; the
    merged count must still exceed cap so the runner retries larger."""
    from tpu_olap.kernels.sparse_groupby import (merge_sparse,
                                                 sparse_group_reduce)
    from tpu_olap.kernels.groupby import AggPlan

    cap = 64
    plans = [AggPlan("n", "count", (), np.int64)]
    env = {"cols": {}, "nulls": {}}
    import jax
    import jax.numpy as jnp

    def chip(n):  # a chip's compact table, fetched as the broker does
        return jax.device_get(sparse_group_reduce(
            jnp.arange(n, dtype=jnp.int64), jnp.ones(n, bool), env, plans,
            {}, SparseProgram(cap)))
    # chip A: 65 distinct keys -> local overflow drops one
    out_a = chip(65)
    assert int(out_a["_count"]) == 65  # local overflow signalled
    # chip B: subset of A's surviving keys
    out_b = chip(32)
    merged = merge_sparse([out_a, out_b], plans, cap)
    assert int(merged["_count"]) == 65  # NOT 64: retry must fire


def test_sparse_theta_rewrites():
    """Round 3: theta over a sparse group space executes on the device
    path (it used to be an UnsupportedAggregation fallback)."""
    eng = _engine()
    eng.sql("SELECT a, b, theta_sketch(c) AS d FROM t GROUP BY a, b")
    assert eng.last_plan.rewritten, eng.last_plan.fallback_reason


# --------------------------------------------------------------------------
# Hash-exchange multi-chip merge (SURVEY.md §3.5 last row, §8.4 #1)

def test_exchange_matches_gather():
    """Both multi-chip sparse merge strategies produce identical results
    (including HLL count-distinct and min/max with nulls)."""
    sql = ("SELECT a, b, sum(v) AS sv, count(*) AS n, min(w) AS mw, "
           "count(distinct c) AS dc FROM t GROUP BY a, b ORDER BY a, b")
    ex = _engine(num_shards=8, sparse_merge="exchange")
    ga = _engine(num_shards=8, sparse_merge="gather")
    got_x, got_g = ex.sql(sql), ga.sql(sql)
    assert ex.history[-1].get("sparse_merge") == "exchange"
    assert "sparse_merge" not in ga.history[-1]
    pd.testing.assert_frame_equal(got_x, got_g)


def test_exchange_parity_vs_fallback():
    check_query(_engine(num_shards=8, sparse_merge="exchange"), SQL)


def test_exchange_scales_past_per_chip_budget():
    """>= 1e6 present groups on 8 chips with a 2^17 per-chip budget:
    the gather strategy must refuse (cap is global there), the exchange
    strategy must answer — its capacity is D x budget (VERDICT r1 #6)."""
    n = 1_000_000  # one group per row (>= 1e6 present groups)
    df = pd.DataFrame({
        "ts": pd.to_datetime("2022-01-01")
        + pd.to_timedelta(np.arange(n) // 2000, unit="min"),
        "k": np.arange(n, dtype=np.int64),
        "v": np.ones(n, dtype=np.int64),
    })
    budget = 1 << 17

    def mk(merge):
        eng = Engine(EngineConfig(
            dense_group_budget=64, num_shards=8, sparse_merge=merge,
            sparse_group_budget=budget))
        eng.register_table("t", df, time_column="ts",
                           block_rows=1 << 14)
        return eng

    ex = mk("exchange")
    got = ex.sql("SELECT k, sum(v) AS s FROM t GROUP BY k LIMIT 7")
    h = ex.history[-1]
    assert h["sparse_merge"] == "exchange"
    assert h["result_groups"] == n  # every group present and counted
    assert len(got) == 7
    assert (got.s == 1).all()

    # exact parity on a filtered slice (1000 groups through the same
    # exchange kernel)
    sub = ex.sql("SELECT k, sum(v) AS s FROM t WHERE k < 1000 "
                 "GROUP BY k ORDER BY k")
    assert len(sub) == 1000
    assert (sub.s == 1).all()
    assert list(sub.k) == list(range(1000))

    # gather at the same budget refuses (falls back to pandas)
    ga = mk("gather")
    ga.sql("SELECT k, sum(v) AS s FROM t GROUP BY k LIMIT 7")
    assert "sparse budget" in (ga.last_plan.fallback_reason or "")


# Skewed-key workloads (VERDICT round-2 weak #8): keys chosen so the old
# hash-exchange would have routed every group to ONE owner chip — the
# worst case for a device-side exchange. The broker merge (per-chip
# compaction + host union, executor/sharding.py) has no owner chips, so
# these pin that skew cannot degrade capacity or correctness.

def _fib_owner(ids: np.ndarray, shards: int) -> np.ndarray:
    """Fibonacci multiplicative hash (the retired sharding._owner_of)
    — kept to CONSTRUCT maximally-skewed key sets."""
    h = ids.astype(np.int64) * np.int64(-7046029254386353131)
    h = (h >> np.int64(33)) & np.int64(0x7FFFFFFF)
    return (h % np.int64(shards)).astype(np.int32)


def _skewed_values(n_groups: int, shards: int = 8) -> np.ndarray:
    """Values for a single numeric dim whose sparse keys (value+1, with 0
    present as the min) all hash to owner(1)."""
    cand = np.arange(1, 400_000, dtype=np.int64)
    target = _fib_owner(np.array([1], np.int64), shards)[0]
    sel = cand[_fib_owner(cand, shards) == target][:n_groups] - 1
    assert sel.size == n_groups, "not enough same-owner candidates"
    assert sel[0] == 0  # value 0 present -> ids are exactly value+1
    return sel


def _skewed_engine(values, rows_per_group=3, **kw):
    vals = np.repeat(values, rows_per_group)
    df = pd.DataFrame({
        "ts": pd.to_datetime("2022-01-01")
        + pd.to_timedelta(np.arange(len(vals)) % 9973, unit="s"),
        "k": vals,
        "v": np.ones(len(vals), dtype=np.int64),
    })
    eng = Engine(EngineConfig(dense_group_budget=64, num_shards=8,
                              sparse_merge="exchange", **kw))
    eng.register_table("t", df, time_column="ts", block_rows=512)
    return eng


SKEW_SQL = "SELECT k, sum(v) AS s, count(*) AS n FROM t GROUP BY k"


def test_exchange_skewed_single_owner_parity():
    """All keys would have landed on one hash owner: the broker's
    merged table must absorb the full group count — answers must still
    match the fallback exactly."""
    eng = _skewed_engine(_skewed_values(1500))
    check_query(eng, SKEW_SQL)
    m = eng.history[-1]
    assert m.get("sparse_merge") == "exchange"
    # the broker table sized to the full group count (not a per-owner
    # count/D estimate)
    assert m["result_cap_owner"] >= 1500


def test_exchange_skew_no_longer_overflows():
    """Hash skew was the old exchange's failure mode (every key owned by
    one chip overflowed that chip's owner table). The broker merge has
    no owner chips — the host union absorbs ANY key distribution — so
    the same shape now answers on the device path with exact parity."""
    eng = _skewed_engine(_skewed_values(1200), sparse_group_budget=512)
    check_query(eng, SKEW_SQL)
    m = eng.history[-1]
    assert m.get("sparse_merge") == "exchange"
    assert m["result_groups"] == 1200


def test_exchange_overflow_falls_back_cleanly():
    """Groups beyond the scaled capacity (local compaction past the
    per-chip budget, or the broker table past D x budget): retries
    exhaust and the engine answers via structural fallback, never an
    error (SURVEY.md §2 property 2)."""
    eng = _skewed_engine(_skewed_values(1200), sparse_group_budget=64)
    got = eng.sql(SKEW_SQL)
    assert eng.last_plan.fallback_reason is not None
    assert "sparse budget" in eng.last_plan.fallback_reason
    ref = _skewed_engine(_skewed_values(1200), sparse_group_budget=64)
    from tpu_olap.planner.fallback import execute_fallback
    expect = execute_fallback(ref.planner.plan(SKEW_SQL).stmt,
                              ref.catalog, ref.config)
    a = got.sort_values("k").reset_index(drop=True)
    b = expect.sort_values("k").reset_index(drop=True)
    pd.testing.assert_frame_equal(a, b, check_dtype=False)


def test_sparse_theta_parity():
    """theta_sketch over a sparse group space (round-3: previously an
    UnsupportedAggregation). Per-group distinct counts here stay under
    the clamped sketch width, so estimates are EXACT and the pandas
    fallback (exact nunique) is a zero-tolerance oracle."""
    eng = _engine()
    plan = eng.planner.plan(
        "SELECT a, b, theta_sketch(v) AS d FROM t GROUP BY a, b")
    phys = lower(plan.query, plan.entry.segments, eng.config)
    assert phys.sparse
    tk = [p.theta_k for p in phys.agg_plans if p.kind == "theta"]
    assert tk == [eng.config.sparse_theta_k_cap]
    check_query(eng,
                "SELECT a, b, theta_sketch(v) AS d, count(*) AS n FROM t "
                "GROUP BY a, b")


def test_sparse_theta_multichip_exchange():
    """theta tables ride the hash-exchange all_to_all merge: each owner
    unions the per-chip [cap, k] rows for its keys."""
    eng = _engine(num_shards=8, sparse_merge="exchange")
    check_query(eng,
                "SELECT a, theta_sketch(b) AS db, count(*) AS n FROM t "
                "GROUP BY a")


def test_sparse_theta_multichip_gather():
    eng = _engine(num_shards=8, sparse_merge="gather")
    check_query(eng,
                "SELECT a, theta_sketch(b) AS db FROM t GROUP BY a")


# --------------------------------------------------------------------------
# The compact tables are read at the sorted runs' boundaries (PR 30): table
# for table equal to a plain numpy reference that knows nothing of sorting.

def _agg(name, kind, field=None, acc=np.int64, filter_fn=None):
    from tpu_olap.kernels.groupby import AggPlan
    return AggPlan(name, kind, (field,) if field else (), acc, filter_fn)


def _numpy_tables(key, mask, env, plans, cap):
    """The compact tables by definition: slot i is the i-th smallest
    present key; one boolean selection a group, numpy's own reductions."""
    from tpu_olap.kernels.groupby import _ident
    from tpu_olap.kernels.sparse_groupby import SENTINEL
    present = np.unique(key[mask])
    out = {"_count": np.int32(len(present)),
           "_keys": np.full(cap, SENTINEL, np.int64),
           "_rows": np.zeros(cap, np.int32)}
    for p in plans:
        acc = np.dtype(p.acc_dtype)
        out[p.name] = np.full(cap, _ident(acc, p.kind)
                              if p.kind in ("min", "max") else 0, acc)
        if p.kind in ("min", "max"):
            out[f"_nn_{p.name}"] = np.zeros(cap, np.int32)
    for i, k in enumerate(present[:cap]):
        sel = mask & (key == k)
        out["_keys"][i] = k
        out["_rows"][i] = sel.sum()
        for p in plans:
            m = sel if p.filter_fn is None else sel & np.asarray(
                p.filter_fn(env, {}))
            if p.kind == "count":
                out[p.name][i] = m.sum()
                continue
            nulls = env["nulls"].get(p.fields[0])
            if nulls is not None:
                m = m & ~nulls
            x = env["cols"][p.fields[0]][m].astype(p.acc_dtype)
            if p.kind == "sum":
                out[p.name][i] = x.sum(dtype=p.acc_dtype)  # int64 wraps
            else:
                out[f"_nn_{p.name}"][i] = m.sum()
                if x.size:
                    out[p.name][i] = x.min() if p.kind == "min" else x.max()
    return out


def _positive(env, consts):
    return env["cols"]["f"] > 0


def _boundary_cases():
    rng = np.random.default_rng(30)
    n = 257
    big = np.int64(1) << 62
    ones = np.ones(n, bool)
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    sums = [_agg("s", "sum", "v"), _agg("n", "count")]

    def env(**cols):
        return {"cols": cols, "nulls": {}}

    yield "every-row-masked", rng.integers(0, 9, n), np.zeros(n, bool), \
        env(v=v), sums, 8
    yield "one-group-spans-all-rows", np.full(n, 5), ones, env(v=v), sums, 8
    yield "count-equals-cap", np.arange(n) % 16 * 3, ones, env(v=v), sums, 16
    yield "count-past-cap", np.arange(n) % 40, rng.random(n) < 0.9, \
        env(v=v), sums, 16
    # the first and the last sorted row are groups of one row, and a
    # masked tail follows the last
    edge = np.r_[0, np.full(n - 12, 7), 99, np.full(10, 3)]
    yield "first-and-last-row-alone", edge, np.r_[np.ones(n - 10, bool),
                                                  np.zeros(10, bool)], \
        env(v=v), sums, 8
    # the running prefix passes 2^63 and wraps; no group's own sum does
    wrap = np.r_[np.full(6, big), np.full(6, -big), np.full(5, big), v[17:]]
    yield "prefix-wraps-past-2^63", np.arange(n) // 2, ones, \
        env(v=wrap), sums, 256
    f = rng.integers(-3, 4, n)
    yield "filtered-sum-and-count", rng.integers(0, 30, n), \
        rng.random(n) < 0.8, env(v=v, f=f), \
        [_agg("s", "sum", "v", filter_fn=_positive),
         _agg("n", "count", filter_fn=_positive), _agg("all", "count")], 32
    w = np.round(rng.random(n) * 50 - 25, 4)
    nul = {"cols": {"w": w, "v": v},
           "nulls": {"w": rng.random(n) < 0.4}}
    yield "min-max-with-nulls", rng.integers(0, 60, n), \
        rng.random(n) < 0.8, nul, \
        [_agg("lo", "min", "w", np.float64), _agg("hi", "max", "w",
                                                  np.float64),
         _agg("xv", "max", "v"),
         _agg("fl", "min", "v", filter_fn=lambda e, c: e["cols"]["v"] > 0)], \
        64
    # a min / max of a column stored in 32 bits or fewer is reduced as
    # int32 and widened a slot: the ends of each width's range, groups a
    # filter empties, and beside them a long past 2^31, which is not
    i8 = rng.integers(-128, 128, n).astype(np.int8)
    i32 = rng.integers(-(1 << 31), 1 << 31, n).astype(np.int32)
    i32[:4] = [np.iinfo(np.int32).min, np.iinfo(np.int32).max, 0, -1]
    u32 = rng.integers(0, 1 << 32, n).astype(np.uint32)
    yield "narrow-min-max", rng.integers(0, 40, n), rng.random(n) < 0.8, \
        env(i8=i8, i32=i32, u32=u32, v=v * (big >> 20), f=f), \
        [_agg("lo8", "min", "i8"), _agg("hi8", "max", "i8"),
         _agg("lo32", "min", "i32"), _agg("hi32", "max", "i32"),
         _agg("hiu", "max", "u32"), _agg("lov", "min", "v"),
         _agg("few", "max", "i8", filter_fn=lambda e, c: e["cols"]["f"] > 2),
         _agg("s", "sum", "i8")], 64
    yield "float64-sum", rng.integers(0, 20, n), rng.random(n) < 0.9, \
        env(w=w * 1e6), [_agg("fs", "sum", "w", np.float64),
                         _agg("n", "count")], 32


@pytest.mark.parametrize("case", list(_boundary_cases()),
                         ids=[c[0] for c in _boundary_cases()])
def test_compact_tables_equal_the_numpy_reference(case):
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import sparse_group_reduce
    EngineConfig().apply_x64()   # int64 keys and sums, as under an Engine
    _, key, mask, env, plans, cap = case
    key = np.asarray(key, np.int64)

    @jax.jit
    def run(key, mask, env):
        return sparse_group_reduce(key, mask, env, plans, {},
                                   SparseProgram(cap))

    got = jax.device_get(run(key, mask, env))
    want = _numpy_tables(key, mask, env, plans, cap)
    assert int(got["_count"]) == int(want["_count"])
    if want["_count"] > cap:
        return  # an overflowing attempt owes the true count and no table
    assert set(got) == set(want)
    for name, table in want.items():
        assert got[name].dtype == table.dtype, name
        if table.dtype.kind == "f":
            # the tolerance the parity oracle holds a float sum to
            np.testing.assert_allclose(got[name], table, rtol=1e-9,
                                       atol=1e-6, err_msg=name)
        else:
            np.testing.assert_array_equal(got[name], table, err_msg=name)


# --------------------------------------------------------------------------
# An integer min / max of a column stored in 32 bits or fewer is read at the
# runs' last rows from a running maximum of (run id << b) | code (PR 37).

EXT_AGGS = {
    "min": lambda f: [_agg("lo", "min", "x", filter_fn=f)],
    "max": lambda f: [_agg("hi", "max", "x", filter_fn=f)],
    "both-of-one-column": lambda f: [_agg("lo", "min", "x", filter_fn=f),
                                     _agg("hi", "max", "x"),
                                     _agg("s", "sum", "x")],
    "of-two-columns": lambda f: [_agg("lo", "min", "x"),
                                 _agg("hi", "max", "y", filter_fn=f),
                                 _agg("n", "count")],
}
# (cap, the share of rows the query's filter keeps, distinct keys)
EXT_SHAPES = {
    "cap-holds-with-empty-slots": (64, 0.8, 40),
    "cap-overflows": (16, 0.9, 40),
    "every-row-masked": (8, 0.0, 9),
    "cap-past-an-int32-word": (1 << 14, 0.8, 40),
}


def _ext_case(stored, rows, shape):
    """Keys, mask, env: a column `x` and a column `y` of dtype `stored`
    that hold the dtype's two ends, negatives and a group of one value."""
    cap, keep, groups = EXT_SHAPES[shape]
    rng = np.random.default_rng([30, np.dtype(stored).itemsize, cap])
    n = 257
    lim = np.iinfo(stored)
    cols = {}
    for c in ("x", "y"):
        v = rng.integers(lim.min, lim.max, n, dtype=np.int64, endpoint=True)
        v[:6] = [lim.min, lim.max, 0, -1, lim.min + 1, lim.max - 1]
        cols[c] = rng.permutation(v).astype(stored)
    cols["f"] = rng.integers(-3, 4, n)
    env = {"cols": cols, "nulls": {}}
    if rows == "nulls":
        env["nulls"] = {"x": rng.random(n) < 0.4, "y": rng.random(n) < 0.9}
    key = rng.integers(0, groups, n).astype(np.int64)
    key[key == 3] = 2          # a group no row has: the slots shift
    return key, rng.random(n) < keep, env, cap


@pytest.mark.parametrize("shape", sorted(EXT_SHAPES))
@pytest.mark.parametrize("rows", ["no-filter", "filtered-aggregator",
                                  "nulls"])
@pytest.mark.parametrize("aggs", sorted(EXT_AGGS))
@pytest.mark.parametrize("stored", ["int8", "int16", "int32", "int64"])
def test_integer_min_max_tables_equal_the_numpy_reference(stored, aggs,
                                                          rows, shape):
    """Every [cap] table, `_nn_<name>` and `_count` among them, equal to
    numpy's for each stored width, on either side of each word width, with
    a filtered aggregator (whole groups left out: the identity, not a
    neighbour's value) and with nulls."""
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import (ext_word_dtype,
                                                 sparse_group_reduce,
                                                 sparse_reduce_form)
    EngineConfig().apply_x64()
    key, mask, env, cap = _ext_case(stored, rows, shape)
    plans = EXT_AGGS[aggs](_positive if rows == "filtered-aggregator"
                           else None)
    word = ext_word_dtype(stored, np.int64, cap)
    assert word == {"int8": np.int32, "int64": None, "int32": np.int64,
                    "int16": np.int32 if cap < 1 << 14 else np.int64}[stored]
    assert sparse_reduce_form(plans, {"x": stored, "y": stored}, cap) \
        == ("scatter" if word is None else "boundary")
    got = jax.device_get(sparse_group_reduce(
        jnp.asarray(key), jnp.asarray(mask),
        {k: {c: jnp.asarray(a) for c, a in d.items()}
         for k, d in env.items()}, plans, {}, SparseProgram(cap)))
    want = _numpy_tables(key, mask, env, plans, cap)
    assert int(got["_count"]) == int(want["_count"])
    if want["_count"] > cap:
        assert shape == "cap-overflows"
        return  # an overflowing attempt owes the true count and no table
    assert shape != "cap-overflows" and set(got) == set(want)
    for name, table in want.items():
        assert got[name].dtype == table.dtype, name
        np.testing.assert_array_equal(got[name], table, err_msg=name)


@pytest.mark.parametrize("dtype,n,block", [
    ("int32", 1000, 8), ("int64", 1000, 8), ("int64", 1024, 8),
    ("int64", 7, 8), ("int64", 3 * 4096 + 5, 4096)])
def test_running_max_equals_numpys(monkeypatch, dtype, n, block):
    """The 64-bit running maximum runs along blocks (the chip's compiler
    does not take a long one-dimensional one): blocks of blocks, a ragged
    last block, a length under one block; the 32-bit one is one scan."""
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels import sparse_groupby as sg
    EngineConfig().apply_x64()
    monkeypatch.setattr(sg, "_SCAN_BLOCK", block)
    rng = np.random.default_rng(n)
    top = np.iinfo(dtype).max
    word = np.sort(rng.integers(0, top >> 9, n, dtype=dtype)) << 9 \
        | rng.integers(0, 512, n, dtype=dtype)
    got = jax.device_get(sg._running_max(jnp.asarray(word)))
    assert got.dtype == word.dtype
    np.testing.assert_array_equal(got, np.maximum.accumulate(word))


def test_a_narrow_min_max_program_holds_no_scatter_and_an_int64_ones_does():
    """A sum, a min and a max of an int8 column: no scatter primitive in
    the program, one running maximum a min / max, and the column rides the
    sort once for both. The same over a column stored in 64 bits keeps
    `jax.ops.segment_min` / `segment_max`, and the form says so."""
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import (ext_word_bits,
                                                 sparse_group_reduce,
                                                 sparse_reduce_form)
    EngineConfig().apply_x64()
    plans = [_agg("s", "sum", "v"), _agg("lo", "min", "v"),
             _agg("hi", "max", "v")]
    n, cap = 4096, 64

    def program(dtype):
        """(the program's text, the operands of its widest sort)"""
        env = {"cols": {"v": jnp.arange(n).astype(dtype)}, "nulls": {}}
        jaxpr = jax.make_jaxpr(lambda k, m, e: sparse_group_reduce(
            k, m, e, plans, {}, SparseProgram(cap)))(
            jnp.arange(n, dtype=jnp.int64) % 50, jnp.ones(n, bool), env)
        return str(jaxpr), max(len(e.invars) for e in jaxpr.jaxpr.eqns
                               if e.primitive.name == "sort")

    (narrow, narrow_sorts), (wide, wide_sorts) = \
        program(jnp.int8), program(jnp.int64)
    # a running maximum is bound as its reduce-window (`_running`)
    assert "scatter" not in narrow \
        and narrow.count("reduce_window_max") == 2
    assert "scatter" in wide and "reduce_window_max" not in wide
    # key, the sum's operand, the column once | key, sum, min, max
    assert (narrow_sorts, wide_sorts) == (3, 4)
    for dtype, form, bits in ((np.int8, "boundary", 32),
                              (np.int32, "boundary", 64),
                              (np.int64, "scatter", None)):
        assert sparse_reduce_form(plans, {"v": dtype}, cap) == form
        assert ext_word_bits(plans, {"v": dtype}, cap) == bits
    # the word follows the cap too: nine bits of code under 22 of run id
    assert ext_word_bits(plans, {"v": np.int8}, 1 << 21) == 32
    assert ext_word_bits(plans, {"v": np.int8}, 1 << 22) == 64
    assert ext_word_bits(plans[:1], {"v": np.int8}, cap) is None


def test_a_min_or_max_rides_at_the_columns_width_up_to_int32():
    from tpu_olap.kernels.sparse_groupby import _ext_dtype
    for col in (np.int8, np.uint8, np.int16, np.uint16, np.int32):
        assert _ext_dtype(col, np.int64) == np.int32
    for col in (np.uint32, np.int64):
        assert _ext_dtype(col, np.int64) == np.int64
    assert _ext_dtype(np.int8, np.int32) == np.int32       # no 64-bit lanes
    assert _ext_dtype(np.int8, np.float64) == np.float64   # a double min
    assert _ext_dtype(np.float32, np.float64) == np.float64


def _walk_spans(eng):
    def walk(tree):
        yield tree
        for c in tree.get("children", []):
            yield from walk(c)
    return walk(eng.tracer.last.to_json())


def _dispatch_forms(eng):
    return [s["attrs"].get("reduce_form")
            for s in _walk_spans(eng) if s["name"] == "dispatch"]


def test_no_scatter_in_a_sum_and_count_program_and_a_sketch_says_so():
    """Integer sums, counts, `_rows` and `_keys` scatter no row: the
    program of such a plan holds no scatter primitive at all. A sketch's
    [cap, m] state keeps its scatter-max, and the record says so."""
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import (sparse_group_reduce,
                                                 sparse_reduce_form)
    EngineConfig().apply_x64()
    plans = [_agg("s", "sum", "v"), _agg("n", "count"),
             _agg("fn", "count", filter_fn=_positive)]
    n = 4096
    env = {"cols": {"v": jnp.arange(n, dtype=jnp.int64),
                    "f": jnp.arange(n, dtype=jnp.int64) - 7}, "nulls": {}}
    jaxpr = jax.make_jaxpr(lambda k, m, e: sparse_group_reduce(
        k, m, e, plans, {}, SparseProgram(64)))(
        jnp.arange(n, dtype=jnp.int64) % 50, jnp.ones(n, bool), env)
    assert "scatter" not in str(jaxpr)
    assert "sort" in str(jaxpr)
    stored = {c: a.dtype for c, a in env["cols"].items()}
    assert sparse_reduce_form(plans, stored, 64) == "boundary"
    # a min is a scatter only of a column stored in 64 bits (or a double)
    for kind, acc in (("min", np.int64), ("sum", np.float64),
                      ("hll", np.int32), ("theta", np.int64)):
        assert sparse_reduce_form(
            plans + [_agg("x", kind, "v", acc)], stored, 64) == "scatter", \
            kind

    eng = _engine()
    eng.sql("SELECT a, b, approx_count_distinct(c) AS d, count(*) AS n "
            "FROM t GROUP BY a, b")
    rec = eng.history[-1]
    assert rec["reduce_path"] == "sparse" and "fallback_reason" not in rec
    assert rec["reduce_form"] == "scatter"
    assert _dispatch_forms(eng) == ["scatter"]


def test_record_and_dispatch_span_say_boundary():
    eng = _engine()
    check_query(eng, "SELECT a, b, sum(v) AS sv, count(*) AS n FROM t "
                     "WHERE w < 40 GROUP BY a, b")
    rec = eng.history[-1]
    assert rec["reduce_path"] == "sparse" and rec["sparse"]
    assert rec["reduce_form"] == "boundary"
    assert _dispatch_forms(eng) == ["boundary"]


# --------------------------------------------------------------------------
# A program that ends in a TopN's threshold ranks first and reads the tables
# it does not rank at the rows it keeps (PR 39): table for table equal to
# the spelling it replaced, kept here as the reference.

def _all_tables_then_cut(tables, metric, threshold, inverted):
    """Every [cap] table, then `top_k` over the metric's, then each table
    indexed with the kept slots: `sparse_top_rows` as it stood before
    PR 39."""
    from tpu_olap.kernels.sparse_groupby import SENTINEL
    from tpu_olap.kernels.topk import top_k_groups
    order, _ = top_k_groups(tables[metric], tables["_keys"] != SENTINEL,
                            threshold, inverted)
    return {name: t if name == "_count" else t[order]
            for name, t in tables.items()}


def _top_cases():
    """(id, key, mask, env, plans, cap, (metric, threshold, inverted))"""
    rng = np.random.default_rng(39)
    n = 1021
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    u = rng.integers(0, 50, n).astype(np.int64)
    f = rng.integers(-3, 4, n)
    i8 = rng.integers(-128, 128, n).astype(np.int8)
    i32 = rng.integers(-(1 << 31), 1 << 31, n).astype(np.int32)
    w = np.round(rng.random(n) * 50 - 25, 4)
    key = rng.integers(0, 90, n).astype(np.int64)
    key[key == 3] = 2          # a group no row has: the slots shift
    mask = rng.random(n) < 0.85

    def env(nulls=None, **cols):
        return {"cols": cols, "nulls": nulls or {}}

    s = _agg("s", "sum", "v")
    yield "integer-sum", key, mask, env(v=v), [s], 128, ("s", 10, False)
    yield "two-sums-a-filtered-count-and-a-filtered-sum", key, mask, \
        env(v=v, u=u, f=f), \
        [s, _agg("su", "sum", "u"), _agg("fn", "count", filter_fn=_positive),
         _agg("fs", "sum", "u", filter_fn=_positive), _agg("n", "count")], \
        128, ("s", 10, False)
    ext8 = [s, _agg("lo", "min", "d"), _agg("hi", "max", "d")]
    yield "min-max-of-int8-int32-word", key, mask, env(v=v, d=i8), ext8, \
        128, ("s", 10, False)
    yield "min-max-of-int32-int64-word", key, mask, env(v=v, d=i32), ext8, \
        128, ("s", 10, False)
    yield "min-max-of-int16-cap-past-an-int32-word", key, mask, \
        env(v=v, d=i32.astype(np.int16)), ext8, 1 << 14, ("s", 10, False)
    yield "min-max-with-nulls", key, mask, \
        env({"d": rng.random(n) < 0.6}, v=v, d=i8, f=f), \
        ext8 + [_agg("few", "max", "d",
                     filter_fn=lambda e, c: e["cols"]["f"] > 2)], \
        128, ("s", 10, False)
    # a metric of four values over ninety groups: the threshold falls
    # inside a tie, which the lower slot (the smaller key) wins
    tied = np.ones(n, np.int64)
    yield "ties-at-the-threshold", key % 40, np.ones(n, bool), \
        env(v=tied * (key % 40 % 4), d=i8), ext8, 64, ("s", 7, False)
    yield "inverted", key, mask, env(v=v, d=i8), ext8, 128, ("s", 10, True)
    yield "ties-inverted", key % 40, np.ones(n, bool), \
        env(v=tied * (key % 40 % 4), d=i8), ext8, 64, ("s", 7, True)
    yield "threshold-past-the-present-groups", key % 6, mask, \
        env(v=v, d=i8, f=f), \
        ext8 + [_agg("fn", "count", filter_fn=_positive)], 32, \
        ("s", 20, False)
    yield "threshold-past-the-cap", key % 6, mask, env(v=v, d=i8), ext8, \
        16, ("s", 100, False)
    yield "cap-overflows", key, mask, env(v=v, d=i8), ext8, 16, \
        ("s", 10, False)
    yield "every-row-masked", key, np.zeros(n, bool), env(v=v, d=i8), \
        ext8, 16, ("s", 10, False)
    yield "float-sum-beside-the-ranked-sum", key, mask, env(v=v, w=w * 1e6), \
        [s, _agg("fs", "sum", "w", np.float64), _agg("n", "count")], 128, \
        ("s", 10, False)
    yield "int64-min-beside-the-ranked-sum", key, mask, \
        env(v=v, d=v * (np.int64(1) << 40)), ext8, 128, ("s", 10, False)
    yield "ranked-by-the-row-count", key, mask, env(v=v, d=i8), \
        [_agg("n", "count")] + ext8, 128, ("n", 10, False)
    yield "ranked-by-a-filtered-count", key, mask, env(v=v, d=i8, f=f), \
        [_agg("fn", "count", filter_fn=_positive)] + ext8, 128, \
        ("fn", 10, True)
    yield "ranked-by-a-filtered-sum", key, mask, env(v=v, d=i8, f=f), \
        [_agg("fs", "sum", "v", filter_fn=_positive)] + ext8, 128, \
        ("fs", 10, False)


@pytest.mark.parametrize("case", list(_top_cases()),
                         ids=[c[0] for c in _top_cases()])
def test_kept_rows_equal_every_table_cut_after_the_ranking(case):
    """`top`'s [threshold] tables, `_keys`, `_rows` and `_nn_<name>` among
    them, equal to the whole tables indexed with `top_k`'s slots, rank for
    rank: the same ties, the SENTINEL key and the identities at a rank past
    the present groups, `_count` the table's own where the cap overflows."""
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import (SENTINEL, cap_tables,
                                                 sparse_group_reduce)
    EngineConfig().apply_x64()
    name, key, mask, env, plans, cap, top = case
    metric, threshold, inverted = top

    @jax.jit
    def run(key, mask, env):
        return (sparse_group_reduce(key, mask, env, plans, {},
                                    SparseProgram(cap, top)),
                _all_tables_then_cut(
                    sparse_group_reduce(key, mask, env, plans, {},
                                        SparseProgram(cap)),
                    *top))

    got, want = jax.device_get(run(key, mask, env))
    present = len(np.unique(key[mask]))
    assert int(got["_count"]) == int(want["_count"]) == present
    assert set(got) == set(want)
    kept = min(threshold, cap)
    for name, table in want.items():
        assert got[name].dtype == table.dtype, name
        assert got[name].shape == table.shape, name
        if name != "_count":
            assert len(table) == kept, name
        if table.dtype.kind == "f":
            np.testing.assert_allclose(got[name], table, rtol=1e-9,
                                       atol=1e-6, err_msg=name)
        else:
            np.testing.assert_array_equal(got[name], table, err_msg=name)
    # the ranks past the present groups hold the empty slot's values
    empty = np.arange(kept) >= min(present, cap)
    assert (got["_keys"][empty] == SENTINEL).all() \
        and (got["_keys"][~empty] != SENTINEL).all()
    assert (got["_rows"][empty] == 0).all()
    # and the ranking is the metric's, the smaller key first among equals
    m = got[metric][~empty].astype(np.int64) * (1 if inverted else -1)
    ranks = np.lexsort((got["_keys"][~empty], m))
    np.testing.assert_array_equal(ranks, np.arange(len(m)))
    stored = {c: a.dtype for c, a in env["cols"].items()}
    assert cap_tables(plans, stored, cap, top, set(env["nulls"])) \
        < cap_tables(plans, stored, cap, None, set(env["nulls"]))


def _cap_sized_gathers(lowered, cap):
    """How many gathers of the lowered program give a [cap] or [cap + 1]
    result: one a table read at every slot (a prefix is read at the
    cap + 1 run boundaries)."""
    import re
    sizes = [int(m.group(1)) for m in re.finditer(
        r'"?stablehlo\.gather"?\(.*->\s*tensor<(\d+)x', lowered.as_text())]
    assert sizes, "no gather in the lowered text: has its spelling changed?"
    return sum(1 for n in sizes if n in (cap, cap + 1))


def _lowered_sparse(plans, cap, top, dtype=np.int8, nulls=False):
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import sparse_group_reduce
    EngineConfig().apply_x64()
    n = 4096
    env = {"cols": {"v": jnp.arange(n, dtype=jnp.int64),
                    "u": jnp.arange(n, dtype=jnp.int64) % 7,
                    "w": jnp.arange(n, dtype=jnp.float64),
                    "d": jnp.arange(n).astype(dtype)},
           "nulls": {"d": jnp.arange(n) % 3 == 0} if nulls else {}}
    return jax.jit(lambda k, m, e: sparse_group_reduce(
        k, m, e, plans, {}, SparseProgram(cap, top))).lower(
        jnp.arange(n, dtype=jnp.int64) % 50, jnp.ones(n, bool), env), \
        {c: a.dtype for c, a in env["cols"].items()}, set(env["nulls"])


TOP_PLANS = [("s", "sum", "v"), ("su", "sum", "u"), ("lo", "min", "d"),
             ("hi", "max", "d")]


def test_a_top_program_gathers_one_cap_sized_table():
    """A sum, a second sum, a min and a max, ranked by the first sum: the
    lowered program holds exactly one gather whose result is cap-sized,
    the ranked sum's prefix at the run boundaries (one int64 gather here;
    the chip splits it in two u32 halves). Every other gather reads
    `threshold` slots, and `cap_tables` says the same."""
    from tpu_olap.kernels.sparse_groupby import cap_tables
    plans = [_agg(*a) for a in TOP_PLANS]
    cap, top = 64, ("s", 10, False)
    lowered, stored, nullable = _lowered_sparse(plans, cap, top)
    assert _cap_sized_gathers(lowered, cap) == 1 \
        == cap_tables(plans, stored, cap, top, nullable)
    assert '"stablehlo.scatter"(' not in lowered.as_text()
    # a float sum beside them keeps its segment reduce: a second table
    plans.append(_agg("fs", "sum", "w", np.float64))
    lowered, stored, nullable = _lowered_sparse(plans, cap, top)
    assert _cap_sized_gathers(lowered, cap) == 1
    assert lowered.as_text().count('"stablehlo.scatter"(') == 1
    assert cap_tables(plans, stored, cap, top, nullable) == 2
    # ranked by the row count: a difference of `starts`, no gather at all
    plans = [_agg("n", "count")] + plans[:4]
    lowered, stored, nullable = _lowered_sparse(plans, cap,
                                                ("n", 10, False))
    assert _cap_sized_gathers(lowered, cap) == 0 \
        == cap_tables(plans, stored, cap, ("n", 10, False), nullable)


@pytest.mark.parametrize("nulls", [False, True], ids=["no-nulls", "nulls"])
def test_a_program_without_top_gathers_one_cap_sized_table_a_table(nulls):
    """The same plan without `top` reads every table at every slot, as
    before PR 39: `_keys`, the two sums, the min and the max (and, where
    the column has nulls, a non-null count each): one cap-sized gather a
    table, the number `cap_tables` gives."""
    from tpu_olap.kernels.sparse_groupby import cap_tables
    plans = [_agg(*a) for a in TOP_PLANS] + [_agg("n", "count")]
    cap = 64
    lowered, stored, nullable = _lowered_sparse(plans, cap, None,
                                                nulls=nulls)
    assert _cap_sized_gathers(lowered, cap) == (7 if nulls else 5) \
        == cap_tables(plans, stored, cap, None, nullable)


@pytest.mark.parametrize("sql,form,tables", [
    # the TopN's threshold is in the program: the ranked sum's table alone
    ("SELECT a, sum(v) AS sv, count(*) AS n, max(v) AS xv FROM t "
     "GROUP BY a ORDER BY sv DESC LIMIT 5", "boundary", 1),
    # a float metric is ranked on the host: every table, as a group-by's
    ("SELECT a, sum(w) AS sw, sum(v) AS sv FROM t "
     "GROUP BY a ORDER BY sw DESC LIMIT 5", "scatter", 3),
    # _keys, sv, xv and its non-null count (v has nulls); n is _rows
    ("SELECT a, b, sum(v) AS sv, count(*) AS n, max(v) AS xv FROM t "
     "GROUP BY a, b", "boundary", 4),
], ids=["device-threshold-topn", "host-ranked-topn", "group-by"])
def test_record_and_dispatch_span_count_the_cap_tables(sql, form, tables):
    eng = _engine()
    check_query(eng, sql)
    rec = eng.history[-1]
    assert rec["reduce_path"] == "sparse" and rec["sparse"], rec
    assert rec["reduce_form"] == form
    assert rec["cap_tables"] == tables
    assert [s["attrs"].get("cap_tables")
            for s in _walk_spans(eng) if s["name"] == "dispatch"] == [tables]
    if "LIMIT" in sql:
        assert rec["query_type"] == "topN"
        assert ("topn_rows_fetched" in rec and rec["topn_rows_fetched"] == 5) \
            == (tables == 1)


# --------------------------------------------------------------------------
# An integer sum of a column stored in 32 bits or fewer rides the sort, its
# prefix sum and its boundary gather as ONE int32 word where the device shows
# that every group's sum fits one (PR 41): table for table equal to the wide
# program, which stays as the reference and as what a wider dataset runs.

def _over_ten(tables, consts):
    return tables["s"][0].astype(np.int64) > 10


def _narrow_cases():
    """(id, key, mask, env, plans, cap, top, having)"""
    rng = np.random.default_rng(41)
    n = 1531
    key = rng.integers(0, 120, n).astype(np.int64)
    key[key == 3] = 2          # a group no row has: the slots shift
    mask = rng.random(n) < 0.85
    f = rng.integers(-3, 4, n)
    wide = rng.integers(-(1 << 40), 1 << 40, n)

    def column(stored):
        lim = min(int(np.iinfo(stored).max), 1 << 24)
        x = rng.integers(-lim, lim + 1, n).astype(stored)
        x[:2] = [-lim, lim]
        return x

    def env(nulls=None, **cols):
        return {"cols": cols, "nulls": nulls or {}}

    s, count = _agg("s", "sum", "x"), _agg("n", "count")
    cuts = {"uncut": (None, None), "top": (("s", 10, False), None),
            "having": (None, (_over_ten, frozenset({"s"}), 64))}
    for stored in ("int8", "int16", "int32"):
        for cut, (top, having) in cuts.items():
            yield f"{stored}-{cut}", key, mask, env(x=column(stored)), \
                [s, count], 128, top, having
    x = column("int16")
    yield "every-value-negative", key, mask, env(x=-np.abs(x) - 1), \
        [s, count], 128, None, None
    yield "top-inverted", key, mask, env(x=x), [s, count], 128, \
        ("s", 10, True), None
    filtered = [_agg("s", "sum", "x", filter_fn=_positive), count,
                _agg("all", "sum", "x")]
    for cut, (top, having) in cuts.items():
        yield f"filtered-sum-{cut}", key, mask, env(x=x, f=f), filtered, \
            128, top, having
    for cut, (top, having) in cuts.items():
        yield f"nullable-column-{cut}", key, mask, \
            env({"x": rng.random(n) < 0.4}, x=x), [s, count], 128, top, \
            having
    # beside sums that stay wide (a column stored in 64 bits, as a virtual
    # column is; a double), a min / max of the same column, a second
    # narrow sum that is not ranked
    mixed = [s, _agg("sw", "sum", "v"), _agg("fs", "sum", "w", np.float64),
             _agg("lo", "min", "x"), _agg("hi", "max", "y"),
             _agg("sy", "sum", "y"), count]
    w = np.round(rng.random(n) * 50 - 25, 4)
    for cut, (top, having) in cuts.items():
        yield f"beside-wide-sums-and-min-max-{cut}", key, mask, \
            env(x=x, y=column("int8"), v=wide, w=w), mixed, 128, top, having
    yield "cap-overflows", key, mask, env(x=x), [s, count], 16, None, None
    yield "every-row-masked", key, np.zeros(n, bool), env(x=x), \
        [s, count], 16, ("s", 10, False), None


@pytest.mark.parametrize("case", list(_narrow_cases()),
                         ids=[c[0] for c in _narrow_cases()])
def test_narrow_sums_equal_the_wide_programs_tables(case):
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import sparse_group_reduce
    EngineConfig().apply_x64()
    _, key, mask, env, plans, cap, top, having = case

    @jax.jit
    def run(key, mask, env):
        return [_reduce(key, mask, env, plans, cap, top, having, narrow)
                for narrow in (True, False)]

    got, want = jax.device_get(run(key, mask, env))
    assert "_narrow_ok" not in want
    # at most 2^24 a row over a few dozen rows a group: the sums fit
    ok = got.pop("_narrow_ok")
    assert ok.dtype == bool and ok.shape == ()
    assert ok or want["_count"] > cap
    assert set(got) == set(want)
    for name, table in want.items():
        assert got[name].dtype == table.dtype, name
        if table.dtype.kind == "f":
            np.testing.assert_allclose(got[name], table, rtol=1e-9,
                                       atol=1e-6, err_msg=name)
        else:
            np.testing.assert_array_equal(got[name], table, err_msg=name)
    if top is None and having is None and want["_count"] <= cap:
        ref = _numpy_tables(key, mask, env, plans, cap)
        for p in plans:
            if p.kind == "sum" and np.dtype(p.acc_dtype).kind == "i":
                assert got[p.name].dtype == np.int64
                np.testing.assert_array_equal(got[p.name], ref[p.name])


I32 = np.iinfo(np.int32)


@pytest.mark.parametrize("values,rows_a_group,ok", [
    ([I32.max, 5, -7, 0], 1, True),          # a sum of exactly 2^31 - 1
    ([-I32.max, 5, -7, 0], 1, True),         # and of -2^31 + 1
    ([I32.min, 5, -7, 0], 1, False),         # |v| = 2^31: one past
    ([1 << 30, 1 << 30, 3, 4], 2, False),    # a group sums to 2^31
    ([-(1 << 30), -(1 << 30), -1, 4], 2, False),
    ([(1 << 30) - 1, (1 << 30) - 1, -3, 4], 2, True),   # 2^31 - 2
    ([1 << 29] * 6, 3, True),                # 3 * 2^29 < 2^31
    ([1 << 29] * 8, 4, False),               # 4 * 2^29 = 2^31
    ([1 << 6] * 8, 4, True),
], ids=["max", "minus-max", "min", "two-rows-2^31", "two-rows-minus-2^31",
        "two-rows-under", "three-rows-under", "four-rows-2^31", "small"])
def test_narrow_ok_holds_exactly_where_rows_times_the_largest_value_fit(
        values, rows_a_group, ok):
    """`_narrow_ok`: the longest run times the largest |value| is at most
    2^31 - 1. Where it says so the narrow tables are numpy's int64 sums;
    where it does not the wide program's are (and the narrow one's may
    have wrapped)."""
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import sparse_group_reduce
    EngineConfig().apply_x64()
    x = np.asarray(values, np.int64).astype(np.int32)
    # a masked row's value is not the column's: it rides as 0
    x = np.r_[x, I32.min].astype(np.int32)
    key = np.r_[np.arange(len(values)) // rows_a_group, 0].astype(np.int64)
    mask = np.r_[np.ones(len(values), bool), False]
    env = {"cols": {"x": x}, "nulls": {}}
    plans = [_agg("s", "sum", "x")]
    got = {narrow: jax.device_get(jax.jit(
        lambda k, m, e: sparse_group_reduce(
            k, m, e, plans, {}, SparseProgram(8, narrow=narrow)))(
        key, mask, env)) for narrow in (True, False)}
    assert bool(got[True]["_narrow_ok"]) == ok
    want = _numpy_tables(key, mask, env, plans, 8)["s"]
    np.testing.assert_array_equal(got[False]["s"], want)
    if ok:
        np.testing.assert_array_equal(got[True]["s"], want)


def _sort_operand_dtypes(fn, *args):
    """The operand dtypes of the widest sort anywhere in fn's jaxpr."""
    import jax

    def sorts(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                yield [str(v.aval.dtype) for v in eqn.invars]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sorts(sub)
    return max(sorts(jax.make_jaxpr(fn)(*args).jaxpr), key=len)


def _narrow_env(n=4096):
    import jax.numpy as jnp
    return {"cols": {"q": (jnp.arange(n) % 50).astype(jnp.int8),
                     "p": jnp.arange(n, dtype=jnp.int32),
                     "v": jnp.arange(n, dtype=jnp.int64),
                     "w": jnp.arange(n, dtype=jnp.float64)},
            "nulls": {}}, jnp.arange(n, dtype=jnp.int64) % 50, \
        jnp.ones(n, bool)


def _lowered_text(plans, narrow, top=None, having=None):
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import sparse_group_reduce
    EngineConfig().apply_x64()
    env, key, mask = _narrow_env()
    return jax.jit(lambda k, m, e: _reduce(
        k, m, e, plans, 64, top, having, narrow)).lower(
        key, mask, env).as_text()


@pytest.mark.parametrize("plans", [
    [("s", "sum", "v"), ("n", "count")],
    [("fs", "sum", "w", np.float64), ("n", "count")],
    [("lo", "min", "q"), ("hi", "max", "p"), ("n", "count")],
    [("n", "count")],
], ids=["int64-stored-sum", "float-sum", "min-max-alone", "count-alone"])
@pytest.mark.parametrize("cut", ["uncut", "top", "having"])
def test_a_plan_with_no_narrow_sum_lowers_to_the_same_text_either_way(
        plans, cut):
    """Asked for the narrow program, a plan none of whose sums is of a
    column stored in 32 bits or fewer (a virtual column is materialised
    in int64) gives the wide program's StableHLO byte for byte: no flag,
    no other operand."""
    from tpu_olap.kernels.sparse_groupby import narrow_sums, sum_word_bits
    plans = [_agg(*a) for a in plans]
    ranked = plans[0].name if plans[0].kind in ("sum", "count") \
        and np.dtype(plans[0].acc_dtype).kind == "i" else "n"
    top = (ranked, 10, False) if cut == "top" else None
    having = (lambda t, c: t["n"][0] > 3, frozenset({"n"}), 16) \
        if cut == "having" else None
    wide = _lowered_text(plans, False, top, having)
    assert _lowered_text(plans, True, top, having) == wide
    stored = {c: a.dtype for c, a in _narrow_env()[0]["cols"].items()}
    assert not narrow_sums(plans, stored)
    assert sum_word_bits(plans, stored, True) \
        == sum_word_bits(plans, stored, False) \
        == (64 if plans[0].name == "s" else None)


def test_a_narrow_sum_rides_the_sort_as_one_int32_operand():
    """`sum(q)` (int8) and `sum(p)` (int32) beside a sum of an int64
    column: asked for, the first two ride as int32 and their prefixes
    are read by ONE gather a boundary each; the third, and everything
    where it is not asked for, at the accumulator's 64 bits."""
    import jax.numpy as jnp

    from tpu_olap.kernels.sparse_groupby import (narrow_sums,
                                                 sparse_group_reduce,
                                                 sum_word_bits)
    EngineConfig().apply_x64()
    plans = [_agg("sq", "sum", "q"), _agg("sp", "sum", "p"),
             _agg("sv", "sum", "v")]
    env, key, mask = _narrow_env()
    stored = {c: a.dtype for c, a in env["cols"].items()}

    def program(narrow):
        return lambda k, m, e: sparse_group_reduce(
            k, m, e, plans, {}, SparseProgram(64, narrow=narrow))

    assert _sort_operand_dtypes(program(True), key, mask, env) \
        == ["int64", "int32", "int32", "int64"]
    assert _sort_operand_dtypes(program(False), key, mask, env) \
        == ["int64"] * 4
    assert narrow_sums(plans, stored) and narrow_sums(plans[:1], stored)
    assert not narrow_sums(plans[2:], stored)
    # the widest word a sum rides at
    assert sum_word_bits(plans, stored, True) == 64
    assert sum_word_bits(plans[:2], stored, True) == 32
    assert sum_word_bits(plans[:2], stored, False) == 64
    # an int32 accumulator (x64 off) has nothing to narrow
    assert not narrow_sums([_agg("sq", "sum", "q", np.int32)], stored)
    text = _lowered_text(plans[:2], True)
    assert "xi64>" in text      # the key, and the tables widened a slot
    assert text != _lowered_text(plans[:2], False)


def _narrow_engine(df, **cfg):
    eng = Engine(EngineConfig(dense_group_budget=64,
                              fallback_on_device_failure=False, **cfg))
    eng.register_table("t", df, time_column="ts", block_rows=512)
    return eng


def _narrow_df(p_top, n=3000, groups=400, seed=41):
    """`q` as TPC-H's quantity (1-50, stored as int8), `p` a price stored
    as int32 whose largest value is `p_top`, `k` the group."""
    rng = np.random.default_rng(seed)
    p = rng.integers(-1000, 1000, n)
    p[:3] = p_top
    k = rng.integers(3, groups, n)
    k[:3] = [0, 0, 1]
    return pd.DataFrame({
        "ts": pd.to_datetime("2022-01-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 100, n), unit="s"),
        "k": k.astype(np.int64), "q": rng.integers(1, 51, n),
        "p": p.astype(np.int64), "w": np.round(rng.random(n) * 50, 4)})


def _attempt_spans(eng):
    return [s["attrs"] for s in _walk_spans(eng)
            if s["name"] == "sparse-attempt"]


def _counter(eng, name):
    return sum(
        float(ln.rsplit(" ", 1)[1])
        for ln in eng.metrics.render().splitlines()
        if ln.startswith(f"tpu_olap_{name}"))


def _fallbacks(eng):
    return _counter(eng, "sparse_narrow_fallbacks_total")


NARROW_SQL = "SELECT k, sum(p) AS sp, sum(q) AS sq, count(*) AS n FROM t " \
             "GROUP BY k"


def test_a_plan_whose_sums_fit_runs_narrow_and_says_so():
    df = _narrow_df(1 << 20)
    eng = _narrow_engine(df)
    assert eng.explain(NARROW_SQL)["sum_word_bits"] == 32
    for _ in range(2):
        check_query(eng, NARROW_SQL)
        rec = eng.history[-1]
        assert rec["reduce_path"] == "sparse" and rec["sparse_attempts"] == 1
        assert rec["sum_word_bits"] == 32 and "narrow_fallback" not in rec
        assert [s["attrs"].get("sum_word_bits") for s in _walk_spans(eng)
                if s["name"] == "dispatch"] == [32]
        assert all("narrow_fallback" not in a for a in _attempt_spans(eng))
    assert rec["jit_cache_hit"] and _fallbacks(eng) == 0
    got = eng.sql(NARROW_SQL)
    want = df.groupby("k").agg(sp=("p", "sum"), sq=("q", "sum"))
    assert got["sp"].tolist() == want["sp"].tolist()
    assert str(got["sp"].dtype) == "int64"


@pytest.mark.parametrize("sql", [
    NARROW_SQL,
    "SELECT k, sum(p) AS sp, sum(q) AS sq FROM t GROUP BY k "
    "ORDER BY sq DESC LIMIT 7",
    "SELECT k, sum(p) AS sp, sum(q) AS sq FROM t GROUP BY k "
    "HAVING sum(p) > 1000000",
], ids=["group-by", "device-topn", "device-having"])
def test_a_group_whose_sum_passes_int32_falls_back_once_and_stays_wide(sql):
    """Three rows of 2^30 in two groups: a group sums to 2^31 and the
    narrow program's flag says so. The answer is numpy's int64 sum (the
    wide program of the same cap runs as one more attempt), the plan's
    next run starts wide, and the counter moved once."""
    df = _narrow_df(1 << 30)
    eng = _narrow_engine(df)
    assert eng.explain(sql)["sum_word_bits"] == 32
    check_query(eng, sql)
    first = eng.history[-1]
    assert first["reduce_path"] == "sparse" \
        and "fallback_reason" not in first
    assert first["sparse_attempts"] == 2 and first["narrow_fallback"] is True
    assert first["sum_word_bits"] == 64
    attempts = _attempt_spans(eng)
    assert [a.get("narrow_fallback") for a in attempts] == [True, None]
    assert attempts[0]["cap"] == attempts[1]["cap"]
    assert _fallbacks(eng) == 1
    assert eng.explain(sql)["sum_word_bits"] == 64
    check_query(eng, sql)
    second = eng.history[-1]
    assert second["sparse_attempts"] == 1 and second["jit_cache_hit"]
    assert second["sum_word_bits"] == 64 and "narrow_fallback" not in second
    assert all("narrow_fallback" not in a for a in _attempt_spans(eng))
    assert _fallbacks(eng) == 1
    got = eng.sql("SELECT k, sum(p) AS sp FROM t GROUP BY k")
    want = df.groupby("k")["p"].sum()
    assert got["sp"].tolist() == want.tolist() and want.max() == 1 << 31


@pytest.mark.parametrize("sql,bits", [
    ("SELECT k, count(*) AS n, min(q) AS lo FROM t GROUP BY k", None),
    ("SELECT k, sum(w) AS sw FROM t GROUP BY k", None),
    ("SELECT k, sum(p * (100 - q)) AS rev FROM t GROUP BY k", 64),
    ("SELECT k, sum(p * (100 - q)) AS rev, sum(q) AS sq FROM t GROUP BY k",
     64),
], ids=["no-sum", "float-sum", "virtual-int64-sum",
        "virtual-sum-beside-a-narrow-one"])
def test_sum_word_bits_is_said_where_a_plan_has_an_integer_sum(sql, bits):
    """TPC-H q3 / q10's shape, `sum(a * (1 - b))`, is a virtual column,
    materialised in int64: no narrow program, the parent's own. EXPLAIN
    and the record say the same, and nothing where no integer sum is."""
    eng = _narrow_engine(_narrow_df(1 << 20))
    assert eng.explain(sql).get("sum_word_bits") == bits
    check_query(eng, sql)
    rec = eng.history[-1]
    assert rec["reduce_path"] == "sparse" and rec["sparse_attempts"] == 1
    assert rec.get("sum_word_bits") == bits and "narrow_fallback" not in rec
    assert [s["attrs"].get("sum_word_bits") for s in _walk_spans(eng)
            if s["name"] == "dispatch"] == [bits]
    assert _fallbacks(eng) == 0
    dense = "SELECT sum(q) AS sq FROM t"
    assert "sum_word_bits" not in eng.explain(dense)
    eng.sql(dense)
    assert "sum_word_bits" not in eng.history[-1]


def test_a_meshs_sparse_programs_stay_wide():
    """The mesh's `shard_map` program is the parent's: its sums ride at
    the accumulator's width and it holds no flag, whatever the columns
    are stored at."""
    import jax

    from tpu_olap.executor import sharding as sh
    eng = _narrow_engine(_narrow_df(1 << 20), num_shards=8)
    assert eng.explain(NARROW_SQL)["sum_word_bits"] == 64
    check_query(eng, NARROW_SQL)
    rec = eng.history[-1]
    assert rec["sparse"] and rec["num_shards"] == 8
    assert rec["sum_word_bits"] == 64 and "narrow_fallback" not in rec
    plan = eng.planner.plan(NARROW_SQL)
    phys = eng.runner._lower_cached(plan.query, plan.entry.segments)
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    consts_dev, seg_arg = eng.runner._args_for(phys, seg_mask,
                                               eng.runner.mesh)
    args = (env, valid, seg_arg, consts_dev)
    program = sh.mesh_sparse_kernel(phys, eng.runner.mesh,
                                    SparseProgram(64))
    # the sums ride wide; the key's one word (a space of a few thousand
    # groups) rides as int32 on a mesh as on one chip (`key_word_dtypes`)
    assert _sort_operand_dtypes(program, *args) == ["int32"] + ["int64"] * 2
    assert "_narrow_ok" not in jax.eval_shape(program, *args)
    one_chip = phys.make_sparse_kernel(SparseProgram(64, narrow=True))
    assert "_narrow_ok" in jax.eval_shape(one_chip, *args)


def test_sparse_gspmd_spelling_parity(monkeypatch):
    """A mesh that spans processes hands the whole sparse program to
    GSPMD over global shapes (no fan-out, no broker merge): the boundary
    reads partition like the rest of it."""
    from tpu_olap.executor import sharding as sh
    monkeypatch.setattr(sh, "is_multihost", lambda mesh: True)
    eng = _engine(num_shards=8)
    check_query(eng, SQL)
    rec = eng.history[-1]
    assert rec["sparse"] and rec["num_shards"] == 8
    assert eng.runner.mesh_program == "gspmd"
    # SQL's min(w) is a double's, which no integer word holds; its max(v),
    # stored as int16 with nulls, is read from one beside it
    assert rec["reduce_form"] == "scatter"
    assert rec["ext_word_bits"] == (32 if rec["sparse_cap"] < 1 << 14
                                    else 64)


# --------------------------------------------------------------------------
# A whole [cap] table read at the sorted runs' boundaries rides `starts`'
# sort as one more operand where the cap is a large share of the rows sorted
# (PR 43): `sorted` against `gather`, table for table, and the rule between
# them, which two shapes decide.

# name -> (plans, the plan a `top` ranks by, the plan a `having` tests,
#          narrow): each holds the aggregate that is read whole and a row
# count; a min / max cannot rank, so a filtered count beside it does
READ_AGGS = {
    "narrow-sum": ([_agg("a", "sum", "i8"), _agg("n", "count")],
                   "a", "a", True),
    "wide-sum": ([_agg("a", "sum", "v"), _agg("n", "count")],
                 "a", "a", False),
    "filtered-count": ([_agg("a", "count", filter_fn=_positive),
                        _agg("s", "sum", "i8")], "a", "a", False),
    "word32-min": ([_agg("a", "min", "i8"), _agg("hi", "max", "i8"),
                    _agg("fc", "count", filter_fn=_positive)],
                   "fc", "a", False),
    "word64-max": ([_agg("a", "max", "i32", filter_fn=_positive),
                    _agg("lo", "min", "i32"),
                    _agg("fc", "count", filter_fn=_positive)],
                   "fc", "a", False),
}
# name -> (rows, cap, distinct keys, the share of rows the mask keeps)
READ_SHAPES = {
    "groups-past-the-cap": (300, 16, 40, 0.9),
    "rows-under-cap-plus-one": (300, 512, 40, 0.9),
    "every-row-masked": (300, 16, 9, 0.0),
    "a-lone-row-at-both-ends": (300, 64, 40, 1.0),
}


def _read_inputs(shape, key_words):
    n, cap, distinct, keep = READ_SHAPES[shape]
    rng = np.random.default_rng(43)
    key = rng.integers(1, distinct, n).astype(np.int64)
    mask = rng.random(n) < keep
    if keep == 1.0:
        # the smallest and the largest key a row each, and no masked
        # tail: sorted row 0 and sorted row n - 1 are runs of one row
        key[5], key[77] = 0, distinct
    env = {"cols": {
        "i8": rng.integers(-128, 128, n).astype(np.int8),
        "i32": rng.integers(-(1 << 31), 1 << 31, n).astype(np.int32),
        "v": rng.integers(-(1 << 40), 1 << 40, n),
        "f": rng.integers(-3, 4, n)}, "nulls": {}}
    if key_words == 2:
        # a run ends where either word changes
        key = (key // 7, key % 7)
    return key, mask, env, cap


@pytest.mark.parametrize("key_words", [1, 2], ids=["one-word", "two-words"])
@pytest.mark.parametrize("cut", ["uncut", "top", "having"])
@pytest.mark.parametrize("shape", sorted(READ_SHAPES))
@pytest.mark.parametrize("aggs", sorted(READ_AGGS))
def test_sorted_boundary_reads_equal_the_gathers(aggs, shape, cut,
                                                 key_words):
    """Every key of the output, the two spellings side by side: a narrow
    and a wide integer sum, a filtered count, a 32-bit and a 64-bit min /
    max word, each read whole (uncut, or tested by a HAVING) or beside the
    table a TopN ranks by; groups past the cap (the overflowing attempt's
    tables too), fewer rows than cap + 1, no unmasked row, a run of one
    row at the first and at the last sorted row; negative values
    throughout."""
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels import sparse_groupby as sg
    EngineConfig().apply_x64()
    plans, ranked, tested, narrow = READ_AGGS[aggs]
    key, mask, env, cap = _read_inputs(shape, key_words)
    top = (ranked, 10, False) if cut == "top" else None
    having = (lambda t, c: t[tested][0].astype(np.int64) > -5,
              frozenset({tested}), 32) if cut == "having" else None
    stored = {c: a.dtype for c, a in env["cols"].items()}
    # each reads a whole table at the boundaries: the rule has a say
    assert sg.boundary_read(plans, stored, cap, mask.shape[0], top, (),
                            having and having[1]) is not None
    n = mask.shape[0]

    def run(spelling):
        return jax.device_get(jax.jit(
            lambda k, m, e: _reduce(
                k, m, e, plans, cap, top, having, narrow, spelling))(
            key, mask, env))

    got, want = run("sorted"), run("gather")
    assert set(got) == set(want)
    assert (int(want["_count"]) > cap) == (shape == "groups-past-the-cap")
    for name, table in want.items():
        assert got[name].dtype == table.dtype, name
        np.testing.assert_array_equal(got[name], table, err_msg=name)
    if narrow:
        assert bool(want["_narrow_ok"])
    if shape == "a-lone-row-at-both-ends" and cut == "uncut":
        assert want["_rows"][0] == 1 \
            and want["_rows"][int(want["_count"]) - 1] == 1
        assert want["_rows"].sum() == n


def _position_sorts(fn, *args):
    """The operand counts of the sorts by an int32 key anywhere in fn's
    jaxpr after the main sort, the program's first (whose first key is an
    int64 word, or an int32 one where the key fits 31 bits): `starts`'
    (and a HAVING's compaction)."""
    import jax

    def sorts(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sorts(sub)

    later = list(sorts(jax.make_jaxpr(fn)(*args).jaxpr))[1:]
    for eqn in later:
        assert str(eqn.invars[0].aval.dtype) != "int32" \
            or eqn.params["num_keys"] == 1
    return sorted(len(eqn.invars) for eqn in later
                  if str(eqn.invars[0].aval.dtype) == "int32")


def test_the_rule_is_rows_a_slot_and_the_gather_side_keeps_its_program():
    """`sorted` up to 8 rows a slot of the compact table, `gather` one row
    past it; a q3-shaped plan (an int64 sum of a virtual column and the row
    count, no cut, one key word) on the `gather` side lowers to the text it
    has with no rule at all, with `starts` a one-operand sort: no edit
    moves the programs of the cells whose shapes keep them there (q3 / q10,
    the Druid TopNs, `q10p`) unseen. At the rule's edge the sum's int64
    prefix rides as a second operand and the program gathers no cap-sized
    table but `_keys`."""
    import jax
    import jax.numpy as jnp

    from tpu_olap.kernels import sparse_groupby as sg
    EngineConfig().apply_x64()
    assert sg.BOUNDARY_SORT_MAX_ROWS_PER_SLOT == 8
    cap = 63
    assert sg.boundary_spelling(8 * (cap + 1), cap) == "sorted"
    assert sg.boundary_spelling(8 * (cap + 1) + 1, cap) == "gather"
    assert sg.boundary_spelling(62_062_592, (1 << 24)) == "sorted"   # Q18
    assert sg.boundary_spelling(20_450_073, 2_000_001) == "gather"
    plans = [_agg("revenue", "sum", "v"), _agg("n", "count")]
    stored = {}   # a virtual column: materialised in 64 bits

    def program(n, ruled=True):
        env = {"cols": {"v": jnp.arange(n, dtype=jnp.int64)}, "nulls": {}}
        args = (jnp.arange(n, dtype=jnp.int64) % 50, jnp.ones(n, bool), env)
        read = sg.boundary_read(plans, stored, cap, n) if ruled else None

        def fn(k, m, e):
            return sg.sparse_group_reduce(
                k, m, e, plans, {}, sg.SparseProgram(cap, boundary=read))
        return read, _position_sorts(fn, *args), jax.jit(fn).lower(*args)

    read, sorts, lowered = program(8 * (cap + 1))
    assert (read, sorts) == ("sorted", [2])
    assert _cap_sized_gathers(lowered, cap) == 1    # `_keys`, AT `starts`
    read, sorts, lowered = program(8 * (cap + 1) + 1)
    assert (read, sorts) == ("gather", [1])
    assert _cap_sized_gathers(lowered, cap) == 2 \
        == sg.cap_tables(plans, stored, cap)
    assert program(8 * (cap + 1) + 1, ruled=False)[2].as_text() \
        == lowered.as_text()


@pytest.mark.parametrize("plans,top,having,nullable,reads", [
    ([("n", "count")], None, None, (), False),         # `_rows` alone
    ([("fs", "sum", "w", np.float64)], None, None, (), False),   # scatter
    ([("lo", "min", "v")], None, None, (), False),     # 64-bit: scatter
    ([("lo", "min", "v")], None, None, ("v",), True),  # its non-null count
    ([("lo", "min", "d")], None, None, (), True),      # a running word
    ([("s", "sum", "v"), ("n", "count")], None, None, (), True),
    # a cut reads whole what it is decided from, and nothing else
    ([("s", "sum", "v"), ("n", "count")], ("n", 5, False), None, (), False),
    ([("s", "sum", "v"), ("n", "count")], ("s", 5, False), None, (), True),
    ([("s", "sum", "v"), ("n", "count")], None, {"n"}, (), False),
    ([("s", "sum", "v"), ("lo", "min", "d")], None, {"lo"}, (), True),
], ids=["count", "float-sum", "int64-min", "nullable-int64-min", "int8-min",
        "sum", "top-by-rows", "top-by-sum", "having-rows", "having-min"])
def test_boundary_read_is_none_where_no_whole_table_is_read_there(
        plans, top, having, nullable, reads):
    from tpu_olap.kernels.sparse_groupby import boundary_read
    plans = [_agg(*a) for a in plans]
    stored = {"v": np.dtype(np.int64), "w": np.dtype(np.float64),
              "d": np.dtype(np.int8)}
    for n, spelling in ((100, "sorted"), (100_000, "gather")):
        assert boundary_read(plans, stored, 64, n, top, nullable,
                             having) == (spelling if reads else None)


@pytest.mark.parametrize("gspmd", [False, True],
                         ids=["a-chip-each", "gspmd"])
def test_a_meshs_record_says_how_its_programs_rows_and_cap_read(
        monkeypatch, gspmd):
    """On a mesh the rule sees what the program sees: a chip's share of
    the rows where the one-chip program is mapped over the chips, all of
    them where GSPMD partitions one program. The record's `boundary_read`
    is the kernel's own function of those rows and the cap, and the
    program the runner built and keeps carries a rider on its `starts`
    sort exactly where the record says `sorted`."""
    from tpu_olap.executor import sharding as sh
    from tpu_olap.kernels import sparse_groupby as sg
    if gspmd:
        monkeypatch.setattr(sh, "is_multihost", lambda mesh: True)
    eng = _engine(num_shards=8)
    check_query(eng, SQL)
    rec = eng.history[-1]
    assert rec["sparse"] and rec["num_shards"] == 8
    plan = eng.planner.plan(SQL)
    segments, block_rows = eng.runner._dataset(plan.entry.segments).shape
    rows = segments * block_rows // (1 if gspmd else 8)
    cap, read = rec["sparse_cap"], rec["boundary_read"]
    assert read == sg.boundary_spelling(rows, cap)
    phys = eng.runner._lower_cached(plan.query, plan.entry.segments)
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    consts_dev, seg_arg = eng.runner._args_for(phys, seg_mask,
                                               eng.runner.mesh)
    key = sparse_dispatch.sparse_key(phys, 8) \
        + ("gspmd" if gspmd else "mesh",
           sg.SparseProgram(cap, boundary=read))
    sorts = _position_sorts(eng.runner._jit_cache[key], env, valid, seg_arg,
                            consts_dev)
    assert (max(sorts) > 1) == (read == "sorted"), sorts
    if not gspmd:
        # and the other spelling of the same cap, said to the mapped
        # program as the runner says it
        other = "gather" if read == "sorted" else "sorted"
        sorts = _position_sorts(
            sh.mesh_sparse_kernel(phys, eng.runner.mesh,
                                  sg.SparseProgram(cap, boundary=other)),
            env, valid, seg_arg, consts_dev)
        assert (max(sorts) > 1) == (other == "sorted"), sorts


# --------------------------------------------------------------------------
# A key word whose ids fit 31 bits rides the sort as ONE int32 operand
# (PR 44): the width follows the domains' sizes where the program is built,
# and the tables are the int64 program's to the bit.

I32_TOP = (1 << 31) - 1


@pytest.mark.parametrize("sizes,bits", [
    ((I32_TOP - 1,), [32]),             # ids up to 2^31 - 3
    ((I32_TOP,), [32]),                 # up to 2^31 - 2: the sentinel free
    ((I32_TOP + 1,), [64]),             # an id would BE the sentinel
    ((1, 2_000_000), [32]),
    ((3, 715_827_882), [32]),           # a product of 2^31 - 2
    ((2, 1 << 30), [64]),               # of 2^31
    ((1 << 40, 1 << 22), [64]),
    ((), [32]),                         # no dimension: the one key 0
], ids=["2^31-2", "2^31-1", "2^31", "druid-partkey", "product-under",
        "product-at", "62-bits", "no-ids"])
def test_key_word_dtypes_of_a_one_word_key_is_the_exact_product(sizes, bits):
    from tpu_olap.kernels import sparse_groupby as sg
    words = sg.pack_key_words(sizes)
    assert len(words) == 1
    assert sg.key_sort_bits(sizes, words) == bits
    assert [8 * d.itemsize for d in sg.key_word_dtypes(sizes, words)] == bits


@pytest.mark.parametrize("sizes,words,bits", [
    # a later word holds no sentinel: 31 bits ride as int32, 32 do not
    ((1 << 40, 1 << 22, 1 << 31), ((0, 1), (2,)), [64, 32]),
    ((1 << 40, 1 << 22, 1 << 32), ((0, 1), (2,)), [64, 64]),
    ((1 << 40, 1 << 22, 1 << 20, 1 << 11), ((0, 1), (2, 3)), [64, 32]),
    ((1 << 40, 1 << 22, 1 << 20, 1 << 12), ((0, 1), (2, 3)), [64, 64]),
    # word 0 holds the sentinel: its largest value must stay under it,
    # by the exact sizes and not by the bits alone
    ((I32_TOP, 1 << 62), ((0,), (1,)), [32, 64]),
    ((I32_TOP + 1, 1 << 62), ((0,), (1,)), [64, 64]),
    ((1 << 30, 1 << 62), ((0,), (1,)), [32, 64]),
    ((1 << 15, 1 << 62, (1 << 16) - 1), ((0, 2), (1,)), [32, 64]),
    ((1 << 15, 1 << 62, 1 << 16), ((0, 2), (1,)), [64, 64]),
    # q10p's shape: 21 + 20 + 21 fill word 0, c_nation's 5 bits word 1
    ((1_499_999, 1_000_000, 1_099_999, 25), ((0, 1, 2), (3,)), [64, 32]),
], ids=["later-31", "later-32", "later-20+11", "later-20+12", "word0-top",
        "word0-sentinel", "word0-30", "word0-two-dims-under",
        "word0-two-dims-at", "q10p"])
def test_key_word_dtypes_of_a_wide_key_a_word(sizes, words, bits):
    from tpu_olap.kernels import sparse_groupby as sg
    assert sg.pack_key_words(sizes) == words
    assert sg.key_sort_bits(sizes, words) == bits


# cell -> template -> (the id domains of its group key at the published
# scale, the time bucket first; what its records' `key_sort_bits` say).
# Both SSB cells (`ssb-sf100-chip`, `ssb-sf4-mesh4`) run no sparse program.
CELL_KEYS = {
    "tpch-flat-sf10-chip": {
        "q3": ((1, 59_999_969, 2406, 1), [64]),
        "q10": ((1, 1_499_999, 1_000_000, 25), [64])},
    "tpch-flat-sf10-mesh4": {
        "q3": ((1, 59_999_969, 2406, 1), [64]),
        "q10": ((1, 1_499_999, 1_000_000, 25), [64])},
    "tpch-flat-sf10-having-chip": {
        "q18": ((1, 1_499_999, 59_999_969, 2406), [64])},
    "tpch-flat-sf10-widekey-chip": {
        "q10p": ((1, 1_499_999, 1_000_000, 1_099_999, 25), [64, 32]),
        "q18p": ((1, 1_000_000, 1_499_999, 59_999_969, 2406, 55_000_000),
                 [64, 64])},
    "druid-lineitem-sf100-chip": {
        "top_100_parts": ((1, 2_000_000), [32]),
        "top_100_parts_details": ((1, 2_000_000), [32]),
        "top_100_parts_filter": ((1, 2_000_000), [32])},
}


@pytest.mark.parametrize("cell,template", [
    (c, t) for c, ts in CELL_KEYS.items() for t in ts])
def test_key_sort_bits_of_the_benchmarks_sparse_templates(cell, template):
    """The Druid TopNs' 21-bit `l_partkey` and `q10p`'s second word are
    the accepted cells' narrow words; every other sparse template keeps
    the int64 program."""
    from tpu_olap.kernels import sparse_groupby as sg
    sizes, bits = CELL_KEYS[cell][template]
    # lowering packs the positions that carry an id: the bucket of
    # granularity "all" carries none
    words = tuple(tuple(i + 1 for i in w)
                  for w in sg.pack_key_words(sizes[1:]))
    assert sg.key_sort_bits(sizes, words) == bits
    assert len(words) == len(bits)


def test_build_group_key64_combines_a_narrow_word_in_int32():
    import jax.numpy as jnp

    from tpu_olap.kernels import sparse_groupby as sg
    EngineConfig().apply_x64()
    rng = np.random.default_rng(44)
    # one word under 2^31 - 1: int32; without `words`, int64 as ever
    sizes = (3, 715_827_882)
    ids = [rng.integers(0, s, 200).astype(np.int32) for s in sizes]
    ids[0][0], ids[1][0] = 2, sizes[1] - 1      # the largest key
    words = sg.pack_key_words(sizes)
    key, total = sg.build_group_key64([jnp.asarray(i) for i in ids],
                                      sizes, words)
    assert key.dtype == np.int32 and total == I32_TOP - 1
    wide, _ = sg.build_group_key64([jnp.asarray(i) for i in ids], sizes)
    assert wide.dtype == np.int64
    np.testing.assert_array_equal(np.asarray(key), np.asarray(wide))
    assert int(np.asarray(key)[0]) == I32_TOP - 2
    # q10p's shape: (int64, int32), each word the int64 combine's
    sizes = (1_499_999, 1_000_000, 1_099_999, 25)
    ids = [rng.integers(0, s, 200).astype(np.int32) for s in sizes]
    words = sg.pack_key_words(sizes)
    keys, _ = sg.build_group_key64([jnp.asarray(i) for i in ids], sizes,
                                   words)
    assert [str(k.dtype) for k in keys] == ["int64", "int32"]
    np.testing.assert_array_equal(np.asarray(keys[1]), ids[3])
    radix = sg.key_radix(sizes, words)
    want = (ids[0].astype(np.int64) * radix[1] + ids[1]) * radix[2] \
        + ids[2]
    np.testing.assert_array_equal(np.asarray(keys[0]), want)


def _narrow_keys(eng):
    return _counter(eng, "sparse_narrow_key_queries_total")


@pytest.mark.parametrize("sql", [
    NARROW_SQL,
    "SELECT k, sum(p) AS sp, min(q) AS lo FROM t GROUP BY k "
    "ORDER BY sp DESC LIMIT 7",
    "SELECT k, sum(q) AS sq, count(*) AS n FROM t GROUP BY k "
    "HAVING sum(q) > 150",
], ids=["group-by", "ordered-limit", "having"])
def test_record_span_explain_and_counter_say_key_sort_bits(sql):
    """A group space of 400: the record, the `dispatch` span and `explain`
    say `key_sort_bits: [32]` beside `key_words` / `key_bits`, the registry
    counts the dispatch, and the answer is pandas'."""
    eng = _narrow_engine(_narrow_df(1 << 20))
    said = eng.explain(sql)
    assert said["key_words"] == 1 and said["key_sort_bits"] == [32]
    before = _narrow_keys(eng)
    check_query(eng, sql)
    rec = eng.history[-1]
    assert rec["reduce_path"] == "sparse" and "fallback_reason" not in rec
    assert rec["key_sort_bits"] == [32] and rec["key_words"] == 1
    assert [s["attrs"].get("key_sort_bits") for s in _walk_spans(eng)
            if s["name"] == "dispatch"] == ["[32]"]   # a span's attribute
    # is a scalar or a string
    assert _narrow_keys(eng) == before + 1
    # a dense plan says nothing of a key
    dense = "SELECT sum(q) AS sq FROM t"
    assert "key_sort_bits" not in eng.explain(dense)
    eng.sql(dense)
    assert "key_sort_bits" not in eng.history[-1]
    assert _narrow_keys(eng) == before + 1
    eng.close()


def test_a_key_past_31_bits_keeps_the_int64_word_and_the_counter_rests():
    """`k` times a second dimension 2^20 wide: 2^29 x 400 groups do not
    fit 31 bits, so the key is the int64 word it was."""
    df = _narrow_df(1 << 20)
    df["j"] = np.where(np.arange(len(df)) % 2 == 0, 5, 5 + (1 << 23))
    eng = _narrow_engine(df)
    sql = "SELECT k, j, sum(q) AS sq FROM t GROUP BY k, j"
    assert eng.explain(sql)["key_sort_bits"] == [64]
    check_query(eng, sql)
    rec = eng.history[-1]
    assert rec["reduce_path"] == "sparse" and rec["key_sort_bits"] == [64]
    assert rec["key_bits"] > 31 and _narrow_keys(eng) == 0
    eng.close()


def test_a_mesh_of_four_merges_a_narrow_key_to_the_one_chip_answer():
    """The mesh's `shard_map` program takes the same rule (its chips sort
    an int32 key) and hands `merge_device` the int64 `_keys` it always
    did: four chips' answer is one chip's, row for row."""
    df = _narrow_df(1 << 20)
    one, four = _narrow_engine(df), _narrow_engine(df, num_shards=4)
    for sql in (NARROW_SQL,
                "SELECT k, sum(p) AS sp, min(q) AS lo, max(q) AS hi "
                "FROM t GROUP BY k ORDER BY sp DESC, k LIMIT 25"):
        check_query(four, sql)
        rec = four.history[-1]
        assert rec["sparse"] and rec["num_shards"] == 4
        assert rec["key_sort_bits"] == [32] \
            and "fallback_reason" not in rec
        pd.testing.assert_frame_equal(four.sql(sql), one.sql(sql))
        assert one.history[-1]["key_sort_bits"] == [32]
    assert _narrow_keys(four) >= 2
    one.close()
    four.close()
