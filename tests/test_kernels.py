"""Kernel golden tests vs numpy/pandas oracle (SURVEY.md §5 implication #2).

The filter, bucket and expression kernels run on numpy (host planning
calls them so) and on jax.numpy; the reduce kernels run as the program
the chip runs, under jax.jit and op by op. All against a pandas oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from tpu_olap.ir import (AndFilter, BoundFilter, CountAggregation,
                         ExpressionFilter, InFilter, LikeFilter, NotFilter,
                         OrFilter, RegexFilter, SelectorFilter,
                         SumAggregation, MinAggregation, MaxAggregation,
                         CardinalityAggregation, ThetaSketchAggregation,
                         FilteredAggregation, PeriodGranularity, parse_expr)
from tpu_olap.kernels import (ConstPool, compile_aggregations, compile_filter,
                              compile_granularity, group_reduce,
                              hll_estimate, top_k_groups)
from tpu_olap.kernels.groupby import build_group_key, merge_partials
from tpu_olap.kernels.theta import theta_estimate, theta_merge
from tpu_olap.kernels.timebucket import compile_time_format
from tpu_olap.segments import ingest_pandas, TIME_COLUMN
from tpu_olap.utils import timeutil as tu

jax.config.update("jax_enable_x64", True)


def make_table(n=4000, seed=7):
    rng = np.random.default_rng(seed)
    t0 = tu.date_to_millis(1993, 1, 1)
    df = pd.DataFrame({
        "ts": t0 + rng.integers(0, 365 * 86_400_000, n),
        "city": rng.choice(["amsterdam", "berlin", "chicago", "denver", None],
                           n, p=[0.3, 0.3, 0.2, 0.15, 0.05]),
        "qty": rng.integers(1, 50, n).astype(np.int64),
        "price": np.round(rng.uniform(0, 100, n), 2),
        "uid": rng.integers(0, 500, n).astype(np.int64),
    })
    ts = ingest_pandas("t", df, time_column="ts", block_rows=1 << 12)
    # ingest time-sorts rows; align the oracle frame the same way
    df = df.sort_values("ts", kind="stable").reset_index(drop=True)
    return df, ts


def flat_env(ts, xp):
    s = ts.segments[0]
    conv = (lambda a: a) if xp is np else jnp.asarray
    return {
        "cols": {c: conv(v) for c, v in s.columns.items()},
        "nulls": {c: conv(v) for c, v in s.null_masks.items()},
    }, conv(np.arange(s.block_rows) < s.meta.n_valid)


DF, TS = make_table()

# the reduce kernels have one implementation, jax.numpy: a case is how
# the program is run, not which array module it is handed
HOW = pytest.mark.parametrize("how", ["jit", "eager"])


def device_consts(pool):
    return {k: jnp.asarray(v) for k, v in pool.consts.items()}


def reduce_on_device(how, key, mask, env, plans, total, consts):
    def fn(key, mask, env, consts):
        return group_reduce(key, mask, env, plans, total, consts)
    return (jax.jit(fn) if how == "jit" else fn)(key, mask, env, consts)


def run_filter(spec, xp):
    pool = ConstPool()
    fn = compile_filter(spec, TS, pool,
                        virtual_exprs={"rev": parse_expr("qty * price")})
    env, valid = flat_env(TS, xp)
    consts = pool.consts if xp is np else {k: jnp.asarray(v)
                                           for k, v in pool.consts.items()}
    mask = fn(env, consts) & valid
    return np.asarray(mask)[:TS.segments[0].meta.n_valid]


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
class TestFilters:
    def test_selector(self, xp):
        got = run_filter(SelectorFilter("city", "berlin"), xp)
        assert (got == (DF.city == "berlin").to_numpy()).all()

    def test_selector_null(self, xp):
        got = run_filter(SelectorFilter("city", None), xp)
        assert (got == DF.city.isna().to_numpy()).all()

    def test_selector_numeric(self, xp):
        got = run_filter(SelectorFilter("qty", 7), xp)
        assert (got == (DF.qty == 7).to_numpy()).all()

    def test_bound_numeric(self, xp):
        got = run_filter(
            BoundFilter("price", lower=20, upper=60, upper_strict=True,
                        ordering="numeric"), xp)
        assert (got == ((DF.price >= 20) & (DF.price < 60)).to_numpy()).all()

    def test_bound_lexicographic(self, xp):
        got = run_filter(BoundFilter("city", lower="b", upper="chicago"), xp)
        want = ((DF.city >= "b") & (DF.city <= "chicago")).fillna(False)
        assert (got == want.to_numpy()).all()

    def test_in_string(self, xp):
        got = run_filter(InFilter("city", ("denver", "berlin")), xp)
        assert (got == DF.city.isin(["denver", "berlin"]).to_numpy()).all()

    def test_in_numeric(self, xp):
        got = run_filter(InFilter("qty", (1, 5, 7)), xp)
        assert (got == DF.qty.isin([1, 5, 7]).to_numpy()).all()

    def test_regex_like(self, xp):
        got = run_filter(RegexFilter("city", "^.e"), xp)
        want = DF.city.str.match(".e").fillna(False)
        assert (got == want.to_numpy()).all()
        got = run_filter(LikeFilter("city", "%er%"), xp)
        want = DF.city.str.contains("er").fillna(False)
        assert (got == want.to_numpy()).all()

    def test_logical(self, xp):
        spec = OrFilter((
            AndFilter((SelectorFilter("city", "berlin"),
                       BoundFilter("qty", lower=25, ordering="numeric"))),
            NotFilter(BoundFilter("price", lower=1, ordering="numeric")),
        ))
        got = run_filter(spec, xp)
        want = (((DF.city == "berlin") & (DF.qty >= 25))
                | ~(DF.price >= 1)).to_numpy()
        assert (got == want).all()

    def test_expression_virtual(self, xp):
        got = run_filter(ExpressionFilter(parse_expr("rev > 2000")), xp)
        want = (DF.qty * DF.price > 2000).to_numpy()
        assert (got == want).all()


@HOW
def test_group_reduce_matches_pandas(how):
    pool = ConstPool()
    aggs = (
        CountAggregation("cnt"),
        SumAggregation("q_sum", "qty", "long"),
        SumAggregation("p_sum", "price", "double"),
        MinAggregation("p_min", "price", "double"),
        MaxAggregation("q_max", "qty", "long"),
        FilteredAggregation(SelectorFilter("city", "berlin"),
                            SumAggregation("b_sum", "qty", "long")),
    )
    plans = compile_aggregations(aggs, TS, pool)
    env, valid = flat_env(TS, jnp)
    consts = device_consts(pool)
    codes = env["cols"]["city"]
    K = TS.dictionaries["city"].size + 1
    key, total = build_group_key([codes], [K], jnp)
    out = reduce_on_device(how, key, valid, env, plans, total, consts)
    out = {k: np.asarray(v) for k, v in out.items()}

    g = DF.assign(city=DF.city.fillna("\0null")).groupby("city")
    for city, sub in g:
        cid = 0 if city == "\0null" else TS.dictionaries["city"].id_of(city)
        assert out["_rows"][cid] == len(sub)
        assert out["cnt"][cid] == len(sub)
        assert out["q_sum"][cid] == sub.qty.sum()
        assert np.isclose(out["p_sum"][cid], sub.price.sum())
        assert np.isclose(out["p_min"][cid], sub.price.min())
        assert out["q_max"][cid] == sub.qty.max()
        want_b = sub.qty[sub.city == "berlin"].sum()
        assert out["b_sum"][cid] == want_b


@HOW
def test_group_reduce_merge_partials_equals_whole(how):
    """Partials of two halves merge to the whole's: fetched and merged
    on the host as the broker does ("jit"), or on device arrays."""
    pool = ConstPool()
    plans = compile_aggregations(
        (SumAggregation("s", "qty", "long"), CountAggregation("c"),
         MinAggregation("m", "price", "double")), TS, pool)
    env, valid = flat_env(TS, jnp)
    consts = device_consts(pool)
    codes = env["cols"]["city"]
    K = TS.dictionaries["city"].size + 1
    key, total = build_group_key([codes], [K], jnp)
    n = TS.segments[0].meta.n_valid
    half = jnp.asarray(np.arange(TS.segments[0].block_rows) < n // 2)
    m1 = valid & half
    m2 = valid & ~half
    p1 = reduce_on_device(how, key, m1, env, plans, total, consts)
    p2 = reduce_on_device(how, key, m2, env, plans, total, consts)
    whole = reduce_on_device(how, key, valid, env, plans, total, consts)
    if how == "jit":
        p1, p2 = jax.device_get((p1, p2))
    merged = merge_partials(p1, p2, plans)
    for k in whole:
        assert np.allclose(np.asarray(merged[k]), np.asarray(whole[k])), k


@HOW
def test_hll_cardinality(how):
    pool = ConstPool()
    plans = compile_aggregations(
        (CardinalityAggregation("u", ("uid",)),), TS, pool)
    env, valid = flat_env(TS, jnp)
    consts = device_consts(pool)
    key, total = build_group_key([env["cols"]["city"]],
                                 [TS.dictionaries["city"].size + 1], jnp)
    out = reduce_on_device(how, key, valid, env, plans, total, consts)
    est = hll_estimate(np.asarray(out["u"]))
    # "\0null", not a bare "\0": modern pandas drops a lone NUL in
    # fillna (the sentinel came back '' and indexed cid -1)
    truth = DF.assign(
        city=DF.city.fillna("\0null")).groupby("city").uid.nunique()
    for city, want in truth.items():
        cid = 0 if city == "\0null" \
            else TS.dictionaries["city"].id_of(city)
        assert abs(est[cid] - want) / max(want, 1) < 0.12, (city, est[cid], want)


@HOW
def test_theta_exact_when_small(how):
    pool = ConstPool()
    plans = compile_aggregations(
        (ThetaSketchAggregation("t", "uid", 1024),), TS, pool)
    env, valid = flat_env(TS, jnp)
    consts = device_consts(pool)
    key, total = build_group_key([env["cols"]["city"]],
                                 [TS.dictionaries["city"].size + 1], jnp)
    out = reduce_on_device(how, key, valid, env, plans, total, consts)
    est = theta_estimate(np.asarray(out["t"]))
    truth = DF.assign(
        city=DF.city.fillna("\0null")).groupby("city").uid.nunique()
    for city, want in truth.items():
        cid = 0 if city == "\0null" \
            else TS.dictionaries["city"].id_of(city)
        # distinct counts < k=1024, so exact
        assert est[cid] == want, (city, est[cid], want)


def test_theta_merge_matches_union():
    rng = np.random.default_rng(3)
    from tpu_olap.kernels.hashing import hash32_int
    from tpu_olap.kernels.theta import theta_update
    a_vals = rng.integers(0, 300, 2000).astype(np.int32)
    b_vals = rng.integers(200, 600, 2000).astype(np.int32)
    key = jnp.zeros(2000, jnp.int32)
    valid = jnp.ones(2000, bool)
    k = 256
    # the update is the device's; the merge is the host broker's
    ta, tb = (np.asarray(theta_update(hash32_int(jnp.asarray(v), jnp),
                                      valid, key, 1, k))
              for v in (a_vals, b_vals))
    merged = theta_merge(ta, tb, np)
    est = theta_estimate(merged)[0]
    truth = len(set(a_vals.tolist()) | set(b_vals.tolist()))
    assert abs(est - truth) / truth < 0.15, (est, truth)


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_granularity_buckets(xp):
    pool = ConstPool()
    t0, t1 = TS.time_boundary
    plan = compile_granularity(PeriodGranularity("P1M"), t0, t1, pool)
    assert plan.n_buckets == 12
    env, valid = flat_env(TS, xp)
    consts = pool.consts if xp is np else {k: jnp.asarray(v)
                                           for k, v in pool.consts.items()}
    ids = np.asarray(plan.ids(env["cols"][TIME_COLUMN], consts))
    n = TS.segments[0].meta.n_valid
    want = pd.to_datetime(DF.ts.to_numpy(), unit="ms").month - 1
    assert (ids[:n] == want.to_numpy()).all()


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
def test_time_format_extraction(xp):
    pool = ConstPool()
    t0, t1 = TS.time_boundary
    plan, remap_name, values = compile_time_format("YYYY", "UTC", t0, t1, pool)
    assert values == ["1993"]
    plan2, remap2, values2 = compile_time_format("%m", "UTC", t0, t1, pool)
    assert len(values2) == 12
    env, _ = flat_env(TS, xp)
    consts = pool.consts if xp is np else {k: jnp.asarray(v)
                                           for k, v in pool.consts.items()}
    fine = np.asarray(plan2.ids(env["cols"][TIME_COLUMN], consts))
    group = np.asarray(consts[remap2])[fine]
    n = TS.segments[0].meta.n_valid
    months = pd.to_datetime(DF.ts.to_numpy(), unit="ms").month
    want = [values2.index(f"{m:02d}") for m in months]
    assert (group[:n] == np.asarray(want)).all()


@HOW
def test_top_k(how):
    m = jnp.asarray([5.0, 1.0, 9.0, 7.0, 3.0])
    p = jnp.asarray([True, True, True, False, True])

    def top(threshold, inverted):
        def fn(m, p):
            return top_k_groups(m, p, threshold, inverted)
        return (jax.jit(fn) if how == "jit" else fn)(m, p)
    idx, valid = top(3, False)
    assert np.asarray(idx).tolist() == [2, 0, 4]
    idx, valid = top(3, True)
    assert np.asarray(idx).tolist() == [1, 4, 0]
    idx, valid = top(5, False)
    assert np.asarray(valid).sum() == 4  # absent group never 'valid'


def test_jitted_group_reduce_compiles_once():
    pool = ConstPool()
    plans = compile_aggregations((SumAggregation("s", "qty", "long"),), TS,
                                 pool)
    env, valid = flat_env(TS, jnp)
    consts = {k: jnp.asarray(v) for k, v in pool.consts.items()}

    calls = {"n": 0}

    def f(env, valid, consts):
        calls["n"] += 1
        key, total = build_group_key([env["cols"]["city"]],
                                     [TS.dictionaries["city"].size + 1], jnp)
        return group_reduce(key, valid, env, plans, total, consts)

    jf = jax.jit(f)
    out1 = jf(env, valid, consts)
    # second call with different consts: no retrace
    consts2 = dict(consts)
    out2 = jf(env, valid, consts2)
    assert calls["n"] == 1
    assert np.allclose(np.asarray(out1["s"]), np.asarray(out2["s"]))


# --- the compare form of the dense reduce (kernels/groupby.py) -----------

def _compare_case(k, n=3000, seed=11, all_masked=False):
    """(key, mask, env, plans) over `k` slots of which the last one no row
    hits (for k > 1), with an int64 column past 2^31 a row, a float
    column, a nullable column and a per-aggregate filter."""
    from tpu_olap.kernels.groupby import AggPlan
    rng = np.random.default_rng(seed)
    key = rng.integers(0, max(1, k - 1), n).astype(np.int32)
    mask = np.zeros(n, bool) if all_masked else rng.random(n) < 0.8
    env = {"cols": {"big": rng.integers(1 << 33, 1 << 40, n),
                    "f": rng.normal(0, 1e3, n),
                    "nul": rng.integers(-50, 50, n),
                    "flag": rng.integers(0, 3, n).astype(np.int32)},
           "nulls": {"nul": rng.random(n) < 0.3}}

    def flag_is_1(e, consts):
        return e["cols"]["flag"] == 1

    plans = [AggPlan("cnt", "count", (), np.int64),
             AggPlan("fcnt", "count", (), np.int64, filter_fn=flag_is_1),
             AggPlan("s_big", "sum", ("big",), np.int64),
             AggPlan("s_f", "sum", ("f",), np.float64),
             AggPlan("s_nul", "sum", ("nul",), np.int64),
             AggPlan("fs_big", "sum", ("big",), np.int64,
                     filter_fn=flag_is_1),
             AggPlan("mn_big", "min", ("big",), np.int64),
             AggPlan("mx_nul", "max", ("nul",), np.int64),
             AggPlan("mn_f", "min", ("f",), np.float64),
             AggPlan("mx_f", "max", ("f",), np.float64,
                     filter_fn=flag_is_1)]
    return key, mask, env, plans


def _bound():
    from tpu_olap.kernels.groupby import COMPARE_MAX_GROUPS
    return COMPARE_MAX_GROUPS


def _slots(k):
    """A case's K: a number, or a place relative to the bound."""
    return {"bound": _bound(), "bound+1": _bound() + 1}.get(k, k)


_COMPARE_NAMES = ("_rows", "cnt", "fcnt", "s_big", "s_f", "s_nul", "fs_big",
                  "mn_big", "mx_nul", "mn_f", "mx_f", "_nn_s_nul",
                  "_nn_fs_big", "_nn_mx_f")


@pytest.mark.parametrize("k,all_masked,block_rows", [
    (2, False, None), ("bound", False, None), ("bound+1", False, None),
    (12, True, None), (12, False, 256)],
    ids=["k2", "k-bound", "k-bound+1", "k12-all-masked", "k12-row-blocks"])
def test_compare_form_equals_the_numpy_reduce(monkeypatch, k, all_masked,
                                              block_rows):
    """The device side of group_reduce against its numpy side, every table
    of it: bit-equal for every integer aggregate whatever the order of the
    adds (a row's value passes 2^31; a slot no row hits holds the
    identity; all rows masked), float sums to the tolerance the scatter
    is held to. K at the bound is the compare form (there a loop over row
    blocks with a tail, as in the last case, whose block is 256 rows),
    one past it the scatter: all are the same tables."""
    from tpu_olap.kernels import groupby
    k = _slots(k)
    if block_rows:
        monkeypatch.setattr(groupby, "_CMP_BLOCK_BYTES", k * 8 * block_rows)
    key, mask, env, plans = _compare_case(k, all_masked=all_masked)
    want = group_reduce(key, mask, env, plans, k, {})
    jenv = {g: {c: jnp.asarray(a) for c, a in env[g].items()} for g in env}
    got = jax.jit(lambda key, mask, env: group_reduce(
        key, mask, env, plans, k, {}))(jnp.asarray(key), jnp.asarray(mask),
                                       jenv)
    assert groupby.reduce_form(k) == \
        ("compare" if k <= _bound() else "scatter")
    assert sorted(got) == sorted(want) and set(_COMPARE_NAMES) <= set(want)
    for name in want:
        g, w = np.asarray(got[name]), want[name]
        assert g.shape == w.shape == (k,) and g.dtype == w.dtype, name
        if w.dtype.kind == "f":
            assert np.allclose(g, w, rtol=1e-9, atol=1e-6), name
        else:
            assert np.array_equal(g, w), name
    if not all_masked:   # a sum past int32; the slot no row hits
        assert want["s_big"].max() > (1 << 40) and want["s_big"][-1] == 0


@pytest.mark.parametrize("k,kinds,form", [
    (2, ("sum", "count"), "compare"),
    ("bound", ("min", "max", "sum"), "compare"),
    ("bound+1", ("sum",), "scatter"),
    (2, ("count", "hll"), "scatter"),
    (12, ("theta",), "scatter"),
    (12, (), "compare"),
])
def test_reduce_form_is_a_function_of_groups_and_kinds(k, kinds, form):
    from tpu_olap.kernels.groupby import reduce_form
    k = _slots(k)
    assert reduce_form(k, kinds) == form


@pytest.mark.parametrize("k,want_scatter", [
    (12, False), ("bound", False), ("bound+1", True)],
    ids=["k12", "k-bound", "k-bound+1"])
def test_compare_form_lowers_without_a_scatter(k, want_scatter):
    """What the jitted reduce IS, on any backend: no scatter op in the
    lowered module at K up to the bound, XLA's scatter past it."""
    k = _slots(k)
    key, mask, env, plans = _compare_case(k)
    text = jax.jit(lambda key, mask, env: group_reduce(
        key, mask, env, plans, k, {})).lower(key, mask, env).as_text()
    assert ("scatter" in text) == want_scatter
