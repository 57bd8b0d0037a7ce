"""The sparse key as more than one int64 word (PR 42).

A GroupBy whose group space is 2^62 or more packs its dimensions' ids into
several int64 words (`sparse_groupby.pack_key_words`), the sort compares
the words in turn, a run ends where any word changes and `_keys` is a table
a word. Here: the kernel with two and three words against a numpy group-by
over seeded ids (plain, with a HAVING's cut, narrow, with a min / max word,
an overflowing cap, masked rows, runs that differ in the last word alone),
the packing itself, an `Engine` over synthetic tables of four and six
numeric dimensions 2^20 wide against pandas, what a one-word plan still
lowers to, what a mesh and a cube still refuse, and what `explain`, the
record, the spans and the counter say.
"""

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.executor import EngineConfig
from tpu_olap.kernels import sparse_groupby as sg
from tpu_olap.kernels.groupby import AggPlan, _ident


def _reduce(key, mask, env, plans, cap, top=None, having=None, narrow=False,
            boundary=None):
    """`sparse_group_reduce`'s tables of the program a case's cut and
    spellings name; `having`: (test, names, kept)."""
    return sg.sparse_group_reduce(
        key, mask, env, plans, {},
        sg.SparseProgram(cap, top, having and having[2], narrow, boundary),
        having and having[:2])


def _agg(name, kind, field=None, acc=np.int64, filter_fn=None):
    return AggPlan(name, kind, (field,) if field else (), acc, filter_fn)


# ------------------------------------------------------------ the packing

def test_one_word_under_2_62_in_group_by_order():
    sizes = (1, 60_000_001, 2407, 2)
    assert sg.pack_key_words(sizes) == ((0, 1, 2, 3),)
    assert sg.key_radix(sizes, sg.pack_key_words(sizes)) == sizes
    assert sg.key_names(1) == ("_keys",)


def test_q18s_five_columns_pack_into_two_words_by_width():
    # c_name, o_custkey, l_orderkey, o_orderdate, o_totalprice at SF10
    sizes = (1_000_001, 1_500_000, 59_986_000, 2407, 55_000_000)
    words = sg.pack_key_words(sizes)
    assert len(words) == 2 and sorted(sum(words, ())) == [0, 1, 2, 3, 4]
    assert words[0][0] == 0          # the word that holds position 0 first
    radix = sg.key_radix(sizes, words)
    assert radix == (1 << 20, 1 << 21, 1 << 26, 1 << 12, 1 << 26)
    for w in words:
        assert sum(sg.dim_bits(sizes[i]) for i in w) <= sg.KEY_WORD_BITS
    assert sg.key_bits(sizes) == 105
    # next-fit in GROUP BY order would take three
    assert 20 + 21 + 26 > 62 and 26 + 12 + 26 > 62
    assert sg.key_names(2) == ("_keys", "_keys1")


@pytest.mark.parametrize("n_dims, n_words", [(3, 2), (6, 3), (9, 5)])
def test_words_grow_with_the_space(n_dims, n_words):
    sizes = ((1 << 30) + 1,) * n_dims       # 31 bits a dimension
    words = sg.pack_key_words(sizes)
    assert len(words) == n_words
    assert sorted(sum(words, ())) == list(range(n_dims))


def test_build_group_key64_words_round_trip():
    import jax.numpy as jnp
    EngineConfig().apply_x64()
    sizes = (1_000_001, 1_500_000, 59_986_000, 2407, 55_000_000)
    rng = np.random.default_rng(7)
    ids = [rng.integers(0, s, 500).astype(np.int32) for s in sizes]
    words = sg.pack_key_words(sizes)
    keys, total = sg.build_group_key64([jnp.asarray(i) for i in ids], sizes,
                                       words)
    keys = [np.asarray(k) for k in keys]
    assert total == int(np.prod([float(s) for s in sizes])) or total > 1 << 62
    radix = sg.key_radix(sizes, words)
    for key, positions in zip(keys, words):
        assert key.dtype == np.int64 and (key >= 0).all() \
            and (key < 1 << 62).all()
        for i in positions[::-1]:
            np.testing.assert_array_equal(key % radix[i], ids[i])
            key = key // radix[i]
    # one word: the caller's guard still stands
    with pytest.raises(Exception, match="overflows the int64 key"):
        sg.build_group_key64(ids, sizes)


# ------------------------------------------------- the kernel against numpy

def _numpy_tables(words, mask, env, plans, cap):
    """The compact tables by definition: slot i is the i-th smallest
    present key in word order; one boolean selection a group."""
    stacked = np.stack(words)
    present = np.unique(stacked[:, mask], axis=1) if mask.any() \
        else stacked[:, :0]
    n = present.shape[1]
    out = {"_count": np.int32(n), "_rows": np.zeros(cap, np.int32)}
    for w, name in enumerate(sg.key_names(len(words))):
        out[name] = np.full(cap, sg.SENTINEL if w == 0 else 0, np.int64)
    for p in plans:
        acc = np.dtype(p.acc_dtype)
        out[p.name] = np.full(cap, _ident(acc, p.kind)
                              if p.kind in ("min", "max") else 0, acc)
        if p.kind in ("min", "max"):
            out[f"_nn_{p.name}"] = np.zeros(cap, np.int32)
    for i in range(min(n, cap)):
        sel = mask & (stacked == present[:, i:i + 1]).all(axis=0)
        for w, name in enumerate(sg.key_names(len(words))):
            out[name][i] = present[w, i]
        out["_rows"][i] = sel.sum()
        for p in plans:
            m = sel if p.filter_fn is None else sel & np.asarray(
                p.filter_fn(env, {}))
            if p.kind == "count":
                out[p.name][i] = m.sum()
                continue
            x = env["cols"][p.fields[0]][m].astype(p.acc_dtype)
            if p.kind == "sum":
                out[p.name][i] = x.sum()
            else:
                out[f"_nn_{p.name}"][i] = len(x)
                if len(x):
                    out[p.name][i] = x.min() if p.kind == "min" else x.max()
    return out


def _positive(env, consts):
    return env["cols"]["f"] > 0


def _over(limit):
    def test(tables, consts):
        return tables["s"][0].astype(np.int64) > limit
    return test


def _cases():
    """(id, words, mask, env, plans, cap, having, narrow)"""
    rng = np.random.default_rng(42)
    n = 1777
    mask = rng.random(n) < 0.85

    def words(k, distinct=40):
        # every word near the top of its 62 bits: a comparison that read
        # the low half alone, or one word alone, would merge groups
        base = [rng.integers(1 << 60, 1 << 62, distinct) for _ in range(k)]
        pick = rng.integers(0, distinct, n)
        ws = [b[pick] for b in base]
        # groups that share every word but one
        ws[0][pick % 5 == 0] = base[0][0]
        if k > 2:
            ws[1][pick % 3 == 0] = base[1][0]
        return [w.astype(np.int64) for w in ws]

    def env(**cols):
        return {"cols": cols, "nulls": {}}

    x8 = rng.integers(-100, 101, n).astype(np.int8)
    x32 = rng.integers(-(1 << 24), 1 << 24, n).astype(np.int32)
    x64 = rng.integers(-(1 << 40), 1 << 40, n)
    f = rng.integers(-3, 4, n)
    s, count = _agg("s", "sum", "x"), _agg("n", "count")
    for k in (2, 3):
        w = words(k)
        yield f"{k}-words-plain", w, mask, env(x=x64), [s, count], 128, \
            None, False
        yield f"{k}-words-having", w, mask, env(x=x32), [s, count], 128, \
            (_over(0), frozenset({"s"}), 64), False
        yield f"{k}-words-narrow", w, mask, env(x=x32), [s, count], 128, \
            None, True
        yield f"{k}-words-narrow-having", w, mask, env(x=x8), [s, count], \
            128, (_over(10), frozenset({"s"}), 64), True
        yield f"{k}-words-min-max-word", w, mask, env(x=x8, y=x32, f=f), \
            [_agg("lo", "min", "x"), _agg("hi", "max", "y"),
             _agg("pos", "sum", "x", filter_fn=_positive), count], 128, \
            None, False
        yield f"{k}-words-cap-overflows", w, mask, env(x=x64), [s, count], \
            16, None, False
        yield f"{k}-words-every-row-masked", w, np.zeros(n, bool), \
            env(x=x64), [s, count], 16, None, False
        # runs that differ in the LAST word alone: every other word is one
        # value, so a boundary test that skipped a word would see one run
        last = [np.full(n, (1 << 61) + 5, np.int64) for _ in range(k - 1)] \
            + [rng.integers(0, 50, n).astype(np.int64)]
        yield f"{k}-words-differ-in-the-last-word", last, mask, \
            env(x=x64), [s, count], 64, None, False
        # a masked row's other words are not masked: where they differ
        # from their unmasked neighbours' they must start no group
        yield f"{k}-words-masked-rows-with-their-own-words", \
            [w[0]] + [rng.integers(0, 1 << 61, n) for _ in range(k - 1)], \
            np.arange(n) % 7 == 0, env(x=x64), [s, count], 512, None, False


@pytest.mark.parametrize("case", list(_cases()),
                         ids=[c[0] for c in _cases()])
def test_wide_key_tables_equal_the_numpy_group_by(case):
    import jax
    import jax.numpy as jnp

    EngineConfig().apply_x64()
    _, words, mask, env, plans, cap, having, narrow = case

    @jax.jit
    def run(words, mask, env):
        return _reduce(tuple(words), mask, env, plans, cap, None, having,
                       narrow)

    got = jax.device_get(run(words, mask, env))
    want = _numpy_tables(words, mask, env, plans, cap)
    assert int(got["_count"]) == int(want["_count"])
    if want["_count"] > cap:
        return  # an overflowing attempt owes the true count and no table
    if narrow:
        assert bool(got.pop("_narrow_ok"))
    names = sg.key_names(len(words))
    if having is not None:
        # the slots that pass, compacted in slot order into `kept` rows
        test, _names, kept = having
        passing = np.flatnonzero(
            (want["_rows"] > 0)
            & test({p.name: (want[p.name], None) for p in plans}, {}))
        assert int(got.pop("_kept")) == len(passing) <= kept
        assert (got["_keys"][len(passing):] == sg.SENTINEL).all()
        assert (got["_rows"][len(passing):] == 0).all()
        want = {k: v if np.ndim(v) == 0 else v[passing]
                for k, v in want.items()}
        got = {k: v if np.ndim(v) == 0 else v[:len(passing)]
               for k, v in got.items()}
    assert set(got) == set(want)
    live = want["_keys"] != sg.SENTINEL
    for name, table in want.items():
        assert got[name].dtype == table.dtype, name
        if name in names[1:]:
            # an empty slot's further words are not defined
            np.testing.assert_array_equal(got[name][live], table[live],
                                          err_msg=name)
        else:
            np.testing.assert_array_equal(got[name], table, err_msg=name)


@pytest.mark.parametrize("k", [1, 2, 3])
def test_count_program_counts_the_groups_of_every_word(k):
    import jax
    import jax.numpy as jnp

    EngineConfig().apply_x64()
    rng = np.random.default_rng(k)
    n = 999
    words = [rng.integers(0, 4, n).astype(np.int64) + (1 << 61)
             for _ in range(k)]
    mask = rng.random(n) < 0.7
    got = jax.jit(lambda w, m: sg.sparse_group_count(
        tuple(w) if k > 1 else w[0], m))(words, mask)
    assert int(got["_count"]) \
        == np.unique(np.stack(words)[:, mask], axis=1).shape[1]


def test_one_word_is_the_program_it_was():
    """One word given as a tuple or as the array lowers to the same text:
    one key operand in the sort's comparator (`num_keys=1`), one `_keys`
    table, no further word."""
    import jax
    import jax.numpy as jnp

    EngineConfig().apply_x64()
    n = 256
    key = jnp.arange(n, dtype=jnp.int64) % 37
    mask = jnp.ones(n, bool)
    env = {"cols": {"x": jnp.arange(n, dtype=jnp.int32)}, "nulls": {}}
    plans = [_agg("s", "sum", "x"), _agg("n", "count")]

    def program(k):
        return lambda key, mask, env: sg.sparse_group_reduce(
            k(key), mask, env, plans, {}, sg.SparseProgram(64))

    array = jax.jit(program(lambda k: k)).lower(key, mask, env).as_text()
    one = jax.jit(program(lambda k: (k,))).lower(key, mask, env).as_text()
    assert array == one
    jaxpr = jax.make_jaxpr(program(lambda k: k))(key, mask, env)
    sorts = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "sort"]
    assert sorted(e.params["num_keys"] for e in sorts) == [1, 1]
    assert max(len(e.invars) for e in sorts) == 2   # the key and the sum
    out = jax.eval_shape(program(lambda k: k), key, mask, env)
    assert sorted(out) == ["_count", "_keys", "_rows", "n", "s"]
    two = jax.make_jaxpr(program(lambda k: (k, k + 1)))(key, mask, env)
    assert max(e.params["num_keys"] for e in two.jaxpr.eqns
               if e.primitive.name == "sort") == 2


# ------------------------------------------------------- through the engine

WIDE = 1 << 20


def _table(n_dims, rows=6000, seed=5):
    rng = np.random.default_rng(seed)
    # few distinct values a dimension, spread over 2^20: thousands of
    # groups, every dimension's domain 2^20 wide; d1 below zero too
    cols = {}
    for d in range(n_dims):
        lo = -WIDE // 2 if d == 1 else 10
        values = np.r_[lo, lo + WIDE - 1,
                       rng.integers(lo, lo + WIDE, 5)]
        cols[f"d{d}"] = values[rng.integers(0, len(values), rows)]
    cols["v"] = rng.integers(-1000, 1000, rows)
    cols["q"] = rng.integers(1, 51, rows).astype(np.int8)
    cols["ts"] = pd.Timestamp("2024-01-01") + pd.to_timedelta(
        rng.integers(0, 90 * 86400, rows), unit="s")
    return pd.DataFrame(cols)


@pytest.fixture(scope="module", params=[4, 6], ids=["4-dims", "6-dims"])
def wide(request):
    n_dims = request.param
    df = _table(n_dims)
    eng = Engine(EngineConfig(fallback_on_device_failure=False))
    eng.register_table("t", df, time_column="ts", block_rows=2048)
    yield n_dims, eng, df
    eng.close()


def _counter(eng, name):
    return sum(
        float(ln.rsplit(" ", 1)[1])
        for ln in eng.metrics.render().splitlines()
        if ln.startswith(f"tpu_olap_{name}"))


def _wide_key_queries(eng):
    return _counter(eng, "sparse_wide_key_queries_total")


def _dims(n_dims):
    return [f"d{d}" for d in range(n_dims)]


def _spans(eng):
    def walk(s):
        yield s
        for c in s.get("children", ()):
            yield from walk(c)
    return list(walk(eng.tracer.last.to_json()))


def test_engine_group_by_equals_pandas(wide):
    n_dims, eng, df = wide
    dims = _dims(n_dims)
    got = eng.sql(f"SELECT {', '.join(dims)}, sum(v) AS sv, count(*) AS n, "
                  f"min(q) AS lo, max(q) AS hi FROM t "
                  f"GROUP BY {', '.join(dims)}")
    rec = eng.runner.history[-1]
    assert rec["reduce_path"] == "sparse" and "fallback_reason" not in rec
    assert rec["key_words"] == n_dims // 2
    assert rec["key_bits"] == 21 * n_dims
    # every word holds two dimensions' 42 bits: none rides as int32
    assert rec["key_sort_bits"] == [64] * (n_dims // 2)
    want = df.groupby(dims, as_index=False).agg(
        sv=("v", "sum"), n=("v", "size"), lo=("q", "min"), hi=("q", "max"))
    assert len(got) == len(want) == rec["present_groups"] > 1000
    got = got.sort_values(dims).reset_index(drop=True)
    want = want.sort_values(dims).reset_index(drop=True)
    for c in want.columns:
        np.testing.assert_array_equal(got[c].to_numpy(np.int64),
                                      want[c].to_numpy(np.int64), err_msg=c)
    spans = _spans(eng)
    assert [s["attrs"]["key_words"] for s in spans
            if s["name"] in ("sparse-count", "sparse-attempt",
                             "decode-groups", "dispatch")
            and "key_words" in s["attrs"]] \
        == [n_dims // 2] * sum(
            s["name"] in ("sparse-count", "sparse-attempt",
                          "decode-groups", "dispatch") for s in spans)
    assert [s["attrs"]["key_bits"] for s in spans
            if s["name"] == "dispatch"] == [21 * n_dims]
    assert [s["attrs"]["groups"] for s in spans
            if s["name"] == "decode-groups"] == [len(want)]


def test_engine_order_by_limit_and_filter(wide):
    n_dims, eng, df = wide
    dims = _dims(n_dims)
    got = eng.sql(f"SELECT {', '.join(dims)}, sum(v) AS sv FROM t "
                  f"WHERE q > 10 GROUP BY {', '.join(dims)} "
                  f"ORDER BY sv DESC, {', '.join(dims)} LIMIT 25")
    assert eng.runner.history[-1]["key_words"] == n_dims // 2
    want = df[df.q > 10].groupby(dims, as_index=False).agg(sv=("v", "sum")) \
        .sort_values(["sv"] + dims, ascending=[False] + [True] * n_dims) \
        .head(25).reset_index(drop=True)
    for c in want.columns:
        np.testing.assert_array_equal(got[c].to_numpy(np.int64),
                                      want[c].to_numpy(np.int64), err_msg=c)


def test_engine_having_on_the_device(wide):
    n_dims, eng, df = wide
    dims = _dims(n_dims)
    before = _wide_key_queries(eng)
    got = eng.sql(f"SELECT {', '.join(dims)}, sum(q) AS sq FROM t "
                  f"GROUP BY {', '.join(dims)} HAVING sum(q) > 40 "
                  f"ORDER BY sq DESC, {', '.join(dims)}")
    rec = eng.runner.history[-1]
    assert rec["having_where"] == "device" and rec["sum_word_bits"] == 32
    assert rec["key_words"] == n_dims // 2 and rec["cap_tables"] == 1
    want = df.groupby(dims, as_index=False).agg(sq=("q", "sum"))
    want = want[want.sq > 40].sort_values(
        ["sq"] + dims, ascending=[False] + [True] * n_dims) \
        .reset_index(drop=True)
    assert 0 < len(want) == len(got) < rec["having_groups_in"]
    for c in want.columns:
        np.testing.assert_array_equal(got[c].to_numpy(np.int64),
                                      want[c].to_numpy(np.int64), err_msg=c)
    assert _wide_key_queries(eng) == before + 1


def test_engine_time_bucket_is_among_the_words(wide):
    """A granularity's bucket is a position of the key like a dimension's
    ids: it is packed into a word and decoded from it."""
    from tpu_olap.ir.aggregations import SumAggregation
    from tpu_olap.ir.dimensions import DefaultDimensionSpec
    from tpu_olap.ir.granularity import PeriodGranularity
    from tpu_olap.ir.query import GroupByQuerySpec

    n_dims, eng, df = wide
    dims = _dims(n_dims)
    table = eng.catalog.get("t").segments
    q = GroupByQuerySpec(
        data_source="t", granularity=PeriodGranularity("P1M"),
        dimensions=tuple(DefaultDimensionSpec(d, d) for d in dims),
        aggregations=(SumAggregation("sv", "v", "long"),))
    res = eng.runner.execute(q, table)
    plan = eng.runner._lower_cached(q, table)
    assert plan.sizes[0] == 3 and any(0 in w for w in plan.key_words)
    assert len(plan.key_words) == n_dims // 2
    want = df.assign(month=df.ts.dt.to_period("M").dt.start_time) \
        .groupby(["month"] + dims).v.sum()
    got = {(pd.Timestamp(r["timestamp"]).tz_localize(None),)
           + tuple(r[d] for d in dims): r["sv"] for r in res.rows}
    assert got == {k: int(v) for k, v in want.items()}


def test_explain_says_key_words_before_a_run(wide):
    n_dims, eng, _df = wide
    dims = ", ".join(_dims(n_dims))
    said = eng.explain(f"SELECT {dims}, count(*) AS n FROM t GROUP BY {dims}")
    assert said["rewritten"]
    assert said["key_words"] == n_dims // 2
    assert said["key_bits"] == 21 * n_dims
    # one word where the space is under 2^62; nothing of a dense plan
    said = eng.explain("SELECT d0, d1, count(*) AS n FROM t GROUP BY d0, d1")
    assert said["key_words"] == 1 and said["key_bits"] == 42
    assert "key_words" not in eng.explain(
        "SELECT q, count(*) AS n FROM t GROUP BY q")


def test_a_domain_that_moves_keeps_the_wide_program(wide):
    """A wide key's program holds each dimension's width in bits and reads
    a numeric dimension's bound from the ConstPool: a table whose minimum
    and maximum differ lowers to the same text."""
    import hashlib

    import jax

    n_dims, eng, df = wide
    dims = ", ".join(_dims(n_dims))
    sql = f"SELECT {dims}, sum(v) AS sv FROM t GROUP BY {dims}"
    moved = df.copy()
    moved["d0"] = np.where(moved.d0 == moved.d0.max(), moved.d0.max() + 3,
                           np.where(moved.d0 == moved.d0.min(),
                                    moved.d0.min() - 2, moved.d0))
    other = Engine(EngineConfig(fallback_on_device_failure=False))
    other.register_table("t", moved, time_column="ts", block_rows=2048)
    texts = []
    for e in (eng, other):
        plan = e.planner.plan(sql)
        phys = e.runner._lower_cached(plan.query, plan.entry.segments)
        env, valid, seg_mask = e.runner._prepare(phys, {})
        consts_dev, seg_arg = e.runner._args_for(phys, seg_mask, None)
        texts.append(hashlib.sha256(jax.jit(phys.make_sparse_kernel(
            sg.SparseProgram(4096)))
                     .lower(env, valid, seg_arg, consts_dev).as_text()
                     .encode()).hexdigest())
        sizes = phys.sizes
    other.close()
    assert texts[0] == texts[1]
    assert sizes[1] == WIDE + 5 + 1   # the moved table's own domain


# ------------------------------------- a word that fits 31 bits (PR 44)

I32_TOP = (1 << 31) - 1


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


# name -> the words of a key as they ride (word dtypes), from one int64
# key below 2^31 - 1: each shape holds the largest value its width allows
KEY_SHAPES = {
    "one-word-int32": lambda k: (k.astype(np.int32),),
    "narrow-second-word": lambda k: (
        (k // 7) + (1 << 40), np.where(k % 7 == 6, I32_TOP,
                                       k % 7).astype(np.int32)),
    "narrow-word-0": lambda k: (
        np.where(k == I32_TOP - 1, k, k // 7).astype(np.int32),
        (k % 7) + (1 << 40)),
}
# name -> (distinct keys, the share of rows the mask keeps): 300 rows into
# 16 slots each, so one program a test serves them all
KEY_DATA = {
    "cap-overflow-and-masked-tail": (40, 0.9),
    "every-row-masked": (9, 0.0),
    "fits-and-no-tail": (12, 1.0),
}
KEY_PLANS = [_agg("a", "sum", "i8"), _agg("lo", "min", "i8"),
             _agg("hi", "max", "i32", filter_fn=_positive),
             _agg("fc", "count", filter_fn=_positive), _agg("n", "count")]
_key_programs = {}


def _key_program(shape, cut, read, narrow, wide):
    """The jitted program of KEY_PLANS over 300 rows and 16 slots (built
    once a parameter set): the words as `shape` rides them, or every word
    as int64 (`wide`: the program that stood before)."""
    import jax
    import jax.numpy as jnp

    EngineConfig().apply_x64()
    name = (shape, cut, read, narrow, wide)
    if name not in _key_programs:
        top = ("a", 10, False) if cut == "top" else None
        having = (lambda t, c: t["a"][0].astype(np.int64) > -5,
                  frozenset({"a"}), 32) if cut == "having" else None

        def program(words, m, e):
            if wide:
                words = tuple(w.astype(jnp.int64) for w in words)
            key = words if len(words) > 1 else words[0]
            return _reduce(key, m, e, KEY_PLANS, 16, top, having, narrow,
                           read)
        _key_programs[name] = jax.jit(program)
    return _key_programs[name]


def _key_inputs(shape, data, n=300):
    distinct, keep = KEY_DATA[data]
    rng = np.random.default_rng(44)
    # the largest key an int32 word 0 may hold, and the smallest
    key = rng.integers(1, distinct, n).astype(np.int64)
    key = key * 7 + key % 7        # both words of a wide shape vary
    key[5], key[77] = 0, I32_TOP - 1
    mask = rng.random(n) < keep
    env = {"cols": {
        "i8": rng.integers(-128, 128, n).astype(np.int8),
        "i32": rng.integers(-(1 << 31), 1 << 31, n).astype(np.int32),
        "f": rng.integers(-3, 4, n)}, "nulls": {}}
    return KEY_SHAPES[shape](key), mask, env


@pytest.mark.parametrize("narrow", [True, False], ids=["narrow", "wide-sums"])
@pytest.mark.parametrize("read", ["gather", "sorted"])
@pytest.mark.parametrize("cut", ["uncut", "top", "having"])
@pytest.mark.parametrize("data", sorted(KEY_DATA))
@pytest.mark.parametrize("shape", sorted(KEY_SHAPES))
def test_a_narrow_key_words_tables_are_the_int64_programs(shape, data, cut,
                                                          read, narrow):
    """A one-word int32 key, a wide key whose second word is int32 and one
    whose word 0 is: every table of the program equal, dtype and bit, to
    the program whose words all ride as int64; `_keys` (and `_keys1`)
    int64, the SENTINEL in the empty slots. A masked tail with more groups
    than slots, no unmasked row (the empty input), no masked row; uncut,
    under a TopN's threshold, under a HAVING; the whole tables gathered and riding
    `starts`' sort; the sum as one int32 word and as int64. Word 0 holds
    2^31 - 2 (one under its sentinel), a later word 2^31 - 1."""
    import jax

    words, mask, env = _key_inputs(shape, data)
    got, want = (jax.device_get(_key_program(shape, cut, read, narrow, wide)(
        words, mask, env)) for wide in (False, True))
    assert set(got) == set(want)
    for name, table in want.items():
        assert got[name].dtype == table.dtype, name
        np.testing.assert_array_equal(got[name], table, err_msg=name)
    count = int(got["_count"])
    assert (count > 16) == (data == "cap-overflow-and-masked-tail")
    assert count == (0 if data == "every-row-masked" else
                     len(np.unique(np.stack([w.astype(np.int64)
                                             for w in words])[:, mask],
                                   axis=1).T))
    for name in sg.key_names(len(words)):
        assert got[name].dtype == np.int64
    present = got["_rows"] > 0
    assert (got["_keys"][~present] == sg.SENTINEL).all()
    assert (got["_keys"][present] < I32_TOP + (1 << 41)).all()
    if cut == "uncut":
        assert present.sum() == min(count, 16)
        if data == "fits-and-no-tail":
            # against numpy's own group-by, every word
            ref = np.unique(np.stack([w.astype(np.int64) for w in words]),
                            axis=1)
            for w, name in enumerate(sg.key_names(len(words))):
                np.testing.assert_array_equal(got[name][:count], ref[w])
            # the last group's word 0 is one under an int32 sentinel
            assert got["_keys"][count - 1] == I32_TOP - 1 \
                or shape == "narrow-second-word"
            assert got["_rows"].sum() == 300


@pytest.mark.parametrize("data", sorted(KEY_DATA))
@pytest.mark.parametrize("shape", sorted(KEY_SHAPES))
def test_a_narrow_keys_count_program_counts_numpys_groups(shape, data):
    """`sparse_group_count` over the words as they ride: numpy's distinct
    keys among the unmasked rows, none where every row is masked."""
    import jax
    import jax.numpy as jnp

    EngineConfig().apply_x64()
    words, mask, _env = _key_inputs(shape, data)
    key = words if len(words) > 1 else words[0]
    got = int(jax.jit(sg.sparse_group_count)(key, mask)["_count"])
    assert got == np.unique(np.stack(
        [w.astype(np.int64) for w in words])[:, mask], axis=1).shape[1]
    assert (got == 0) == (data == "every-row-masked")


@pytest.mark.parametrize("shape", sorted(KEY_SHAPES))
def test_words_ride_the_sort_and_the_key_gather_in_their_own_width(shape):
    """The main sort's leading operands are the words' own dtypes, the
    count program's too, and an int32 word's table is ONE gather of s32
    where an int64's is emulated as two u32 on the chip."""
    import jax.numpy as jnp

    EngineConfig().apply_x64()
    words, mask, env = _key_inputs(shape, "fits-and-no-tail")
    key = words if len(words) > 1 else words[0]
    dtypes = [str(w.dtype) for w in words]
    assert sorted(set(dtypes)) in (["int32"], ["int32", "int64"])
    ops = _sort_operand_dtypes(
        lambda k, m, e: sg.sparse_group_reduce(
            k, m, e, KEY_PLANS, {}, sg.SparseProgram(16)), key, mask, env)
    assert ops[:len(words)] == dtypes
    assert _sort_operand_dtypes(
        sg.sparse_group_count, key, mask) == dtypes



def _narrow_key_queries(eng):
    return _counter(eng, "sparse_narrow_key_queries_total")


def _odd_dims(n_dims):
    """GROUP BY columns whose last 21-bit dimension is alone in its word:
    three of them and the 6 bits of `q` (a product past 2^62: `q` joins
    word 0) on the four-dimension table, five on the six-dimension one."""
    return _dims(n_dims)[:3] + ["q"] if n_dims == 4 else _dims(n_dims)[:5]


@pytest.mark.parametrize("cut", ["uncut", "ordered-limit", "having"])
def test_an_odd_dimension_rides_alone_as_an_int32_word(wide, cut):
    """Three dimensions of 21 bits and one of 6 (five of 21): the last
    wide one is alone in its word, which rides the sort as int32
    (`key_sort_bits` [64, 32] / [64, 64, 32]) and comes back as the int64
    `_keys1` (`_keys2`) the host decodes: pandas' answer, whole, under an
    ordered LIMIT and under a HAVING the device decides."""
    n_dims, eng, df = wide
    dims = _odd_dims(n_dims)
    grouped = len(dims)
    cols = ", ".join(dims)
    bits = [64, 32] if n_dims == 4 else [64, 64, 32]
    sql = {"uncut": f"SELECT {cols}, sum(v) AS sv, sum(q) AS sq, "
                    f"min(v) AS lo FROM t GROUP BY {cols}",
           "ordered-limit": f"SELECT {cols}, sum(v) AS sv FROM t "
                            f"WHERE q > 10 GROUP BY {cols} "
                            f"ORDER BY sv DESC, {cols} LIMIT 25",
           "having": f"SELECT {cols}, sum(q) AS sq FROM t GROUP BY {cols} "
                     f"HAVING sum(q) > 60 ORDER BY sq DESC, {cols}"}[cut]
    said = eng.explain(sql)
    assert said["key_words"] == len(bits) and said["key_sort_bits"] == bits
    narrow, wide_keys = _narrow_key_queries(eng), _wide_key_queries(eng)
    got = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert rec["reduce_path"] == "sparse" and "fallback_reason" not in rec
    assert rec["key_sort_bits"] == bits and rec["key_words"] == len(bits)
    assert rec["key_bits"] == (69 if n_dims == 4 else 105)
    assert [s["attrs"]["key_sort_bits"] for s in _spans(eng)
            if s["name"] == "dispatch"] == [str(bits)]
    assert _narrow_key_queries(eng) == narrow + 1
    assert _wide_key_queries(eng) == wide_keys + 1
    if cut == "uncut":
        want = df.groupby(dims, as_index=False).agg(
            sv=("v", "sum"), sq=("q", "sum"), lo=("v", "min"))
        got = got.sort_values(dims).reset_index(drop=True)
        want = want.sort_values(dims).reset_index(drop=True)
    elif cut == "ordered-limit":
        want = df[df.q > 10].groupby(dims, as_index=False) \
            .agg(sv=("v", "sum")).sort_values(
                ["sv"] + dims, ascending=[False] + [True] * grouped) \
            .head(25).reset_index(drop=True)
    else:
        assert rec["having_where"] == "device"
        want = df.groupby(dims, as_index=False).agg(sq=("q", "sum"))
        want = want[want.sq > 60].sort_values(
            ["sq"] + dims, ascending=[False] + [True] * grouped) \
            .reset_index(drop=True)
    assert len(got) == len(want) > 0
    for c in want.columns:
        np.testing.assert_array_equal(got[c].to_numpy(np.int64),
                                      want[c].to_numpy(np.int64), err_msg=c)


def test_the_narrow_word_program_sorts_by_int64_words_then_int32(wide):
    """The program's own text: the main sort's two leading operands are
    the words as `key_word_dtypes` says, and the tables leave as int64."""
    import jax

    n_dims, eng, _df = wide
    cols = ", ".join(_odd_dims(n_dims))
    plan = eng.planner.plan(f"SELECT {cols}, sum(v) AS sv FROM t "
                            f"GROUP BY {cols}")
    phys = eng.runner._lower_cached(plan.query, plan.entry.segments)
    dtypes = [str(d) for d in
              sg.key_word_dtypes(phys.sizes, phys.key_words)]
    assert dtypes == ["int64"] * (len(dtypes) - 1) + ["int32"]
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    consts_dev, seg_arg = eng.runner._args_for(phys, seg_mask, None)
    args = (env, valid, seg_arg, consts_dev)

    for cap in (4096, None):
        kernel = phys.make_sparse_kernel(sg.SparseProgram(cap))
        assert _sort_operand_dtypes(kernel, *args)[:len(dtypes)] == dtypes
        out = jax.eval_shape(kernel, *args)
        assert cap is None or all(
            out[n].dtype == np.int64 for n in sg.key_names(len(dtypes)))


# ------------------------------------------------------- what stays refused

def test_a_mesh_refuses_a_wide_key_with_the_reason():
    df = _table(4, rows=2000)
    eng = Engine(EngineConfig(num_shards=2))
    eng.register_table("t", df, time_column="ts", block_rows=512)
    dims = ", ".join(_dims(4))
    sql = f"SELECT {dims}, sum(v) AS sv FROM t GROUP BY {dims}"
    assert eng.explain(sql)["key_words"] is None
    got = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert "needs one chip: the mesh's merge sorts one int64 key" \
        in rec["fallback_reason"]
    assert len(got) == len(df.groupby(_dims(4)).size())
    # under 2^62 the mesh serves the sparse group-by as it did
    eng.sql("SELECT d0, d1, sum(v) AS sv FROM t GROUP BY d0, d1")
    rec = eng.runner.history[-1]
    assert rec["reduce_path"] == "sparse" and rec["key_words"] == 1 \
        and rec["num_shards"] == 2 and "fallback_reason" not in rec
    eng.close()


def test_without_x64_a_wide_key_is_refused_as_any_sparse_key():
    df = _table(4, rows=2000)
    eng = Engine(EngineConfig(enable_x64=False))
    eng.register_table("t", df, time_column="ts", block_rows=512)
    dims = ", ".join(_dims(4))
    sql = f"SELECT {dims}, count(*) AS n FROM t GROUP BY {dims}"
    said = eng.explain(sql)
    assert said["rewritten"] and said["key_words"] is None
    eng.sql(sql)
    assert "enable_x64=False" in eng.runner.history[-1]["fallback_reason"]
    eng.close()


def test_mergeable_partials_refuse_a_wide_key(wide):
    from tpu_olap.kernels.groupby import UnsupportedAggregation

    n_dims, eng, _df = wide
    dims = ", ".join(_dims(n_dims))
    plan = eng.planner.plan(
        f"SELECT {dims}, sum(v) AS sv FROM t GROUP BY {dims}")
    with pytest.raises(UnsupportedAggregation, match="no flat int64"):
        eng.runner.compute_partials(plan.query, plan.entry.segments)
