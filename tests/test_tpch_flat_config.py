"""The deployments `tpch-flat-sf10-chip` and `tpch-flat-sf10-mesh4`
(perfbench/configs) at 60,000 rows on the CPU, one device and a mesh of
four: the benchmark's own generator through `Engine.register_table`
and `Engine.sql`, every template's answer against the benchmark's plain
reference by the comparison that decides `correct` (equality), the record
naming the group-reduce implementation that served it, and nothing served
by the pandas fallback. `use_pallas="force"` makes the lowering decide as
it does on the chip (the kernel then runs in interpret mode)."""

import json
import os
import re
import subprocess
import sys

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from perfbench.datasets import tpch_flat
from perfbench.lib import verify
from tpu_olap import Engine
from tpu_olap.executor import EngineConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, SEED = 60_000, 2_147_483_659   # a seed beyond 32 signed bits
SERVED_BY_DEVICE = dict(fallback_on_device_failure=False,
                        breaker_failure_threshold=0)
# what lowering picks per template, and why not Pallas where it is not
REDUCE_PATH = {
    "q1": "scatter",   # sum_charge's input passes int32
    "q3": "sparse",    # l_orderkey x o_orderdate passes the dense budget
    "q5": "pallas", "q6": "pallas", "q7": "pallas", "q8": "pallas",
    "q10": "sparse",   # o_custkey x c_name
    "q12": "scatter",  # ordered comparison with the time column
    "q14": "pallas", "q19": "pallas",
}


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch_flat")
    out = tpch_flat.generate(ROWS, SEED, str(d), workers=1,
                             orders_per_chunk=7_000)
    out["expected"] = tpch_flat.answers(out["reference"])
    return out


def _engine(data, **fields):
    eng = Engine(EngineConfig(**SERVED_BY_DEVICE, **fields))
    tpch_flat.register(eng, data["paths"], ROWS, SEED)
    return eng


@pytest.fixture(scope="module")
def eng(data):
    return _engine(data, use_pallas="force")


def _served(eng, sql):
    df = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert rec.get("query_type") != "fallback" \
        and "fallback_reason" not in rec and not rec.get("failed"), rec
    return ({"columns": list(df.columns),
             "rows": json.loads(df.to_json(orient="records"))}, rec)


@pytest.fixture(scope="module")
def eng4(data):
    return _engine(data, use_pallas="force", num_shards=4)


@pytest.fixture(scope="module")
def eng4_broker(data):
    return _engine(data, use_pallas="force", num_shards=4,
                   mesh_merge="broker")


@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("name", sorted(REDUCE_PATH))
def test_template_equals_the_reference(eng, eng4, data, name, shards):
    """Every template through one chip's program and through the mesh's
    (each chip the one-chip program on its own rows, the broker's merge
    above them): the same answer, the same path, the record's num_shards."""
    eng = eng if shards == 1 else eng4
    served, rec = _served(eng, tpch_flat.templates()[name])
    assert verify.answer_mismatches(served, data["expected"][name]) == []
    assert rec["reduce_path"] == REDUCE_PATH[name]
    assert rec.get("num_shards", 1) == shards
    if REDUCE_PATH[name] == "scatter":
        # the generic grouped kernel, here a masked reduce a slot: Q1 has
        # a dozen dense slots, Q12 as many as l_shipmode has values
        assert rec["pallas_reason"]
        assert rec["reduce_form"] == "compare"
    elif REDUCE_PATH[name] == "sparse":
        # q3 and q10 hold an int64 sum and the row count: every [cap]
        # table is read at the sorted runs' boundaries, no row scattered
        assert rec["reduce_form"] == "boundary"
        assert _dispatch_forms(eng) == ["boundary"]
    else:
        assert "reduce_form" not in rec
    if REDUCE_PATH[name] == "sparse":
        assert rec["sparse_attempts"] >= 1
        # on a mesh the cap is a chip's, the groups the merged table's
        assert rec["present_groups"] == data["reference"]["groups"][name]
        assert shards * rec["sparse_cap"] >= rec["present_groups"]


MESH_SPARSE_COUNTERS = ("sparse_merge_rows_in", "sparse_fetch_bytes")


@pytest.mark.parametrize("where", ["device", "broker"])
@pytest.mark.parametrize("name", ["q3", "q10"])
def test_mesh_sparse_dispatch_names_its_parts(eng, eng4, eng4_broker, name,
                                              where, monkeypatch):
    """The span tree of a mesh's sparse query: the cap attempt and its
    count probe, the merge of the chips' present rows and the fetch, all
    under `dispatch`; the record counts what the merge was handed. Merged
    on the device (the default), one chip's copy of the merged table is
    fetched and the host's merge is never called; merged at the broker,
    the four chips' rows are fetched first. Both answer alike. One chip's
    sparse query has no merge and carries none of it."""
    from tpu_olap.kernels import sparse_groupby

    handed = []
    merge = sparse_groupby.merge_sparse

    def watched(parts, *args):
        handed.append(sum(len(p["_keys"]) for p in parts))
        return merge(parts, *args)

    monkeypatch.setattr(sparse_groupby, "merge_sparse", watched)
    mesh = eng4 if where == "device" else eng4_broker
    sql = tpch_flat.templates()[name]
    served, rec = _served(mesh, sql)
    assert rec["merge"] == where
    dispatch = [s for s in _walk(mesh.tracer.last.to_json())
                if s["name"] == "dispatch"]
    assert len(dispatch) == 1
    under = {s["name"]: s for s in _walk(dispatch[0])}
    assert {"sparse-attempt", "count-probe", "sparse-shard-fetch",
            "broker-merge"} <= set(under)
    attempt, fetch, merged = (under[n]["attrs"] for n in (
        "sparse-attempt", "sparse-shard-fetch", "broker-merge"))
    assert attempt["cap"] == rec["sparse_cap"] \
        >= attempt["present_groups"] > 0
    assert "jit_cache_hit" in attempt
    assert merged["where"] == where
    assert fetch["bytes"] == rec["sparse_fetch_bytes"] > 0
    assert merged["rows_in"] == rec["sparse_merge_rows_in"] \
        >= merged["groups_out"] == rec["present_groups"]
    if where == "device":
        # a power-of-two bucket of the merged groups leaves one chip
        assert handed == [] and fetch["chips"] == 1
        assert merged["groups_out"] <= fetch["rows"] \
            <= max(64, 2 * merged["groups_out"])
    else:
        # a power-of-two bucket of the fullest chip's present groups,
        # not the cap, leaves each chip
        assert fetch["chips"] == 4 and fetch["rows"] % 4 == 0
        assert attempt["present_groups"] <= fetch["rows"] // 4 \
            <= max(64, 2 * attempt["present_groups"])
        assert fetch["rows"] >= merged["rows_in"] == handed[-1]
    assert served == _served(eng, sql)[0]
    one = eng.runner.history[-1]
    assert not any(k in one for k in MESH_SPARSE_COUNTERS + ("merge",))
    assert "broker-merge" not in {
        s["name"] for s in _walk(eng.tracer.last.to_json())}


@pytest.mark.parametrize("where", ["device", "broker"])
def test_the_head_programs_first_build_is_a_counted_compile(
        eng4, eng4_broker, where):
    """The program that cuts the tables (every chip's, or the merged one)
    to a bucket of present rows is built once a bucket: that build shows on the record like the
    sort program's (a warm-up run that compiled is run again), and a
    warm dispatch shows none."""
    sql = tpch_flat.templates()["q3"]
    mesh = eng4 if where == "device" else eng4_broker
    for _ in range(3):   # the cap re-sizes from the first run's count
        _, warm = _served(mesh, sql)
    assert warm["jit_cache_hit"] is True and not warm.get("recompiles")
    cache = mesh.runner._jit_cache
    heads = [k for k in list(cache) if k[0] == "sparse-head"]
    assert heads
    for k in heads:
        del cache[k]
    _, rec = _served(mesh, sql)
    assert rec["jit_cache_hit"] is False and rec["recompiles"] == 1
    _, again = _served(mesh, sql)
    assert again["jit_cache_hit"] is True and not again.get("recompiles")


def test_four_chips_tables_merge_to_the_one_chip_table():
    """The broker's merge of four chips' compact tables is the table one
    chip builds over all the rows, for a sum, a count, a min and a max;
    one chip holds no row of the window, and one is full (count == cap)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_olap.kernels.groupby import AggPlan
    from tpu_olap.kernels.sparse_groupby import (SENTINEL, SparseProgram,
                                                 merge_device, merge_sparse,
                                                 sparse_group_reduce)

    cap = 64
    plans = [AggPlan("s", "sum", ("v",), np.int64),
             AggPlan("n", "count", (), np.int64),
             AggPlan("lo", "min", ("v",), np.int64),
             AggPlan("hi", "max", ("v",), np.int64)]
    rng = np.random.default_rng(36)
    chips = [(rng.permutation(np.repeat(np.arange(cap), 3)), True),  # full
             (rng.integers(0, 200, 500), False),   # no row of the window
             (rng.integers(40, 90, 400), True),
             (rng.integers(1 << 40, (1 << 40) + 30, 300), True)]
    chips = [(k.astype(np.int64), np.full(len(k), m),
              rng.integers(-1000, 1000, len(k))) for k, m in chips]

    def table(keys, mask, v, cap):
        env = {"cols": {"v": jnp.asarray(v)}, "nulls": {}}
        return jax.device_get(sparse_group_reduce(
            jnp.asarray(keys), jnp.asarray(mask), env, plans, {},
            SparseProgram(cap)))

    parts = [table(*c, cap) for c in chips]
    counts = [int(p["_count"]) for p in parts]
    assert counts[0] == cap and counts[1] == 0
    # the broker is handed each chip's present rows
    handed = [dict({k: v[:n] for k, v in p.items() if k != "_count"},
                   _count=np.int32(n)) for p, n in zip(parts, counts)]
    whole = table(*(np.concatenate(x) for x in zip(*chips)), 256)
    n = int(whole["_count"])
    for given in (handed, parts):   # and whole [cap] tables merge alike
        merged = merge_sparse(given, plans, 256)
        assert int(merged["_count"]) == n
        assert (merged["_keys"][n:] == SENTINEL).all()
        for name in ("_keys", "_rows", "s", "n", "lo", "hi"):
            assert np.array_equal(merged[name][:n], whole[name][:n]), name
    # the device's merge of the same four tables laid end to end: the
    # broker's table slot for slot, the reduces' identities past it
    laid = {k: jnp.concatenate([jnp.asarray(p[k]) for p in parts])
            for k in parts[0] if k != "_count"}
    on_device = jax.device_get(merge_device(laid, plans, len(parts)))
    assert int(on_device["_count"]) == n
    assert set(on_device) == set(merged)
    for name, table in merged.items():
        if name != "_count":
            assert np.array_equal(on_device[name][:256], table), name
            assert len(on_device[name]) == 4 * cap


def test_the_brokers_merge_past_its_cap_and_of_nothing():
    """Sorted runs of distinct keys merge to one run of distinct keys, the
    empty slots past it the SENTINEL's; a merge that does not fit its cap
    says how many groups there were (the runner then stops the query);
    and four chips with no group at all merge to an empty table."""
    import numpy as np

    from tpu_olap.kernels import sparse_groupby as sg
    from tpu_olap.kernels.groupby import AggPlan

    plans = [AggPlan("s", "sum", ("v",), np.int64),
             AggPlan("lo", "min", ("v",), np.int64)]
    rng = np.random.default_rng(7)
    parts = []
    for n in (700, 0, 450, 64):
        keys = np.sort(rng.choice(1500, n, replace=False)).astype(np.int64)
        v = rng.integers(-50, 50, n)
        parts.append({"_keys": keys, "_rows": np.ones(n, np.int32), "s": v,
                      "lo": v, "_nn_lo": np.ones(n, np.int32),
                      "_count": np.int32(n)})
    distinct = np.unique(np.concatenate([p["_keys"] for p in parts]))
    whole = sg.merge_sparse(parts, plans, 4096)
    n = int(whole["_count"])
    assert n == len(distinct) and len(whole["_keys"]) == 4096
    assert np.array_equal(whole["_keys"][:n], distinct)
    assert (whole["_keys"][n:] == sg.SENTINEL).all()
    assert int(whole["_rows"].sum()) == 700 + 450 + 64
    assert int(whole["s"][:n].sum()) == sum(int(p["s"].sum()) for p in parts)
    cut = sg.merge_sparse(parts, plans, 128)   # past the cap: counted
    assert len(cut["_keys"]) == 128 and int(cut["_count"]) == n > 128
    none = sg.merge_sparse([{k: v[:0] if k != "_count" else np.int32(0)
                             for k, v in p.items()} for p in parts],
                           plans, 64)
    assert int(none["_count"]) == 0
    assert (none["_keys"] == sg.SENTINEL).all() and not none["_rows"].any()


@pytest.mark.parametrize("name", ["q3", "q10"])
def test_tied_revenues_keep_one_chips_rows_and_order_on_the_mesh(data, name):
    """Every lineitem worth the same: a group's revenue is its row count
    times a constant, so the ten (twenty) rows a LIMIT keeps are cut out
    of long runs of equal revenues and the last ORDER BY key decides. The
    mesh's merged table gives the rows and the order one chip gives."""
    import pyarrow.compute as pc

    t = pa.concat_tables([pq.read_table(p) for p in sorted(data["paths"])])
    for col, value in (("l_extendedprice", 100_000), ("l_discount", 0)):
        i = t.schema.get_field_index(col)
        t = t.set_column(i, col, pa.array([value] * t.num_rows,
                                          t.schema.field(col).type))
    frames = []
    for shards in (1, 4):
        eng = Engine(EngineConfig(**SERVED_BY_DEVICE, num_shards=shards))
        eng.register_table(tpch_flat.TABLE, t, time_column="l_shipdate")
        served, rec = _served(eng, tpch_flat.templates()[name])
        assert rec["reduce_path"] == "sparse" \
            and rec.get("num_shards", 1) == shards
        frames.append(served)
    assert frames[0] == frames[1]
    revenue = [r["revenue"] for r in frames[0]["rows"]]
    assert len(revenue) in (10, 20) and len(set(revenue)) < len(revenue)
    assert pc.sum(t["l_discount"]).as_py() == 0


@pytest.mark.parametrize("shards", [1, 4])
def test_q12_reads_three_resident_streams(data, shards):
    """Q12's two row-by-row comparisons read derived streams (the ranks of
    l_commitdate and l_receiptdate, l_commitdate's values as int64
    millis), built by its first dispatch and by no later one; on one chip
    and on the mesh's per-chip programs, exact, on the generic kernel."""
    eng = _engine(data, num_shards=shards)
    sql = tpch_flat.templates()["q12"]
    builds = []
    for _ in range(2):
        served, rec = _served(eng, sql)
        assert verify.answer_mismatches(served, data["expected"]["q12"]) == []
        assert rec["reduce_path"] == "scatter"
        assert rec["reduce_form"] == "compare"
        assert rec.get("num_shards", 1) == shards
        assert rec["filter_streams"] == 3
        builds.append(rec["filter_stream_builds"])
    assert builds == [3, 0]
    plan = eng.planner.plan(sql)
    phys = eng.runner._lower_cached(plan.query, plan.entry.segments)
    ds = eng.runner._datasets[tpch_flat.TABLE]
    assert sorted(str(ds._derived[t].dtype)
                  for t, _, _ in phys.filter_streams) \
        == ["int32", "int32", "int64"]


def test_past_the_bound_the_generic_kernel_is_the_scatter(eng):
    """o_orderdate x l_returnflag is a dense space of ~9,600 slots, past
    COMPARE_MAX_GROUPS, and sum_charge's input passes int32, so Pallas
    turns the plan down: the record and the `dispatch` span both say
    which program ran."""
    from tpu_olap.kernels.groupby import COMPARE_MAX_GROUPS
    sql = f"""
        SELECT o_orderdate, l_returnflag, count(*) AS n,
               sum(l_extendedprice * (100 - l_discount) * (100 + l_tax))
                   AS sum_charge
        FROM {tpch_flat.TABLE} GROUP BY o_orderdate, l_returnflag"""
    plan = eng.planner.plan(sql)
    phys = eng.runner._lower_cached(plan.query, plan.entry.segments)
    assert COMPARE_MAX_GROUPS < phys.total_groups and not phys.sparse
    _, rec = _served(eng, sql)
    assert rec["reduce_path"] == "scatter" and rec["pallas_reason"]
    assert rec["reduce_form"] == "scatter"
    assert _dispatch_forms(eng) == ["scatter"]
    _served(eng, tpch_flat.templates()["q1"])
    assert _dispatch_forms(eng) == ["compare"]


def _dispatch_forms(eng):
    return [s["attrs"].get("reduce_form")
            for s in _walk(eng.tracer.last.to_json())
            if s["name"] == "dispatch"]


def test_sparse_cap_grows_once_and_the_retry_is_the_warm_program(data):
    """A template whose group space is past the budget and whose present
    groups no hint tells: the first run counts them (a program that builds
    no table), sizes the compact table from the count and compiles twice;
    the next run starts from that cap and finds the program in the jit
    cache. A hint that has gone stale (the groups grew past it) still
    overflows, grows the table once and leaves the grown cap behind."""
    eng = _engine(data, sparse_group_cap=64)
    sql = tpch_flat.templates()["q10"]
    _served(eng, sql)
    first = dict(eng.runner.history[-1])
    spans = [s for s in _walk(eng.tracer.last.to_json())]
    _served(eng, sql)
    second = eng.runner.history[-1]
    groups = data["reference"]["groups"]["q10"]
    assert (first["sparse_attempts"], second["sparse_attempts"]) == (2, 1)
    assert first["sparse_cap"] == second["sparse_cap"] == 1024 >= 2 * groups
    assert first["present_groups"] == second["present_groups"] == groups
    assert first.get("recompiles") == 2
    assert second.get("jit_cache_hit") and not second.get("recompiles")
    assert [s["attrs"].get("cap", s["attrs"].get("present_groups"))
            for s in spans
            if s["name"] in ("sparse-count", "sparse-attempt")] \
        == [groups, 1024]
    assert {"count-probe", "host-transfer", "ordered-limit"} \
        <= {s["name"] for s in spans}
    hints = eng.runner._cap_hints
    stale = [k for k, v in hints.items() if v == groups]
    assert len(stale) == 1
    hints[stale[0]] = 20
    _served(eng, sql)
    third = eng.runner.history[-1]
    assert third["sparse_attempts"] == 2 and third["sparse_cap"] == 1024
    assert [s["attrs"]["cap"] for s in _walk(eng.tracer.last.to_json())
            if s["name"] == "sparse-attempt"] == [64, 1024]


def _walk(tree):
    yield tree
    for c in tree.get("children", []):
        yield from _walk(c)


def test_group_by_a_long_column_wider_than_the_label_budget(eng, data):
    """l_extendedprice spans ~10,000,000 here, past numeric_dim_label_budget
    (4,194,304): its labels are arithmetic, the sparse path groups by it
    on the device."""
    t = pa.concat_tables([pq.read_table(p, columns=[
        "l_extendedprice", "l_linenumber", "l_quantity"])
        for p in data["paths"]])
    price = t["l_extendedprice"].to_numpy()
    assert price.max() - price.min() > eng.config.numeric_dim_label_budget
    served, rec = _served(eng, f"""
        SELECT l_extendedprice, l_linenumber, count(*) AS n,
               sum(l_quantity) AS qty
        FROM {tpch_flat.TABLE}
        GROUP BY l_extendedprice, l_linenumber
        ORDER BY n DESC, l_extendedprice DESC, l_linenumber LIMIT 7""")
    assert rec["reduce_path"] == "sparse"
    want = {}
    for p, ln, q in zip(price.tolist(), t["l_linenumber"].to_pylist(),
                        t["l_quantity"].to_pylist()):
        cur = want.setdefault((p, ln), [0, 0])
        cur[0] += 1
        cur[1] += q
    assert rec["present_groups"] == len(want)
    top = sorted(want.items(), key=lambda kv: (-kv[1][0], -kv[0][0],
                                               kv[0][1]))[:7]
    assert served["rows"] == [
        {"l_extendedprice": p, "l_linenumber": ln, "n": n, "qty": q}
        for (p, ln), (n, q) in top]


def test_x64_off_control_differs():
    """The configuration's control, as the benchmark runs it
    (`--control x64-off`) in a process of its own, because JAX's x64
    switch is global: without 64-bit lanes Q1's sums overflow (sum_charge
    passes 2^31 in a single row), the sparse group-by has no key, and the
    run comes out not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the suite's 8 virtual devices: 1 chip here
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "tpch-flat-sf10-chip.tpch10-c1", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0", "--allow-cpu",
         "--rehearse-rows", str(ROWS), "--control", "x64-off"],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    m = re.search(r"differ from the reference = (\d+) of 10", out.stdout)
    assert m, out.stdout[-2000:] + out.stderr[-2000:]
    assert int(m.group(1)) >= 3
    assert "would have reported correct=False" in out.stdout


def test_an_expressions_columns_are_widened_in_sorted_order():
    """A set's order follows the process's hash seed. Traced in that
    order, Q1's `l_extendedprice * (100 - l_discount) * (100 + l_tax)`
    gave up to six programs, each with its own key in the persistent
    compile cache, so a fresh process compiled the sort again (PR 27)."""
    import numpy as np

    from tpu_olap.ir.expr import Col
    from tpu_olap.kernels.exprs import widen_int_env

    order = []

    class Narrow:
        dtype = np.dtype(np.int32)

        def __init__(self, name):
            self.name = name

        def astype(self, _dtype):
            order.append(self.name)
            return self

    names = ["l_tax", "l_extendedprice", "l_discount", "l_quantity"]
    ex = Col("l_tax") * (Col("l_quantity") - Col("l_extendedprice")) \
        * Col("l_discount")
    widen_int_env(ex, {n: Narrow(n) for n in names}, np)
    assert order == sorted(names)
