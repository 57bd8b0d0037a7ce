"""The deployment `tpch-flat-sf10-chip` (perfbench/configs) at 60,000 rows
on the CPU: the benchmark's own generator through `Engine.register_table`
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


@pytest.mark.parametrize("name", sorted(REDUCE_PATH))
def test_template_equals_the_reference(eng, data, name):
    served, rec = _served(eng, tpch_flat.templates()[name])
    assert verify.answer_mismatches(served, data["expected"][name]) == []
    assert rec["reduce_path"] == REDUCE_PATH[name]
    assert rec.get("num_shards", 1) == 1
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
        assert rec["sparse_cap"] >= rec["present_groups"] \
            == data["reference"]["groups"][name]


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
    """A template whose present groups pass the starting cap: the first run
    overflows, grows the compact table once and compiles twice; the next
    run starts from the grown cap and finds that program in the jit cache."""
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
    assert [s["attrs"]["cap"] for s in spans
            if s["name"] == "sparse-attempt"] == [64, 1024]
    assert {"count-probe", "host-transfer", "ordered-limit"} \
        <= {s["name"] for s in spans}


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
