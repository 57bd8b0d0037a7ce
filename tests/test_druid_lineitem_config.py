"""The deployment `druid-lineitem-sf100-chip` (perfbench/configs) at 60,000
rows on the CPU: the benchmark's own generator through
`Engine.register_table` and `Engine.sql`, each of Druid's nine queries
against the benchmark's plain reference by the comparison that decides
`correct` (equality), nothing served by the pandas fallback; and the TopN
that this deployment brought to the sparse path: equal to the dense TopN of
the same data, ties at the threshold included, the routing rule's cases,
and what the record and the span tree say of where the threshold ran."""

import dataclasses
import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

from perfbench.datasets import druid_lineitem
from perfbench.datasets.druid_lineitem import reference
from perfbench.lib import verify
from tpu_olap import Engine
from tpu_olap.executor import EngineConfig
from tpu_olap.executor.lowering import topn_takes_sparse
from tpu_olap.ir import (DefaultDimensionSpec, MaxAggregation,
                         MinAggregation, SumAggregation, TopNQuerySpec)
from tpu_olap.ir.granularity import PeriodGranularity
from tpu_olap.kernels.groupby import COMPARE_MAX_GROUPS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, SEED = 60_000, 2_147_483_659   # a seed beyond 32 signed bits
PARTS = 2_000                        # l_partkey's span at this size
SERVED_BY_DEVICE = dict(fallback_on_device_failure=False,
                        breaker_failure_threshold=0)
# (query type, reduce_path) the lowering picks off the TPU, where Pallas
# is not on offer: the part keys' 2,001 slots are under COMPARE_MAX_GROUPS
# here (2,000,001 at the cell's size: test_routing_rule), the commit
# dates' 2,558 are past it
PLANNED = {
    "count_star_interval": ("timeseries", "reduce"),
    "sum_price": ("timeseries", "reduce"),
    "sum_all": ("timeseries", "reduce"),
    "sum_all_year": ("groupBy", "scatter"),
    "sum_all_filter": ("timeseries", "reduce"),
    "top_100_parts": ("topN", "scatter"),
    "top_100_parts_details": ("topN", "scatter"),
    "top_100_parts_filter": ("topN", "scatter"),
    "top_100_commitdate": ("topN", "sparse"),
}
WIDE = sorted(t for t in PLANNED if "parts" in t)


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    d = tmp_path_factory.mktemp("druid_lineitem")
    out = druid_lineitem.generate(ROWS, SEED, str(d), workers=1,
                                  orders_per_chunk=7_000)
    out["expected"] = druid_lineitem.answers(out["reference"])
    return out


def _engine(data, **fields):
    eng = Engine(EngineConfig(**SERVED_BY_DEVICE, **fields))
    druid_lineitem.register(eng, data["paths"], ROWS, SEED)
    return eng


@pytest.fixture(scope="module")
def eng(data):
    return _engine(data)


@pytest.fixture(scope="module")
def eng_sparse(data):
    """The part keys' 2,001 slots past the dense budget: every TopN over
    l_partkey takes the sparse path, as the cell's do at 2,000,001."""
    return _engine(data, dense_group_budget=1024)


def _served(eng, sql):
    df = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert rec.get("query_type") != "fallback" \
        and "fallback_reason" not in rec and not rec.get("failed"), rec
    return ({"columns": list(df.columns),
             "rows": json.loads(df.to_json(orient="records"))}, rec)


def _walk(tree):
    yield tree
    for c in tree.get("children", []):
        yield from _walk(c)


def _threshold_spans(eng):
    return [s["attrs"] for s in _walk(eng.tracer.last.to_json())
            if s["name"] == "topn-threshold"]


@pytest.mark.parametrize("name", sorted(PLANNED))
def test_template_equals_the_reference(eng, data, name):
    served, rec = _served(eng, druid_lineitem.templates()[name])
    assert verify.answer_mismatches(served, data["expected"][name]) == []
    assert (rec["query_type"], rec["reduce_path"]) == PLANNED[name]
    assert rec.get("num_shards", 1) == 1
    if rec["query_type"] == "topN":
        k = rec["topn_group_space"]
        assert k == (PARTS + 1 if name in WIDE else 2_558)
        sparse = rec["reduce_path"] == "sparse"
        assert rec["topn_rows_fetched"] == (100 if sparse else k)
        assert [a["where"] for a in _threshold_spans(eng)] \
            == ["device" if sparse else "host"]
    else:
        assert "topn_group_space" not in rec \
            and "topn_rows_fetched" not in rec


@pytest.mark.parametrize("name", WIDE)
def test_wide_topn_on_the_sparse_path_equals_the_reference(eng_sparse, data,
                                                           name):
    """The cell's three TopNs over l_partkey as the chip runs them: the
    sort, the compact table, the threshold on the device; 100 group rows
    leave it, not the table's cap."""
    served, rec = _served(eng_sparse, druid_lineitem.templates()[name])
    assert verify.answer_mismatches(served, data["expected"][name]) == []
    assert (rec["query_type"], rec["reduce_path"]) == ("topN", "sparse")
    # l_discount is stored as int8: its min and max are read at the sorted
    # runs' last rows from an int32 word, no row scattered (PR 37)
    assert rec["reduce_form"] == "boundary"
    assert rec.get("ext_word_bits") == (None if name == "top_100_parts"
                                        else 32)
    # the program ranks first: of its [cap] tables it gathers the ranked
    # sum's alone and reads the others at the 100 kept rows (PR 39)
    assert rec["cap_tables"] == 1
    # l_quantity (int8) and l_extendedprice (int32) are summed as one
    # int32 word each: ~30 rows a part times 10,495,000 cents fit (PR 41)
    assert rec["sum_word_bits"] == 32 and "narrow_fallback" not in rec
    assert rec["sparse_attempts"] == 1
    assert rec["topn_rows_fetched"] == 100 < rec["sparse_cap"]
    assert rec["topn_group_space"] == PARTS + 1
    assert rec["present_groups"] == PARTS
    assert _threshold_spans(eng_sparse) == [
        {"where": "device", "groups": 100, "threshold": 100}]


def test_the_threshold_cuts_through_a_tie(data):
    """sum(l_quantity) of the 100th and the 101st part are equal in this
    data (and of the 100th and 101st commit date are not: both sides), so
    the answers rest on the tie rule: metric descending, key ascending."""
    total = data["reference"]
    r = reference.ranked_parts(total["parts"], 102)
    assert r[99]["sum_quantity"] == r[100]["sum_quantity"]
    assert r[99]["l_partkey"] < r[100]["l_partkey"]
    w = reference.ranked_parts(total["parts_window"], 101)
    assert [x["l_partkey"] for x in w[:100]] \
        != [x["l_partkey"] for x in r[:100]]


AGGS = {"sums": "sum(l_quantity) AS q",
        "details": "sum(l_quantity) AS q, sum(l_extendedprice) AS p, "
                   "min(l_discount) AS lo, max(l_discount) AS hi"}


@pytest.mark.parametrize("direction", ["DESC", "ASC"])
@pytest.mark.parametrize("aggs", sorted(AGGS))
@pytest.mark.parametrize("limit", [100, 7])
def test_sparse_topn_equals_dense_topn(eng, eng_sparse, data, aggs,
                                       direction, limit):
    """The same TopN from the dense [K] table ranked on the host and from
    the compact table with the threshold on the device: the same rows in
    the same order, descending and inverted, with min / max aboard, where
    the metric ties at the threshold (checked against numpy)."""
    sql = (f"SELECT l_partkey, {AGGS[aggs]} FROM lineitem "
           f"GROUP BY l_partkey ORDER BY q {direction} LIMIT {limit}")
    dense, drec = _served(eng, sql)
    sparse, srec = _served(eng_sparse, sql)
    assert (drec["query_type"], drec["reduce_path"]) == ("topN", "scatter")
    assert (srec["query_type"], srec["reduce_path"]) == ("topN", "sparse")
    assert dense == sparse
    assert srec["topn_rows_fetched"] == limit
    assert drec["topn_rows_fetched"] == PARTS + 1
    by = data["reference"]["parts"]
    want = reference.top(by["rows"], by["qty"], limit + 1,
                         ascending=direction == "ASC")
    assert [r["l_partkey"] for r in sparse["rows"]] == want[:limit].tolist()
    if limit == 100 and direction == "DESC":
        assert by["qty"][want[limit - 1]] == by["qty"][want[limit]]


def _topn_ir(**over):
    return TopNQuerySpec(**{**dict(
        data_source="lineitem", dimension=DefaultDimensionSpec("l_partkey"),
        metric="q", threshold=5,
        aggregations=(SumAggregation("q", "l_quantity", "long"),)), **over})


def _run_ir(eng, q):
    res = eng.execute_ir(q)
    rec = eng.runner.history[-1]
    assert "fallback_reason" not in rec and not rec.get("failed"), rec
    return res.rows, rec


def test_time_bucketed_sparse_topn_ranks_on_the_host(data, eng_sparse):
    """A TopN a year: seven buckets of the compact table, so the device
    does not apply the threshold; the host ranks the fetched table bucket
    by bucket, and the rows equal the dense path's (7 x 2,001 slots would
    scatter, so the rule sends them to the sparse path too: the dense side
    is an engine whose compact table could not hold them)."""
    q = _topn_ir(granularity=PeriodGranularity("P1Y"))
    dense, drec = _run_ir(_engine(data, sparse_group_budget=64), q)
    sparse, srec = _run_ir(eng_sparse, q)
    assert drec["reduce_path"] != "sparse" and srec["reduce_path"] == "sparse"
    assert sparse == dense and len(dense) == 7 * 5
    assert len({r["timestamp"] for r in dense}) == 7
    assert _threshold_spans(eng_sparse)[0]["where"] == "host"
    assert srec["topn_rows_fetched"] == srec["sparse_cap"] > 5


@pytest.mark.parametrize("metric, aggregation", [
    ("lo", MinAggregation("lo", "l_discount", "long")),
    ("hi", MaxAggregation("hi", "l_extendedprice", "long"))])
def test_sparse_topn_by_a_min_or_max_ranks_on_the_host(eng, eng_sparse,
                                                       metric, aggregation):
    """A min / max is finalized on the host (a group without a non-null
    row is null there), so a TopN by one is ranked there too: legible on
    the record, and equal to the dense path."""
    q = _topn_ir(metric=metric, aggregations=(aggregation,), inverted=True)
    dense, _ = _run_ir(eng, q)
    sparse, srec = _run_ir(eng_sparse, q)
    assert sparse == dense and len(dense) == 5
    assert srec["reduce_path"] == "sparse"
    assert _threshold_spans(eng_sparse)[0]["where"] == "host"


def _lowered(eng, sql):
    plan = eng.planner.plan(sql)
    return plan.query, eng.runner._lower_cached(plan.query,
                                                plan.entry.segments)


TOP_PARTS = ("SELECT l_partkey, sum(l_quantity) AS q FROM lineitem "
             "GROUP BY l_partkey ORDER BY q DESC LIMIT 100")
TOP_DATES = ("SELECT l_commitdate, sum(l_quantity) AS q FROM lineitem "
             "GROUP BY l_commitdate ORDER BY q DESC LIMIT 100")
THETA = "theta_sketch_estimate(theta_sketch(l_suppkey))"
ROUTING = [
    # id, engine fields, sql, (query type, sparse?, Pallas took it?)
    ("topn-under-the-compare-bound-stays-dense", {}, TOP_PARTS,
     ("topN", False, False)),
    ("topn-past-it-would-scatter-goes-sparse", {}, TOP_DATES,
     ("topN", True, False)),
    ("topn-past-it-that-pallas-takes-stays-dense",
     {"use_pallas": "force"}, TOP_DATES, ("topN", False, True)),
    ("topn-past-the-sparse-budget-stays-dense",
     {"sparse_group_budget": 2_500}, TOP_DATES, ("topN", False, False)),
    ("topn-past-the-dense-budget-goes-sparse",
     {"dense_group_budget": 1024, "use_pallas": "force"}, TOP_PARTS,
     ("topN", True, False)),
    ("groupby-that-would-scatter-stays-dense", {},
     TOP_DATES.replace("ORDER BY q DESC", "ORDER BY q DESC, l_commitdate"),
     ("groupBy", False, False)),
    # the compact table would clamp the sketch to sparse_theta_k_cap
    ("topn-with-a-theta-sketch-stays-dense", {},
     TOP_DATES.replace("sum(l_quantity)", THETA), ("topN", False, False)),
    ("topn-with-a-sum-and-a-theta-sketch-stays-dense", {},
     TOP_DATES.replace("sum(l_quantity) AS q",
                       f"sum(l_quantity) AS q, {THETA} AS d"),
     ("topN", False, False)),
    ("topn-with-an-hll-sketch-stays-dense", {},
     TOP_DATES.replace("sum(l_quantity)",
                       "approx_count_distinct(l_suppkey)"),
     ("topN", False, False)),
    # nothing of these would be read at the sorted runs' boundaries
    ("topn-of-a-min-and-a-max-alone-stays-dense", {},
     TOP_DATES.replace("sum(l_quantity) AS q",
                       "max(l_discount) AS q, min(l_tax) AS t"),
     ("topN", False, False)),
    ("topn-of-a-sum-a-min-and-a-max-goes-sparse", {},
     TOP_DATES.replace("sum(l_quantity) AS q",
                       "sum(l_quantity) AS q, min(l_discount) AS lo, "
                       "max(l_discount) AS hi"),
     ("topN", True, False)),
    ("topn-by-a-row-count-goes-sparse", {},
     TOP_DATES.replace("sum(l_quantity)", "count(*)"),
     ("topN", True, False)),
]


@pytest.mark.parametrize("fields, sql, want",
                         [c[1:] for c in ROUTING],
                         ids=[c[0] for c in ROUTING])
def test_routing_rule(data, fields, sql, want):
    """Which TopN takes the sparse path, from the plan's static facts: past
    the dense budget always; under it where the dense plan would be XLA's
    scatter (K past COMPARE_MAX_GROUPS, Pallas not taking it) and the
    compact table can hold the whole space. A GroupBy's routing is as it
    was."""
    query, phys = _lowered(_engine(data, **fields), sql)
    assert (query.query_type, phys.sparse, phys.pallas_reason is None) \
        == want
    assert (phys.kernel is None) == phys.sparse \
        == (phys.make_sparse_kernel is not None)
    assert phys.statics[-1 if not want[2] else -3] \
        == ("sparse" if phys.sparse else "dense")
    # a sketch is as wide as the query asked, whichever way it went
    assert [p.theta_k for p in phys.agg_plans if p.kind == "theta"] \
        == [16384] * sql.count("theta_sketch(")


def test_routing_rule_at_the_cells_size_and_without_x64(eng):
    """`topn_takes_sparse` on the static facts the cell's plans have on the
    chip: l_partkey's 2,000,001 slots (Pallas's factorized cap is 65,536)
    go sparse, l_commitdate's 2,558 stay with Pallas; without 64-bit lanes
    there is no sparse key and the dense plan stays."""
    query, _ = _lowered(eng, TOP_PARTS)
    cfg = eng.config

    _, dense = _lowered(eng, TOP_PARTS)

    def plan(k, pallas_reason):
        return types.SimpleNamespace(total_groups=k,
                                     pallas_reason=pallas_reason,
                                     agg_plans=dense.agg_plans)

    assert topn_takes_sparse(query, plan(2_000_001, "K too large"), cfg)
    assert not topn_takes_sparse(query, plan(2_558, None), cfg)
    assert topn_takes_sparse(query, plan(2_558, "not on a TPU"), cfg)
    assert not topn_takes_sparse(query, plan(COMPARE_MAX_GROUPS, "x"), cfg)
    assert not topn_takes_sparse(
        query, plan(cfg.sparse_group_budget + 1, "K too large"), cfg)
    assert not topn_takes_sparse(
        query, plan(2_000_001, "K too large"),
        dataclasses.replace(cfg, enable_x64=False))
    groupby, _ = _lowered(eng, TOP_PARTS.replace("q DESC",
                                                 "q DESC, l_partkey"))
    assert not topn_takes_sparse(groupby, plan(2_000_001, "K"), cfg)


def test_min_and_max_of_a_long_come_back_as_integers(eng):
    df = eng.sql("SELECT min(l_discount) AS lo, max(l_tax) AS hi, "
                 "min(l_extendedprice) AS p FROM lineitem")
    assert [df[c].dtype.kind for c in df.columns] == ["i", "i", "i"]
    assert df.iloc[0].tolist()[:2] == [0, 8]


def test_x64_off_control_differs():
    """The configuration's control, as the benchmark runs it
    (`--control x64-off`) in a process of its own, because JAX's x64
    switch is global: without 64-bit lanes sum(l_extendedprice) overflows
    and the run comes out not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the suite's 8 virtual devices: 1 chip here
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", "druid-lineitem-sf100-chip.druid9-c1",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0",
         "--allow-cpu", "--rehearse-rows", str(ROWS),
         "--control", "x64-off"],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    m = re.search(r"differ from the reference = (\d+) of 9", out.stdout)
    assert m, out.stdout[-2000:] + out.stderr[-2000:]
    assert int(m.group(1)) >= 3
    assert "would have reported correct=False" in out.stdout


def test_top_k_groups_keeps_the_lower_index_among_equals():
    """The device threshold's tie rule, on the kernel itself: integers
    ranked as integers (exact past 2^53), absent slots last."""
    import jax.numpy as jnp

    from tpu_olap.kernels import top_k_groups

    big = 1 << 60
    metric = jnp.asarray([5, big + 1, 5, big, 7, 5, 99], jnp.int64)
    present = jnp.asarray([1, 1, 1, 1, 1, 1, 0], bool)
    order, valid = top_k_groups(metric, present, 4, False)
    assert np.asarray(order).tolist() == [1, 3, 4, 0]
    assert np.asarray(valid).all()
    order, valid = top_k_groups(metric, present, 7, True)
    assert np.asarray(order).tolist()[:6] == [0, 2, 5, 4, 3, 1]
    assert np.asarray(valid).tolist() == [True] * 6 + [False]
