"""A GroupBy's HAVING decided on the device (PR 40).

Where a GroupBy takes the one-chip sparse path and its HAVING is built of
comparisons of integer tables alone, the sparse program builds only the
tested aggregates' [cap] tables, cuts to the slots that pass and reads every
other table there (`sparse_groupby.compile_having`, `sparse_group_reduce`'s
`having`); the host decodes, orders and limits the kept rows. Everything
else keeps the host's `eval_having` over the fetched table. Here: TPC-H
Q18 on the benchmark's own dataset against its plain reference and pandas,
every HAVING form device against host, the kept bucket's edges, what stays
on the host, a mesh, and the group budget past 2^21 scaled down.
"""

import numpy as np
import pandas as pd
import pytest

from perfbench.datasets import tpch_flat_having
from tpu_olap import Engine
from tpu_olap.executor import EngineConfig
from tpu_olap.executor import sparse_dispatch

Q18_ROWS = 400_000
# (seed, orders past 300 / 312 / 315): the second seed leaves q18_315 empty
Q18_SEEDS = {17: (4, 2, 1), 2_147_483_659: (4, 1, 0)}
N_GROUPS = 3000


@pytest.fixture(scope="module", params=sorted(Q18_SEEDS))
def q18(request, tmp_path_factory):
    seed = request.param
    out = tmp_path_factory.mktemp(f"q18_{seed}")
    data = tpch_flat_having.generate(Q18_ROWS, seed, str(out), workers=1,
                                     orders_per_chunk=49_000)
    eng = Engine(EngineConfig(fallback_on_device_failure=False,
                              sparse_group_budget=1 << 17))
    tpch_flat_having.register(eng, data["paths"], Q18_ROWS, seed)
    cols = ["o_custkey", "l_orderkey", "o_orderdate", "o_totalprice",
            "l_quantity"]
    df = pd.concat([pd.read_parquet(p, columns=cols)
                    for p in data["paths"]], ignore_index=True)
    df["o_orderdate"] = df["o_orderdate"].astype(str)
    yield seed, eng, data["reference"], df
    eng.close()


@pytest.mark.parametrize("template", sorted(tpch_flat_having.templates()))
def test_q18_equals_the_plain_reference_and_pandas(q18, template):
    seed, eng, reference, df = q18
    got = eng.sql(tpch_flat_having.templates()[template])
    rec = eng.runner.history[-1]
    want = tpch_flat_having.answers(reference)[template]
    assert list(got.columns) == want["columns"]
    assert got.to_dict("records") == want["rows"]
    quantity = tpch_flat_having.reference.QUANTITY[template]
    assert len(want["rows"]) == Q18_SEEDS[seed][
        sorted(tpch_flat_having.reference.QUANTITY.values()).index(quantity)]
    g = df.groupby(["o_custkey", "l_orderkey", "o_orderdate"],
                   as_index=False).agg(o_totalprice=("o_totalprice", "max"),
                                       sum_quantity=("l_quantity", "sum"))
    g = g[g.sum_quantity > quantity].sort_values(
        ["o_totalprice", "o_orderdate", "l_orderkey"],
        ascending=[False, True, True]).head(100)
    assert got["o_orderkey"].tolist() == g["l_orderkey"].tolist()
    assert got["c_custkey"].tolist() == g["o_custkey"].tolist()
    assert got["o_totalprice"].tolist() == g["o_totalprice"].tolist()
    assert got["sum_quantity"].tolist() == g["sum_quantity"].tolist()
    # the record: the sparse path, every table read at the runs'
    # boundaries, the int64 word under max(o_totalprice), one [cap] table
    assert rec["reduce_path"] == "sparse" and rec["having_where"] == "device"
    assert rec["reduce_form"] == "boundary" and rec["ext_word_bits"] == 64
    assert rec["cap_tables"] == 1
    # the tested sum(l_quantity), stored as int8, rides as one int32 word:
    # at most seven lines of fifty an order (PR 41)
    assert rec["sum_word_bits"] == 32 and "narrow_fallback" not in rec
    assert rec["having_groups_in"] == reference["n_orders"] \
        == rec["present_groups"]
    assert rec["having_rows_fetched"] == sparse_dispatch.HAVING_KEPT_MIN


def test_q18s_three_literals_are_one_program(q18):
    _seed, eng, _reference, _df = q18
    for sql in tpch_flat_having.templates().values():
        eng.sql(sql)        # warm, whatever ran before
    for sql in tpch_flat_having.templates().values():
        eng.sql(sql)
        rec = eng.runner.history[-1]
        assert rec["sparse_attempts"] == 1 and rec["jit_cache_hit"]
        assert not rec.get("recompiles")
    said = eng.explain(tpch_flat_having.templates()["q18"])
    assert said["rewritten"] and said["having_where"] == "device"


# ------------------------------------------------- every form, both sides

def _table(n=12_000, seed=5):
    rng = np.random.default_rng(seed)
    g = rng.integers(0, N_GROUPS, n)
    df = pd.DataFrame({
        "ts": pd.to_datetime("2021-03-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 30, n), unit="s"),
        "k": g.astype(np.int64),
        "s": np.array([f"s{i:02d}" for i in range(40)])[g % 40],
        "q": rng.integers(1, 51, n).astype(np.int64),
        "p": rng.integers(-2_000_000_000, 2_000_000_000, n).astype(np.int64),
        "d": rng.integers(-5, 60, n).astype(np.int64),
        "w": np.round(rng.random(n) * 50, 3),
    })
    df["d"] = df["d"].astype("Int64")
    df.loc[rng.random(n) < 0.3, "d"] = pd.NA
    return df


def _engine(df, **cfg):
    eng = Engine(EngineConfig(fallback_on_device_failure=False,
                              dense_group_budget=64, **cfg))
    eng.register_table("t", df, time_column="ts", block_rows=2048)
    return eng


@pytest.fixture(scope="module")
def table():
    return _table()


@pytest.fixture(scope="module")
def device(table):
    eng = _engine(table)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def host(table):
    """The same engine with the rule turned off: the parent's path."""
    import dataclasses
    eng = _engine(table)
    lower = eng.runner._lower_cached_inner
    eng.runner._lower_cached_inner = lambda query, t: dataclasses.replace(
        lower(query, t), having=None)   # nothing the device can decide
    yield eng
    eng.close()


SELECT = ("SELECT k, s, sum(q) AS sq, count(*) AS n, min(p) AS lo, "
          "max(p) AS hi, sum(w) AS sw FROM t GROUP BY k, s ")
FORMS = [
    ("gt", "sum(q) > 120"), ("lt", "sum(q) < 40"), ("eq", "sum(q) = 77"),
    ("ge", "sum(q) >= 120"), ("le", "sum(q) <= 40"), ("ne", "sum(q) <> 77"),
    ("and", "sum(q) > 90 AND count(*) < 5"),
    ("or", "sum(q) > 200 OR count(*) = 1"),
    ("not", "NOT (sum(q) > 60 OR count(*) > 3)"),
    ("alias", "sq > 150"), ("count", "count(*) >= 7"),
    ("int-min", "min(p) > 1500000000"), ("int-max", "max(p) < -1200000000"),
    ("min-and-sum", "min(p) < 0 AND sum(q) > 150"),
    ("negative-literal", "min(p) > -100000000"),
]


def _rows(frame):
    """The frame's rows, a null as None (NaN is not equal to itself)."""
    return frame.astype(object).where(frame.notna(), None).to_dict("records")


@pytest.mark.parametrize("case,having", FORMS, ids=[f[0] for f in FORMS])
def test_every_form_device_against_host(device, host, case, having):
    sql = SELECT + "HAVING " + having + " ORDER BY k"
    got, want = device.sql(sql), host.sql(sql)
    on_dev, on_host = device.runner.history[-1], host.runner.history[-1]
    assert on_dev["having_where"] == "device"
    assert on_host["having_where"] == "host"
    assert 0 < len(want) < on_host["having_groups_in"]
    assert _rows(got) == _rows(want)
    assert on_dev["having_groups_in"] == on_host["having_groups_in"] \
        == on_dev["present_groups"]
    # the device brings the kept bucket, the host the compact table
    kept = on_dev["having_rows_fetched"]
    assert kept >= max(sparse_dispatch.HAVING_KEPT_MIN, len(got)) \
        and kept & (kept - 1) == 0
    assert on_host["having_rows_fetched"] == on_host["sparse_cap"] > kept
    # only the tested aggregates' tables are built at [cap]
    tested = sum(name in having for name in ("sum(q)", "sq", "min(p)",
                                             "max(p)"))
    assert on_dev["cap_tables"] == 1 + tested      # sum(w) still scatters
    assert on_host["cap_tables"] == 5              # _keys and four tables
    spans = {s["name"]: s["attrs"] for s in _walk(
        device.tracer.last.to_json()) if s["name"] in ("having", "dispatch")}
    assert spans["having"] == {"where": "device",
                               "groups_in": on_dev["present_groups"],
                               "groups_out": len(got)}
    assert spans["dispatch"]["having_where"] == "device"


def test_the_hosts_having_runs_under_its_own_span(host):
    host.sql(SELECT + "HAVING sum(q) > 120")
    rec = host.runner.history[-1]
    tree = host.tracer.last.to_json()
    assemble = next(s for s in _walk(tree) if s["name"] == "assemble")
    span = next(s for s in _walk(assemble) if s["name"] == "having")
    assert span["attrs"]["where"] == "host" and not span.get("children")
    assert span["attrs"]["groups_in"] == rec["having_groups_in"]
    assert 0 < span["attrs"]["groups_out"] < span["attrs"]["groups_in"]
    assert "decode-groups" in {s["name"] for s in _walk(assemble)}


def _walk(tree):
    yield tree
    for c in tree.get("children", []):
        yield from _walk(c)


# --------------------------------------------------- the bucket's edges

@pytest.fixture(scope="module")
def ladder():
    """N_GROUPS groups of two rows; group g sums to g."""
    g = np.repeat(np.arange(N_GROUPS, dtype=np.int64), 2)
    df = pd.DataFrame({
        "ts": pd.to_datetime("2021-03-01")
        + pd.to_timedelta(np.arange(len(g)), unit="s"),
        "k": g, "v": np.where(np.arange(len(g)) % 2 == 0, g, 0)})
    eng = _engine(df)
    yield eng
    eng.close()


@pytest.mark.parametrize("passing,attempts,bucket", [
    (0, 1, 1024), (1, 1, 1024), (1023, 1, 1024), (1024, 1, 1024),
    (1025, 2, 2048), (N_GROUPS, 2, 4096)],
    ids=["none", "one", "kept-1", "exactly-kept", "overflow", "all"])
def test_the_kept_bucket_holds_what_passes_or_is_grown(ladder, passing,
                                                       attempts, bucket):
    eng = ladder
    eng.runner._cap_hints = {k: v for k, v in eng.runner._cap_hints.items()
                             if k[-1] != "kept"}   # each case starts anew
    sql = ("SELECT k, sum(v) AS sv FROM t GROUP BY k "
           f"HAVING sum(v) >= {N_GROUPS - passing}")
    eng.sql(sql)        # the count probe of the first case; then warm
    eng.runner._cap_hints = {k: v for k, v in eng.runner._cap_hints.items()
                             if k[-1] != "kept"}
    got = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert got["k"].tolist() == got["sv"].tolist() \
        == list(range(N_GROUPS - passing, N_GROUPS))
    assert rec["having_where"] == "device"
    assert rec["sparse_attempts"] == attempts
    # no bucket is larger than the compact table it is cut from
    bucket = min(bucket, rec["sparse_cap"])
    assert rec["having_rows_fetched"] == bucket >= passing
    again = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert _rows(again) == _rows(got)
    # the hint keeps the grown bucket: the next run attempts once
    assert rec["sparse_attempts"] == 1 and rec["jit_cache_hit"]
    assert rec["having_rows_fetched"] == bucket


# ------------------------------------------------ what stays on the host

HOST_SIDE = [
    ("float-sum", "sum(w) > 100.5", None),
    ("float-literal", "sum(q) > 120.5", None),
    ("post-aggregation", "sum(q) / count(*) > 30", None),
    ("sketch", "approx_count_distinct(d) > 3", ("u",)),
    ("mixed", "sum(q) > 100 AND sum(w) > 100", None),
]


@pytest.mark.parametrize("case,having,approx", HOST_SIDE,
                         ids=[h[0] for h in HOST_SIDE])
def test_what_the_device_cannot_decide_stays_on_the_host(device, host, case,
                                                         having, approx):
    sql = ("SELECT k, s, sum(q) AS sq, count(*) AS n, sum(w) AS sw, "
           "approx_count_distinct(d) AS u FROM t GROUP BY k, s HAVING "
           + having + " ORDER BY k")
    got, want = device.sql(sql), host.sql(sql)
    rec = device.runner.history[-1]
    assert rec["having_where"] == "host" and rec["reduce_path"] == "sparse"
    assert rec["having_rows_fetched"] == rec["sparse_cap"]
    assert 0 < len(got) < rec["having_groups_in"]
    assert _rows(got) == _rows(want)


def test_a_dimension_selector_stays_on_the_host(device, table):
    query = {
        "queryType": "groupBy", "dataSource": "t", "granularity": "all",
        "dimensions": ["k", "s"], "intervals": ["2021-01-01/2022-01-01"],
        "aggregations": [{"type": "longSum", "name": "sq",
                          "fieldName": "q"}],
        "having": {"type": "and", "havingSpecs": [
            {"type": "dimSelector", "dimension": "s", "value": "s07"},
            {"type": "greaterThan", "aggregation": "sq", "value": 100}]}}
    res = device.execute_ir(query)
    rec = device.runner.history[-1]
    assert rec["having_where"] == "host" and rec["reduce_path"] == "sparse"
    g = table.groupby(["k", "s"], as_index=False).agg(sq=("q", "sum"))
    g = g[(g.s == "s07") & (g.sq > 100)]
    assert sorted((r["k"], r["sq"]) for r in res.rows) \
        == sorted(zip(g.k.tolist(), g.sq.tolist()))
    assert len(g) > 0


NULLABLE = [
    ("min", "min(d) > 20"), ("max", "max(d) < 10"),
    ("not-min", "NOT (min(d) > 0)"),
    ("filtered", "max(d) >= 55 OR min(d) = -5"),
]


@pytest.mark.parametrize("case,having", NULLABLE,
                         ids=[n[0] for n in NULLABLE])
def test_a_nullable_min_max_is_null_where_no_row_counts(device, host, table,
                                                        case, having):
    """A min / max over no non-null row is null and a comparison with null
    false, on the device as on the host: the program tests the aggregate's
    non-null count beside its table. Groups whose `d` is null in every row
    are among those tested."""
    sql = ("SELECT k, s, min(d) AS lo, max(d) AS hi, count(*) AS n FROM t "
           "GROUP BY k, s HAVING " + having + " ORDER BY k")
    got, want = device.sql(sql), host.sql(sql)
    rec = device.runner.history[-1]
    assert rec["having_where"] == "device"
    all_null = table.groupby(["k", "s"])["d"].count().eq(0).sum()
    assert all_null > 0 and 0 < len(want) < rec["having_groups_in"]
    assert _rows(got) == _rows(want)
    # each tested min / max brings its non-null count's table too
    assert rec["cap_tables"] == 2 * sum(f in having
                                        for f in ("min(d)", "max(d)"))


def test_having_with_order_by_limit_and_offset(device, host, table):
    sql = (SELECT + "HAVING sum(q) > 100 AND count(*) > 2 "
           "ORDER BY sq DESC, k LIMIT 25 OFFSET 10")
    got, want = device.sql(sql), host.sql(sql)
    assert device.runner.history[-1]["having_where"] == "device"
    assert _rows(got) == _rows(want) and len(got) == 25
    g = table.groupby(["k", "s"], as_index=False).agg(sq=("q", "sum"),
                                                      n=("q", "size"))
    g = g[(g.sq > 100) & (g.n > 2)].sort_values(
        ["sq", "k"], ascending=[False, True]).iloc[10:35]
    assert got["k"].tolist() == g["k"].tolist()
    assert got["sq"].tolist() == g["sq"].tolist()


# ------------------------------------------------------------- a mesh

@pytest.mark.parametrize("chips", [2, 4])
def test_a_mesh_gives_the_one_chip_answer_with_the_having_on_the_host(
        device, table, chips):
    """A chip's partial sum decides nothing: over a mesh the merged table
    goes to the host and the HAVING with it."""
    sql = ("SELECT k, s, sum(q) AS sq, count(*) AS n, min(p) AS lo FROM t "
           "GROUP BY k, s HAVING sum(q) > 120 AND min(p) < 0 ORDER BY k")
    eng = _engine(table, num_shards=chips)
    try:
        got = eng.sql(sql)
        rec = eng.runner.history[-1]
        assert rec["having_where"] == "host"
        assert rec["num_shards"] == chips and rec["reduce_path"] == "sparse"
        assert eng.explain(sql)["having_where"] == "host"
    finally:
        eng.close()
    want = device.sql(sql)
    assert device.runner.history[-1]["having_where"] == "device"
    assert _rows(got) == _rows(want) and len(got) > 0


# ----------------------------------------------------------- the budget

@pytest.mark.parametrize("budget,serves", [(1 << 11, False),
                                           (1 << 12, True)],
                         ids=["refused", "served"])
def test_the_group_budget_refuses_legibly_or_serves(table, budget, serves):
    """The default budget (2^21) against 14,996,491 order groups, scaled
    down: ~2,900 present groups against 2,048 and 4,096. The refusal comes
    from the count, before any table is sized."""
    eng = _engine(table, sparse_group_budget=budget, sparse_group_cap=64)
    sql = SELECT + "HAVING sum(q) > 200 ORDER BY k"
    groups = table.groupby(["k", "s"]).ngroups
    assert 1 << 11 < groups <= 1 << 12
    g = table.groupby(["k", "s"], as_index=False).agg(sq=("q", "sum"))
    try:
        got = eng.sql(sql)
        rec = eng.runner.history[-1]
        assert got["sq"].tolist() == g[g.sq > 200]["sq"].tolist()
        if not serves:
            # not a device answer: the benchmark counts it a failed query
            assert rec["query_type"] == "fallback"
            assert (f"{groups} present groups exceed sparse budget "
                    f"{budget}") in rec["fallback_reason"]
            spans = [s["name"] for s in _walk(eng.tracer.last.to_json())]
            assert "sparse-count" in spans
            assert "sparse-attempt" not in spans
            return
        assert rec["sparse_cap"] == budget and rec["sparse_attempts"] == 2
        assert rec["present_groups"] == groups
        assert rec["having_where"] == "device"
        eng.sql(sql)
        assert eng.runner.history[-1]["sparse_attempts"] == 1
    finally:
        eng.close()


# ------------------------- how the tested table is read (PR 43): the counter

def _boundary_sorted(eng) -> float:
    return sum(
        float(ln.rsplit(" ", 1)[1])
        for ln in eng.metrics.render().splitlines()
        if ln.startswith("tpu_olap_sparse_boundary_sorted_total"))


def _dispatch_attrs(eng) -> dict:
    return next(s["attrs"] for s in _walk(eng.tracer.last.to_json())
                if s["name"] == "dispatch")


def test_q18s_tested_sum_rides_the_sort_of_the_runs_first_rows(q18):
    """Q18's compact table is a large share of the rows sorted (one slot
    to three or four rows, as at SF10): the tested sum's prefix rides
    `starts`' sort. The record and the `dispatch` span say `sorted`, and
    the registry counts the dispatch."""
    _seed, eng, _reference, _df = q18
    sql = tpch_flat_having.templates()["q18_312"]
    before = _boundary_sorted(eng)
    eng.sql(sql)
    rec = eng.runner.history[-1]
    assert rec["boundary_read"] == "sorted" and rec["cap_tables"] == 1
    assert rec["sum_word_bits"] == 32 and "narrow_fallback" not in rec
    assert _dispatch_attrs(eng)["boundary_read"] == "sorted"
    assert _boundary_sorted(eng) == before + 1


def test_a_small_table_over_many_rows_is_gathered_and_not_counted(table):
    """A hundred groups over twelve thousand rows: a HAVING's tested table
    stays a gather after `starts`' one-operand sort, and the counter does
    not move. A HAVING over the row count alone reads no table at the
    boundaries and says nothing."""
    df = table.copy()
    df["k"] = df["k"] % 100
    eng = _engine(df)
    try:
        sql = "SELECT k, sum(q) AS sq FROM t GROUP BY k HAVING sum(q) > 3000"
        got = eng.sql(sql)
        rec = eng.runner.history[-1]
        want = df.groupby("k")["q"].sum()
        assert got["sq"].tolist() == want[want > 3000].tolist()
        assert rec["reduce_path"] == "sparse" \
            and rec["having_where"] == "device"
        assert rec["boundary_read"] == "gather"
        assert _dispatch_attrs(eng)["boundary_read"] == "gather"
        assert _boundary_sorted(eng) == 0
        sql = "SELECT k, sum(q) AS sq FROM t GROUP BY k HAVING count(*) > 110"
        eng.sql(sql)
        assert "boundary_read" not in eng.runner.history[-1]
        assert "boundary_read" not in _dispatch_attrs(eng)
        assert _boundary_sorted(eng) == 0
    finally:
        eng.close()


def test_the_counted_cap_decides_how_the_table_program_reads(table):
    """A group space past the budget: the cap is what holds the groups a
    run counts, so the rule is asked once the count is in. The program
    the runner keeps for that cap is the one the record names (`sorted`
    in its key), and a second run of the plan is that program again."""
    eng = _engine(table, sparse_group_budget=1 << 12)
    try:
        sql = SELECT + "HAVING sum(q) > 120"
        eng.sql(sql)
        rec = eng.runner.history[-1]
        # the count, the table, and again where the kept bucket grew
        assert rec["sparse_attempts"] >= 2
        assert rec["boundary_read"] == "sorted"
        tables = [k for k in eng.runner._jit_cache
                  if "sparse" in k and k[-1].cap == rec["sparse_cap"]]
        assert tables and all(k[-1].boundary == "sorted" for k in tables)
        eng.sql(sql)
        rec = eng.runner.history[-1]
        assert rec["boundary_read"] == "sorted" and rec["jit_cache_hit"] \
            and rec["sparse_attempts"] == 1
        assert _boundary_sorted(eng) == 2
    finally:
        eng.close()
