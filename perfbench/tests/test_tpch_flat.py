"""The TPC-H flat generator and its plain reference, at 60,000 rows."""

import datetime

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from perfbench.datasets import tpch_flat
from perfbench.datasets.tpch_flat import reference
from perfbench.lib import verify

ROWS, SEED = 60_000, 2_147_483_659   # a seed beyond 32 signed bits
OPC = 7_000                          # orders a chunk: three files


@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch_flat")
    return tpch_flat.generate(ROWS, SEED, str(d), workers=1,
                              orders_per_chunk=OPC)


def _table(paths):
    return pa.concat_tables([pq.read_table(p) for p in sorted(paths)])


@pytest.fixture(scope="module")
def rows(generated):
    return _table(generated["paths"]).to_pylist()


def test_totals_equal_pyarrow_over_the_files(generated):
    t = _table(generated["paths"])
    totals = tpch_flat.totals(generated["reference"])
    assert totals["rows"] == t.num_rows == ROWS
    # the harness prints this key by SSB's name; here it is the base price
    assert totals["sum_lo_revenue"] == pc.sum(t["l_extendedprice"]).as_py()
    assert sum(generated["reference"]["rows_by_shipmonth"]) == ROWS
    assert t.num_columns == 38
    assert "l_comment" not in t.schema.names


def test_same_tables_for_any_worker_count(generated, tmp_path):
    """Three spawned workers write what one process writes, chunk by chunk,
    and return the same reference."""
    other = tpch_flat.generate(ROWS, SEED, str(tmp_path), workers=3,
                               orders_per_chunk=OPC)
    assert len(other["paths"]) == len(generated["paths"]) == 3
    assert _table(other["paths"]).equals(_table(generated["paths"]))
    assert other["reference"] == generated["reference"]


def test_another_seed_gives_other_data_in_the_same_months(generated,
                                                          tmp_path):
    other = tpch_flat.generate(ROWS, SEED + 1, str(tmp_path), workers=1,
                               orders_per_chunk=OPC)
    a, b = generated["reference"], other["reference"]
    assert a["sum_l_extendedprice"] != b["sum_l_extendedprice"]
    assert tpch_flat.answers(a)["q3"]["rows"] \
        != tpch_flat.answers(b)["q3"]["rows"]
    # every seed fills the calendar months alike: the engine cuts the same
    # segments, every program keeps its shape (datagen.py, departure 3)
    assert a["rows_by_shipmonth"] == b["rows_by_shipmonth"]


@pytest.mark.parametrize("seed", [SEED, SEED + 1, 7])
def test_every_seed_gives_every_customer_an_order(seed):
    """Three orders a customer here, so independent draws would leave ten
    of the 200 without one; every seed has to give `c_name` the same
    dictionary, because the engine compiles its size into the programs
    (datagen.py, departure 5)."""
    from perfbench.datasets.tpch_flat import datagen
    n_rows = 2_400
    dims = datagen.dimension_codes(n_rows, seed)
    cols = datagen.fact_columns(n_rows, seed, 0, dims, opc=700)
    n_cust = datagen.dim_sizes(n_rows)[0]
    assert sorted(set(cols["o_custkey"].tolist())) \
        == [k for k in range(1, n_cust + 1) if k % 3]


def test_q1_and_q3_equal_a_row_by_row_loop(generated, rows):
    """Q1 and Q3 again, as plain Python over the rows of the files: dates
    compared as dates and strings, sums as Python integers."""
    q1, q3 = {}, {}
    cut = datetime.datetime(1998, 9, 2)
    day = datetime.datetime(1995, 3, 15)
    for r in rows:
        volume = r["l_extendedprice"] * (100 - r["l_discount"])
        if r["l_shipdate"] <= cut:
            g = q1.setdefault((r["l_returnflag"], r["l_linestatus"]),
                              [0, 0, 0, 0, 0])
            g[0] += r["l_quantity"]
            g[1] += r["l_extendedprice"]
            g[2] += volume
            g[3] += volume * (100 + r["l_tax"])
            g[4] += 1
        if r["c_mktsegment"] == "BUILDING" \
                and r["o_orderdate"] < "1995-03-15" and r["l_shipdate"] > day:
            k = (r["l_orderkey"], r["o_orderdate"], r["o_shippriority"])
            q3[k] = q3.get(k, 0) + volume
    ans = tpch_flat.answers(generated["reference"])
    assert ans["q1"]["rows"] == [
        dict(zip(ans["q1"]["columns"], k + tuple(v)))
        for k, v in sorted(q1.items())]
    top = sorted(q3.items(), key=lambda kv: (-kv[1], kv[0][1], kv[0][0]))
    assert ans["q3"]["rows"] == [
        {"l_orderkey": k[0], "revenue": v, "o_orderdate": k[1],
         "o_shippriority": k[2]} for k, v in top[:10]]
    assert generated["reference"]["groups"]["q3"] == len(q3) > 10


def test_clause_4_2_3_rules_the_templates_depend_on(rows):
    ship_of = datetime.datetime.fromisoformat
    orders = {}
    for r in rows:
        pk = r["l_partkey"]
        price = 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1000)
        assert r["p_retailprice"] == price
        assert r["l_extendedprice"] == r["l_quantity"] * price
        assert 1 <= r["l_quantity"] <= 50 and 0 <= r["l_discount"] <= 10 \
            and 0 <= r["l_tax"] <= 8
        order, ship = ship_of(r["o_orderdate"]), r["l_shipdate"]
        commit, receipt = ship_of(r["l_commitdate"]), \
            ship_of(r["l_receiptdate"])
        assert 1 <= (ship - order).days <= 121
        assert 30 <= (commit - order).days <= 90
        assert 1 <= (receipt - ship).days <= 30
        assert "1992-01-01" <= r["o_orderdate"] <= "1998-08-02"
        current = datetime.datetime(1995, 6, 17)
        assert (r["l_returnflag"] in "RA") == (receipt <= current)
        assert (r["l_linestatus"] == "O") == (ship > current)
        assert r["o_custkey"] % 3 != 0
        assert r["l_orderkey"] % 32 in range(1, 9)      # sparse keys
        assert r["c_name"] == f"Customer#{r['o_custkey']:09d}"
        orders.setdefault(r["l_orderkey"], []).append(r)
    sizes = {len(v) for v in orders.values()}
    assert sizes == set(range(1, 8))
    for lines in orders.values():
        assert [r["l_linenumber"] for r in lines] \
            == list(range(1, len(lines) + 1))
        assert len({(r["o_orderdate"], r["o_custkey"], r["o_orderstatus"],
                     r["o_totalprice"]) for r in lines}) == 1
        status = {r["l_linestatus"] for r in lines}
        assert lines[0]["o_orderstatus"] == (
            "P" if len(status) == 2 else status.pop())


@pytest.mark.parametrize("name", ["q3", "q10"])
def test_no_tie_at_the_limit(generated, name):
    """The row a LIMIT keeps last and the first it drops differ in revenue,
    so the answer does not rest on the tie-breaking key."""
    limit = reference.SPECS[name]["limit"]
    ranked = reference.ranked(generated["reference"], name, limit + 1)
    assert len(ranked) == limit + 1
    assert ranked[limit - 1]["revenue"] > ranked[limit]["revenue"]
    assert tpch_flat.answers(generated["reference"])[name]["rows"] \
        == ranked[:limit]


def test_equal_answer_passes_and_a_doctored_one_fails(generated):
    for name, exp in tpch_flat.answers(generated["reference"]).items():
        served = {"columns": list(exp["columns"]),
                  "rows": [dict(r) for r in exp["rows"]]}
        assert verify.answer_mismatches(served, exp) == [], name
        col = exp["columns"][-1] if name != "q10" else "revenue"
        if served["rows"][0][col] is not None:
            served["rows"][0][col] += 1
            assert verify.answer_mismatches(served, exp) != [], name


def test_needed_bytes_follow_the_time_filter(generated):
    ref = generated["reference"]
    assert tpch_flat.needed_rows("q10", ref) == ROWS
    assert 0 < tpch_flat.needed_rows("q14", ref) \
        < tpch_flat.needed_rows("q6", ref) \
        < tpch_flat.needed_rows("q7", ref) \
        < tpch_flat.needed_rows("q3", ref) \
        < tpch_flat.needed_rows("q1", ref) < ROWS
    # rows the program pruned are never counted as read
    assert tpch_flat.needed_bytes("q1", ref, rows_scanned=1000) == 1000 * 9
    assert set(tpch_flat.templates()) == set(reference.SPECS) \
        == set(tpch_flat.bytes.SCAN)
