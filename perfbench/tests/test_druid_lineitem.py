"""The Druid lineitem generator and its plain reference, at 60,000 rows."""

import datetime

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from perfbench.datasets import druid_lineitem
from perfbench.datasets.druid_lineitem import bytes as lbytes
from perfbench.datasets.druid_lineitem import datagen, reference
from perfbench.lib import verify

ROWS, SEED = 60_000, 2_147_483_659   # a seed beyond 32 signed bits
OPC = 7_000                          # orders a chunk: three files


@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    d = tmp_path_factory.mktemp("druid_lineitem")
    return druid_lineitem.generate(ROWS, SEED, str(d), workers=1,
                                   orders_per_chunk=OPC)


def _table(paths):
    return pa.concat_tables([pq.read_table(p) for p in sorted(paths)])


@pytest.fixture(scope="module")
def rows(generated):
    return _table(generated["paths"]).to_pylist()


def _same_reference(a, b):
    assert druid_lineitem.answers(a) == druid_lineitem.answers(b)
    assert a["rows_by_shipmonth"] == b["rows_by_shipmonth"]
    for name in ("parts", "parts_window"):
        for k, v in a[name].items():
            assert np.array_equal(v, b[name][k]), (name, k)


def test_totals_equal_pyarrow_over_the_files(generated):
    t = _table(generated["paths"])
    totals = druid_lineitem.totals(generated["reference"])
    assert totals["rows"] == t.num_rows == ROWS
    assert totals["sum_lo_revenue"] == pc.sum(t["l_extendedprice"]).as_py()
    assert sum(generated["reference"]["rows_by_shipmonth"]) == ROWS
    assert tuple(t.schema.names) == datagen.COLUMNS and t.num_columns == 15
    assert pc.max(t["l_partkey"]).as_py() <= datagen.parts(ROWS) == 2_000


def test_same_seed_same_tables_for_any_worker_count(generated, tmp_path):
    """Three spawned workers, whose parts arrive in any order, write what
    one process writes and merge to the same reference."""
    other = druid_lineitem.generate(ROWS, SEED, str(tmp_path), workers=3,
                                    orders_per_chunk=OPC)
    assert len(other["paths"]) == len(generated["paths"]) == 3
    assert _table(other["paths"]).equals(_table(generated["paths"]))
    _same_reference(other["reference"], generated["reference"])


def test_another_seed_gives_other_data_in_the_same_months(generated,
                                                          tmp_path):
    other = druid_lineitem.generate(ROWS, SEED + 1, str(tmp_path),
                                    workers=1, orders_per_chunk=OPC)
    a, b = generated["reference"], other["reference"]
    assert a["sum_all"] != b["sum_all"]
    assert druid_lineitem.answers(a)["top_100_parts"]["rows"] \
        != druid_lineitem.answers(b)["top_100_parts"]["rows"]
    # every seed fills the calendar months alike and spans the same keys
    # and commit dates: the engine cuts the same segments and every
    # program keeps its shape
    assert a["rows_by_shipmonth"] == b["rows_by_shipmonth"]
    assert np.array_equal(a["commit_rows"] > 0, b["commit_rows"] > 0)


def test_the_nine_answers_equal_a_row_by_row_loop(generated, rows):
    """Every template again as plain Python over the rows of the files:
    dates compared as dates and strings, sums as Python integers, the
    top-100s by (quantity descending, key ascending)."""
    day = datetime.datetime
    four = ("l_extendedprice", "l_discount", "l_tax", "l_quantity")
    names = reference.SUM_NAMES

    def sums(keep):
        return dict(zip(names, (sum(r[c] for r in rows if keep(r))
                                for c in four)))

    def details(keep):
        by = {}
        for r in rows:
            if keep(r):
                g = by.setdefault(r["l_partkey"], [0, 0, 99, -1])
                g[0] += r["l_quantity"]
                g[1] += r["l_extendedprice"]
                g[2] = min(g[2], r["l_discount"])
                g[3] = max(g[3], r["l_discount"])
        top = sorted(by.items(), key=lambda kv: (-kv[1][0], kv[0]))[:100]
        return [dict(zip(reference.DETAILS, (k, *v))) for k, v in top]

    ans = {k: v["rows"] for k, v in
           druid_lineitem.answers(generated["reference"]).items()}
    assert ans["count_star_interval"] == [{"cnt": sum(
        day(1992, 1, 3) <= r["l_shipdate"] <= day(1998, 11, 30)
        for r in rows)}]
    assert ans["sum_all"] == [sums(lambda r: True)]
    assert ans["sum_price"] == [{"sum_price": ans["sum_all"][0]["sum_price"]}]
    assert ans["sum_all_filter"] == [sums(lambda r: "AIR" in r["l_shipmode"])]
    assert {r["l_shipmode"] for r in rows if "AIR" in r["l_shipmode"]} \
        == {"AIR", "REG AIR"}
    assert ans["sum_all_year"] == [
        {"l_year": y, **sums(lambda r: r["l_shipdate"].year == y)}
        for y in range(1992, 1999)]
    all_parts = details(lambda r: True)
    assert ans["top_100_parts_details"] == all_parts
    assert ans["top_100_parts"] == [
        {k: r[k] for k in ("l_partkey", "sum_quantity")} for r in all_parts]
    assert ans["top_100_parts_filter"] == details(
        lambda r: day(1996, 1, 15) <= r["l_shipdate"] <= day(1998, 3, 15))
    by_date = {}
    for r in rows:
        by_date[r["l_commitdate"]] = by_date.get(r["l_commitdate"], 0) \
            + r["l_quantity"]
    assert ans["top_100_commitdate"] == [
        {"l_commitdate": d, "sum_quantity": q} for d, q in
        sorted(by_date.items(), key=lambda kv: (-kv[1], kv[0]))[:100]]


def test_the_threshold_cuts_through_a_tie(generated):
    """The 100th and the 101st part have the same quantity here, as they
    have for certain at the cell's size: the answer rests on the tie rule,
    and an answer that keeps the other of the two is refused."""
    total = generated["reference"]
    ranked = reference.ranked_parts(total["parts"], 101)
    assert ranked[99]["sum_quantity"] == ranked[100]["sum_quantity"]
    exp = druid_lineitem.answers(total)["top_100_parts"]
    served = {"columns": list(exp["columns"]),
              "rows": [dict(r) for r in exp["rows"]]}
    assert verify.answer_mismatches(served, exp) == []
    served["rows"][99] = {k: ranked[100][k] for k in exp["columns"]}
    assert verify.answer_mismatches(served, exp) != []


def test_equal_answer_passes_and_a_doctored_one_fails(generated):
    for name, exp in druid_lineitem.answers(generated["reference"]).items():
        served = {"columns": list(exp["columns"]),
                  "rows": [dict(r) for r in exp["rows"]]}
        assert verify.answer_mismatches(served, exp) == [], name
        served["rows"][0][exp["columns"][-1]] += 1
        assert verify.answer_mismatches(served, exp) != [], name


def test_needed_bytes_follow_the_time_filter(generated):
    ref = generated["reference"]
    assert set(druid_lineitem.templates()) == set(lbytes.SCAN) \
        == set(druid_lineitem.answers(ref))
    assert druid_lineitem.needed_rows("top_100_parts", ref) == ROWS
    assert 0 < druid_lineitem.needed_rows("count_star_interval", ref) \
        < druid_lineitem.needed_rows("top_100_parts_filter", ref) < ROWS
    assert druid_lineitem.needed_bytes("top_100_parts", ref) == ROWS * 5
    assert druid_lineitem.needed_bytes("top_100_parts_details", ref) \
        == ROWS * 10
    # rows the program pruned are never counted as read
    assert druid_lineitem.needed_bytes("sum_all", ref, rows_scanned=1000) \
        == 1000 * 7
    assert set(druid_lineitem.TOPN) == {t for t in lbytes.SCAN
                                        if t.startswith("top_100_")}


def test_register_stops_a_program_that_answers_min_with_a_float(generated):
    """The commit before this dataset came hands `min(l_discount)` over as
    a float64, which the comparison refuses: `register` ends such a run
    with an exit code and no result line."""
    import pandas as pd

    class Program:
        def __init__(self, lo):
            self.lo, self.tables = lo, []

        def register_table(self, name, *_a, **_k):
            self.tables.append(name)

        def sql(self, _query):
            return pd.DataFrame({"lo": [self.lo]})

    with pytest.raises(SystemExit, match="min\\(l_discount\\)"):
        druid_lineitem.register(Program(0.0), generated["paths"], ROWS, SEED)
    new = Program(0)
    druid_lineitem.register(new, generated["paths"], ROWS, SEED)
    assert new.tables == [druid_lineitem.TABLE]
