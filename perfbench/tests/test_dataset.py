"""The generator copy and the plain reference, at 60,000 rows."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from perfbench.datasets import ssb
from perfbench.lib import verify

ROWS, SEED = 60_000, 2_147_483_659   # a seed beyond 32 signed bits


@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    d = tmp_path_factory.mktemp("ssb")
    return ssb.generate(ROWS, SEED, str(d), workers=1, chunk_rows=25_000)


def _table(paths):
    return pa.concat_tables([pq.read_table(p) for p in sorted(paths)])


def test_totals_convention_equals_pyarrow(generated):
    """chip_smoke.py's convention: count(*) and sum(lo_revenue) of the
    reference against pyarrow over the files themselves."""
    t = _table(generated["paths"])
    totals = ssb.totals(generated["reference"])
    assert totals["rows"] == t.num_rows == ROWS
    assert totals["sum_lo_revenue"] == pc.sum(t["lo_revenue"]).as_py()
    assert sum(generated["reference"]["rows_by_yearmonth"]) == ROWS


def test_same_tables_for_any_worker_count(generated, tmp_path):
    """Three spawned workers write what one process writes, chunk by chunk,
    and return the same reference."""
    other = ssb.generate(ROWS, SEED, str(tmp_path), workers=3,
                         chunk_rows=25_000)
    assert len(other["paths"]) == len(generated["paths"]) == 3
    assert _table(other["paths"]).equals(_table(generated["paths"]))
    assert other["reference"] == generated["reference"]


def test_another_seed_gives_other_data(generated, tmp_path):
    other = ssb.generate(ROWS, SEED + 1, str(tmp_path), workers=1,
                         chunk_rows=25_000)
    assert other["reference"]["sum_lo_revenue"] != \
        generated["reference"]["sum_lo_revenue"]


def test_reference_equals_a_row_by_row_loop(generated):
    """Q2.1 and Q1.1 again, as plain Python over the rows of the files:
    strings compared as strings, sums as Python integers."""
    rows = _table(generated["paths"]).to_pylist()
    want, q11 = {}, 0
    for r in rows:
        if r["p_category"] == "MFGR#12" and r["s_region"] == "AMERICA":
            k = (r["d_year"], r["p_brand1"])
            want[k] = want.get(k, 0) + r["lo_revenue"]
        if r["d_year"] == 1993 and 1 <= r["lo_discount"] <= 3 \
                and r["lo_quantity"] < 25:
            q11 += r["lo_extendedprice"] * r["lo_discount"]
    ans = ssb.answers(generated["reference"])
    got = {(r["d_year"], r["p_brand1"]): r["revenue"]
           for r in ans["q2.1"]["rows"]}
    assert got == want and len(want) > 0
    assert [tuple(r[c] for c in ("d_year", "p_brand1"))
            for r in ans["q2.1"]["rows"]] == sorted(want)
    assert ans["q1.1"]["rows"] == [{"revenue": q11}]


def _served(expected):
    return {"columns": list(expected["columns"]),
            "rows": [dict(r) for r in expected["rows"]]}


def test_equal_answer_passes(generated):
    for name, exp in ssb.answers(generated["reference"]).items():
        assert verify.answer_mismatches(_served(exp), exp) == [], name


@pytest.mark.parametrize("doctor", ["sum_off_by_one", "sum_in_f32",
                                    "row_missing", "rows_swapped",
                                    "column_renamed"])
def test_doctored_answer_fails(generated, doctor):
    exp = ssb.answers(generated["reference"])["q3.1"]
    served = _served(exp)
    rows = served["rows"]
    if doctor == "sum_off_by_one":
        rows[3]["revenue"] += 1
    elif doctor == "sum_in_f32":
        # the whole column accumulated in float32: most sums at 60k rows
        # pass 2^24 and do not survive it
        for r in rows:
            r["revenue"] = int(np.float32(r["revenue"]))
        assert any(a["revenue"] != b["revenue"]
                   for a, b in zip(rows, exp["rows"]))
    elif doctor == "row_missing":
        rows.pop()
    elif doctor == "rows_swapped":
        rows[0], rows[-1] = rows[-1], rows[0]
    elif doctor == "column_renamed":
        served["columns"][-1] = "rev"
    assert verify.answer_mismatches(served, exp) != []


def test_needed_bytes_follow_the_time_filter(generated):
    ref = generated["reference"]
    assert ssb.needed_rows("q2.1", ref) == ROWS
    assert 0 < ssb.needed_rows("q1.2", ref) < ssb.needed_rows("q1.1", ref) \
        < ssb.needed_rows("q3.1", ref) < ROWS
    # rows the program pruned are never counted as read
    assert ssb.needed_bytes("q2.1", ref, rows_scanned=1000) == 1000 * 10
    assert ssb.needed_bytes("q2.1", ref) == ROWS * 10
