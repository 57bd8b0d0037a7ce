"""columnComparison filter: row-vs-row equality across columns
(SURVEY.md §3.3 filter family; the TPC-H Q5/Q7 shape).

Semantics under test (kernels/filtereval._colcmp_pair): a NULL operand
never matches at the leaf; NOT inversion makes NULL rows match `<>` —
exactly the pandas fallback's object-dtype behavior, so parity holds by
construction. String pairs translate codes across dictionaries via a
derived stream (one elementwise compare per dispatch, no gather); so do
the ordered comparisons: ranks for two string columns, int64 millis for
a string column against the time column (TPC-H Q12's two legs).
"""

import datetime
import json
import operator
import re

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.bench.parity import assert_frame_parity, run_both
from tpu_olap.executor import EngineConfig
from tpu_olap.ir.filters import ColumnComparisonFilter, filter_from_json


def _frame(n=4000, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 5, n).astype(float)
    x[rng.random(n) < 0.1] = np.nan
    y = rng.integers(0, 5, n).astype(float)
    y[rng.random(n) < 0.1] = np.nan
    return pd.DataFrame({
        "ts": pd.to_datetime("2024-03-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 30, n), unit="s"),
        # overlapping-but-distinct vocabularies: "kiev" only on the left,
        # "bern" only on the right — exercises absent-value translation
        "city": rng.choice(["rome", "oslo", "lima", "kiev", None], n),
        "dest": rng.choice(["rome", "oslo", "lima", "bern", None], n),
        "x": x, "y": y,
        "v": rng.integers(0, 100, n).astype(np.int64),
    })


@pytest.fixture(scope="module")
def eng():
    e = Engine()
    e.register_table("t", _frame(), time_column="ts")
    return e


PARITY_SQL = [
    "SELECT count(*) AS n, sum(v) AS s FROM t WHERE city = dest",
    "SELECT count(*) AS n FROM t WHERE city <> dest",
    "SELECT count(*) AS n FROM t WHERE NOT (city = dest)",
    "SELECT city, count(*) AS n FROM t WHERE city = dest GROUP BY city",
    "SELECT count(*) AS n FROM t WHERE x = y",
    # <> with NULL operands: NOT(==) matches the fallback's NaN != x;
    # a bare ExpressionFilter(!=) would exclude them (regression lock
    # for the round-4 lowering fix in planner/plan.py::_to_filter)
    "SELECT count(*) AS n FROM t WHERE x <> y",
    "SELECT count(*) AS n FROM t WHERE x + 1 <> y + 1",
    "SELECT count(*) AS n FROM t WHERE city = dest AND x = y",
]


@pytest.mark.parametrize("sql", PARITY_SQL)
def test_device_parity(eng, sql):
    dev, fb, _ = run_both(eng, sql)  # raises ParityError on fallback
    assert_frame_parity(dev, fb, ordered=False, label=sql)


def test_null_semantics_exact(eng):
    """Pin the counts, not just parity: nulls never match `=`; every
    null-operand row matches `<>` (NOT inversion)."""
    f = _frame()
    both = (f.city.notna() & f.dest.notna())
    eq = int((both & (f.city == f.dest)).sum())
    got = eng.sql("SELECT count(*) AS n FROM t WHERE city = dest")
    assert int(got.iloc[0]["n"]) == eq
    got = eng.sql("SELECT count(*) AS n FROM t WHERE city <> dest")
    assert int(got.iloc[0]["n"]) == len(f) - eq


def test_mesh_and_pallas_force():
    frame = _frame(seed=11)
    for cfg, tag in [(EngineConfig(num_shards=8), "mesh8"),
                     (EngineConfig(use_pallas="force"), "pallas-force")]:
        e = Engine(cfg)
        e.register_table("t", frame, time_column="ts")
        sql = ("SELECT city, sum(v) AS s FROM t WHERE city = dest "
               "GROUP BY city")
        dev, fb, _ = run_both(e, sql)
        assert_frame_parity(dev, fb, ordered=False, label=tag)
        if tag == "pallas-force":
            # columnComparison IS Pallas-whitelisted: the translation
            # stream enters the kernel as an int32 row (no in-kernel
            # gather), so the fused kernel must be active for this plan
            from tpu_olap.executor.lowering import lower
            plan = e.planner.plan(sql)
            phys = lower(plan.query, plan.entry.segments, e.config)
            assert phys.pallas_reason is None, phys.pallas_reason


def test_scan_path(eng):
    got = eng.sql("SELECT city, dest, v FROM t WHERE city = dest "
                  "ORDER BY v DESC LIMIT 5")
    assert len(got) == 5
    assert (got["city"] == got["dest"]).all()


def test_raw_ir_passthrough(eng):
    body = json.dumps({
        "queryType": "timeseries", "granularity": "all",
        "aggregations": [{"type": "count", "name": "n"}],
        "filter": {"type": "columnComparison",
                   "dimensions": ["city", "dest"]},
        "intervals": ["1000-01-01/3000-01-01"],
    })
    out = eng.sql(f"ON DRUID DATASOURCE t EXECUTE QUERY '{body}'")
    f = _frame()
    exp = int((f.city.notna() & (f.city == f.dest)).sum())
    assert int(out.iloc[0]["n"]) == exp


def test_serde_roundtrip():
    f = ColumnComparisonFilter(("a", "b", "c"))
    assert filter_from_json(f.to_json()) == f
    with pytest.raises(ValueError):
        filter_from_json({"type": "columnComparison", "dimensions": ["a"]})


def test_mixed_types_fall_back(eng):
    """String-vs-numeric comparison is outside the filter algebra — the
    fallback must answer it (correct-but-slow, never an error)."""
    from tpu_olap.bench.parity import ParityError
    with pytest.raises(ParityError):
        run_both(eng, "SELECT count(*) AS n FROM t WHERE city = v")


@pytest.mark.parametrize("op", ["<", "<=", ">", ">="])
def test_ordered_string_comparison_on_device(eng, op):
    """Row-vs-row ORDER of two string columns (TPC-H Q12's `l_commitdate <
    l_receiptdate`): ranks in the merged dictionary, served by the device
    (a structural fallback before), NULL operands never matching."""
    out, fb, _plan = run_both(
        eng, f"SELECT count(*) AS n FROM t WHERE city {op} dest")
    f = _frame()
    both = f[f.city.notna() & f.dest.notna()]
    exp = int(eval(f"(both.city {op} both.dest).sum()"))
    assert 0 < exp < len(both)
    assert int(out.iloc[0]["n"]) == int(fb.iloc[0]["n"]) == exp


def test_ordered_serde_roundtrip():
    f = ColumnComparisonFilter(("a", "b"), "<=")
    assert f.to_json()["op"] == "<=" and filter_from_json(f.to_json()) == f
    assert "op" not in ColumnComparisonFilter(("a", "b")).to_json()
    with pytest.raises(ValueError):
        ColumnComparisonFilter(("a", "b", "c"), "<")


def test_derived_stream_cached(eng):
    """The translation stream is built once per content token and reused
    across dispatches (the round-4 no-per-dispatch-gather rule)."""
    ds = eng.runner._datasets.get("t")
    if ds is None:
        eng.sql("SELECT count(*) AS n FROM t WHERE city = dest")
        ds = eng.runner._datasets["t"]
    eng.sql("SELECT count(*) AS n FROM t WHERE city = dest")
    n0 = len(ds._derived)
    eng.sql("SELECT sum(v) AS s FROM t WHERE city = dest")
    assert len(ds._derived) == n0  # same pair -> same token, no rebuild


# ---- the time column against a string column of dates (TPC-H Q12's
# `l_shipdate < l_commitdate`)

DATES_ROWS = 20_000
_DAY0 = pd.Timestamp("2024-03-01")


def _dates_frame(n=DATES_ROWS, seed=3, span=35):
    """`due` holds ISO dates, ISO date-times with a time of day (a tenth of
    them the row's own `ts`, where `<` and `<=` part), NULLs and a string
    that is no date; `ts` is never at midnight. `commit` / `receipt` /
    `mode` / `prio` give the query Q12's shape, `commit` and `receipt`
    dictionaries of some `span` dates."""
    rng = np.random.default_rng(seed)
    ts = _DAY0 + pd.to_timedelta(
        rng.integers(0, 30, n) * 86400 + rng.integers(1, 86400, n), unit="s")
    day = pd.Series(_DAY0 + pd.to_timedelta(rng.integers(0, 30, n),
                                            unit="D"))
    at = day + pd.to_timedelta(rng.integers(1, 86400, n), unit="s")
    kind = rng.integers(0, 10, n)
    due = day.dt.strftime("%Y-%m-%d")
    due = due.where(kind < 4, at.dt.strftime("%Y-%m-%dT%H:%M:%S"))
    due = due.where(kind != 7, pd.Series(ts).dt.strftime("%Y-%m-%d %H:%M:%S"))
    due = due.where(kind != 8, None).where(kind != 9, "no date")

    def dates(lo, hi):
        return pd.Series(_DAY0 + pd.to_timedelta(
            rng.integers(lo, hi, n), unit="D")).dt.strftime("%Y-%m-%d")

    return pd.DataFrame({
        "ts": ts, "due": due, "commit": dates(0, span),
        "receipt": dates(1, span + 5),
        "mode": rng.choice(["MAIL", "SHIP", "AIR", "RAIL", "TRUCK"], n),
        "prio": rng.choice(["1-URGENT", "2-HIGH", "3-MEDIUM"], n),
    })


Q12_SHAPED = """
    SELECT mode, sum(CASE WHEN prio = '1-URGENT' OR prio = '2-HIGH'
                          THEN 1 ELSE 0 END) AS high, count(*) AS n
    FROM d WHERE mode IN ('MAIL', 'SHIP') AND commit < receipt
      AND ts < commit AND receipt >= '{lo}' AND receipt < '2024-03-25'
    GROUP BY mode ORDER BY mode"""


@pytest.fixture(scope="module")
def dates_eng():
    e = Engine()
    e.register_table("d", _dates_frame(), time_column="ts")
    return e


_OPS = {"<": operator.lt, "<=": operator.le,
        ">": operator.gt, ">=": operator.ge}


@pytest.mark.parametrize("time_first", [True, False],
                         ids=["time-op-string", "string-op-time"])
@pytest.mark.parametrize("op", sorted(_OPS))
def test_time_column_against_string_column(dates_eng, op, time_first):
    """Millisecond-exact on either side of all four operators: a string
    with a time of day compares by it, a NULL and a string that is no
    date never match. Device = pandas fallback = the count made here."""
    sql = "SELECT count(*) AS n FROM d WHERE " + (
        f"ts {op} due" if time_first else f"due {op} ts")
    out, fb, _plan = run_both(dates_eng, sql)
    rec = dates_eng.runner.history[-1]
    assert rec["filter_streams"] == 1
    f = _dates_frame()

    def millis(v):
        try:
            return datetime.datetime.fromisoformat(v).replace(
                tzinfo=datetime.timezone.utc).timestamp() * 1000
        except (TypeError, ValueError):
            return None

    t = f.ts.astype("datetime64[ms]").astype(np.int64)
    due = f.due.map(millis)
    is_date = due.notna()       # a NULL and "no date" never match
    sides = (t[is_date], due[is_date])
    exp = int(_OPS[op](*(sides if time_first else sides[::-1])).sum())
    assert 0 < exp < len(f)
    assert int(out.iloc[0]["n"]) == int(fb.iloc[0]["n"]) == exp
    if op in ("<", ">"):
        # the rows whose string IS the row's time tell < from <=
        other = dates_eng.sql(sql.replace(op, op + "="))
        assert int(other.iloc[0]["n"]) > exp


def _row_gathers(text, rows):
    """(dtype, line) of every gather of the program whose result has one
    element a row."""
    out = []
    for ln in text.splitlines():
        m = re.search(r"= (\w+)\[([\d,]*)\]\S* gather\(", ln)
        if m and np.prod([int(d) for d in m.group(2).split(",") if d]) \
                == rows:
            out.append((m.group(1), ln.strip()))
    return out


def _q12_shaped_dispatch(eng):
    """(plan, the jitted packed program the runner dispatches for it, its
    arguments): with the filter's streams among the env's columns, and
    `without_streams` for the closures' gather spelling, which a host or
    interpreter caller with no runner gets."""
    import jax
    r = eng.runner
    plan = eng.planner.plan(Q12_SHAPED.format(lo="2024-03-05"))
    phys = r._lower_cached(plan.query, plan.entry.segments)
    env, valid, seg_mask = r._prepare(phys, {})
    consts_dev, seg_arg = r._args_for(phys, seg_mask, None)
    jitted, _layout, _hit = r._packed_jit(
        phys, min(eng.config.result_group_cap, phys.total_groups), None)
    streams = {"\0d:" + t for t, _, _ in phys.filter_streams}
    bare = {"cols": {k: v for k, v in env["cols"].items()
                     if k not in streams}, "nulls": env["nulls"]}
    return phys, {
        "with_streams": (jitted, (env, valid, seg_arg, consts_dev)),
        "without_streams": (jax.jit(jitted.__wrapped__),
                            (bare, valid, seg_arg, consts_dev))}


def test_q12_shaped_program_gathers_no_dictionary_by_row(dates_eng):
    """The dispatched program reads the three comparison streams as
    columns: its only gather over the rows is the `IN` list's predicate
    table; without the streams the two rank tables and the int64 millis
    table are gathered by every row's code."""
    phys, programs = _q12_shaped_dispatch(dates_eng)
    assert len(phys.filter_streams) == 3
    gathered = {}
    for name, (jitted, args) in programs.items():
        text = jitted.lower(*args).as_text(dialect="hlo")
        gathered[name] = sorted(
            d for d, _ in _row_gathers(text, int(np.prod(args[1].shape))))
    assert gathered == {"with_streams": ["pred"],
                        "without_streams": ["pred", "s32", "s32", "s64"]}


def test_time_string_stream_cached_ledgered_and_rebuilt(dates_eng):
    """The millis stream's life: built once, int64, ledgered at its real
    bytes, reused by another literal, rebuilt after an eviction to the
    same answer; the record counts the streams read and built."""
    eng = dates_eng
    sql = Q12_SHAPED.format(lo="2024-03-05")
    first = eng.sql(sql)
    ds = eng.runner._datasets["d"]
    n0 = len(ds._derived)
    again = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert (rec["filter_streams"], rec["filter_stream_builds"]) == (3, 0)
    assert rec["reduce_path"] == "scatter" \
        and rec["reduce_form"] == "compare"
    eng.sql(Q12_SHAPED.format(lo="2024-03-09"))   # another literal
    assert len(ds._derived) == n0
    assert eng.runner.history[-1]["filter_stream_builds"] == 0
    pd.testing.assert_frame_equal(first, again)
    prep = [s for _depth, s in eng.tracer.last.walk()
            if s.name == "prepare"]
    assert prep and prep[0].attrs["filter_streams"] == 3 \
        and prep[0].attrs["filter_stream_builds"] == 0

    plan = eng.planner.plan(sql)
    phys = eng.runner._lower_cached(plan.query, plan.entry.segments)
    ledger = eng.runner._hbm_ledger
    wide = []
    for token, _src, _cname in phys.filter_streams:
        arr = ds._derived[token]
        assert ledger._entries[("d", "derived", token)][0] == arr.nbytes
        wide += [token] * (arr.dtype == np.int64)
    assert len(wide) == 1     # the millis; the two rank streams are int32
    assert ds._derived[wide[0]].nbytes == 8 * np.prod(ds.shape)

    # evict it as the budget would (HbmLedger.add): entry out, then its fn
    key = ("d", "derived", wide[0])
    evict = ledger._entries[key][1]
    ledger.remove(key)
    evict()
    assert wide[0] not in ds._derived
    rebuilt = eng.sql(sql)
    rec = eng.runner.history[-1]
    assert (rec["filter_streams"], rec["filter_stream_builds"]) == (3, 1)
    pd.testing.assert_frame_equal(first, rebuilt)

    eng.sql("SELECT mode, count(*) AS n FROM d GROUP BY mode")
    rec = eng.runner.history[-1]
    assert (rec["filter_streams"], rec["filter_stream_builds"]) == (0, 0)


def test_time_string_comparison_under_a_mesh():
    """Built from the sharded source column, the int64 stream is sharded
    like it: the mesh's per-chip programs give one chip's answer."""
    frame = _dates_frame(seed=5)
    sql = Q12_SHAPED.format(lo="2024-03-05")
    one = Engine()
    one.register_table("d", frame, time_column="ts")
    mesh = Engine(EngineConfig(num_shards=8))
    mesh.register_table("d", frame, time_column="ts")
    dev, fb, _ = run_both(mesh, sql)
    assert_frame_parity(dev, fb, ordered=True, label="mesh8")
    pd.testing.assert_frame_equal(dev, one.sql(sql))
    rec = mesh.runner.history[-1]
    assert rec["num_shards"] == 8 and rec["filter_streams"] == 3
