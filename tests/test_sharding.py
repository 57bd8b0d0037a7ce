"""Multi-chip tests on the 8-virtual-device CPU mesh (SURVEY.md §5
implication #4): sharded execution must agree exactly with single-device
and with pandas."""

import jax
import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.executor import EngineConfig
from tpu_olap.utils import timeutil as tu

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def build(num_shards=None, **cfg):
    rng = np.random.default_rng(3)
    n = 20_000
    t0 = tu.date_to_millis(1993, 1, 1)
    df = pd.DataFrame({
        "ts": pd.to_datetime(t0 + rng.integers(0, 2 * 365 * 86_400_000, n),
                             unit="ms"),
        "brand": rng.choice([f"B{i:02d}" for i in range(30)], n),
        "region": rng.choice(["ASIA", "EUROPE", "AMERICA"], n),
        "qty": rng.integers(1, 50, n).astype(np.int64),
        "price": np.round(rng.uniform(0, 100, n), 2),
        "uid": rng.integers(0, 3000, n).astype(np.int64),
    })
    eng = Engine(EngineConfig(num_shards=num_shards, **cfg))
    eng.register_table("f", df, time_column="ts", block_rows=1 << 11)
    return eng, df


QUERIES = [
    "SELECT sum(qty) AS s, count() AS n FROM f",
    """SELECT brand, sum(qty * price) AS rev FROM f
       WHERE region = 'ASIA' GROUP BY brand""",
    """SELECT region, min(price) AS mn, max(qty) AS mx, avg(price) AS av
       FROM f GROUP BY region""",
    """SELECT year(ts) AS yr, count() AS n FROM f GROUP BY year(ts)""",
    """SELECT brand, sum(qty) AS s FROM f GROUP BY brand
       ORDER BY s DESC LIMIT 5""",
    """SELECT count() AS n FROM f WHERE ts >= '1993-06-01'
       AND ts < '1994-02-01'""",
]


@pytest.mark.parametrize("idx", range(len(QUERIES)))
def test_sharded_matches_single(idx):
    sql = QUERIES[idx]
    e1, _ = build(num_shards=None)
    e8, _ = build(num_shards=8)
    a = e1.sql(sql)
    b = e8.sql(sql)
    assert e8.last_plan.rewritten, e8.last_plan.fallback_reason
    assert e8.runner.history[-1]["num_shards"] == 8
    pd.testing.assert_frame_equal(a, b)


def test_sharded_theta_matches_single():
    from tpu_olap.ir import (ThetaSketchAggregation, TimeseriesQuerySpec,
                             GroupByQuerySpec, DefaultDimensionSpec)
    q = GroupByQuerySpec(
        data_source="f", dimensions=(DefaultDimensionSpec("region"),),
        aggregations=(ThetaSketchAggregation("u", "uid", 1 << 12),))
    e1, df = build(num_shards=None)
    e8, _ = build(num_shards=8)
    r1 = e1.execute_ir(q)
    r8 = e8.execute_ir(q)
    assert r1.rows == r8.rows
    truth = df.groupby("region").uid.nunique()
    for r in r8.rows:
        want = truth[r["region"]]
        assert abs(r["u"] - want) / want < 0.1, (r, want)


def test_sharded_hll_and_scan():
    e8, df = build(num_shards=8)
    out = e8.sql("SELECT count(DISTINCT uid) AS u FROM f")
    want = df.uid.nunique()
    assert abs(out.u[0] - want) / want < 0.1
    scan = e8.sql("SELECT brand, qty FROM f WHERE qty = 49 LIMIT 12")
    truth = df.sort_values("ts", kind="stable")
    truth = truth[truth.qty == 49]
    assert scan.qty.tolist() == truth.qty.head(12).tolist()
    assert scan.brand.tolist() == truth.brand.head(12).tolist()


def test_sharded_pruning_still_correct():
    e8, df = build(num_shards=8)
    out = e8.sql("SELECT count() AS n FROM f WHERE year(ts) = 1994")
    years = pd.to_datetime(df.ts).dt.year
    assert out.n[0] == int((years == 1994).sum())
    # the last DEVICE record: a fallback-served environment (device
    # failure) records the fallback execution after the device attempt
    m = [h for h in e8.runner.history if "segments_total" in h][-1]
    assert m["segments_scanned"] < m["segments_total"]


# ---------------------------------------------------------------------------
# jit + NamedSharding rebuild (ISSUE 15): interleaved placement, per-chip
# windows, broker merge, cache shards, sys.devices, incremental re-place


def test_interleaved_placement_perms():
    """placement(): chip-major placed order, logical i on chip i mod D,
    and the two permutations are inverses."""
    from tpu_olap.executor.sharding import chip_of, placement
    to_place, to_logical = placement(16, 8)
    per_chip = 2
    for i in range(16):
        assert to_logical[to_place[i]] == i
        assert to_place[i] // per_chip == i % 8 == chip_of(i, 8)


def _month_build(num_shards=None, **cfg):
    rng = np.random.default_rng(11)
    n = 60_000
    df = pd.DataFrame({
        "ts": pd.to_datetime("1993-01-01")
        + pd.to_timedelta(rng.integers(0, 730, n), unit="D"),
        "g": rng.choice([f"g{i}" for i in range(16)], n),
        "v": rng.integers(0, 100, n).astype(np.int64),
    })
    eng = Engine(EngineConfig(num_shards=num_shards, **cfg))
    eng.register_table("m", df, time_column="ts", block_rows=512,
                       time_partition="month")
    return eng, df


WINDOW_SQL = ("SELECT g, sum(v) AS s FROM m "
              "WHERE ts >= '1993-03-01' AND ts < '1993-06-01' "
              "GROUP BY g ORDER BY g")


def test_per_chip_window_prunes_working_set():
    """Interleaved placement turns a contiguous time range into a LOCAL
    window on every chip: the record carries segments_window_per_chip
    well under each chip's resident share, and results stay exact."""
    e1, _ = _month_build()
    e8, _ = _month_build(num_shards=8)
    a, b = e1.sql(WINDOW_SQL), e8.sql(WINDOW_SQL)
    pd.testing.assert_frame_equal(a, b)
    m = e8.runner.history[-1]
    n_seg = len(e8.catalog.get("m").segments.segments)
    per_chip = -(-n_seg // 8)
    w = m["segments_window_per_chip"]
    assert w is not None and 0 < w < per_chip, (w, per_chip)
    assert m["num_shards"] == 8
    assert m["mesh_program"] == "per_chip"


def test_mesh_tier1_cache_shards_merge_at_broker():
    """Per-(chip, segment) tier-1 entries under a mesh: the first run
    populates per-segment partials from the sharded dispatch, the
    repeat serves them via the host broker fold, and sys.devices
    reports the per-chip cache-shard census."""
    e8, _ = _month_build(num_shards=8, segment_cache_enabled=True)
    a = e8.sql(WINDOW_SQL)
    m1 = e8.runner.history[-1]
    assert m1.get("segment_cache") is None  # tier served, not bypassed
    b = e8.sql(WINDOW_SQL)
    m2 = e8.runner.history[-1]
    pd.testing.assert_frame_equal(a, b)
    assert m2["cache_hit"] and m2["cache_tier"] == "segment"
    assert m2["segments_cached"] > 0 and m2["segments_computed"] == 0
    dev = e8.sql("SELECT sum(cache_shard_entries) AS n, count(*) AS d "
                 "FROM sys.devices")
    assert int(dev.d[0]) == 8
    assert int(dev.n[0]) == m2["segments_cached"]
    # parity against the single-device tier-1 path
    e1, _ = _month_build(segment_cache_enabled=True)
    e1.sql(WINDOW_SQL)
    pd.testing.assert_frame_equal(e1.sql(WINDOW_SQL), b)


def test_sys_devices_census():
    e8, _ = build(num_shards=8)
    e8.sql(QUERIES[0])
    out = e8.sql("SELECT * FROM sys.devices")
    assert len(out) == 8
    n_seg = len(e8.catalog.get("f").segments.segments)
    assert int(out.segments.sum()) == n_seg
    assert (out.chips == 8).all()
    assert int(out.dispatches.sum()) > 0


def test_incremental_replace_on_append():
    """A delta append re-places ONLY the touched segments' rows: the
    swapped-in dataset rebases resident stacks device-side instead of
    re-uploading every column, and mesh results stay exact."""
    e8, _ = _month_build(num_shards=8)
    e1, _ = _month_build()
    base = e8.sql(WINDOW_SQL)
    row = {"ts": "1994-12-30T00:00:00", "g": "g1", "v": 7}
    e8.append("m", [row])
    e1.append("m", [row])
    got = e8.sql("SELECT count() AS n FROM m")
    assert int(got.n[0]) == 60_001
    ds = e8.runner._datasets["m"]
    assert ds.rebased_cols > 0
    # uploaded rows bounded by the delta-touched segments, not the table
    n_seg = len(e8.catalog.get("m").segments.segments)
    assert ds.rebase_rows_uploaded < n_seg * 512 // 2
    pd.testing.assert_frame_equal(e1.sql(WINDOW_SQL), e8.sql(WINDOW_SQL))
    pd.testing.assert_frame_equal(base, e8.sql(WINDOW_SQL))


def test_compaction_keeps_untouched_cache_shards():
    """Partition-aligned incremental compaction shares untouched sealed
    segments by object, and tier-1 keys ride the segment uid — so only
    the delta-touched partition's entries invalidate (under a mesh:
    only the affected chip's cache shard)."""
    e8, _ = _month_build(num_shards=8, segment_cache_enabled=True,
                         ingest_auto_compact=False)
    e8.sql(WINDOW_SQL)          # populate per-segment entries
    warm = e8.sql(WINDOW_SQL)
    assert e8.runner.history[-1]["cache_hit"]
    # append OUTSIDE the queried window, then compact: the queried
    # months' sealed segments are untouched partitions
    e8.append("m", [{"ts": "1994-12-30T00:00:00", "g": "g1", "v": 7}])
    res = e8.compact_now("m")
    assert res.get("mode") == "incremental", res
    again = e8.sql(WINDOW_SQL)
    m = e8.runner.history[-1]
    pd.testing.assert_frame_equal(warm, again)
    assert m["cache_hit"], m.get("segment_cache")
    assert m["segments_cached"] > 0 and m["segments_computed"] == 0, m


# ---------------------------------------------------------------------------
# shard_map of the single-chip kernel (ISSUE 26): the mesh program
# "per_chip" is plan.kernel on each chip's own rows — no collective, [K]
# partials a chip. Which program a mesh runs is a fact of the mesh (ISSUE
# 29): "gspmd" only where it spans processes (sharding.is_multihost)


def _theta_query():
    from tpu_olap.ir import (DefaultDimensionSpec, GroupByQuerySpec,
                             ThetaSketchAggregation)
    return GroupByQuerySpec(
        data_source="f", dimensions=(DefaultDimensionSpec("region"),),
        aggregations=(ThetaSketchAggregation("u", "uid", 1 << 10),))


SUM_SQL = """SELECT brand, sum(qty) AS s, count(*) AS n FROM f
             WHERE region = 'ASIA' GROUP BY brand ORDER BY brand"""
MINMAX_SQL = """SELECT region, min(qty) AS mn, max(qty) AS mx, sum(qty) AS s
                FROM f GROUP BY region ORDER BY region"""
HLL_SQL = """SELECT region, count(DISTINCT uid) AS u FROM f
             GROUP BY region ORDER BY region"""
# one month of the month-partitioned table: fewer segments than chips,
# so some chips' windows hold no row of any group
ONE_MONTH_SQL = ("SELECT g, sum(v) AS s, min(v) AS mn, count(*) AS n "
                 "FROM m WHERE ts >= '1993-03-01' AND ts < '1993-04-01' "
                 "GROUP BY g ORDER BY g")



def _sketch_build(num_shards=None, **cfg):
    """A sketch-wide group table over few rows: [K, 2048] HLL registers
    for K = 31 x 41 dense slots against 4,096 rows — the shape the
    retired per-query cost model sent to the GSPMD spelling."""
    rng = np.random.default_rng(3)
    n = 4096
    df = pd.DataFrame({
        "ts": pd.to_datetime(rng.integers(725846400000, 757382400000, n),
                             unit="ms"),
        "dim": rng.choice([f"d{i}" for i in range(30)], n),
        "val": rng.integers(0, 40, n).astype(np.int64),
    })
    eng = Engine(EngineConfig(num_shards=num_shards, **cfg))
    eng.register_table("t", df, time_column="ts", block_rows=512)
    return eng, df


def _wide_build(num_shards=None, **cfg):
    """A numeric dimension 968 values wide over 4,096 rows: twice the K
    at which that model's merge estimate crossed its scan estimate."""
    rng = np.random.default_rng(5)
    n, k = 4096, 968
    df = pd.DataFrame({
        "ts": pd.to_datetime("2024-01-01")
        + pd.to_timedelta(np.arange(n) % 9999, unit="s"),
        "g": np.concatenate([np.array([0, k - 1]),
                             rng.integers(0, k, n - 2)]).astype(np.int64),
        "v": rng.integers(0, 100, n).astype(np.int64),
    })
    eng = Engine(EngineConfig(num_shards=num_shards, **cfg))
    eng.register_table("t", df, time_column="ts", block_rows=512)
    return eng, df


SKETCH_SQL = """SELECT dim, val, count(DISTINCT dim) AS u FROM t
                GROUP BY dim, val ORDER BY dim, val"""
SMALL_SQL = "SELECT dim, sum(val) AS s FROM t GROUP BY dim ORDER BY dim"
WIDE_SQL = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY g"

# (id, engine builder, query, use_pallas, record's `pallas`, windowed)
PER_CHIP_CASES = [
    ("ungrouped", build, QUERIES[0], "never", False, False),
    ("sum-count", build, SUM_SQL, "never", False, False),
    ("sum-count-pallas", build, SUM_SQL, "force", True, False),
    ("minmax", build, MINMAX_SQL, "never", False, False),
    ("minmax-pallas", build, MINMAX_SQL, "force", True, False),
    ("hll", build, HLL_SQL, "never", False, False),
    ("hll-pallas-ineligible", build, HLL_SQL, "force", False, False),
    ("theta", build, _theta_query, "never", False, False),
    ("windowed", _month_build, WINDOW_SQL, "never", False, True),
    ("windowed-pallas", _month_build, WINDOW_SQL, "force", True, True),
    ("one-month", _month_build, ONE_MONTH_SQL, "never", False, True),
    ("one-month-pallas", _month_build, ONE_MONTH_SQL, "force", True, True),
    ("sketch-heavy", _sketch_build, SKETCH_SQL, "never", False, False),
    ("wide-numeric-dim", _wide_build, WIDE_SQL, "never", False, False),
]
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


def _answer_sha(eng, query):
    import hashlib
    if isinstance(query, str):
        body = eng.sql(query).to_csv(index=False)
    else:
        body = repr(eng.execute_ir(query()).rows)
    return hashlib.sha256(body.encode()).hexdigest()


@pytest.mark.parametrize("case,builder,query,use_pallas,want_pallas,windowed",
                         PER_CHIP_CASES, ids=[c[0] for c in PER_CHIP_CASES])
def test_per_chip_program(monkeypatch, case, builder, query, use_pallas,
                          want_pallas, windowed):
    """The "per_chip" mesh program is the single-chip kernel mapped
    over the chips: answers sha256-equal to one chip's, no collective
    in the compiled program, a [K] partial table from every chip (the
    identity where a chip's segments hold no row of a group), and a
    record that says what ran."""
    from tpu_olap.executor import sharding as sh
    from tpu_olap.kernels.groupby import merge_partials

    D = 8
    calls = []
    real = sh.mesh_agg_kernel

    def spy(plan, mesh, per_chip, program, win=None):
        jitted = real(plan, mesh, per_chip, program, win)

        def run(*args):
            calls.append((plan, program, win, jitted, args))
            return jitted(*args)
        return run

    monkeypatch.setattr(sh, "mesh_agg_kernel", spy)
    e1, _ = builder(use_pallas="never")
    eD, _ = builder(num_shards=D, use_pallas=use_pallas)
    assert _answer_sha(e1, query) == _answer_sha(eD, query)

    rec = eD.runner.history[-1]
    assert rec["num_shards"] == D
    assert rec["mesh_program"] == "per_chip" and rec["merge"] == "broker"
    assert bool(rec.get("pallas")) == want_pallas
    assert rec["path"] == ("pallas" if want_pallas else "dense")
    assert bool(rec.get("segments_window_per_chip")) == windowed

    (plan, program, win, jitted, args), = calls
    assert program == "per_chip" and (win is not None) == windowed
    assert (plan.pallas_reason is None) == want_pallas
    text = jitted.lower(*args).compile().as_text()
    assert not [c for c in COLLECTIVES if c in text]

    K = plan.total_groups
    out = jitted(*args)
    for name, v in out.items():
        assert v.shape[0] == D * K, (name, v.shape)
        assert {s.data.shape[0] for s in v.addressable_shards} == {K}
    fetched = jax.device_get(out)
    merged = sh.broker_merge(fetched, plan.agg_plans, D)
    assert all(v.shape[0] == K for v in merged.values())
    parts = [{n: v.reshape((D, K) + v.shape[1:])[d]
              for n, v in fetched.items()} for d in range(D)]
    empty = [p for p in parts if not p["_rows"].any()]
    full = [p for p in parts if p["_rows"].any()]
    assert full
    if case.startswith("one-month"):
        assert empty
    for p in empty:  # an empty chip's table is the merge's identity
        got = merge_partials(p, full[0], plan.agg_plans)
        for name, v in full[0].items():
            np.testing.assert_array_equal(np.asarray(got[name]), v)


def _spanning_processes(monkeypatch):
    """Substitute sharding.is_multihost: every mesh built from here on
    reads as one that spans processes. Returns the meshes it was asked
    about."""
    from tpu_olap.executor import sharding as sh
    asked = []

    def spans(mesh):
        asked.append(mesh)
        return True
    monkeypatch.setattr(sh, "is_multihost", spans)
    return asked


def test_gspmd_record_says_what_ran(monkeypatch):
    """A mesh that spans processes keeps the GSPMD spelling: the generic
    key_fn over global shapes, so the record carries mesh_program
    "gspmd" and no `pallas`, whatever the plan was eligible for."""
    e1, _ = build(use_pallas="never")
    _spanning_processes(monkeypatch)
    e8, _ = build(num_shards=8, use_pallas="force")
    assert _answer_sha(e1, SUM_SQL) == _answer_sha(e8, SUM_SQL)
    rec = e8.runner.history[-1]
    assert rec["mesh_program"] == "gspmd" and rec["merge"] == "gspmd"
    assert not rec.get("pallas") and rec["path"] == "dense"


@pytest.mark.parametrize("program", ["per_chip", "gspmd"])
def test_mesh_programs_agree_with_fallback(program, monkeypatch):
    """Both spellings of the mesh aggregate against the pandas oracle."""
    from tpu_olap.bench.parity import check_query
    if program == "gspmd":
        _spanning_processes(monkeypatch)
    eng, _ = _sketch_build(num_shards=8)
    check_query(eng, """
        SELECT dim, sum(val) AS s, count() AS n, min(val) AS lo
        FROM t GROUP BY dim ORDER BY dim
    """, label=f"mesh_program={program}")
    m = eng.runner.history[-1]
    assert m["mesh_program"] == program
    assert m["num_shards"] == 8


def _mesh_keys(eng):
    return [k for k in eng.runner._jit_cache if "mesh" in k]


def test_mesh_program_is_a_property_of_the_mesh():
    """One engine, a small and a sketch-heavy aggregate: both run the
    per-chip program, and the jit-cache keys carry no strategy slot."""
    eng, _ = _sketch_build(num_shards=8, use_pallas="never")
    for sql in (SMALL_SQL, SKETCH_SQL):
        eng.sql(sql)
        rec = eng.runner.history[-1]
        assert rec["mesh_program"] == "per_chip" and "cost" not in rec
    assert eng.runner.mesh_program == "per_chip"
    keys = _mesh_keys(eng)
    assert len(keys) == 2
    for k in keys:
        # ..., "mesh", D, per-chip window width: nothing else
        assert k[k.index("mesh"):] == ("mesh", 8, 0)
        assert not {"historicals", "broker", "per_chip", "gspmd"} & set(k)


def test_multiprocess_mesh_selects_gspmd_once(monkeypatch):
    """With is_multihost substituted, a fresh runner's mesh_program is
    "gspmd" for a small and for the sketch-heavy aggregate, and the
    mesh was asked once, when it was built: not once a query."""
    asked = _spanning_processes(monkeypatch)
    eng, _ = _sketch_build(num_shards=8, use_pallas="never")
    assert eng.runner.mesh_program is None and not asked  # no mesh yet
    for sql in (SMALL_SQL, SKETCH_SQL):
        eng.sql(sql)
        rec = eng.runner.history[-1]
        assert rec["mesh_program"] == "gspmd" and rec["merge"] == "gspmd"
    assert eng.runner.mesh_program == "gspmd"
    assert asked == [eng.runner.mesh]


def test_explain_reports_mesh_program():
    """EXPLAIN names the mesh's program for an aggregate; one chip has
    no mesh and nothing to report."""
    e8, _ = _sketch_build(num_shards=8)
    out = e8.explain(SMALL_SQL)
    assert out["rewritten"]
    assert out["mesh_program"] == "per_chip" and "cost" not in out
    e1, _ = _sketch_build()
    out = e1.explain(SMALL_SQL)
    assert out["rewritten"] and "mesh_program" not in out
