"""Shared-scan batch executor (executor.batch) + PR-4 satellite fixes.

Parity contract: every query in a batch returns EXACTLY what the
sequential path returns for it — the fused pass reads each segment
window once, but per-leg masks add only exact zeros, so results stay
bitwise identical (see docs/BATCH_EXECUTION.md).
"""

import threading
import time

import numpy as np
import pandas as pd
import pytest

from tpu_olap import Engine
from tpu_olap.executor import EngineConfig


@pytest.fixture(scope="module")
def frame():
    rng = np.random.default_rng(19)
    rows = 30_000
    return pd.DataFrame({
        "ts": pd.to_datetime("2024-01-01")
        + pd.to_timedelta(rng.integers(0, 86400 * 120, rows), unit="s"),
        "g": rng.choice([f"g{i}" for i in range(16)], rows),
        "h": rng.choice(["a", "b", "c"], rows),
        "v": rng.integers(0, 1000, rows).astype(np.int64),
        "w": rng.normal(size=rows),
    })


@pytest.fixture(scope="module")
def eng(frame):
    e = Engine()
    e.register_table("t", frame, time_column="ts", block_rows=1 << 12)
    return e


# a mixed dashboard: grouped/ungrouped, HAVING, ORDER/LIMIT (topN
# shape), post-aggs (avg), time bucketing, interval filters, duplicates
BATCH = [
    "SELECT g, sum(v) AS s, count(*) AS n FROM t GROUP BY g ORDER BY g",
    "SELECT h, avg(w) AS m, max(v) AS mx FROM t WHERE v > 500 "
    "GROUP BY h ORDER BY h",
    "SELECT sum(v) AS s, count(*) AS n FROM t WHERE h = 'a'",
    "SELECT g, sum(v) AS s FROM t GROUP BY g HAVING sum(v) > 100000 "
    "ORDER BY s DESC LIMIT 3",
    "SELECT month(ts) AS m, sum(v) AS s FROM t GROUP BY month(ts) "
    "ORDER BY m",
    "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY s DESC LIMIT 5",
    "SELECT sum(v) AS s FROM t "
    "WHERE ts < TIMESTAMP '2024-02-01 00:00:00'",
    "SELECT g, count(*) AS n FROM t "
    "WHERE ts >= TIMESTAMP '2030-01-01 00:00:00' GROUP BY g",
    # duplicates: one physical scan must serve every copy
    "SELECT g, sum(v) AS s, count(*) AS n FROM t GROUP BY g ORDER BY g",
    "SELECT sum(v) AS s, count(*) AS n FROM t WHERE h = 'a'",
]


def test_batch_parity_bitwise(eng):
    seq = [eng.sql(q) for q in BATCH]          # warm + oracle
    bat = eng.sql_batch(BATCH)
    for i, (a, b) in enumerate(zip(seq, bat)):
        assert a.equals(b), f"batch leg {i} diverged from sequential"


def test_batch_metrics_shared_scan_counted_once(eng):
    h0 = len(eng.history)
    eng.sql_batch(BATCH)
    hist = eng.history[h0:]
    # dedup fan-out records are annotated COPIES of the leg's metrics —
    # the physical pass is only the non-dedup records
    fused = [m for m in hist if m.get("batch_legs", 0) >= 2
             and not m.get("batch_dedup")]
    assert fused, "no fused multi-leg dispatch was recorded"
    by_id = {}
    for m in fused:
        by_id.setdefault(m["batch_id"], []).append(m)
    for recs in by_id.values():
        # scan_ms_shared is the ONE shared pass: identical on every leg
        # of the batch (count it once per batch_id); agg_ms is the
        # per-leg share and never exceeds the shared wall
        shared = {m["scan_ms_shared"] for m in recs}
        assert len(shared) == 1
        assert all(m["agg_ms"] > 0 for m in recs)
        assert sum(m["agg_ms"] for m in recs) <= recs[0][
            "scan_ms_shared"] * 1.01
        assert len(recs) == recs[0]["batch_legs"]


def test_batch_dedupe_one_scan_many_queries(eng):
    sql = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY g"
    ref = eng.sql(sql)
    h0 = len(eng.history)
    out = eng.sql_batch([sql] * 4)
    assert all(f.equals(ref) for f in out)
    hist = eng.history[h0:]
    scans = [m for m in hist if m.get("batch_legs") == 1
             and m.get("batch_size") == 4 and not m.get("batch_dedup")]
    dups = [m for m in hist if m.get("batch_dedup")]
    assert len(scans) == 1, "identical queries must share ONE scan"
    assert len(dups) == 3
    assert scans[0]["scan_ms_shared"] >= 0
    assert scans[0]["agg_ms"] >= 0


def test_batch_mixed_with_unfusable_legs(eng):
    # a raw scan (mask-kind plan) rides the same submission but runs
    # through the single-query path; agg legs still fuse around it
    mixed = [
        "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY g",
        "SELECT g, v FROM t WHERE v > 995 LIMIT 7",
        "SELECT h, count(*) AS n FROM t GROUP BY h ORDER BY h",
    ]
    seq = [eng.sql(q) for q in mixed]
    bat = eng.sql_batch(mixed)
    for a, b in zip(seq, bat):
        assert a.equals(b)


def test_runner_execute_batch_boxes_failures_per_leg(eng):
    from tpu_olap.ir.aggregations import SumAggregation
    from tpu_olap.ir.dimensions import DefaultDimensionSpec
    from tpu_olap.ir.query import GroupByQuerySpec
    from tpu_olap.kernels.groupby import UnsupportedAggregation

    table = eng.catalog.get("t").segments
    good = GroupByQuerySpec(
        data_source="t", intervals=(),
        dimensions=(DefaultDimensionSpec("g"),),
        aggregations=(SumAggregation("s", "v"),))
    bad = GroupByQuerySpec(
        data_source="t", intervals=(),
        dimensions=(DefaultDimensionSpec("g"),),
        aggregations=(SumAggregation("s", "no_such_col"),))
    boxed = eng.runner._execute_batch_boxed([good, bad, good], table)
    assert isinstance(boxed[1], UnsupportedAggregation)
    assert boxed[0].rows == boxed[2].rows and boxed[0].rows
    with pytest.raises(UnsupportedAggregation):
        eng.runner.execute_batch([good, bad], table)


def test_compile_predicates_shared_env(eng):
    """Kernel-level multi-predicate evaluation: N filters compiled
    against ONE ConstPool evaluate over one shared column env."""
    from tpu_olap.ir.filters import BoundFilter, SelectorFilter
    from tpu_olap.kernels.filtereval import (ConstPool, compile_predicates,
                                             eval_predicates)

    table = eng.catalog.get("t").segments
    pool = ConstPool()
    fns = compile_predicates(
        [SelectorFilter("g", "g1"),
         BoundFilter("v", lower="500", ordering="numeric"),
         None],
        table, pool)
    seg = table.segments[0]
    env = {"cols": {"g": seg.columns["g"], "v": seg.columns["v"]},
           "nulls": {}}
    masks = eval_predicates(fns, env, pool.consts)
    n = seg.meta.n_valid
    g_vals = table.dictionaries["g"].decode(seg.columns["g"][:n])
    assert masks[0][:n].sum() == (g_vals == "g1").sum()
    assert masks[1][:n].sum() == (seg.columns["v"][:n] >= 500).sum()
    assert masks[2] is None


def test_group_reduce_batch_matches_single_legs(rng):
    from tpu_olap.kernels.groupby import (AggPlan, group_reduce,
                                          group_reduce_batch)
    import jax.numpy as jnp
    n = 4096
    env = {"cols": {"x": jnp.asarray(rng.integers(0, 100, n), jnp.int64)},
           "nulls": {}}
    legs = []
    for k in (4, 7):
        key = jnp.asarray(rng.integers(0, k, n), jnp.int32)
        mask = jnp.asarray(rng.random(n) < 0.8)
        plans = [AggPlan("s", "sum", ("x",), np.int64)]
        legs.append((key, mask, env, plans, k))
    batch = group_reduce_batch(legs, [{}, {}])
    for leg, got in zip(legs, batch):
        key, mask, e, plans, k = leg
        one = group_reduce(key, mask, e, plans, k, {})
        for name in one:
            np.testing.assert_array_equal(one[name], got[name])


def test_sql_batch_propagates_interrupt_instead_of_retrying(eng,
                                                            monkeypatch):
    """run_batch boxes BaseException per leg so the Coalescer can fan
    failures out to their own callers — but sql_batch must NOT treat a
    boxed KeyboardInterrupt/SystemExit as a retryable device failure:
    a cancel mid-dispatch aborts the submission, it does not silently
    re-run every leg through the single-query path (double work)."""
    single_runs = []
    monkeypatch.setattr(
        eng.runner, "_execute_batch_boxed",
        lambda queries, table, query_ids=None:
        [KeyboardInterrupt()] * len(queries))
    real = eng._execute_plan
    monkeypatch.setattr(
        eng, "_execute_plan",
        lambda plan: single_runs.append(plan) or real(plan))
    with pytest.raises(KeyboardInterrupt):
        eng.sql_batch([BATCH[0], BATCH[1]])
    assert not single_runs, "interrupt was retried on the single path"


def test_coalesced_path_honors_query_deadline(frame):
    """query_deadline_s must bound the coalesced/batch path exactly like
    the single-query path: a hung dispatch raises QueryDeadlineExceeded
    to the caller within ~the deadline (not never), the engine falls
    back to pandas ('never an error'), and the wedged device is
    reprobed — not trusted — on the next dispatch."""
    eng = Engine(EngineConfig(batch_window_ms=10.0))
    eng.register_table("t", frame, time_column="ts",
                       block_rows=1 << 12)
    sql = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY g"
    want = eng.sql(sql)  # warm (compile) BEFORE arming the deadline

    armed = {"hang": True}

    def injector(stage, attempt):
        if stage == "dispatch" and armed.pop("hang", False):
            time.sleep(30)

    eng.config.query_deadline_s = 1.0
    eng.config.fault_injector = injector
    t0 = time.perf_counter()
    got = eng.sql(sql)   # deadline fires -> pandas fallback
    dt = time.perf_counter() - t0
    assert dt < 15, "coalesced caller hung past the deadline"
    assert got["g"].tolist() == want["g"].tolist()
    assert got["s"].tolist() == want["s"].tolist()
    assert any(m.get("deadline_exceeded") for m in eng.runner.history)
    # device recovers: the reprobe clears the wedge and the same query
    # rides the device path again
    again = eng.sql(sql)
    assert again.equals(want)


def test_coalescer_leader_interrupt_does_not_strand_followers():
    """An async exception in the leader (KeyboardInterrupt mid-window)
    must still reset the collecting flag, drain the queue, and wake
    every follower — otherwise the coalescer wedges for the process
    lifetime (every later agg query enqueues behind a dead leader)."""
    from tpu_olap.executor.batch import Coalescer

    class StubRunner:
        dispatch_lock = threading.RLock()

    co = Coalescer(StubRunner(), 0.25)
    real_sleep = time.sleep
    out = {}

    def boom(s):
        if s == 0.25:        # the leader's window sleep
            real_sleep(0.1)  # let the follower enqueue first
            raise KeyboardInterrupt
        real_sleep(s)

    def leader():
        try:
            co.submit("q1", "t")
        except BaseException as e:  # noqa: BLE001 — inspected below
            out["leader"] = e

    def follower():
        try:
            out["follower"] = co.submit("q2", "t")
        except BaseException as e:  # noqa: BLE001 — inspected below
            out["follower"] = e

    time.sleep = boom
    try:
        tl = threading.Thread(target=leader)
        tl.start()
        real_sleep(0.02)
        tf = threading.Thread(target=follower)
        tf.start()
        tl.join(timeout=10)
        tf.join(timeout=10)
    finally:
        time.sleep = real_sleep
    assert not tf.is_alive(), "follower stranded by the dead leader"
    assert isinstance(out["leader"], KeyboardInterrupt)
    assert isinstance(out["follower"], RuntimeError)
    # the coalescer is reusable: the next caller becomes a fresh leader
    assert co._collecting is False and co._queue == []


# ------------------------------------------------------ satellite fixes


def test_fallback_parallel_timeout_default_and_scale():
    from tpu_olap.planner.fallback import _parallel_timeout_s
    cfg = EngineConfig()
    # ADVICE r5: a deadlocked fork pool must trigger the sequential
    # retry interactively, not after 15 minutes
    assert cfg.fallback_parallel_timeout_s == 45.0

    class E:
        parquet_rows = 0
    e = E()
    assert _parallel_timeout_s(cfg, e) == 45.0
    e.parquet_rows = 200_000_000
    assert _parallel_timeout_s(cfg, e) == 45.0
    e.parquet_rows = 2_000_000_000   # scan-size scaling kicks in
    assert _parallel_timeout_s(cfg, e) == pytest.approx(450.0)
    assert _parallel_timeout_s(cfg, None) == 45.0


def test_worker_pair_cap_divided_across_pool():
    # the per-worker caps must SUM to the configured cap: with the full
    # cap per worker, in-flight distinct pairs could transiently reach
    # workers x pair_cap before the parent-side merge re-checks
    from tpu_olap.planner import fallback as fb
    src = open(fb.__file__).read()
    assert "pair_cap // workers" in src
    import ast
    tree = ast.parse(src)
    fn = next(n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef)
              and n.name == "_parallel_chunk_partials")
    assert "pair_cap // workers" in ast.get_source_segment(src, fn), \
        "the division must happen where the fork ctx is built"


def test_bool_object_columns_survive_null_normalization():
    from tpu_olap.planner.fallback import _coerce_nullable_numeric
    df = pd.DataFrame({
        "flag": pd.Series([True, None, False], dtype=object),
        "npflag": pd.Series([np.bool_(True), None, np.bool_(False)],
                            dtype=object),
        "m": pd.Series([1, None, 3], dtype=object),
    })
    out = _coerce_nullable_numeric(df)
    # nullable numeric -> float64 + NaN (the device-frame contract) ...
    assert out["m"].dtype == np.float64
    assert np.isnan(out["m"].iloc[1])
    # ... but nullable BOOLEAN stays boolean (bool is an int subclass;
    # it must not silently coerce to 1.0/0.0)
    assert out["flag"].dtype == object
    assert out["flag"].iloc[0] is True and out["flag"].iloc[2] is False
    assert out["npflag"].dtype == object


def test_grouping_sets_union_absent_keys_are_nan(frame, eng):
    sql = ("SELECT g, h, sum(v) AS s FROM t GROUP BY ROLLUP(g, h) "
           "ORDER BY g, h")
    got = eng.sql(sql)
    plan = eng.last_plan
    # the device union path served it (legs, not the whole-statement
    # fallback) — otherwise this test is not exercising the reattachment
    assert getattr(plan, "grouping_legs", None)
    assert plan.fallback_reason is None
    # absent group keys reattach as np.nan like the whole-statement
    # fallback, never as object None
    assert not any(v is None for v in got["g"])
    assert not any(v is None for v in got["h"])
    grand = got[got["g"].isna() & got["h"].isna()]
    assert len(grand) == 1
    assert int(grand["s"].iloc[0]) == int(frame["v"].sum())
    # oracle: identical statement through the pure pandas fallback
    e2 = Engine()
    e2.register_table("t", frame, time_column="ts", accelerate=False)
    want = e2.sql(sql)
    assert got["s"].tolist() == want["s"].tolist()
    assert [x if not pd.isna(x) else None for x in got["g"]] \
        == [x if not pd.isna(x) else None for x in want["g"]]
