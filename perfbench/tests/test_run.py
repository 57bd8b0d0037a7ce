"""A whole run on the CPU at 60,000 rows: sound, with the timed path broken
underneath, and with the program's lower precision in its place.

The chip gate (`harness.main`) is skipped: `run_cell` is everything after
it. The control needs a process of its own, because JAX's x64 switch is
global and the sound engine has turned it on in this one.
"""

import os
import re
import subprocess
import sys

import pytest

from perfbench.lib import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "ssb-sf100-chip.ssb13-c1"
ROWS = 60_000


def _run(seed=7, seconds=1.0, trace=False):
    spec = harness.load_cell(ROOT, CELL)
    spec["peaks"] = {"hbm_bytes_per_s": 819e9}
    return harness.run_cell(spec, seed, seconds, trace, rows=ROWS)


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 13
    assert set(r["metrics"]) == {"query_p50_ms", "slowest_query_p50_ms",
                                 "queries_per_s", "setup_s"} \
        or "query_p95_ms" in r["metrics"]
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_traced_run_reports_the_per_layer_metrics_and_a_breakdown():
    r = _run(trace=True)
    assert r["correct"] is True
    assert {"http_overhead_ms", "plan_ms", "execute_ms", "flight1_p50_ms",
            "window_compiles", "device_idle_share"} <= set(r["metrics"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]
    assert len(r["breakdown"]["device_ops"]) <= 10


def _doctor(monkeypatch, when):
    """Alter one sum where the answer is produced: q2.1's first group gets
    +1, from the `when`-th q2.1 answer on (0 = every one, warm-up too)."""
    from tpu_olap.api.engine import Engine

    inner = Engine._sql_traced
    seen = {"n": 0}

    def altered(self, query, traceparent=None):
        frame, trace = inner(self, query, traceparent=traceparent)
        if "p_category = 'MFGR#12'" in query:
            seen["n"] += 1
            if seen["n"] > when:
                frame = frame.copy()
                frame.loc[frame.index[0], "revenue"] += 1
        return frame, trace

    monkeypatch.setattr(Engine, "_sql_traced", altered)


@pytest.mark.parametrize("when, where", [(0, "warm-up"), (4, "window")])
def test_an_altered_answer_makes_the_run_incorrect(monkeypatch, when, where):
    _doctor(monkeypatch, when)
    r = _run()
    assert r["correct"] is False
    if where == "window":
        # q2.1 has four warm-up answers at this size (one compiling run,
        # one that compiles nothing, two settling rounds), all right: only
        # the window's digests differ
        assert 0 < r["failed"] < r["attempted"]


def test_a_query_served_by_the_fallback_makes_the_run_incorrect(monkeypatch):
    """A record that says `fallback` counts as failed even when the answer
    is right: no pandas answer under a TPU name."""
    inner = harness.history_by_id

    def marked(engine):
        recs = inner(engine)
        for r in recs.values():
            if r.get("query_type") == "groupBy":
                r["query_type"] = "fallback"
        return recs

    monkeypatch.setattr(harness, "history_by_id", marked)
    r = _run()
    assert r["correct"] is False and r["failed"] > 0


@pytest.mark.parametrize("seed", [11, 2_147_483_777, 12345])
def test_lower_precision_control_is_not_correct(seed):
    """`enable_x64=false`, the program's own lower precision, in the
    program's place: int32 sums overflow at any size, so the answers do not
    equal the reference. The same command on the chip at the cell's size is
    the control run of PERF.md."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", str(seed), "--seconds", "1",
         "--trace", "0", "--allow-cpu", "--rehearse-rows", str(ROWS),
         "--control", "x64-off"],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)
    m = re.search(r"differ from the reference = (\d+) of 13", out.stdout)
    assert m, out.stdout[-2000:] + out.stderr[-2000:]
    assert int(m.group(1)) >= 3
    assert "would have reported correct=False" in out.stdout


def test_no_accelerator_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
