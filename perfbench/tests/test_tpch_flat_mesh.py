"""The configuration `tpch-flat-sf10-mesh4` and its cell: the files, a whole
run on a CPU mesh of four at 120,000 rows (a rehearsal: never `correct`), the
lower-precision control, and the five readers the cell brought, each fed a
record and a span tree with and without the names it reads."""

import json
import os
import subprocess
import sys
import types

import pytest

from perfbench.lib import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "tpch-flat-sf10-mesh4"
CELL = CONFIG + ".tpch10-c1"
NEW = ("sparse_chip_ms_per_query", "sparse_chip_roofline",
       "sparse_shard_fetch_ms", "sparse_merge_ms", "sparse_merge_rows_in")


def test_the_configuration_is_the_published_scale_on_four_chips():
    spec = harness.load_cell(ROOT, CELL)
    config, cell = spec["config"], spec["cell"]
    assert config["name"] == cell["config"] == CONFIG
    assert config["chips"] == cell["chips"] == 4 \
        == config["engine_config"]["num_shards"]
    assert config["dataset"] == "tpch_flat"
    assert config["rows"] == 59_986_052 and config["reduced"] == []
    assert config["scale_factor"] == config["scale_factor_published"] == 10
    assert config["engine_config"]["fallback_on_device_failure"] is False
    assert config["engine_config"]["breaker_failure_threshold"] == 0
    assert config["controls"] == {"x64-off": {"enable_x64": False}}
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "tpch-flat-sf10-chip.json")) as f:
        one_chip = json.load(f)
    for k in ("answers", "caches", "precision"):
        assert config["guarantees"][k] == one_chip["guarantees"][k]
    assert config["templates"] == one_chip["templates"]
    assert cell["traffic"] == "tpch10-c1"
    assert {m["name"] for m in spec["end_to_end"]} == {
        "query_p50_ms", "slowest_query_p50_ms", "queries_per_s", "setup_s"}
    assert [m["name"] for m in spec["per_layer"]] == list(NEW)
    for m in spec["per_layer"]:
        assert harness.load_reader(spec["bench_dir"],
                                   m["name"]).UNIT == m["unit"]


def _run(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--allow-cpu", "--rehearse-rows", "120000", *extra],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)


def test_the_cell_runs_on_a_cpu_mesh_to_its_not_correct_line():
    out = _run("--trace", "1")
    assert out.returncode == harness.EXIT_REHEARSAL, \
        out.stdout[-2000:] + out.stderr[-2000:]
    assert "would have reported correct=True" in out.stdout
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 10 and line["device"]["count"] == 4
    # the roofline needs a chip's published peak: left out on the CPU
    assert set(NEW) - {"sparse_chip_roofline"} <= set(line["metrics"])
    assert line["metrics"]["sparse_merge_rows_in"]["value"] > 0
    for t in ("q3", "q10"):
        assert f"template {t}:" in out.stdout
    assert out.stdout.count("path=sparse") == 2
    assert out.stdout.count("num_shards=4") == 10


def test_the_lower_precision_control_is_not_correct():
    out = _run("--trace", "0", "--control", "x64-off")
    assert "would have reported correct=False" in out.stdout, \
        out.stdout[-2000:] + out.stderr[-2000:]


def _span(name, start, dur, attrs=None, children=()):
    s = {"name": name, "start_ms": start, "duration_ms": dur,
         "children": list(children)}
    if attrs:
        s["attrs"] = attrs
    return s


def _ctx(new_names: bool):
    """Two sparse queries and a dense one, as a mesh serves them: with the
    spans and counters this cell's program has, or as an older one would."""
    def sparse_tree(fetch, merge):
        under = [_span("sparse-attempt", 1, 50, {"cap": 1024},
                       [_span("count-probe", 2, 48)])]
        if new_names:
            under.append(_span("sparse-shard-fetch", 51, fetch,
                               {"chips": 4, "rows": 2048, "bytes": 40960}))
        under.append(_span("broker-merge", 60, merge, {"num_shards": 4}))
        return _span("sql", 0, 100, None, [_span(
            "device-call", 1, 90, None, [_span("dispatch", 1, 89, None,
                                               under)])])

    dense_tree = _span("sql", 0, 20, None, [
        _span("device-call", 1, 10), _span("broker-merge", 12, 0.05)])
    samples = [{"qid": "a", "template": "q3", "ms": 100.0},
               {"qid": "b", "template": "q10", "ms": 100.0},
               {"qid": "c", "template": "q1", "ms": 20.0}]
    counter = {"sparse_merge_rows_in": 1500} if new_names else {}
    records = {"a": dict(reduce_path="sparse", rows_scanned=60_000,
                         **counter),
               "b": dict(reduce_path="sparse", rows_scanned=60_000,
                         **counter),
               "c": dict(reduce_path="scatter", rows_scanned=60_000)}
    traces = {"a": sparse_tree(4.0, 8.0), "b": sparse_tree(6.0, 12.0),
              "c": dense_tree}
    trace = {"queries": [
        {"qid": "a", "template": "q3", "device_s": 0.040, "whole": True},
        {"qid": "b", "template": "q10", "device_s": 0.060, "whole": True},
        {"qid": "c", "template": "q1", "device_s": 0.010, "whole": False}],
        "busy_s_by_device": {0: 0.10, 1: 0.10, 2: 0.125, 3: 0.075}}
    dataset = types.SimpleNamespace(
        needed_bytes=lambda template, reference, rows: 819_000 * 4)
    return types.SimpleNamespace(
        samples=samples, records=records, traces=traces, trace=trace,
        dataset=dataset, reference={}, chips=4,
        peaks={"hbm_bytes_per_s": 819e9})


def _read(name, ctx):
    return harness.load_reader(os.path.join(ROOT, "perfbench"),
                               name).read(ctx)


def test_the_new_readers_read_the_new_names():
    ctx = _ctx(new_names=True)
    assert _read("sparse_shard_fetch_ms", ctx) == 6.0     # q10's
    assert _read("sparse_merge_ms", ctx) == 12.0          # not q1's 0.05
    assert _read("sparse_merge_rows_in", ctx) == 1500
    assert _read("sparse_chip_ms_per_query", ctx) == pytest.approx(50.0)
    # 2 x 3,276,000 bytes over 4 x 819 GB/s = 2 us; the busiest chip is
    # busy 1.25 times the mean: 100 x 2e-6 / (0.1 x 1.25)
    assert _read("sparse_chip_roofline", ctx) == pytest.approx(0.0016)


def test_the_new_readers_find_nothing_in_an_older_program():
    ctx = _ctx(new_names=False)
    assert _read("sparse_shard_fetch_ms", ctx) is None
    assert _read("sparse_merge_rows_in", ctx) is None
    # the `broker-merge` span and the device trace predate this cell
    assert _read("sparse_merge_ms", ctx) == 12.0
    assert _read("sparse_chip_ms_per_query", ctx) == pytest.approx(50.0)
    for rec in ctx.records.values():
        del rec["reduce_path"]
    ctx.trace = None
    ctx.traces = {}
    for name in NEW:
        assert _read(name, ctx) is None
