"""The configuration `tpch-flat-sf10-having-chip`, its dataset
`tpch_flat_having` and its cell: the plain reference against a pandas
group-by, the files against the sibling dataset's, the manifest's new
entries, and the five readers the cell brought."""

import filecmp
import json
import os
import types

import pandas as pd
import pytest

from perfbench.datasets import tpch_flat, tpch_flat_having
from perfbench.datasets.tpch_flat_having import reference
from perfbench.lib import harness, verify

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "tpch-flat-sf10-having-chip"
CELL = CONFIG + ".tpch-q18-c1"
NEW = ("having_ms_per_query", "having_roofline", "having_stage_ms_per_query",
       "having_rows_fetched", "having_host_ms")
ROWS = 400_000                     # ~100,000 orders: a handful pass 300
OPC = 35_000                       # orders a chunk: three files
SEEDS = (17, 2_147_483_659)        # the second beyond 32 signed bits


@pytest.fixture(scope="module", params=SEEDS)
def generated(request, tmp_path_factory):
    d = tmp_path_factory.mktemp(f"having_{request.param}")
    out = tpch_flat_having.generate(ROWS, request.param, str(d), workers=1,
                                    orders_per_chunk=OPC)
    return request.param, out


def _pandas_answer(paths, quantity):
    cols = ["o_custkey", "l_orderkey", "o_orderdate", "o_totalprice",
            "l_quantity"]
    df = pd.concat([pd.read_parquet(p, columns=cols) for p in sorted(paths)],
                   ignore_index=True)
    df["o_orderdate"] = df["o_orderdate"].astype(str)
    g = df.groupby(["o_custkey", "l_orderkey", "o_orderdate"],
                   as_index=False).agg(o_totalprice=("o_totalprice", "max"),
                                       sum_quantity=("l_quantity", "sum"))
    g = g[g.sum_quantity > quantity].sort_values(
        ["o_totalprice", "o_orderdate", "l_orderkey"],
        ascending=[False, True, True]).head(reference.LIMIT)
    g = g.rename(columns={"o_custkey": "c_custkey",
                          "l_orderkey": "o_orderkey"})
    return [{c: (r[c] if c == "o_orderdate" else int(r[c]))
             for c in reference.COLUMNS} for r in g.to_dict("records")], \
        df["l_orderkey"].nunique()


@pytest.mark.parametrize("template", sorted(reference.QUANTITY))
def test_the_reference_equals_a_pandas_group_by(generated, template):
    _seed, out = generated
    want, n_orders = _pandas_answer(out["paths"],
                                    reference.QUANTITY[template])
    answer = tpch_flat_having.answers(out["reference"])[template]
    assert answer["columns"] == list(reference.COLUMNS)
    assert answer["rows"] == want
    assert out["reference"]["groups"][template] == len(want) < 100
    assert out["reference"]["n_orders"] == n_orders
    assert not verify.answer_mismatches(
        {"columns": answer["columns"], "rows": want}, answer)


def test_a_low_quantity_fills_the_limit_in_order_by_order(generated,
                                                          monkeypatch):
    """The three literals keep a handful of orders at this size: a lower
    QUANTITY shows the ranking and the LIMIT."""
    _seed, out = generated
    total = dict(out["reference"])
    monkeypatch.setitem(reference.QUANTITY, "q18", 305)
    rows = reference.ranked(total, "q18", None)
    assert rows == sorted(rows, key=lambda r: (-r["o_totalprice"],
                                               r["o_orderdate"],
                                               r["o_orderkey"]))
    assert all(r["sum_quantity"] > 305 for r in rows)
    assert len(reference.ranked(total, "q18")) == min(len(rows), 100)


def test_four_workers_write_what_one_writes(generated, tmp_path):
    seed, out = generated
    other = tpch_flat_having.generate(ROWS, seed, str(tmp_path), workers=4,
                                      orders_per_chunk=OPC)
    assert len(other["paths"]) == len(out["paths"]) == 3
    for a, b in zip(sorted(other["paths"]), sorted(out["paths"])):
        assert filecmp.cmp(a, b, shallow=False)
    ref_a, ref_b = other["reference"], out["reference"]
    assert tpch_flat_having.answers(ref_a) == tpch_flat_having.answers(ref_b)
    assert {k: ref_a[k] for k in ("n_orders", "rows", "groups",
                                  "sum_l_extendedprice")} \
        == {k: ref_b[k] for k in ("n_orders", "rows", "groups",
                                  "sum_l_extendedprice")}


def test_the_files_are_the_sibling_datasets_byte_for_byte(generated,
                                                          tmp_path):
    seed, out = generated
    sibling = tpch_flat.generate(ROWS, seed, str(tmp_path), workers=1,
                                 orders_per_chunk=OPC)
    assert [os.path.basename(p) for p in sorted(sibling["paths"])] \
        == [os.path.basename(p) for p in sorted(out["paths"])]
    for a, b in zip(sorted(sibling["paths"]), sorted(out["paths"])):
        assert filecmp.cmp(a, b, shallow=False)
    assert tpch_flat_having.totals(out["reference"]) \
        == tpch_flat.totals(sibling["reference"])
    assert tpch_flat_having.TABLE == tpch_flat.TABLE


def test_the_reference_names_nothing_of_the_program():
    for name in ("__init__.py", "reference.py", "bytes.py"):
        with open(os.path.join(tpch_flat_having.HERE, name)) as f:
            text = f.read()
        assert "import jax" not in text and "tpu_olap" not in text


def test_needed_bytes_are_the_five_columns_once():
    total = {"rows": 1000}
    for t in tpch_flat_having.templates():
        assert tpch_flat_having.needed_bytes(t, total) == 15_000
        assert tpch_flat_having.needed_bytes(t, total, 400) == 6_000
    with pytest.raises(KeyError):
        tpch_flat_having.needed_bytes("q3", total)


def test_the_manifests_new_entries_load():
    spec = harness.load_cell(ROOT, CELL)
    config, cell, traffic = spec["config"], spec["cell"], spec["traffic"]
    assert config["name"] == cell["config"] == CONFIG
    assert config["chips"] == cell["chips"] == 1
    assert config["dataset"] == "tpch_flat_having"
    assert config["rows"] == 59_986_052 and config["reduced"] == []
    assert config["scale_factor"] == config["scale_factor_published"] == 10
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "tpch-flat-sf10-chip.json")) as f:
        sibling = json.load(f)
    assert config["engine_config"] == dict(sibling["engine_config"],
                                           sparse_group_budget=1 << 24)
    assert config["controls"] == {"x64-off": {"enable_x64": False}}
    for k in ("served_by", "caches"):
        assert config["guarantees"][k] == sibling["guarantees"][k]
    assert set(config["templates"]) == set(tpch_flat_having.templates()) \
        == set(reference.QUANTITY) == set(traffic["templates"])
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["stop"] == "round_end" and traffic["think_ms"] == 0
    for t, sql in tpch_flat_having.templates().items():
        assert f"HAVING sum(l_quantity) > {reference.QUANTITY[t]} " in sql
    assert {m["name"] for m in spec["end_to_end"]} == {
        "query_p50_ms", "slowest_query_p50_ms", "queries_per_s", "setup_s"}
    assert [m["name"] for m in spec["per_layer"]] == list(NEW)
    for m in spec["per_layer"]:
        assert m["workloads"] == [CELL]
        assert harness.load_reader(spec["bench_dir"],
                                   m["name"]).UNIT == m["unit"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert len(entry["source"]) <= 200


def _span(name, start, dur, attrs=None, children=()):
    s = {"name": name, "start_ms": start, "duration_ms": dur,
         "children": list(children)}
    if attrs:
        s["attrs"] = attrs
    return s


def _ctx(new_names: bool):
    """Two queries with a HAVING and one without, as this cell's program
    records them, or as an older one would."""
    def tree(total, call_end):
        return _span("sql", 0, total, None,
                     [_span("device-call", 1, call_end - 1)])
    samples = [{"qid": "a", "template": "q18", "ms": 1300.0},
               {"qid": "b", "template": "q18_312", "ms": 1310.0},
               {"qid": "c", "template": "q3", "ms": 400.0}]
    counters = {"having_groups_in": 15_000_000, "having_rows_fetched": 1024,
                "having_where": "device"} if new_names else {}
    records = {"a": dict(reduce_path="sparse", rows_scanned=60_000,
                         **counters),
               "b": dict(reduce_path="sparse", rows_scanned=60_000,
                         **counters),
               "c": dict(reduce_path="sparse", rows_scanned=60_000)}
    traces = {"a": tree(1300, 1294), "b": tree(1310, 1302),
              "c": tree(400, 391)}
    trace = {"queries": [
        {"qid": "a", "template": "q18", "device_s": 1.2, "whole": True},
        {"qid": "b", "template": "q18_312", "device_s": 1.3, "whole": True},
        {"qid": "c", "template": "q3", "device_s": 0.3, "whole": True}],
        "busy_s_by_device": {0: 2.8}}
    dataset = types.SimpleNamespace(
        needed_bytes=lambda template, reference, rows: 819_000_000)
    return types.SimpleNamespace(
        samples=samples, records=records, traces=traces, trace=trace,
        dataset=dataset, reference={}, chips=1,
        peaks={"hbm_bytes_per_s": 819e9})


def _read(name, ctx):
    return harness.load_reader(os.path.join(ROOT, "perfbench"),
                               name).read(ctx)


def test_the_new_readers_read_the_new_names():
    ctx = _ctx(new_names=True)
    assert _read("having_ms_per_query", ctx) == pytest.approx(1250.0)
    # 2 x 819 MB over 819 GB/s = 2 ms of 2.5 s
    assert _read("having_roofline", ctx) == pytest.approx(0.08)
    assert _read("having_rows_fetched", ctx) == 1024
    assert _read("having_host_ms", ctx) == 8.0         # q18_312's, not q3's


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_in_an_older_program_or_an_empty_run(
        name):
    assert _read(name, _ctx(new_names=False)) is None
    empty = types.SimpleNamespace(
        samples=[], records={}, traces={}, trace=None, dataset=None,
        reference={}, chips=1, peaks={"hbm_bytes_per_s": 819e9})
    assert _read(name, empty) is None
