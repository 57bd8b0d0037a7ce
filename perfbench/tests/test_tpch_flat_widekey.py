"""The configuration `tpch-flat-sf10-widekey-chip`, its dataset
`tpch_flat_widekey` and its cell: the plain reference against a pandas
group-by over the published GROUP BY lists, the files against the sibling
dataset's, the manifest's new entries, and the five readers the cell
brought."""

import filecmp
import json
import os
import types

import pandas as pd
import pytest

from perfbench.datasets import tpch_flat, tpch_flat_widekey
from perfbench.datasets.tpch_flat_widekey import reference
from perfbench.lib import harness, verify

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = "tpch-flat-sf10-widekey-chip"
CELL = CONFIG + ".tpch-wide2-c1"
NEW = ("widekey_ms_per_query", "widekey_roofline",
       "widekey_sort_ms_per_query", "widekey_runs_ms_per_query",
       "widekey_host_ms")
ROWS = 400_000                     # ~100,000 orders: a handful pass 300
OPC = 35_000                       # orders a chunk: three files
SEEDS = (17, 2_147_483_659)        # the second beyond 32 signed bits


@pytest.fixture(scope="module", params=SEEDS)
def generated(request, tmp_path_factory):
    d = tmp_path_factory.mktemp(f"widekey_{request.param}")
    out = tpch_flat_widekey.generate(ROWS, request.param, str(d), workers=1,
                                     orders_per_chunk=OPC)
    return request.param, out


def _frame(paths, cols):
    df = pd.concat([pd.read_parquet(p, columns=cols) for p in sorted(paths)],
                   ignore_index=True)
    for c in ("o_orderdate", "c_name", "c_nation", "l_returnflag"):
        if c in df:
            df[c] = df[c].astype(str)
    return df


def _pandas_q18p(paths):
    df = _frame(paths, ["c_name", "o_custkey", "l_orderkey", "o_orderdate",
                        "o_totalprice", "l_quantity"])
    g = df.groupby(["c_name", "o_custkey", "l_orderkey", "o_orderdate",
                    "o_totalprice"], as_index=False).agg(
        sum_quantity=("l_quantity", "sum"))
    n_groups = len(g)
    g = g[g.sum_quantity > reference.QUANTITY].sort_values(
        ["o_totalprice", "o_orderdate", "l_orderkey"],
        ascending=[False, True, True]).head(reference.LIMIT["q18p"])
    g = g.rename(columns={"o_custkey": "c_custkey",
                          "l_orderkey": "o_orderkey"})
    return [{c: (r[c] if c in ("o_orderdate", "c_name") else int(r[c]))
             for c in reference.COLUMNS["q18p"]}
            for r in g.to_dict("records")], n_groups


def _pandas_q10p(paths):
    df = _frame(paths, ["o_custkey", "c_name", "c_acctbal", "c_nation",
                        "l_returnflag", "o_orderdate", "l_extendedprice",
                        "l_discount"])
    df = df[(df.l_returnflag == "R") & (df.o_orderdate >= "1993-10-01")
            & (df.o_orderdate < "1994-01-01")]
    df = df.assign(revenue=df.l_extendedprice * (100 - df.l_discount))
    g = df.groupby(["o_custkey", "c_name", "c_acctbal", "c_nation"],
                   as_index=False).agg(revenue=("revenue", "sum"))
    n_groups = len(g)
    g = g.sort_values(["revenue", "o_custkey"], ascending=[False, True]) \
        .head(reference.LIMIT["q10p"]) \
        .rename(columns={"o_custkey": "c_custkey"})
    return [{c: (r[c] if c in ("c_name", "c_nation") else int(r[c]))
             for c in reference.COLUMNS["q10p"]}
            for r in g.to_dict("records")], n_groups


@pytest.mark.parametrize("template, pandas_answer",
                         [("q10p", _pandas_q10p), ("q18p", _pandas_q18p)])
def test_the_reference_equals_a_pandas_group_by_over_the_published_list(
        generated, template, pandas_answer):
    _seed, out = generated
    want, n_groups = pandas_answer(out["paths"])
    answer = tpch_flat_widekey.answers(out["reference"])[template]
    assert answer["columns"] == list(reference.COLUMNS[template])
    assert answer["rows"] == want and len(want) > 0
    assert not verify.answer_mismatches(
        {"columns": answer["columns"], "rows": want}, answer)
    # the groups of the full list are the determining column's
    if template == "q10p":
        assert out["reference"]["groups"]["q10p"] == n_groups
    else:
        assert out["reference"]["n_orders"] == n_groups
        assert out["reference"]["groups"]["q18p"] == len(want)


def test_a_customer_with_two_balances_is_refused(generated):
    _seed, out = generated
    parts = [{"orders": {c: v[:0] for c, v in
                         out["reference"]["orders"].items()},
              "customers": {c: v[:1] for c, v in
                            out["reference"]["customers"].items()},
              "n_orders": 0, "rows": 0, "sum_l_extendedprice": 0}
             for _ in range(2)]
    parts[1]["customers"] = dict(parts[1]["customers"],
                                 c_acctbal=parts[1]["customers"]["c_acctbal"]
                                 + 1)
    with pytest.raises(ValueError, match="two values of c_acctbal"):
        reference.merge(parts)


def test_four_workers_write_what_one_writes(generated, tmp_path):
    seed, out = generated
    other = tpch_flat_widekey.generate(ROWS, seed, str(tmp_path), workers=4,
                                       orders_per_chunk=OPC)
    assert len(other["paths"]) == len(out["paths"]) == 3
    for a, b in zip(sorted(other["paths"]), sorted(out["paths"])):
        assert filecmp.cmp(a, b, shallow=False)
    ref_a, ref_b = other["reference"], out["reference"]
    assert tpch_flat_widekey.answers(ref_a) == tpch_flat_widekey.answers(ref_b)
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
    assert tpch_flat_widekey.totals(out["reference"]) \
        == tpch_flat.totals(sibling["reference"])
    assert tpch_flat_widekey.TABLE == tpch_flat.TABLE
    # q10p is the sibling's q10 with one more group column: the same rows
    q10 = tpch_flat.answers(sibling["reference"])["q10"]["rows"]
    q10p = tpch_flat_widekey.answers(out["reference"])["q10p"]["rows"]
    assert [{c: r[c] for c in q10[0]} for r in q10p] == q10


def test_the_reference_names_nothing_of_the_program():
    for name in ("__init__.py", "reference.py", "bytes.py"):
        with open(os.path.join(tpch_flat_widekey.HERE, name)) as f:
            text = f.read()
        assert "import jax" not in text and "tpu_olap" not in text


def test_needed_bytes_are_the_templates_columns_once():
    total = {"rows": 1000}
    assert tpch_flat_widekey.needed_bytes("q18p", total) == 19_000
    assert tpch_flat_widekey.needed_bytes("q10p", total) == 21_000
    assert tpch_flat_widekey.needed_bytes("q10p", total, 400) == 8_400
    with pytest.raises(KeyError):
        tpch_flat_widekey.needed_bytes("q3", total)


def test_the_manifests_new_entries_load():
    spec = harness.load_cell(ROOT, CELL)
    config, cell, traffic = spec["config"], spec["cell"], spec["traffic"]
    assert config["name"] == cell["config"] == CONFIG
    assert config["chips"] == cell["chips"] == 1
    assert config["dataset"] == "tpch_flat_widekey"
    assert config["rows"] == 59_986_052 and config["reduced"] == []
    assert config["scale_factor"] == config["scale_factor_published"] == 10
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "tpch-flat-sf10-having-chip.json")) as f:
        sibling = json.load(f)
    assert config["engine_config"] == sibling["engine_config"]
    assert config["controls"] == {"x64-off": {"enable_x64": False}}
    for k in ("served_by", "caches"):
        assert config["guarantees"][k] == sibling["guarantees"][k]
    assert set(config["templates"]) == set(tpch_flat_widekey.templates()) \
        == set(reference.COLUMNS) == set(traffic["templates"])
    assert traffic["loop"] == "closed" and traffic["clients"] == 1
    assert traffic["stop"] == "round_end" and traffic["think_ms"] == 0
    assert traffic["order"] == "round_permutation"
    t = tpch_flat_widekey.templates()
    assert "GROUP BY c_name, o_custkey, l_orderkey, o_orderdate, " \
        "o_totalprice HAVING sum(l_quantity) > 300 " in t["q18p"]
    assert "GROUP BY o_custkey, c_name, c_acctbal, c_nation " in t["q10p"]
    assert {m["name"] for m in spec["end_to_end"]} == {
        "query_p50_ms", "slowest_query_p50_ms", "queries_per_s", "setup_s"}
    # a prefix, not the list: a later PR may give the cell more metrics
    assert [m["name"] for m in spec["per_layer"]][:len(NEW)] == list(NEW)
    for m in spec["per_layer"][:len(NEW)]:
        assert m["workloads"][0] == CELL
        assert harness.load_reader(spec["bench_dir"],
                                   m["name"]).UNIT == m["unit"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and entry["reduced"] == []
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200


def _span(name, start, dur, children=()):
    return {"name": name, "start_ms": start, "duration_ms": dur,
            "children": list(children)}


def _ctx(new_names: bool):
    """Two queries with a two-word key and one with one word, as this
    cell's program records them, or as an older one would."""
    def tree(total, call_end):
        return _span("sql", 0, total,
                     [_span("device-call", 1, call_end - 1)])
    samples = [{"qid": "a", "template": "q18p", "ms": 870.0},
               {"qid": "b", "template": "q10p", "ms": 900.0},
               {"qid": "c", "template": "q3", "ms": 400.0}]

    def key(words):
        return {"key_words": words, "key_bits": 50 * words} \
            if new_names else {}
    records = {"a": dict(reduce_path="sparse", rows_scanned=60_000,
                         **key(2)),
               "b": dict(reduce_path="sparse", rows_scanned=60_000,
                         **key(2)),
               "c": dict(reduce_path="sparse", rows_scanned=60_000,
                         **key(1))}
    traces = {"a": tree(870, 864), "b": tree(900, 840), "c": tree(400, 309)}
    queries = [
        {"qid": "a", "template": "q18p", "device_s": 0.85, "whole": True},
        {"qid": "b", "template": "q10p", "device_s": 0.75, "whole": True},
        {"qid": "c", "template": "q3", "device_s": 0.3, "whole": True}]
    trace = {"queries": queries, "busy_s_by_device": {0: 1.9}}
    # lib/stages.py's reduction, as `stages.reduce` keeps it on the context
    staged = {"stages": {"sort": 1.2, "runs": 0.3},
              "queries": [dict(q, stage_s={"sort": 0.4, "runs": 0.1})
                          for q in queries]} if new_names else None
    dataset = types.SimpleNamespace(
        needed_bytes=lambda template, reference, rows: 819_000_000)
    return types.SimpleNamespace(
        samples=samples, records=records, traces=traces, trace=trace,
        _stages=staged, dataset=dataset, reference={}, chips=1,
        peaks={"hbm_bytes_per_s": 819e9})


def _read(name, ctx):
    return harness.load_reader(os.path.join(ROOT, "perfbench"),
                               name).read(ctx)


def test_the_new_readers_read_the_new_names():
    ctx = _ctx(new_names=True)
    assert _read("widekey_ms_per_query", ctx) == pytest.approx(800.0)
    # 2 x 819 MB over 819 GB/s = 2 ms of 1.6 s
    assert _read("widekey_roofline", ctx) == pytest.approx(0.125)
    assert _read("widekey_sort_ms_per_query", ctx) == pytest.approx(400.0)
    assert _read("widekey_runs_ms_per_query", ctx) == pytest.approx(100.0)
    assert _read("widekey_host_ms", ctx) == 60.0       # q10p's, not q3's


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_finds_nothing_in_an_older_program_or_an_empty_run(
        name):
    assert _read(name, _ctx(new_names=False)) is None
    empty = types.SimpleNamespace(
        samples=[], records={}, traces={}, trace=None, dataset=None,
        reference={}, chips=1, peaks={"hbm_bytes_per_s": 819e9})
    assert _read(name, empty) is None
