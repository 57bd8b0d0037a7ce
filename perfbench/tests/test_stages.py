"""The join of device time with the program's stage names, on hand-made
events with known answers."""

import os
import types

import pytest

from perfbench.lib import stages, xspace
from perfbench.lib.xspace import Op

STAGES = ("filter", "key", "reduce", "pack", "sort", "runs", "prefix",
          "gather", "merge")


# ------------------------------------------- a protobuf written by hand

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _instruction(iid, name, opcode, op_name="", operands=(), called=()):
    body = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if op_name:
        body += _field(7, _field(2, op_name))
    if operands:
        body += _field(36, b"".join(_varint(o) for o in operands))
    for c in called:
        body += _field(38, c)
    return _field(2, body)


def _hlo_proto(name, entry, computations) -> bytes:
    """computations: [(id, name, root id, [instruction bytes])]"""
    module = _field(1, name) + _field(6, entry)
    for cid, cname, root, instructions in computations:
        module += _field(3, _field(1, cname) + b"".join(instructions)
                         + _field(5, cid) + _field(6, root))
    return _field(1, module)


def test_stage_of_takes_the_innermost_scope_and_never_the_primitive():
    assert stages.stage_of("jit(fn)/sort/filter/and", STAGES) == "filter"
    assert stages.stage_of("jit(fn)/gather", STAGES) is None
    assert stages.stage_of("jit(fn)/sort/sort", STAGES) == "sort"
    assert stages.stage_of("jit(per_chip)/shard_map/prefix/"
                           "reduce_window_sum", STAGES) == "prefix"
    assert stages.stage_of("reduce_window_sum", STAGES) is None
    assert stages.stage_of("", STAGES) is None


def _sparse_program():
    """sort -> copy -> reduce-window -> fusion(prefix) -> gather fusion,
    with what XLA:TPU's rewriter and its layout assignment leave unnamed,
    a fusion named only in its body, and a loop."""
    body = [_instruction(20, "p", "parameter"),
            _instruction(21, "and.1", "and", "jit(f)/filter/and", [20])]
    loop = [_instruction(30, "lp", "parameter"),
            _instruction(31, "copy.9", "copy", "", [30]),
            _instruction(32, "tuple.9", "tuple", "", [31])]
    entry = [
        _instruction(1, "x", "parameter", "x"),
        _instruction(2, "fusion.7", "fusion", "", [1], [2]),
        _instruction(3, "sort.1", "sort", "jit(f)/sort/sort", [2]),
        _instruction(4, "copy.1", "copy", "", [3]),
        _instruction(5, "reduce-window.1", "reduce-window", "", [4]),
        _instruction(6, "reduce-window.2", "reduce-window", "", [5]),
        _instruction(7, "fusion.1", "fusion",
                     "jit(f)/prefix/reduce_window_sum", [6, 4]),
        _instruction(8, "fusion.2", "fusion", "jit(f)/gather/gather", [7]),
        _instruction(9, "slice.1", "slice", "", [8]),
        _instruction(10, "while.1", "while", "jit(f)/merge/while", [8], [3]),
        _instruction(11, "tuple.1", "tuple", "", [9, 10]),
    ]
    return _hlo_proto("jit_f", 1, [(2, "fused", 21, body),
                                   (3, "loop_body", 32, loop),
                                   (1, "main", 11, entry)])


def test_an_unnamed_op_takes_its_consumers_stage():
    buf = _sparse_program()
    hlo = xspace.read_hlo(buf, (0, len(buf)))
    assert hlo["name"] == "jit_f" and hlo["entry"] == 1
    assert hlo["computations"][1]["instructions"][7]["operands"] == [6, 4]
    got = stages.program_stages(hlo, STAGES)
    assert got["sort.1"] == ("sort", stages.OWN)
    # named in its body's root only
    assert got["fusion.7"] == ("filter", stages.OWN)
    # the rewritten scan's tree and the copy made for it: the scan's
    assert got["reduce-window.1"] == got["reduce-window.2"] \
        == got["copy.1"] == ("prefix", stages.INHERITED)
    # nothing reads the last slice but the outputs: its operand's
    assert got["slice.1"] == ("gather", stages.INHERITED)
    # a loop's body: the loop's
    assert got["copy.9"] == ("merge", stages.INHERITED)
    # a parameter with a name that is no path has none, and needs none
    assert got["x"] == ("filter", stages.INHERITED)


def _space(programs: dict, devices: dict, host: list) -> dict:
    buf, spans = b"", {}
    for pid, proto in programs.items():
        spans[pid] = (len(buf), len(buf) + len(proto))
        buf += proto
    return {"devices": {d: {"ops": ops, "modules": []}
                        for d, ops in devices.items()},
            "host": host, "programs": spans, "buf": buf}


def _op(program, name, tf_op, start, end, category=""):
    return Op(program, f"%{name} fusion", name, tf_op, category, "", start,
              end)


def _two_programs():
    """Two queries on four chips, each its own program, and both programs
    hold a `%fusion.1`: q3's is the sort, q12's the reduce. q3 also runs a
    reduce-window with no tf_op, which its program's HLO gives to `prefix`,
    and a layout copy that nothing names; every chip does the same, chip d
    being d times as slow."""
    sparse = _sparse_program()
    dense = _hlo_proto("jit_g", 1, [(1, "main", 2, [
        _instruction(1, "fusion.1", "fusion", "jit(g)/reduce/mul"),
        _instruction(2, "copy.3", "copy", "", [1])])])
    devices = {}
    for d in range(4):
        k = d + 1
        devices[d] = [
            # the capture starts before the first annotation: cut at it
            _op(7, "fusion.2", "jit(f)/gather/gather:", 99.0, 100.0 + 0.1),
            _op(7, "fusion.1", "jit(f)/sort/sort:", 101.0, 101.0 + 2 * k),
            _op(7, "reduce-window.1", "", 110.0, 110.0 + k),
            _op(7, "copy.77", "", 116.0, 116.5, "data formatting"),
            _op(8, "fusion.1", "jit(g)/reduce/mul:", 120.0, 120.0 + k),
            _op(8, "copy.3", "", 125.0, 126.0, "data formatting"),
        ]
    host = [("qid-a", 100.0, 115.0), ("qid-b", 119.0, 130.0),
            ("other", 1.0, 2.0)]
    queries = [
        {"qid": "qid-a", "template": "q3", "start_s": 0.0, "whole": True},
        {"qid": "qid-b", "template": "q12", "start_s": 19.0,
         "whole": True},
        {"qid": "qid-c", "template": "q3", "start_s": 40.0,
         "whole": False}]
    busy = {d: 0.1 + 2 * (d + 1) + (d + 1) + 0.5 + (d + 1) + 1.0
            for d in range(4)}
    return _space({7: sparse, 8: dense}, devices, host), queries, busy


def test_the_join_keeps_programs_apart_and_means_over_the_chips():
    space, queries, busy = _two_programs()
    out = stages.reduce_space(space, queries, busy, STAGES)
    assert out is not None
    q3, q12, last = out["queries"]
    mean_k = 2.5   # of 1, 2, 3, 4
    assert q3["stage_s"] == pytest.approx(
        {"gather": 0.1, "sort": 2 * mean_k, "prefix": mean_k,
         stages.UNNAMED: 0.5})
    assert q3["layout_copy_s"] == pytest.approx(0.5)
    assert q12["stage_s"] == pytest.approx({"reduce": mean_k + 1.0})
    assert q12["layout_copy_s"] == pytest.approx(1.0)
    assert last["device_s"] == 0.0 and not last["whole"]
    assert q3["device_s"] == pytest.approx(sum(q3["stage_s"].values()))
    # one row a (template, stage, program, op): `%fusion.1` twice
    rows = {k: v for k, v in out["rows"].items() if "fusion.1" in k[3]}
    assert {(k[0], k[1], k[2]): round(v["s"], 9) for k, v in rows.items()} \
        == {("q3", "sort", 7): 2 * mean_k, ("q12", "reduce", 8): mean_k}
    how = out["how_s"]
    assert how[stages.OWN] == pytest.approx(0.1 + 2 * mean_k + mean_k)
    assert how[stages.INHERITED] == pytest.approx(mean_k + 1.0)
    assert how[None] == pytest.approx(0.5)
    assert out["busy_s_by_device"] == pytest.approx(busy)


def test_without_the_programs_hlo_an_unnamed_op_stays_unnamed():
    space, queries, busy = _two_programs()
    space["programs"] = {}
    out = stages.reduce_space(space, queries, busy, STAGES)
    q3, q12, _last = out["queries"]
    assert q3["stage_s"][stages.UNNAMED] == pytest.approx(2.5 + 0.5)
    assert q12["stage_s"][stages.UNNAMED] == pytest.approx(1.0)


def test_events_off_the_queries_axis_are_not_reported(capsys):
    space, queries, busy = _two_programs()
    assert stages.reduce_space(space, queries, {**busy, 2: 1.0},
                               STAGES) is None
    assert "not on the queries' axis" in capsys.readouterr().out
    space["host"] = [("other", 1.0, 2.0)]
    assert stages.reduce_space(space, queries, busy, STAGES) is None
    assert stages.reduce_space(dict(space, devices={}), queries, busy,
                               STAGES) is None


def _ctx(out, records):
    return types.SimpleNamespace(
        _stages=out, records=records,
        trace={"queries": [], "busy_s_by_device": {}})


def test_the_readers_take_the_sparse_queries_and_none_without_a_scope():
    space, queries, busy = _two_programs()
    out = stages.reduce_space(space, queries, busy, STAGES)
    ctx = _ctx(out, {"qid-a": {"reduce_path": "sparse"},
                     "qid-b": {"reduce_path": "scatter"},
                     "qid-c": {"reduce_path": "sparse"}})
    assert stages.sparse_ms_per_query(ctx, "sort") == pytest.approx(5000.0)
    assert stages.sparse_ms_per_query(ctx, "prefix") == pytest.approx(2500.0)
    assert stages.sparse_ms_per_query(ctx, "merge") == 0.0
    assert stages.sparse_ms_per_query(ctx, "threshold") is None
    assert stages.sparse_ms_per_query(_ctx(out, {}), "sort") is None
    assert stages.sparse_ms_per_query(_ctx(None, {}), "sort") is None
    stages._print_table(out, [dict(q, device_s=1.0) for q in queries])


def test_a_run_without_a_capture_or_a_vocabulary_reads_nothing(
        monkeypatch, tmp_path, capsys):
    ctx = types.SimpleNamespace(trace=None, records={})
    assert stages.reduce(ctx) is None and ctx._stages is None
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path / "work" / "tpu_logs"))
    monkeypatch.setattr(stages.tempfile, "gettempdir",
                        lambda: str(tmp_path / "nowhere"))
    assert stages.find_capture() is None
    ctx = types.SimpleNamespace(
        records={}, trace={"queries": [{"qid": "a"}],
                           "busy_s_by_device": {}})
    monkeypatch.setattr(stages, "vocabulary", lambda: STAGES)
    assert stages.reduce(ctx) is None
    assert "no capture found" in capsys.readouterr().out
    monkeypatch.setattr(stages, "vocabulary", lambda: None)
    del ctx._stages
    assert stages.reduce(ctx) is None
    assert "no stage vocabulary" in capsys.readouterr().out


def test_the_capture_is_found_beside_the_log_directory_or_the_newest(
        monkeypatch, tmp_path):
    def capture(work):
        d = tmp_path / work / "trace" / "plugins" / "profile" / "t0"
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(b"")
        return str(d / "vm.xplane.pb")

    mine = capture("perfbench_mine")
    monkeypatch.setenv("TPU_LOG_DIR",
                       str(tmp_path / "perfbench_mine" / "tpu_logs"))
    monkeypatch.setattr(stages.tempfile, "gettempdir", lambda: str(tmp_path))
    assert stages.find_capture() == mine
    # another TPU_LOG_DIR (the driver's, a test's): the newest work
    # directory under the temporary directory
    monkeypatch.setenv("TPU_LOG_DIR", str(tmp_path / "elsewhere" / "logs"))
    assert stages.find_capture() == mine
    newer = capture("perfbench_newer")
    os.utime(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(newer)))), (2e9, 2e9))
    assert stages.find_capture() == newer


def test_the_programs_vocabulary_is_the_one_the_readers_use():
    from tpu_olap.kernels.groupby import STAGES as PROGRAM
    assert stages.vocabulary() == PROGRAM
    for name in ("sort", "runs", "prefix", "gather", "merge", "filter",
                 "key", "threshold", "reduce", "pack"):
        assert name in PROGRAM
