"""The wire-format reader of a capture, on the small trace recorded on the
chip (`data/tpu_600k.xplane.pb.gz`: see test_xplane.py; one TPU v5e, PR 24,
before the programs had named scopes, so it shows an op's path but no stage)
and on a capture of the CPU backend made here, which keeps the programs'
HLO."""

import gzip
import os

import pytest

from perfbench.lib import xplane, xspace

HERE = os.path.dirname(os.path.abspath(__file__))
FIRST_PROGRAM = 17201160773399703773


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("xspace") / "tpu_600k.xplane.pb")
    with gzip.open(os.path.join(HERE, "data", "tpu_600k.xplane.pb.gz")) as f:
        with open(path, "wb") as out:
            out.write(f.read())
    return path


def test_the_recorded_chip_trace_reads_as_it_was_read_by_hand(recorded):
    space = xspace.read(recorded)
    assert list(space["devices"]) == [0] and space["programs"] == {}
    ops = space["devices"][0]["ops"]
    assert len(ops) == 7802
    by_path: dict = {}
    for op in ops:
        by_path[op.tf_op] = by_path.get(op.tf_op, 0.0) \
            + (op.end_s - op.start_s)
    assert len(by_path) == 30
    top = sorted(by_path.items(), key=lambda kv: -kv[1])[:5]
    assert [(k, round(1e3 * v, 3)) for k, v in top] == [
        ("jit(fn)/pallas_call:", 30.394), ("", 10.608),
        ("jit(fn)/scatter-add:", 10.464),
        ("jit(fn)/convert_element_type:", 0.833), ("jit(fn)/gather:", 0.547)]
    modules = space["devices"][0]["modules"]
    assert len(modules) == 154
    assert modules[0][:2] == (FIRST_PROGRAM, f"jit_fn({FIRST_PROGRAM})")
    assert {op.program_id for op in ops} == {m[0] for m in modules}
    layout = [op.name.split()[1] for op in ops
              if op.category == "data formatting"]
    assert (layout.count("copy"), layout.count("reshape"), len(layout)) \
        == (1215, 463, 1678)
    named = [op for op in ops if op.source]
    assert named and all(":" in op.source for op in named)
    assert ops[0].instruction == "custom-call.9" \
        and ops[0].name == "%custom-call.9 custom-call:X64SplitLow"


def test_it_finds_the_busy_seconds_and_the_annotations_of_xplane(recorded):
    planes = xplane.read_planes(recorded)
    qid = next(name for name, _s, _e in planes["host"]
               if len(name) == 32 or name.count("-") == 4)
    space = xspace.read(recorded, host_names=(qid,))
    ops = space["devices"][0]["ops"]
    theirs = planes["devices"][0]
    assert len(ops) == len(theirs)
    mine_busy = sum(e - s for s, e in xplane._union(
        [(op.start_s, op.end_s) for op in ops]))
    their_busy = sum(e - s for s, e in xplane._union(
        [(s, e) for _n, s, e in theirs]))
    # ProfileData gives an event's start and duration in whole nanoseconds
    assert abs(mine_busy - their_busy) < 2e-9 * len(ops)
    for op, (name, s, e) in zip(ops, theirs):
        assert op.name == name
        assert abs(op.start_s - s) < 2e-9 and abs(op.end_s - e) < 4e-9
    want = sorted((s, e) for name, s, e in planes["host"] if name == qid)
    got = sorted((s, e) for _name, s, e in space["host"])
    assert len(got) == len(want) >= 1
    for (s0, e0), (s1, e1) in zip(got, want):
        assert abs(s0 - s1) < 2e-9 and abs(e0 - e1) < 4e-9


def test_it_agrees_with_the_generated_protos_where_they_import(recorded):
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        pytest.skip("tensorflow's xplane_pb2 does not import here")
    proto = xplane_pb2.XSpace()
    with open(recorded, "rb") as f:
        proto.ParseFromString(f.read())
    plane = next(p for p in proto.planes if p.name == "/device:TPU:0")
    stat_name = {k: v.name for k, v in plane.stat_metadata.items()}
    want = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            meta = plane.event_metadata[ev.metadata_id]
            stats = {}
            for st in meta.stats:
                kind = st.WhichOneof("value")
                value = getattr(st, kind)
                stats[stat_name[st.metadata_id]] = \
                    stat_name[value] if kind == "ref_value" else value
            start = line.timestamp_ns * 1e-9 + ev.offset_ps * 1e-12
            want.append((stats.get("program_id"), meta.name,
                         stats.get("tf_op", ""),
                         stats.get("hlo_category", ""),
                         stats.get("source", ""), start,
                         start + ev.duration_ps * 1e-12))
    ops = xspace.read(recorded)["devices"][0]["ops"]
    assert len(ops) == len(want)
    for op, (program, name, tf_op, category, source, s, e) in zip(ops, want):
        assert (op.program_id, op.name, op.tf_op, op.category, op.source) \
            == (program, xplane.short_name(name), tf_op, category, source)
        assert op.start_s == pytest.approx(s, abs=1e-12) \
            and op.end_s == pytest.approx(e, abs=1e-12)


def test_a_cpu_capture_stands_in_and_keeps_the_programs_hlo(tmp_path):
    """No device plane on the CPU backend: the thunks' host events stand in
    as device 0, as in lib/xplane.py, and the capture's `/host:metadata`
    plane gives each program's optimised HLO with the scopes in it."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("runs"):
            y = jax.lax.reduce_window(x, 0, jax.lax.add, (x.shape[0],),
                                      (1,), [(x.shape[0] - 1, 0)])
        with jax.named_scope("sort"):
            return jax.lax.sort(y * 3 % 17)

    g = jax.jit(f)
    x = jnp.arange(1 << 12)
    g(x).block_until_ready()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    with jax.profiler.TraceAnnotation("the-query"):
        g(x).block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_xplane(str(tmp_path))
    space = xspace.read(path, host_names=("the-query",))
    assert [name for name, _s, _e in space["host"]] == ["the-query"]
    ops = space["devices"][0]["ops"]
    theirs = xplane.read_planes(path)["devices"][0]
    assert len(ops) == len(theirs) > 0
    program = {op.program_id for op in ops}
    assert len(program) == 1 and program <= set(space["programs"])
    hlo = xspace.read_hlo(space["buf"], space["programs"][program.pop()])
    assert hlo["name"] == "jit_f" and hlo["entry"] in hlo["computations"]
    entry = hlo["computations"][hlo["entry"]]
    names = {i["name"] for i in entry["instructions"].values()}
    assert {op.instruction for op in ops} <= names
    assert entry["root"] in entry["instructions"]
    paths = {i["op_name"] for c in hlo["computations"].values()
             for i in c["instructions"].values()}
    assert any("/sort/" in p for p in paths) \
        and any("/runs/" in p for p in paths)
    by_id = {iid: i for c in hlo["computations"].values()
             for iid, i in c["instructions"].items()}
    assert all(o in by_id for i in by_id.values() for o in i["operands"])
    assert all(c in hlo["computations"] for i in by_id.values()
               for c in i["called"])
