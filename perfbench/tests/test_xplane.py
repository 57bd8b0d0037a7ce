"""The trace reduction: on hand-made planes with known answers, and on one
small trace recorded on the chip.

`data/tpu_600k.xplane.pb.gz` is the `.xplane.pb` of a one-second traced
window of `ssb-sf100-chip.ssb13-c1` at 600,000 rows on one TPU v5e (my chip
run, PR 24: `--seed 1010 --seconds 1 --trace 1 --rehearse-rows 600000`),
with the `/host:metadata` plane (9.6 MB of HLO protos, which the reduction
does not read) cut out of the file. It holds 154 annotated queries.
"""

import gzip
import os
import types

import pytest

from perfbench.lib import harness, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def test_union_merges_overlapping_and_touching_intervals():
    assert xplane._union([(5, 6), (0, 2), (1, 3), (3, 4)]) == \
        [[0, 4], [5, 6]]


def test_self_time_subtracts_nested_operations():
    # a 10 s loop holding two bodies of 3 s and 4 s, then a lone op
    ev = [("while", 0.0, 10.0), ("body", 1.0, 4.0), ("body", 5.0, 9.0),
          ("copy", 10.0, 11.0)]
    got = {}
    for name, _s, sec in xplane._self_times(ev):
        got[name] = got.get(name, 0.0) + sec
    assert got == {"while": 3.0, "body": 7.0, "copy": 1.0}


def test_short_name_keeps_result_kind_and_custom_call_target():
    hlo = ('%fn.1 = s32[1,128,256]{2,1,0:T(8,128)S(1)} custom-call(s16[1,64]'
           '{1,0:T(2,128)(2,1)S(1)} %bitcast.43), custom_call_target='
           '"tpu_custom_call", operand_layout_constraints={s16[1,64]{1,0}}')
    assert xplane.short_name(hlo) == "%fn.1 custom-call:tpu_custom_call"
    assert xplane.short_name(
        "%copy-start.1 = (s32[98,65536]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
        "copy-start(s32[98,65536]{1,0:T(8,128)} %p)") == \
        "%copy-start.1 copy-start"
    assert xplane.short_name(
        "%fusion.1 = (u32[72]{0:T(128)S(1)}, u32[72]{0:T(128)S(1)}) fusion("
        "u32[72]{0:T(128)S(1)} %b, s32[27525120]{0:T(1024)S(1)} %c), "
        "kind=kCustom, calls=%fused_computation.1") == \
        "%fusion.1 fusion:kCustom"
    assert xplane.short_name("wrapped_scatter.1") == "wrapped_scatter.1"


def _planes():
    """Two chips, two queries. Chip 0 is busy 1-3 and 6-7 (of a window that
    runs from the first annotation at 0.5 to 9.0, the last operation's end),
    chip 1 busy 1-2 and 6-9."""
    return {
        "devices": {
            0: [("k", 1.0, 3.0), ("copy", 6.0, 7.0)],
            1: [("k", 1.0, 2.0), ("k", 6.0, 9.0)],
        },
        "host": [("qa-1", 0.5, 3.5), ("qa-2", 5.5, 6.5),
                 ("something else", 0.0, 20.0)],
        "extent": (0.0, 20.0),
    }


def test_reduction_of_hand_made_planes():
    r = xplane.reduce_planes(_planes(), {"qa-1": "q2.1", "qa-2": "q1.1"})
    assert r["window_s"] == pytest.approx(8.5)
    assert r["busy_s_by_device"] == {0: 3.0, 1: 4.0}
    assert r["busy_s"] == pytest.approx(3.5)           # mean over the chips
    assert r["op_s"] == {"k": pytest.approx(3.0), "copy": pytest.approx(0.5)}
    q1, q2 = r["queries"]
    assert (q1["template"], q1["whole"], q2["whole"]) == ("q2.1", True, False)
    assert q1["device_s"] == pytest.approx(1.5)        # (2 + 1) / 2 chips
    assert q2["device_s"] == pytest.approx(2.0)        # (1 + 3) / 2 chips
    assert q1["op_s"] == {"k": pytest.approx(1.5)}
    # chip 0's gaps: 0.5-1 and 3-3.5 inside qa-1's annotation... by their
    # midpoints: 0.5-1.0 in dispatch, 3.0-6.0 between (midpoint 4.5 is past
    # the annotation's end), 7.0-9.0 after qa-2
    assert r["idle_gap_s"] == {
        "in_dispatch:q2.1": pytest.approx(0.5),
        "between_queries:after_q2.1": pytest.approx(3.0),
        "between_queries:after_q1.1": pytest.approx(2.0)}


def _readers_ctx(reduced, records):
    ds = types.SimpleNamespace(
        needed_bytes=lambda t, ref, rows: 1_000_000_000)
    return types.SimpleNamespace(
        trace=reduced, records=records, dataset=ds, reference=None, chips=2,
        peaks={"hbm_bytes_per_s": 1e9})


def test_trace_readers_on_hand_made_planes():
    r = xplane.reduce_planes(_planes(), {"qa-1": "q2.1", "qa-2": "q1.1"})
    ctx = _readers_ctx(r, {"qa-1": {"rows_scanned": 5}, "qa-2": {}})
    bench = os.path.join(ROOT, "perfbench")

    def read(name):
        return harness.load_reader(bench, name).read(ctx)

    assert read("device_idle_share") == pytest.approx(100 * (1 - 3.5 / 8.5))
    assert read("chip_busy_skew") == pytest.approx(25.0)
    # one whole grouped query (qa-1); "k" is no kernel name the reader knows
    assert read("kernel_ms_per_query") == pytest.approx(0.0)
    # 1e9 bytes over 2 chips at 1e9 B/s = 0.5 s least, 1.5 s of device time
    assert read("scan_roofline") == pytest.approx(100 * 0.5 / 1.5)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tpu_600k.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "tpu_600k.xplane.pb.gz")) as g:
        path.write_bytes(g.read())
    # the run's query ids were sequential; its traffic is the generator's
    spec = harness.load_cell(ROOT, "ssb-sf100-chip.ssb13-c1")
    names = [f"q{a}.{b}" for a, n in ((1, 3), (2, 3), (3, 4), (4, 3))
             for b in range(1, n + 1)]
    seq = traffic.plan(spec["traffic"], names, 1010, 1.0)["sequences"][0]
    qids = {f"q041d5b0-{58 + i:06d}": t for i, t in enumerate(seq[:154])}
    return xplane.reduce_file(str(path), qids)


def test_recorded_tpu_trace(recorded):
    r = recorded
    assert len(r["queries"]) == 154 and list(r["busy_s_by_device"]) == [0]
    # what the run itself printed (chiprun_out/t2.log of that call)
    assert r["window_s"] == pytest.approx(0.996692938, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.05432122500003011, rel=1e-9)
    # self times add up to the busy time: operations on one chip's line do
    # not overlap except by nesting, which self time takes out
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert xplane.top(r["op_s"], 1)[0][0] == \
        "%fn.1 custom-call:tpu_custom_call"
    # the Pallas kernel runs in the ten grouped templates and in no other
    with_kernel = {q["template"] for q in r["queries"] if any(
        "tpu_custom_call" in n for n in q["op_s"])}
    assert with_kernel == {t for t in (q["template"] for q in r["queries"])
                           if not t.startswith("q1.")}
    assert sum(r["idle_gap_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_readers_on_the_recorded_trace(recorded):
    ctx = types.SimpleNamespace(trace=recorded)
    bench = os.path.join(ROOT, "perfbench")
    assert harness.load_reader(bench, "kernel_ms_per_query").read(ctx) == \
        pytest.approx(0.3508619999999955, rel=1e-9)
    assert harness.load_reader(bench, "device_idle_share").read(ctx) == \
        pytest.approx(94.54985352770402, rel=1e-9)
