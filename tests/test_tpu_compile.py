"""Compile the serving path's device programs for a DESCRIBED TPU v5e.

Interpret mode never lowers through Mosaic, so every other Pallas test
in this suite passes on kernels the chip's compiler refuses
(docs/TPU_NOTES.md, pitfalls #1-#5). The TPU compiler is installed in
the sandbox and compiles for a topology that is described, not attached;
nothing runs, so these cases guard lowering only — never results or
times. This is the only file that describes the chip: the topology call
lives in a fixture (one xdist worker loads libtpu, the others never do),
never at import, in a skipif, in parametrize arguments, or in conftest.
"""

import dataclasses
import math
import os
import re

import pytest

from tpu_olap import Engine
from tpu_olap.bench import QUERIES
from tpu_olap.bench.ssb import generate_tables, register_ssb
from tpu_olap.executor import EngineConfig, sparse_dispatch

ROWS = 120_000
ROWS_75M = 75_000_000

MINMAX_SQL = """
    SELECT d_year, min(lo_revenue) AS lo, max(lo_supplycost) AS hi,
           sum(lo_revenue) AS revenue
    FROM lineorder GROUP BY d_year"""
# min/max disables the lane factorization, so K ~ 7 x 1000 tiles over
# ceil(K / pallas_k_per_block) K-blocks
KTILED_SQL = """
    SELECT d_year, p_brand1, max(lo_revenue) AS hi,
           sum(lo_revenue) AS revenue
    FROM lineorder GROUP BY d_year, p_brand1"""

# sketch finals are float64 computed ON the device (packing.device_finalize)
HLL_SQL = """
    SELECT s_region, approx_count_distinct(lo_custkey) AS u
    FROM lineorder JOIN supplier ON lo_suppkey = s_suppkey
    GROUP BY s_region"""

# (id, sql, rows the segment axis is scaled to, expect the Pallas kernel).
# min/max and sketches finalize to float64, which the TPU cannot bitcast
# into the packed int32 buffer: those slabs travel as an f32 pair
# (packing.PackLayout.f64_as_pair) and the program stays single-fetch.
CASES = [
    ("q1.1-generic", QUERIES["q1.1"], ROWS, False),
    ("q2.1", QUERIES["q2.1"], ROWS, True),
    ("q2.2-factorized", QUERIES["q2.2"], ROWS, True),
    ("minmax", MINMAX_SQL, ROWS, True),
    ("k-tiled", KTILED_SQL, ROWS, True),
    ("hll", HLL_SQL, ROWS, False),
    ("q4.3-chunked-75M", QUERIES["q4.3"], ROWS_75M, True),
]


@pytest.fixture(scope="module")
def topo(tmp_path_factory):
    from jax.experimental import topologies
    # libtpu otherwise logs under the fixed /tmp/tpu_logs, outside the
    # checkout and TMPDIR, where every run of this file would meet
    # ("disabled" only moves the files to the top of TMPDIR or /tmp)
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    recompiles) — keep the cache off around these compiles."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def ssb_tables():
    return generate_tables(ROWS, seed=7)


def _engine(ssb_tables, **cfg):
    eng = Engine(EngineConfig(fallback_on_device_failure=False, **cfg))
    register_ssb(eng, tables=ssb_tables)
    return eng


def _as_tpu(monkeypatch):
    """Code that asks jax.default_backend() sees the CPU here; steer the
    one seam the lowering reads so `use_pallas="auto"` builds the real
    (non-interpret) kernel, exactly as it would on the chip."""
    from tpu_olap.executor import lowering
    monkeypatch.setattr(lowering, "_default_backend", lambda: "tpu")


def _physical(eng, sql):
    plan = eng.planner.plan(sql)
    assert plan.rewritten, plan.fallback_reason
    return eng.runner._lower_cached(plan.query, plan.entry.segments)


def _scaled(tree, seg_factor, sharding):
    """ShapeDtypeStructs of `tree` on the described device, leading
    (segment) axis of every >=1-D leaf multiplied by `seg_factor`."""
    import jax

    def leaf(x):
        shape = tuple(x.shape)
        if seg_factor != 1 and shape:
            shape = (shape[0] * seg_factor,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)

    return jax.tree_util.tree_map(leaf, tree)


def _chosen(eng, phys, env, rows, cap):
    """The sparse program of `cap` slots over `rows` sorted rows, as the
    dispatch chooses it (`sparse_dispatch.choose_program`)."""
    return sparse_dispatch.choose_program(
        eng.runner, phys, {c: a.dtype for c, a in env["cols"].items()},
        frozenset(env["nulls"]), rows, cap)


def compile_dispatch(eng, phys, sharding, rows=None):
    """Lower + compile, from shapes alone, the packed single-fetch
    program (runner._packed_jit) the runner would dispatch for `phys`
    on `sharding`'s device."""
    r = eng.runner
    env, valid, seg_mask = r._prepare(phys, {})
    n_seg = len(seg_mask)
    factor = 1 if rows is None else max(
        1, math.ceil(rows / (n_seg * phys.table.block_rows)))
    consts_dev, seg_arg = r._args_for(phys, seg_mask, None)
    cap = min(eng.config.result_group_cap, phys.total_groups)
    jitted, layout, _ = r._packed_jit(phys, cap, None)
    data = _scaled((env, valid, seg_arg), factor, sharding)
    consts = _scaled(consts_dev, 1, sharding)
    return jitted.lower(*data, consts).compile(), factor * n_seg, layout


@pytest.mark.parametrize("case,sql,rows,want_pallas", CASES,
                         ids=[c[0] for c in CASES])
def test_dispatch_program_compiles_for_v5e(topo, no_persistent_cache,
                                           ssb_tables, monkeypatch, case,
                                           sql, rows, want_pallas):
    from jax.sharding import SingleDeviceSharding
    _as_tpu(monkeypatch)
    eng = _engine(ssb_tables)
    phys = _physical(eng, sql)
    if want_pallas:
        assert phys.pallas_reason is None, phys.pallas_reason
    compiled, n_seg, layout = compile_dispatch(
        eng, phys, SingleDeviceSharding(topo.devices[0]),
        rows if rows != ROWS else None)
    assert layout.f64_as_pair
    assert n_seg * phys.table.block_rows >= rows
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == want_pallas
    if case == "k-tiled":
        assert phys.total_groups > eng.config.pallas_k_per_block
    if rows == ROWS_75M:
        # enough grid steps that the int32 accumulator chunks: the
        # kernel's [n_chunks, K_pad, W] output has n_chunks > 1, so the
        # f64 chunk recombination is part of the compiled program
        call = next(ln for ln in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in ln)
        assert int(re.search(r"= s32\[(\d+),\d+,\d+\]", call).group(1)) > 1


# TPC-H Q1's shape on the SSB table: a sum whose input passes int32, so
# Pallas turns the plan down and the generic grouped kernel serves it
CHARGE = "sum(lo_extendedprice * (100 - lo_discount) * (100 + lo_tax))"
GENERIC_ROWS = 6_000_000
# (id, sql, expect XLA's scatter in the program)
GENERIC_CASES = [
    ("q1-shaped-8-slots", f"""
        SELECT d_year, count(*) AS n, sum(lo_quantity) AS qty,
               sum(lo_extendedprice) AS base, {CHARGE} AS charge,
               min(lo_extendedprice) AS lo, max(lo_supplycost) AS hi
        FROM lineorder GROUP BY d_year""", False),
    ("q1-shaped-1001-slots", f"""
        SELECT p_brand1, count(*) AS n, {CHARGE} AS charge
        FROM lineorder GROUP BY p_brand1""", False),
    ("q1-shaped-past-the-bound", f"""
        SELECT d_year, p_brand1, count(*) AS n, {CHARGE} AS charge
        FROM lineorder GROUP BY d_year, p_brand1""", True),
    ("hll-8-slots", HLL_SQL, True),
]


@pytest.mark.parametrize("case,sql,want_scatter", GENERIC_CASES,
                         ids=[c[0] for c in GENERIC_CASES])
def test_generic_grouped_program_compare_or_scatter(
        topo, no_persistent_cache, ssb_tables, monkeypatch, case, sql,
        want_scatter):
    """The generic dense reduce at 6M rows, compiled for the chip: up to
    COMPARE_MAX_GROUPS slots there is no scatter in the program and no
    [K, N] buffer (the compare-select is the reduce's fused input; what
    the program holds is each aggregate's input, evaluated once: a small
    multiple of one int64 column an aggregate); past the bound, and for a
    sketch's [K, m] state, XLA's scatter stays."""
    from jax.sharding import SingleDeviceSharding
    from tpu_olap.kernels.groupby import COMPARE_MAX_GROUPS, reduce_form
    _as_tpu(monkeypatch)
    eng = _engine(ssb_tables)
    phys = _physical(eng, sql)
    assert phys.pallas_reason is not None and not phys.sparse
    form = reduce_form(phys.total_groups,
                       [p.kind for p in phys.agg_plans])
    assert (form == "scatter") == want_scatter
    assert (phys.total_groups > COMPARE_MAX_GROUPS) == \
        (case == "q1-shaped-past-the-bound")
    compiled, n_seg, _ = compile_dispatch(
        eng, phys, SingleDeviceSharding(topo.devices[0]), GENERIC_ROWS)
    n = n_seg * phys.table.block_rows
    assert n >= GENERIC_ROWS
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    # the `pack` stage compacts the [K] tables with a K-row scatter of
    # its own; the reduce stage's ops carry its scope in their op_name
    in_reduce = [ln for ln in text.splitlines()
                 if " scatter(" in ln and "/reduce/" in ln]
    assert bool(in_reduce) == want_scatter
    if not want_scatter:
        temp = compiled.memory_analysis().temp_size_in_bytes
        n_aggs = len(phys.agg_plans)
        assert temp < n_aggs * n * 8 < phys.total_groups * n * 8, (temp, n)


def test_sparse_topn_program_compiles_for_v5e(topo, no_persistent_cache,
                                              ssb_tables, monkeypatch):
    """A TopN whose dense plan would be XLA's scatter (lo_partkey's 4,001
    slots are past COMPARE_MAX_GROUPS and Pallas is kept off it, as its
    factorized cap keeps it off l_partkey's 2,000,001 in the Druid
    lineitem cell): the sparse program with the threshold at its end,
    compiled for the chip. One
    multi-operand sort, the one-operand sort of `starts`, the int64
    `top_k`; no scatter, and `threshold` rows a table are its output."""
    from jax.sharding import SingleDeviceSharding

    from tpu_olap.kernels.groupby import COMPARE_MAX_GROUPS
    _as_tpu(monkeypatch)
    eng = _engine(ssb_tables, use_pallas="never")
    phys = _physical(eng, """
        SELECT lo_partkey, sum(lo_quantity) AS qty, count(*) AS n
        FROM lineorder GROUP BY lo_partkey ORDER BY qty DESC LIMIT 100""")
    assert phys.query.query_type == "topN" and phys.sparse
    assert phys.pallas_reason is not None
    assert COMPARE_MAX_GROUPS < phys.total_groups \
        <= eng.config.sparse_group_budget
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    # the wide program of the runner's choice (the narrow one is the
    # Druid cell's test below)
    program = dataclasses.replace(
        _chosen(eng, phys, env, valid.size, phys.total_groups), narrow=False)
    assert program.top == ("qty", 100, False) and program.kept is None
    consts_dev, seg_arg = eng.runner._args_for(phys, seg_mask, None)
    one_chip = SingleDeviceSharding(topo.devices[0])
    import jax
    compiled = jax.jit(phys.make_sparse_kernel(program)) \
        .lower(*_scaled((env, valid, seg_arg), 1, one_chip),
               _scaled(consts_dev, 1, one_chip)).compile()
    text = compiled.as_text()
    assert " scatter(" not in text and " sort(" in text
    out = jax.eval_shape(phys.make_sparse_kernel(program),
                         env, valid, seg_arg, consts_dev)
    assert {k: v.shape for k, v in out.items()} == {
        "_count": (), "_rows": (100,), "_keys": (100,), "qty": (100,),
        "n": (100,)}


def test_sparse_min_max_program_compiles_for_v5e(topo, no_persistent_cache,
                                                 monkeypatch, tmp_path):
    """`top_100_parts_details` of the Druid lineitem cell, as the chip
    runs it (the part keys past the dense budget): two int64 sums and the
    min and max of `l_discount`, stored as int8, read at the sorted runs'
    last rows from a running maximum of an int32 word. The chip's compiler
    takes the program; no scatter is in it, the running reduces are
    reduce-windows, and the column rides the sort once for both."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from perfbench.datasets import druid_lineitem
    from tpu_olap.kernels.sparse_groupby import ext_word_bits
    _as_tpu(monkeypatch)
    rows, seed = 60_000, 2_147_483_659
    data = druid_lineitem.generate(rows, seed, str(tmp_path), workers=1,
                                   orders_per_chunk=7_000)
    eng = Engine(EngineConfig(fallback_on_device_failure=False,
                              dense_group_budget=1024))
    druid_lineitem.register(eng, data["paths"], rows, seed)
    phys = _physical(eng,
                     druid_lineitem.templates()["top_100_parts_details"])
    assert phys.query.query_type == "topN" and phys.sparse
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    assert env["cols"]["l_discount"].dtype == "int8"
    stored = {c: a.dtype for c, a in env["cols"].items()}
    # the cell's cap: 2,000,001 part keys, 21 bits over nine of code
    for cap in (phys.total_groups, 2_000_001):
        assert ext_word_bits(phys.agg_plans, stored, cap) == 32
    consts_dev, seg_arg = eng.runner._args_for(phys, seg_mask, None)
    one_chip = SingleDeviceSharding(topo.devices[0])
    # the narrow program, which the runner tries first (PR 41): the sums
    # of l_quantity (int8) and l_extendedprice (int32) ride as one int32
    # word each, and `_narrow_ok` says whether every part's sums fit
    from tpu_olap.kernels.sparse_groupby import sum_word_bits
    assert env["cols"]["l_extendedprice"].dtype == "int32"
    assert sum_word_bits(phys.agg_plans, stored, True) == 32
    # at the cell's 30 rows a slot the ranked table is a gather
    program = _chosen(eng, phys, env, 59_986_052, phys.total_groups)
    from tpu_olap.kernels.sparse_groupby import SparseProgram
    assert program == SparseProgram(
        phys.total_groups, ("sum_quantity", 100, False), None, True,
        "gather")
    kernel = phys.make_sparse_kernel(program)
    lowered = jax.jit(kernel).lower(
        *_scaled((env, valid, seg_arg), 1, one_chip),
        _scaled(consts_dev, 1, one_chip))
    main_sort = max(re.findall(r"stablehlo\.sort\"?\(([^)]*)\)",
                               lowered.as_text()), key=len)
    # key, two sums, l_discount once
    assert len(main_sort.split(",")) == 4, main_sort
    text = lowered.compile().as_text()
    assert " scatter(" not in text and " sort(" in text
    assert " reduce-window(" in text
    # the key (21 bits of part keys: an int32 word since PR 44, where an
    # int64 word was two u32 halves), the two sums and l_discount as one
    # s32 each
    from tpu_olap.kernels.sparse_groupby import key_sort_bits
    assert key_sort_bits(phys.sizes, phys.key_words) == [32]
    assert key_sort_bits((1, 2_000_001), ((1,),)) == [32]
    widest = max(re.findall(r"= \((.*?)\) sort\(", text), key=len)
    assert re.findall(r"([us]\d+)\[", widest) == ["s32"] * 4, widest
    out = jax.eval_shape(kernel, env, valid, seg_arg, consts_dev)
    assert out["_narrow_ok"].shape == () and out["sum_price"].dtype == "int64"
    assert {k: (v.shape, str(v.dtype)) for k, v in out.items()
            if "discount" in k} == {
        "min_discount": ((100,), "int64"), "max_discount": ((100,), "int64"),
        "_nn_min_discount": ((100,), "int32"),
        "_nn_max_discount": ((100,), "int32")}


def test_sparse_having_program_compiles_for_v5e(topo, no_persistent_cache,
                                                monkeypatch, tmp_path):
    """Q18 of `tpch-flat-sf10-having-chip`, as the chip runs it: the sparse
    program with the HAVING at its end (compiled on small shapes under a
    cap of 2^16 slots; the cell's is 2^24, whose word is checked). The tested
    sum's prefix is the one table read at [cap], and at the cell's 3.7 rows
    a slot (one here) it rides `starts`' sort as a second operand (PR 43):
    no cap-sized gather is left; with the rule turned off it is ONE gather
    of an s32. max(o_totalprice)
    rides the sort as int32 and is read from an int64 running maximum (25
    + 32 + 1 bits) at the kept rows; three sorts (the rows, `starts`, the
    passing slots), no scatter, `kept` rows a table out. The count probe
    is one sort and no table."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from perfbench.datasets import tpch_flat_having
    from tpu_olap.kernels import sparse_groupby as sg
    from tpu_olap.kernels.sparse_groupby import cap_tables, ext_word_bits
    _as_tpu(monkeypatch)
    rows, seed, cap, kept = 60_000, 2_147_483_659, 1 << 24, 1024
    data = tpch_flat_having.generate(rows, seed, str(tmp_path), workers=1,
                                     orders_per_chunk=7_000)
    eng = Engine(EngineConfig(fallback_on_device_failure=False,
                              sparse_group_budget=cap))
    tpch_flat_having.register(eng, data["paths"], rows, seed)
    phys = _physical(eng, tpch_flat_having.templates()["q18"])
    assert phys.query.query_type == "groupBy" and phys.sparse
    assert phys.total_groups > cap
    assert sparse_dispatch.device_having(eng.runner.mesh, phys)
    assert phys.having[1] == {"sum_quantity"}
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    assert env["cols"]["o_totalprice"].dtype == "int32"
    stored = {c: a.dtype for c, a in env["cols"].items()}
    assert ext_word_bits(phys.agg_plans, stored, cap) == 64
    assert cap_tables(phys.agg_plans, stored, cap,
                      having=phys.having[1]) == 1
    assert cap_tables(phys.agg_plans, stored, cap) == 3
    consts_dev, seg_arg = eng.runner._args_for(phys, seg_mask, None)
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = (*_scaled((env, valid, seg_arg), 1, one_chip),
            _scaled(consts_dev, 1, one_chip))
    assert ext_word_bits(phys.agg_plans, stored, 1 << 16) == 64
    # the narrow program, which the runner tries first (PR 41): the
    # tested sum(l_quantity), stored as int8, rides as one int32 word
    # as the runner builds it: the rules' answers for these rows and cap
    program = _chosen(eng, phys, env, valid.size, 1 << 16)
    assert program == sg.SparseProgram(1 << 16, None, kept, True, "sorted")
    assert sg.boundary_spelling(62_062_592, cap) == "sorted"
    kernel = phys.make_sparse_kernel(program)
    lowered = jax.jit(kernel).lower(*args)
    main_sort = max(re.findall(r"stablehlo\.sort\"?\(([^)]*)\)",
                               lowered.as_text()), key=len)
    # key, l_quantity's sum operand, o_totalprice once
    assert len(main_sort.split(",")) == 3, main_sort

    def sort_operands(lowered):
        return sorted(len(m.split(",")) for m in re.findall(
            r"stablehlo\.sort\"?\(([^)]*)\)", lowered.as_text()))

    def cap_gathers(text, dtype):
        return len(re.findall(
            rf"= {dtype}\[{(1 << 16) + 1}\]\S* gather\(", text))
    # the passing slots' sort, `starts` with the tested sum's int32 prefix
    # as its rider, the rows
    assert sort_operands(lowered) == [1, 2, 3]
    text = lowered.compile().as_text()
    assert " scatter(" not in text and text.count(" sort(") == 3
    assert not cap_gathers(text, "s32") and not cap_gathers(text, "u32")
    # said `gather` the prefix is ONE cap-sized gather of an s32, where
    # the wide program's int64 prefix is two of a u32 half
    gathered = jax.jit(phys.make_sparse_kernel(dataclasses.replace(
        program, boundary="gather"))).lower(*args)
    assert sort_operands(gathered) == [1, 1, 3]
    text = gathered.compile().as_text()
    assert cap_gathers(text, "s32") == 1 and not cap_gathers(text, "u32")
    out = jax.eval_shape(kernel, env, valid, seg_arg, consts_dev)
    assert {k: (v.shape, str(v.dtype)) for k, v in out.items()} == {
        "_count": ((), "int32"), "_kept": ((), "int32"),
        "_narrow_ok": ((), "bool"),
        "_rows": ((kept,), "int32"), "_keys": ((kept,), "int64"),
        "sum_quantity": ((kept,), "int64"),
        "o_totalprice": ((kept,), "int64"),
        "_nn_o_totalprice": ((kept,), "int32")}
    counts = phys.make_sparse_kernel(
        _chosen(eng, phys, env, valid.size, None))
    count = jax.jit(counts).lower(*args).compile()
    assert count.as_text().count(" sort(") == 1
    assert jax.eval_shape(counts, env, valid, seg_arg,
                          consts_dev)["_count"].shape == ()


def test_sparse_wide_key_program_compiles_for_v5e(topo, no_persistent_cache,
                                                  monkeypatch, tmp_path):
    """`q18p` of `tpch-flat-sf10-widekey-chip`, as the chip runs it: Q18 with
    the five group columns the specification publishes, a group space past
    2^62 (compiled on small shapes under a cap of 2^16 slots). The sort
    compares TWO int64 key words and carries `l_quantity`'s sum as one int32
    word; `o_totalprice` is a key, so no running maximum is left; three
    sorts (the rows, `starts` with the tested sum's prefix riding it, the
    passing slots), no cap-sized gather, no row-sized scatter,
    and a `_keys` table a word at the kept rows. The count probe sorts the
    two words and builds no table."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from perfbench.datasets import tpch_flat_widekey
    from tpu_olap.kernels.sparse_groupby import SparseProgram, cap_tables
    _as_tpu(monkeypatch)
    rows, seed, cap, kept = 60_000, 2_147_483_659, 1 << 24, 1024
    data = tpch_flat_widekey.generate(rows, seed, str(tmp_path), workers=1,
                                      orders_per_chunk=7_000)
    eng = Engine(EngineConfig(fallback_on_device_failure=False,
                              sparse_group_budget=cap))
    tpch_flat_widekey.register(eng, data["paths"], rows, seed)
    phys = _physical(eng, tpch_flat_widekey.templates()["q18p"])
    assert phys.query.query_type == "groupBy" and phys.sparse
    assert phys.total_groups >= 1 << 62 and len(phys.key_words) == 2
    assert sorted(sum(phys.key_words, ())) == [1, 2, 3, 4, 5]
    assert sparse_dispatch.device_having(eng.runner.mesh, phys)
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    stored = {c: a.dtype for c, a in env["cols"].items()}
    assert cap_tables(phys.agg_plans, stored, cap,
                      having=phys.having[1]) == 1
    consts_dev, seg_arg = eng.runner._args_for(phys, seg_mask, None)
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = (*_scaled((env, valid, seg_arg), 1, one_chip),
            _scaled(consts_dev, 1, one_chip))
    program = _chosen(eng, phys, env, valid.size, 1 << 16)
    assert program == SparseProgram(1 << 16, None, kept, True, "sorted")
    kernel = phys.make_sparse_kernel(program)
    lowered = jax.jit(kernel).lower(*args)
    main_sort = max(re.findall(r"stablehlo\.sort\"?\(([^)]*)\)",
                               lowered.as_text()), key=len)
    # two key words and l_quantity's sum operand
    assert len(main_sort.split(",")) == 3, main_sort
    # the passing slots' sort, `starts` with the tested sum's int32 prefix
    # as its rider (one row a slot here, 3.7 in the cell: PR 43), the rows
    assert sorted(len(m.split(",")) for m in re.findall(
        r"stablehlo\.sort\"?\(([^)]*)\)", lowered.as_text())) == [1, 2, 3]
    text = lowered.compile().as_text()
    assert " scatter(" not in text and text.count(" sort(") == 3
    assert not re.findall(rf"= [su]32\[{(1 << 16) + 1}\]\S* gather\(", text)
    out = jax.eval_shape(kernel, env, valid, seg_arg, consts_dev)
    assert {k: (v.shape, str(v.dtype)) for k, v in out.items()} == {
        "_count": ((), "int32"), "_kept": ((), "int32"),
        "_narrow_ok": ((), "bool"),
        "_rows": ((kept,), "int32"), "_keys": ((kept,), "int64"),
        "_keys1": ((kept,), "int64"),
        "sum_quantity": ((kept,), "int64")}
    counts = phys.make_sparse_kernel(
        _chosen(eng, phys, env, valid.size, None))
    count = jax.jit(counts).lower(*args).compile()
    assert count.as_text().count(" sort(") == 1
    assert jax.eval_shape(counts, env, valid, seg_arg,
                          consts_dev)["_count"].shape == ()


@pytest.mark.parametrize("word", ["int32", "int64"])
def test_running_max_compiles_at_the_druid_cells_rows(topo,
                                                      no_persistent_cache,
                                                      word):
    """The running maximum a sparse min / max is read from, over the
    59,986,052 rows of the Druid lineitem cell: the chip's compiler takes
    the int32 word as one `lax.cummax` and the int64 word along blocks (a
    one-dimensional int64 `lax.cummax` of that length takes it down, and a
    million rows a minute: PERF.md section 6, PR 37)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from tpu_olap.kernels.sparse_groupby import _running_max
    EngineConfig().apply_x64()
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(_running_max).lower(jax.ShapeDtypeStruct(
        (59_986_052,), word, sharding=one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def test_q12_shaped_program_has_no_row_gather_on_v5e(topo,
                                                     no_persistent_cache,
                                                     monkeypatch):
    """TPC-H Q12's shape at 6M rows, compiled for the chip: both
    row-by-row comparisons read resident streams, so no `kCustom` gather
    fusion has a row-sized result (the parent's two, the u32 halves of an
    int64 dictionary gathered by every row's code, took 355 ms each at
    36M rows; the `IN` list's small predicate table is no gather on the
    chip). Without the streams the closures' gather spelling brings them
    back: the control."""
    from jax.sharding import SingleDeviceSharding

    from test_colcmp import _dates_frame, _q12_shaped_dispatch
    _as_tpu(monkeypatch)
    eng = Engine(EngineConfig(fallback_on_device_failure=False))
    # dictionaries as wide as the cell's: XLA:TPU turns a gather from a
    # table of a few dozen entries into a select an entry
    eng.register_table("d", _dates_frame(span=2500), time_column="ts")
    phys, programs = _q12_shaped_dispatch(eng)
    assert phys.pallas_reason is not None and len(phys.filter_streams) == 3
    one_chip = SingleDeviceSharding(topo.devices[0])
    factor = math.ceil(GENERIC_ROWS / phys.table.block_rows)
    rows = factor * phys.table.block_rows
    row_gather_fusions = {}
    for name, (jitted, (*data, consts)) in programs.items():
        assert len(data[1]) == 1    # one segment block, scaled to 6M rows
        text = jitted.lower(*_scaled(tuple(data), factor, one_chip),
                            _scaled(consts, 1, one_chip)).compile().as_text()
        row_gather_fusions[name] = [
            ln for ln in text.splitlines()
            if "kind=kCustom" in ln and "/gather" in ln
            and re.search(rf"= \w+\[{rows}\]", ln)]
    assert row_gather_fusions["with_streams"] == []
    assert len(row_gather_fusions["without_streams"]) >= 2


# (id, sql, per-chip window, expect the Pallas kernel, expect a scatter)
MESH_CASES = [
    ("q2.1", QUERIES["q2.1"], False, True, False),
    ("q2.1-windowed", QUERIES["q2.1"], True, True, False),
    ("q1.1-windowed-generic", QUERIES["q1.1"], True, False, False),
    ("hll-generic", HLL_SQL, False, False, True),
]
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


@pytest.mark.parametrize("case,sql,windowed,want_pallas,want_scatter",
                         MESH_CASES, ids=[c[0] for c in MESH_CASES])
def test_sharded_program_compiles_for_four_chips(
        topo, no_persistent_cache, ssb_tables, monkeypatch, case, sql,
        windowed, want_pallas, want_scatter):
    """The dense mesh program (sharding.mesh_agg_kernel, "per_chip")
    on a four-device Mesh built from the described topology: the
    single-chip plan.kernel mapped over the chips — the Mosaic call when
    the plan is eligible, XLA's scatter otherwise — on each chip's OWN
    rows, with no collective anywhere in the program."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tpu_olap.executor import sharding as sh
    _as_tpu(monkeypatch)
    eng = _engine(ssb_tables)
    phys = _physical(eng, sql)
    assert (phys.pallas_reason is None) == want_pallas, phys.pallas_reason
    mesh = Mesh(np.asarray(topo.devices[:4]), (sh.AXIS,))
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    # shapes only: the segment axis is scaled to eight blocks a chip
    per_chip, block_rows = 8, phys.table.block_rows
    n_seg = 4 * per_chip
    win = (0, per_chip // 2) if windowed else None

    def struct(x, spec):
        shape = tuple(x.shape)
        if spec != P():
            shape = (n_seg,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    seg = P(sh.AXIS)
    args = (jax.tree_util.tree_map(lambda x: struct(x, seg), env),
            struct(valid, seg), struct(seg_mask, seg),
            {k: struct(v, P()) for k, v in phys.pool.consts.items()})
    if windowed:
        args += (jax.ShapeDtypeStruct((), np.int32,
                                      sharding=NamedSharding(mesh, P())),)
    fn = sh.mesh_agg_kernel(phys, mesh, per_chip, "per_chip", win)
    text = fn.lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == want_pallas
    assert ("scatter" in text) == want_scatter
    assert not [c for c in COLLECTIVES if c in text]
    # every chip's program holds its own rows only: the resident blocks
    # come in as [S/D, block_rows], nothing has the global row count,
    # and the kernel's row operands are the chip's (windowed) rows
    shapes = {tuple(int(d) for d in m.split(","))
              for m in re.findall(r"\w\d+\[([\d,]+)\]", text)}
    assert (per_chip, block_rows) in shapes
    assert not [s for s in shapes if math.prod(s) == n_seg * block_rows]
    if want_pallas:
        call = next(ln for ln in text.splitlines()
                    if 'custom_call_target="tpu_custom_call"' in ln)
        assert "onehot_group_reduce" in call
        rows = (win[1] if windowed else per_chip) * block_rows
        assert f"[1,{rows}]" in call


def test_sparse_program_maps_over_four_chips_as_one_program(
        topo, no_persistent_cache, ssb_tables, monkeypatch):
    """The mesh's sparse group-by (sharding.mesh_sparse_kernel) on a
    four-device Mesh of the described topology: the one-chip sort/compact
    kernel mapped over the chips as ONE program a cap (a single-device jit
    a chip compiles the sort once a chip: the persistent cache's key holds
    the device assignment), each chip's sort over its own rows, no
    collective and no scatter, the D tables laid end to end; the program
    that cuts tables to the present groups' bucket before a fetch; and
    the merge of the D tables on the device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tpu_olap.executor import sharding as sh
    _as_tpu(monkeypatch)
    eng = _engine(ssb_tables, use_pallas="never")
    phys = _physical(eng, """
        SELECT lo_orderkey, lo_partkey, sum(lo_revenue) AS revenue,
               count(*) AS n
        FROM lineorder GROUP BY lo_orderkey, lo_partkey
        ORDER BY revenue DESC, lo_orderkey LIMIT 10""")
    assert phys.sparse and phys.total_groups > eng.config.sparse_group_budget
    mesh = Mesh(np.asarray(topo.devices[:4]), (sh.AXIS,))
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    per_chip, block_rows, cap = 2, phys.table.block_rows, 1 << 12
    n_seg = 4 * per_chip

    def struct(x, spec):
        shape = tuple(x.shape)
        if spec != P():
            shape = (n_seg,) + shape[1:]
        return jax.ShapeDtypeStruct(shape, x.dtype,
                                    sharding=NamedSharding(mesh, spec))

    seg = P(sh.AXIS)
    args = (jax.tree_util.tree_map(lambda x: struct(x, seg), env),
            struct(valid, seg), struct(seg_mask, seg),
            {k: struct(v, P()) for k, v in phys.pool.consts.items()})
    from tpu_olap.kernels.sparse_groupby import SparseProgram
    fn = sh.mesh_sparse_kernel(phys, mesh, SparseProgram(cap))
    text = fn.lower(*args).compile().as_text()
    assert " sort(" in text and " scatter(" not in text
    assert not [c for c in COLLECTIVES if c in text]
    shapes = {tuple(int(d) for d in m.split(","))
              for m in re.findall(r"\w\d+\[([\d,]+)\]", text)}
    assert (per_chip * block_rows,) in shapes   # a chip sorts its own rows
    assert not [s for s in shapes if math.prod(s) == n_seg * block_rows]
    out = jax.eval_shape(fn, *args)
    assert {k: v.shape for k, v in out.items()} == {
        "_count": (4,), "_rows": (4 * cap,), "_keys": (4 * cap,),
        "revenue": (4 * cap,), "n": (4 * cap,)}
    tables = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                      sharding=NamedSharding(mesh, seg))
              for k, v in out.items() if k != "_count"}
    head = sh.mesh_head_kernel(mesh, 1 << 10)
    head_text = head.lower(tables).compile().as_text()
    assert not [c for c in COLLECTIVES if c in head_text]
    assert {k: v.shape for k, v in jax.eval_shape(head, tables).items()} \
        == {k: (4 << 10,) for k in tables}
    # the merge on the device: every chip gathers the others' first rows
    # (the program's one collective) and sorts them twice; nothing is
    # gathered by row or scattered, and the merged table is replicated
    merge = sh.mesh_merge_kernel(phys, mesh, 1 << 10)
    merge_text = merge.lower(tables).compile().as_text()
    assert "all-gather" in merge_text and merge_text.count(" sort(") == 2
    assert " scatter(" not in merge_text and " gather(" not in merge_text
    merged = jax.eval_shape(merge, tables)
    assert {k: v.shape for k, v in merged.items()} == dict(
        {k: (4 << 10,) for k in tables}, _count=())
    whole = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                     sharding=NamedSharding(mesh, P()))
             for k, v in merged.items() if k != "_count"}
    cut = sh.mesh_head_kernel(mesh, 1 << 9, merged=True)
    assert not [c for c in COLLECTIVES
                if c in cut.lower(whole).compile().as_text()]
    assert {k: v.shape for k, v in jax.eval_shape(cut, whole).items()} \
        == {k: (1 << 9,) for k in tables}
