"""Every device program names its stages.

The jitted programs wrap each stage in a `jax.named_scope` of one vocabulary
(`kernels/groupby.py::STAGES`); a scope lands in the op_name of the ops
traced under it, which a device capture keeps (`tf_op`) and the benchmark's
`perfbench/lib/stages.py` reads. These cases lower each program on small
shapes and read the lowered module's op_names: every stage the program
should have is there, and no sort, reduce_window (a `cumsum` / `cummax`),
gather, scatter or Pallas call lies outside a stage.

An op traced inside an out-of-line function (a non-inline `jit` such as
`jnp.cumsum` or `jnp.nonzero`, or a primitive lowered out of line such as
`lax.cumsum`) carries a path that starts at that function: the caller's
scope is not in it, and XLA's inliner does not put it back. Such an op counts
as staged only through its call sites, and only where all of them agree: one
`cumsum` called from two stages would read as either.

The second half is the host side's lost leaf: `assemble` names what it does
between its children.
"""

import re

import numpy as np
import pytest

from tpu_olap import Engine
from tpu_olap.bench import QUERIES
from tpu_olap.bench.ssb import generate_tables, register_ssb
from tpu_olap.executor import EngineConfig, sparse_dispatch
from tpu_olap.kernels.groupby import STAGES, stage_scope

ROWS = 20_000
CHARGE = "sum(lo_extendedprice * (100 - lo_discount) * (100 + lo_tax))"
COMPARE_SQL = f"""
    SELECT d_year, count(*) AS n, {CHARGE} AS charge
    FROM lineorder GROUP BY d_year"""
SCATTER_SQL = f"""
    SELECT d_year, p_brand1, count(*) AS n, {CHARGE} AS charge
    FROM lineorder GROUP BY d_year, p_brand1"""
SPARSE_BOUNDARY_SQL = """
    SELECT lo_partkey, sum(lo_quantity) AS qty, count(*) AS n,
           min(lo_discount) AS lo, max(lo_discount) AS hi
    FROM lineorder WHERE lo_quantity < 40 GROUP BY lo_partkey"""
SPARSE_SCATTER_SQL = """
    SELECT lo_partkey, sum(lo_quantity) AS qty,
           approx_count_distinct(lo_custkey) AS u
    FROM lineorder GROUP BY lo_partkey"""
SPARSE_TOPN_SQL = """
    SELECT lo_partkey, sum(lo_quantity) AS qty, count(*) AS n
    FROM lineorder GROUP BY lo_partkey ORDER BY qty DESC LIMIT 100"""

SPARSE_HAVING_SQL = """
    SELECT lo_partkey, sum(lo_quantity) AS qty, count(*) AS n,
           max(lo_discount) AS hi
    FROM lineorder WHERE lo_quantity < 40 GROUP BY lo_partkey
    HAVING sum(lo_quantity) > 60 AND count(*) < 9"""

# the ops a stage is told by: where one of these has no stage, seconds of a
# capture have no name
TOLD = ("stablehlo.sort", "stablehlo.reduce_window", "stablehlo.gather",
        "stablehlo.dynamic_gather", "stablehlo.scatter",
        "stablehlo.custom_call", "stablehlo.all_gather")
_NAME = re.compile(r'loc\("([^"]*)"')


def test_the_vocabulary_is_closed():
    import jax.numpy as jnp
    assert len(set(STAGES)) == len(STAGES)
    with stage_scope("sort", jnp), stage_scope("merge", np):
        pass
    with pytest.raises(ValueError, match="not a stage"):
        stage_scope("sorting", jnp)


def _stage(op_name: str):
    # the rule the benchmark reads a capture by
    from perfbench.lib.stages import stage_of
    return stage_of(op_name, STAGES)


def staged_ops(lowered):
    """[(op kind, op_name, the stages it can read as)] of the lowered
    module's TOLD ops: its own stage, else those of the call sites of the
    function it lies in, through as many calls as it takes."""
    module = lowered.compiler_ir("stablehlo")
    ops, calls = [], {}     # calls: callee -> [(caller function, op_name)]

    def walk(op, fn):
        name = _NAME.match(str(op.location))
        name = name.group(1) if name else ""
        if op.name == "func.func":
            fn = str(op.attributes["sym_name"]).strip('"')
        elif op.name == "func.call":
            callee = str(op.attributes["callee"]).lstrip("@")
            calls.setdefault(callee, []).append((fn, name))
        elif op.name in TOLD:
            ops.append((op.name, name, fn))
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    walk(inner.operation, fn)

    walk(module.operation, None)

    def of_function(fn, seen=()):
        out = set()
        for caller, name in calls.get(fn, ()):
            stage = _stage(name + "/")
            if stage is not None:
                out.add(stage)
            elif caller not in seen:
                out |= of_function(caller, seen + (fn,))
        return out

    return [(kind, name,
             {_stage(name)} if _stage(name) else of_function(fn))
            for kind, name, fn in ops]


@pytest.fixture(scope="module")
def ssb_tables():
    return generate_tables(ROWS, seed=7)


def _engine(ssb_tables, **cfg):
    eng = Engine(EngineConfig(fallback_on_device_failure=False, **cfg))
    register_ssb(eng, tables=ssb_tables)
    return eng


def _physical(eng, sql):
    plan = eng.planner.plan(sql)
    assert plan.rewritten, plan.fallback_reason
    return eng.runner._lower_cached(plan.query, plan.entry.segments)


def _dispatch_args(eng, phys, mesh=None):
    env, valid, seg_mask = eng.runner._prepare(phys, {})
    consts_dev, seg_arg = eng.runner._args_for(phys, seg_mask, mesh)
    return env, valid, seg_arg, consts_dev


def _packed(ssb_tables, monkeypatch, sql, platform=None, **cfg):
    """The packed single-fetch program the runner dispatches for `sql`."""
    if platform == "tpu":
        # the real kernel, not its interpreter: lowered for the TPU from
        # here, as an export would be
        from tpu_olap.executor import lowering
        monkeypatch.setattr(lowering, "_default_backend", lambda: "tpu")
    eng = _engine(ssb_tables, **cfg)
    phys = _physical(eng, sql)
    cap = min(eng.config.result_group_cap, phys.total_groups)
    jitted, _layout, _ = eng.runner._packed_jit(phys, cap, None)
    traced = jitted.trace(*_dispatch_args(eng, phys))
    return phys, traced.lower(lowering_platforms=(platform,)) if platform \
        else traced.lower()


def _pallas(ssb_tables, monkeypatch):
    phys, lowered = _packed(ssb_tables, monkeypatch, QUERIES["q2.1"], "tpu")
    assert phys.pallas_reason is None
    assert "tpu_custom_call" in lowered.as_text()
    return lowered


def _compare(ssb_tables, monkeypatch):
    from tpu_olap.kernels.groupby import reduce_form
    phys, lowered = _packed(ssb_tables, monkeypatch, COMPARE_SQL)
    assert phys.pallas_reason is not None and not phys.sparse
    assert reduce_form(phys.total_groups) == "compare"
    return lowered


def _scatter(ssb_tables, monkeypatch):
    from tpu_olap.kernels.groupby import reduce_form
    phys, lowered = _packed(ssb_tables, monkeypatch, SCATTER_SQL)
    assert phys.pallas_reason is not None and not phys.sparse
    assert reduce_form(phys.total_groups) == "scatter"
    assert "stablehlo.scatter" in lowered.as_text()
    return lowered


def _sparse(ssb_tables, sql, form, top=False, having=False, **cfg):
    import jax

    from tpu_olap.kernels.sparse_groupby import sparse_reduce_form
    eng = _engine(ssb_tables, dense_group_budget=64, **cfg)
    phys = _physical(eng, sql)
    assert phys.sparse
    args = _dispatch_args(eng, phys)
    stored = {c: a.dtype for c, a in args[0]["cols"].items()}
    assert sparse_reduce_form(phys.agg_plans, stored, 4096) == form
    program = sparse_dispatch.choose_program(
        eng.runner, phys, stored, frozenset(args[0]["nulls"]), args[1].size,
        4096)
    assert (program.top is not None) == top
    assert (program.kept == 1024) == having == (program.kept is not None)
    return jax.jit(phys.make_sparse_kernel(program)).lower(*args)


def _mesh(ssb_tables, which):
    import jax

    from tpu_olap.executor import sharding as sh
    eng = _engine(ssb_tables, dense_group_budget=64, num_shards=4,
                  mesh_merge="device")
    mesh = eng.runner.mesh
    phys = _physical(eng, SPARSE_BOUNDARY_SQL)
    args = _dispatch_args(eng, phys, mesh)
    from tpu_olap.kernels.sparse_groupby import SparseProgram
    sort = sh.mesh_sparse_kernel(phys, mesh, SparseProgram(4096))
    if which == "sort":
        return sort.lower(*args)
    tables = {k: v for k, v in jax.eval_shape(sort, *args).items()
              if k != "_count"}
    merge = sh.mesh_merge_kernel(phys, mesh, 1024)
    if which == "merge":
        return merge.lower(tables)
    merged = {k: v for k, v in jax.eval_shape(merge, tables).items()
              if k != "_count"}
    return sh.mesh_head_kernel(mesh, 256, True).lower(merged)


SPARSE_STAGES = {"filter", "key", "sort", "runs", "prefix", "gather"}
# (id, the program's lowering, the stages it must have)
PROGRAMS = [
    ("pallas", _pallas, {"filter", "reduce", "pack"}),
    ("compare", _compare, {"filter", "key", "reduce", "pack"}),
    ("xla-scatter", _scatter, {"filter", "key", "reduce", "pack"}),
    ("sparse-boundary", lambda t, m: _sparse(t, SPARSE_BOUNDARY_SQL,
                                             "boundary"), SPARSE_STAGES),
    ("sparse-scatter", lambda t, m: _sparse(t, SPARSE_SCATTER_SQL,
                                            "scatter"),
     SPARSE_STAGES | {"segment"}),
    ("sparse-topn", lambda t, m: _sparse(t, SPARSE_TOPN_SQL, "boundary",
                                         top=True, use_pallas="never"),
     (SPARSE_STAGES - {"filter"}) | {"threshold"}),
    ("sparse-having", lambda t, m: _sparse(t, SPARSE_HAVING_SQL, "boundary",
                                           having=True),
     SPARSE_STAGES | {"having"}),
    ("mesh-sparse", lambda t, m: _mesh(t, "sort"), SPARSE_STAGES),
    ("mesh-merge", lambda t, m: _mesh(t, "merge"), {"merge"}),
    ("mesh-head", lambda t, m: _mesh(t, "head"), {"pack"}),
]


@pytest.mark.parametrize("case,lower,want", PROGRAMS,
                         ids=[p[0] for p in PROGRAMS])
def test_every_told_op_of_a_device_program_lies_in_one_stage(
        ssb_tables, monkeypatch, case, lower, want):
    lowered = lower(ssb_tables, monkeypatch)
    if case == "mesh-head":     # a slice a table: no told op, only names
        assert _scopes(lowered) == want
        return
    ops = staged_ops(lowered)
    # a custom call that is no kernel (a sharding annotation) tells nothing
    ops = [(kind, name, stages) for kind, name, stages in ops
           if kind != "stablehlo.custom_call" or "pallas_call" in name
           or _stage(name)]
    assert ops, case
    loose = [(kind, name, sorted(stages)) for kind, name, stages in ops
             if len(stages) != 1]
    assert not loose, loose
    found = {next(iter(stages)) for _kind, _name, stages in ops}
    assert want <= found | _scopes(lowered), (want, found)
    if case.startswith(("sparse", "mesh-sparse")):
        # the sparse path's prefix sums are bound inline (`_running`): each
        # carries its own stage, none reads through a call site
        sums = [(name, stages) for kind, name, stages in ops
                if kind == "stablehlo.reduce_window"]
        assert sums and all(_stage(name) for name, _s in sums), sums
        by = {s: sum(1 for name, _ in sums if _stage(name) == s)
              for s in ("runs", "prefix")}
        # the run ids' prefix sum is in the program where a table is read
        # by run id (a min / max's word, a segment reduce) and dead else
        assert by["runs"] == (0 if case == "sparse-topn" else 1), by
        assert by["prefix"] >= 1, by
        sorts = sorted(_stage(name) for kind, name, _s in ops
                       if kind == "stablehlo.sort")
        # a device HAVING compacts the passing slots with a sort of its own
        assert sorts == (["having"] if case == "sparse-having" else []) \
            + ["runs", "sort"], sorts
    if case == "pallas":
        assert [_stage(name) for kind, name, _s in ops
                if "pallas_call" in name] == ["reduce"]
    if case == "mesh-merge":
        assert {kind for kind, _n, _s in ops} >= {"stablehlo.sort",
                                                  "stablehlo.all_gather"}


def _scopes(lowered) -> set:
    """Every stage that any op_name of the lowered module holds."""
    return {part for name in _NAME.findall(lowered.as_text(debug_info=True))
            for part in name.split("/")[:-1] if part in STAGES}


# ------------------------------------------------------- the host's leaf

def _pieces_of(tree, name):
    from perfbench.lib import timeline
    span = timeline.spans_named(tree, name)[0]
    pieces: list = []
    timeline._pieces(span, 0.0, pieces)
    return span, pieces


def test_assemble_names_what_it_does_between_its_children(ssb_tables):
    """A grouped ORDER BY query: `assemble` holds `decode-groups`,
    `ordered-limit` (PR 27) and the per-row loop as the leaf
    `assemble-rows`; what is left between them, which the benchmark's idle
    account counts as unnamed, is no piece over 5% of it."""
    eng = _engine(ssb_tables)
    for _ in range(3):
        res = eng.sql(QUERIES["q2.1"])
    tree = eng.tracer.last.to_json()
    span, pieces = _pieces_of(tree, "assemble")
    names = [c["name"] for c in span["children"]]
    assert names == ["decode-groups", "ordered-limit", "assemble-rows"]
    rows = next(c for c in span["children"] if c["name"] == "assemble-rows")
    assert not rows.get("children")
    assert rows["attrs"]["rows"] == len(res) > 50
    own = [(n, b - a) for n, a, b in pieces if n == "self:assemble"]
    assert own and max(ms for _n, ms in own) < 0.05 * span["duration_ms"], \
        (own, span["duration_ms"])
    named = sum(b - a for n, a, b in pieces if not n.startswith("self:"))
    assert named > 0.9 * span["duration_ms"]


@pytest.mark.parametrize("sql,rows", [
    ("SELECT sum(lo_revenue) AS r FROM lineorder", 1),
    (SPARSE_TOPN_SQL, 100)], ids=["timeseries", "topn"])
def test_assemble_rows_is_a_leaf_of_every_assembler(ssb_tables, sql, rows):
    eng = _engine(ssb_tables, use_pallas="never")
    eng.sql(sql)
    span, _pieces = _pieces_of(eng.tracer.last.to_json(), "assemble")
    leaf = [c for c in span["children"] if c["name"] == "assemble-rows"]
    assert len(leaf) == 1 and not leaf[0].get("children")
    assert leaf[0]["attrs"]["rows"] == rows
