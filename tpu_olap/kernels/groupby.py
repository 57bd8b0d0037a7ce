"""Dense group-by: mixed-radix key + XLA segmented reduces.

The TPU-first replacement for Druid's per-segment hash aggregation + broker
merge (SURVEY.md §3.5 P2/P3): group keys are dense ids (dictionary codes ×
time buckets), the group table is a static-shape [K] (or [K, m]) array, and
partial tables from different segments/chips merge with add/min/max — i.e.
an allreduce, never a hash exchange, as long as K fits the dense budget
(SURVEY.md §8.4 #1; the planner's cost model guards the budget).
"""

from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from tpu_olap.ir import aggregations as A
from tpu_olap.kernels import hll as hll_mod
from tpu_olap.kernels import theta as theta_mod
from tpu_olap.kernels.exprs import eval_expr
from tpu_olap.kernels.filtereval import UnsupportedFilter, compile_filter
from tpu_olap.segments.segment import ColumnType


class UnsupportedAggregation(Exception):
    pass


_NO_SCOPE = contextlib.nullcontext()  # stateless, so one serves every call

# The dense group space up to which the device program computes each slot of
# a [K] table as a masked reduce over the rows (compare the key with the
# slot, select, reduce) in place of XLA's scatter: the `k == 1` plain sum
# widened to a few groups. Set from one sweep on a v5e
# (tools/sweep_group_reduce.py; the table is in PERF.md section 6, PR 28):
# the largest K of the sweep's grid at which the compare form was at least
# twice as fast as the scatter for an int64 sum and an int32 count at 36M
# and at 6M rows (there 22x and 3.8x; at 4,096 the count is 1.4x, at 8,192
# the scatter wins it) and compiled in seconds. Past it the scatter stays.
COMPARE_MAX_GROUPS = 2048
# The compare form's row block: the [K, R] compare-select of one block is
# at most this many bytes. 32 MiB is 137 blocks for an int64 sum over twelve
# slots of 36M rows; on the chip 4 MiB is up to twice as slow and 128 MiB no
# faster (same sweep).
_CMP_BLOCK_BYTES = 32 << 20
_SKETCH_KINDS = ("hll", "theta")


def reduce_form(num_groups: int, kinds=()) -> str:
    """Which program the generic dense reduce is, from what the plan
    compiled and nothing else: "compare" where every [K] table of it is a
    masked reduce a slot, "scatter" where XLA's scatter is in it — K past
    COMPARE_MAX_GROUPS, or a sketch aggregate, whose [K, m] state keeps its
    scatter-max at any K (its plan's counts and sums still take the
    compare form at small K: the [K] tables ask with no kinds)."""
    if num_groups > COMPARE_MAX_GROUPS or any(k in _SKETCH_KINDS
                                              for k in kinds):
        return "scatter"
    return "compare"


# The stages of a device program, one flat vocabulary: every op of every
# jitted program lies under one of these `jax.named_scope`s, and the
# innermost one in an op's `op_name` path is its stage (the path's last
# part is the primitive's own name, which may spell like a stage: `sort`,
# `gather`). docs/OBSERVABILITY.md says which stage wraps what.
STAGES = (
    "window",     # the dynamic slice of the [S, R] inputs to the pruned
    #               segments (runner._window_kernel)
    "filter",     # the row mask: validity, the filter, the interval mask
    "key",        # the dimensions' ids and their mixed-radix group key
    "reduce",     # the dense [K] tables: Pallas one-hot, compare, scatter
    "pack",       # finalize, compact and lay out the one fetched buffer
    "sort",       # sparse: the multi-operand sort and its operands
    "runs",       # sparse: run boundaries, run ids, first-row positions
    "prefix",     # sparse: prefix sums and running maxima over the rows
    "gather",     # sparse: the [cap] tables read at the runs' boundaries
    "segment",    # sparse: what still segment-reduces the sorted rows
    "threshold",  # a TopN's top-k of the compact table, on the device
    "having",     # sparse: a HAVING's predicate over the tested [cap]
    #               tables and the compaction of the slots that pass
    "merge",      # mesh: all_gather of the chips' tables and their merge
)


def stage_scope(name: str, xp=None):
    """`jax.named_scope(name)` while a device program is being traced
    (xp is jax.numpy, or not given), nothing on the numpy path: every op of the jitted
    programs carries its stage, one of `STAGES`, in its op_name, which the
    profiler records beside the compiler's own name for the op (a
    capture's event metadata holds it as the stat `tf_op`). A scope
    touches the ops' metadata and nothing else of the compiled program."""
    if name not in STAGES:
        raise ValueError(f"{name!r} is not a stage; STAGES has {STAGES}")
    return _NO_SCOPE if xp is np else jax.named_scope(name)


@dataclass
class AggPlan:
    name: str
    kind: str            # sum | min | max | count | hll | theta
    fields: tuple        # input column/virtual names ((), for count)
    acc_dtype: object    # accumulator dtype (sum/min/max/count)
    filter_fn: object = None   # compiled FilterSpec for filtered aggs
    theta_k: int = 0
    is_string_input: tuple = ()  # per-field: True if dict codes
    by_row: bool = True  # multi-field HLL: distinct tuples (True) vs
    #                      union of per-field value sets (Druid byRow=False)
    hash_tables: tuple = ()  # per-field ConstPool name of the value-hash
    #                          table for string fields (None for numeric):
    #                          hashing VALUES (not codes) keeps hashes
    #                          consistent across dictionaries/fields


def compile_aggregations(aggs, table, pool, virtual_exprs=None,
                         long_dtype=np.int64, double_dtype=np.float64,
                         theta_k_cap=1 << 14):
    """AggregationSpec tuple -> list[AggPlan]. Raises Unsupported* for specs
    the device path can't run (planner then falls back)."""
    virtual_exprs = virtual_exprs or {}
    plans = []

    def field_type(f):
        if f in virtual_exprs:
            return ColumnType.DOUBLE
        if f not in table.schema:
            raise UnsupportedAggregation(f"unknown field {f!r}")
        return table.schema[f]

    def acc_dtype_for(spec):
        return long_dtype if spec.value_type == "long" else double_dtype

    def lower(spec, filter_fn=None):
        if isinstance(spec, A.FilteredAggregation):
            if filter_fn is not None:
                raise UnsupportedAggregation("nested filtered aggregator")
            try:
                ffn = compile_filter(spec.filter, table, pool, virtual_exprs)
            except UnsupportedFilter as e:
                raise UnsupportedAggregation(str(e)) from e
            return lower(spec.aggregator, ffn)
        if isinstance(spec, A.CountAggregation):
            return AggPlan(spec.name, "count", (), long_dtype, filter_fn)
        if isinstance(spec, (A.SumAggregation, A.MinAggregation,
                             A.MaxAggregation)):
            if field_type(spec.field_name) is ColumnType.STRING:
                raise UnsupportedAggregation(
                    f"numeric agg over string column {spec.field_name!r}")
            kind = {"SumAggregation": "sum", "MinAggregation": "min",
                    "MaxAggregation": "max"}[type(spec).__name__]
            return AggPlan(spec.name, kind, (spec.field_name,),
                           acc_dtype_for(spec), filter_fn)
        if isinstance(spec, A.CardinalityAggregation):
            fields = tuple(spec.fields)
            return AggPlan(spec.name, "hll", fields, np.int32, filter_fn,
                           is_string_input=tuple(
                               field_type(f) is ColumnType.STRING
                               for f in fields),
                           by_row=spec.by_row,
                           hash_tables=_hash_tables(fields, table, pool,
                                                    field_type))
        if isinstance(spec, A.HyperUniqueAggregation):
            f = (spec.field_name,)
            return AggPlan(spec.name, "hll", f, np.int32, filter_fn,
                           is_string_input=(field_type(spec.field_name)
                                            is ColumnType.STRING,),
                           hash_tables=_hash_tables(f, table, pool,
                                                    field_type))
        if isinstance(spec, A.ThetaSketchAggregation):
            k = min(int(spec.size), theta_k_cap)
            f = (spec.field_name,)
            return AggPlan(spec.name, "theta", f,
                           np.float64, filter_fn, theta_k=k,
                           is_string_input=(field_type(spec.field_name)
                                            is ColumnType.STRING,),
                           hash_tables=_hash_tables(f, table, pool,
                                                    field_type))
        raise UnsupportedAggregation(
            f"cannot lower aggregation {type(spec).__name__}")

    for a in aggs:
        plans.append(lower(a))
    return plans


def _hash_tables(fields, table, pool, field_type):
    """Per-field value-hash const tables for string fields (None slots for
    numeric fields). table[0] (null) is 0 — nulls are masked out anyway.
    The table depends only on the dictionary, so it's memoized there (it's
    an O(cardinality) host loop that must not run per query)."""
    out = []
    for f in fields:
        if field_type(f) is ColumnType.STRING:
            d = table.dictionaries[f]
            t = getattr(d, "_value_hash_table", None)
            if t is None:
                import zlib
                t = np.zeros(d.size + 1, np.int32)
                for i, v in enumerate(d.values):
                    t[i + 1] = np.int32(zlib.crc32(v.encode()) & 0x7FFFFFFF)
                d._value_hash_table = t
            out.append(pool.add(t))
        else:
            out.append(None)
    return tuple(out)


def build_group_key(ids, sizes, xp):
    """Mixed-radix combine of dense id arrays into one int32 key.

    ids: list of arrays in [0, size_i); sizes: list of ints. The product
    must fit in int32 — callers enforce the dense-K budget.
    """
    total = 1
    for s in sizes:
        total *= int(s)
    if total > (1 << 31) - 1:
        raise UnsupportedAggregation(
            f"dense group space {total} overflows int32")
    key = None
    for i, s in zip(ids, sizes):
        i = i.astype(xp.int32)
        key = i if key is None else key * xp.int32(s) + i
    if key is None:
        key = xp.zeros((), xp.int32)
    return key, total


def group_reduce(key, mask, env, plans, num_groups, consts):
    """One segment batch -> per-group partial aggregates.

    key: [N] int32 dense group ids; mask: [N] bool (validity ∧ filter);
    env: {"cols", "nulls"} with numeric/virtual columns materialized.
    Returns dict: "_rows" -> [K] row counts, then one entry per plan —
    [K] arrays for sum/min/max/count, [K, m] registers for hll,
    ([K, k] hashes, [K] counts) for theta. All outputs are mergeable
    across segments/chips (add for sums/counts, min/max elementwise,
    hll max, theta re-merge).
    """
    xp = jnp if not isinstance(mask, np.ndarray) else np
    with stage_scope("reduce", xp):
        return _group_reduce(key, mask, env, plans, num_groups, consts, xp)


def _group_reduce(key, mask, env, plans, num_groups, consts, xp):
    out = {}
    key = xp.where(mask, key, 0)  # masked rows: contribute zeros to group 0
    out["_rows"] = _seg_sum(mask.astype(np.int32), key, num_groups, xp)

    for p in plans:
        m = mask if p.filter_fn is None else (mask & p.filter_fn(env, consts))
        if p.filter_fn is not None:
            m_key = xp.where(m, key, 0)
        else:
            m_key = key
        if p.kind == "count":
            if p.filter_fn is None:
                # unfiltered COUNT(*) is the _rows scatter, already
                # computed — a [K] cast instead of a second [N]->[K]
                # segment reduction (scatters dominate grouped cost)
                out[p.name] = out["_rows"].astype(p.acc_dtype)
            else:
                out[p.name] = _seg_sum(m.astype(p.acc_dtype), m_key,
                                       num_groups, xp)
            continue
        if p.kind in ("sum", "min", "max"):
            x = _field_value(env, p.fields[0], xp)
            nulls = env["nulls"].get(p.fields[0])
            mm = m & ~nulls if nulls is not None else m
            if p.kind == "sum":
                v = xp.where(mm, x, 0).astype(p.acc_dtype)
                out[p.name] = _seg_sum(v, xp.where(mm, key, 0), num_groups, xp)
            else:
                ident = _ident(p.acc_dtype, p.kind)
                v = xp.where(mm, x.astype(p.acc_dtype), ident)
                out[p.name] = _seg_minmax(v, xp.where(mm, key, 0), num_groups,
                                          p.kind, xp)
            # per-plan non-null counts for null-correct finalize. With no
            # per-agg filter and no null bitmap, mm IS the row mask, so
            # the non-null count IS _rows — reuse it instead of paying a
            # third segment scatter per aggregate.
            if p.filter_fn is None and nulls is None:
                out[f"_nn_{p.name}"] = out["_rows"]
            else:
                out[f"_nn_{p.name}"] = _seg_sum(mm.astype(np.int32),
                                                xp.where(mm, key, 0),
                                                num_groups, xp)
            continue
        if p.kind == "hll":
            if p.by_row or len(p.fields) <= 1:
                h, valid = _hash_fields(env, p, m, xp, consts)
                out[p.name] = hll_mod.hll_update(h, valid,
                                                 xp.where(valid, key, 0),
                                                 num_groups)
            else:
                # Druid byRow=False: distinct over the UNION of each
                # field's values — update once per field, max-merge
                regs = None
                for i, f in enumerate(p.fields):
                    sub = AggPlan(p.name, "hll", (f,), p.acc_dtype,
                                  is_string_input=(p.is_string_input[i],),
                                  hash_tables=(p.hash_tables[i],))
                    h, valid = _hash_fields(env, sub, m, xp, consts)
                    r = hll_mod.hll_update(h, valid,
                                           xp.where(valid, key, 0),
                                           num_groups)
                    regs = r if regs is None else xp.maximum(regs, r)
                out[p.name] = regs
            continue
        if p.kind == "theta":
            h, valid = _hash_fields(env, p, m, xp, consts)
            out[p.name] = theta_mod.theta_update(h, valid, key, num_groups,
                                                 p.theta_k)
            continue
        raise UnsupportedAggregation(p.kind)
    return out


def group_reduce_batch(legs, consts_by_leg) -> list:
    """Multi-plan shared-scan reduce: N query legs over ONE column env.

    legs: list of (key, mask, env, plans, num_groups) — each leg's dense
    group ids and row mask were computed from the same materialized
    segment stream (executor.batch builds them via PhysicalPlan.key_fn),
    so tracing this function into a single jitted program yields ONE
    device pass in which every shared column is read once and fed to all
    N (filter-mask, agg-plan) legs. Returns N independent partials dicts
    (the same shape group_reduce emits), one per leg — mergeable and
    finalizable exactly like single-query partials.
    """
    return [group_reduce(key, mask, env, plans, num_groups, consts)
            for (key, mask, env, plans, num_groups), consts
            in zip(legs, consts_by_leg)]


def partials_radix(plans) -> int:
    """Per-group state width (in scalar slots) of a partial-aggregate
    dict: 1 for _rows, then each agg's unfinalized representation —
    the HLL register file, the theta table, or value + _nn. Shared by
    every state-budget guard over partials (segment-cache bypass, cube
    serve, delta fold) so the widths cannot drift apart."""
    from tpu_olap.kernels.hll import NUM_REGISTERS
    radix = 1  # _rows
    for p in plans:
        if p.kind == "hll":
            radix += NUM_REGISTERS
        elif p.kind == "theta":
            radix += p.theta_k
        else:
            radix += 2  # value + _nn
    return radix


def merge_partials(a: dict, b: dict, plans) -> dict:
    """Merge two partial-aggregate dicts (tree-reduce across segments; the
    same op runs as an ICI collective across chips)."""
    xp = jnp if not isinstance(a["_rows"], np.ndarray) else np
    out = {"_rows": a["_rows"] + b["_rows"]}
    for p in plans:
        if p.kind in ("count", "sum"):
            out[p.name] = a[p.name] + b[p.name]
        elif p.kind == "min":
            out[p.name] = xp.minimum(a[p.name], b[p.name])
        elif p.kind == "max":
            out[p.name] = xp.maximum(a[p.name], b[p.name])
        elif p.kind == "hll":
            out[p.name] = xp.maximum(a[p.name], b[p.name])
        elif p.kind == "theta":
            out[p.name] = theta_mod.theta_merge(a[p.name], b[p.name], xp)
        if f"_nn_{p.name}" in a:
            out[f"_nn_{p.name}"] = a[f"_nn_{p.name}"] + b[f"_nn_{p.name}"]
    return out


# ---------------------------------------------------------------------------


def _field_value(env, field, xp):
    if field in env["cols"]:
        return env["cols"][field]
    raise UnsupportedAggregation(f"field {field!r} not materialized")


def _seg_sum(v, key, k, xp):
    if k == 1:
        # single group (granularity=all, no dims — the BI total shape):
        # a plain sum vectorizes where a 1-slot scatter-add would not
        return v.sum(axis=0).reshape((1,) + v.shape[1:])
    if xp is np:
        out = np.zeros((k,) + v.shape[1:], v.dtype)
        np.add.at(out, key, v)
        return out
    if reduce_form(k) == "compare":
        # dtype: jnp.sum widens an int32 count to int64, the scatter
        # does not
        return _cmp_reduce(v, key, k, 0, jnp.add,
                           functools.partial(jnp.sum, dtype=v.dtype))
    return jax.ops.segment_sum(v, key, num_segments=k)


def _seg_minmax(v, key, k, kind, xp):
    if k == 1:
        # single group: plain reduction, not a 1-slot scatter
        red = v.min if kind == "min" else v.max
        return red(axis=0).reshape((1,) + v.shape[1:])
    if xp is np:
        ident = _ident(v.dtype, kind)
        out = np.full((k,), ident, v.dtype)
        (np.minimum if kind == "min" else np.maximum).at(out, key, v)
        return out
    if reduce_form(k) == "compare":
        return _cmp_reduce(v, key, k, _ident(v.dtype, kind),
                           *((jnp.minimum, jnp.min) if kind == "min"
                             else (jnp.maximum, jnp.max)))
    f = jax.ops.segment_min if kind == "min" else jax.ops.segment_max
    return f(v, key, num_segments=k)


def _cmp_reduce(v, key, k, ident, merge, red):
    """[N] values, [N] dense ids -> [K]: slot s is `red` over the rows
    whose id is s, `ident` where no row has it. `v` is evaluated once.
    The rows go by in blocks of R, each block's [K, R] compare-select
    reduced to a [K] partial and `merge`d into the table, with R from
    `_CMP_BLOCK_BYTES`: the most any backend can hold is one block's
    [K, R], never [K, N] (the TPU fuses the compare-select into the
    reduce and holds neither; XLA:CPU materializes it). A table of N <=
    R rows is the one reduce with no loop. R is a power of two, so a
    block starts on a tile boundary of the chip's layout."""
    def block(vb, kb):
        slots = jnp.arange(k, dtype=key.dtype)
        return red(jnp.where(kb[None, :] == slots[:, None], vb[None, :],
                             ident), axis=1)

    n = v.shape[0]
    r = _CMP_BLOCK_BYTES // (k * v.dtype.itemsize)
    r = 1 << (max(r, 1).bit_length() - 1)
    if n <= r:
        return block(v, key)
    steps = n // r

    def body(i, acc):
        def sl(a):
            return jax.lax.dynamic_slice_in_dim(a, i * r, r)
        return merge(acc, block(sl(v), sl(key)))

    acc = jax.lax.fori_loop(0, steps, body, jnp.full((k,), ident, v.dtype))
    if n > steps * r:
        acc = merge(acc, block(v[steps * r:], key[steps * r:]))
    return acc


def _ident(dtype, kind):
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return dt.type(np.inf if kind == "min" else -np.inf)
    info = np.iinfo(dt)
    return dt.type(info.max if kind == "min" else info.min)


def _hash_fields(env, p: AggPlan, mask, xp, consts):
    """Rows -> 32-bit hashes of the (possibly multi-)field value; valid
    excludes SQL-null inputs (nulls don't count toward COUNT DISTINCT).
    String fields hash their dictionary VALUES via host-built tables."""
    from tpu_olap.kernels.hashing import hash32_int, hash_combine

    h = None
    valid = mask
    for f, is_code, tbl in zip(p.fields, p.is_string_input, p.hash_tables):
        x = env["cols"][f]
        if is_code:
            valid = valid & (x > 0)  # code 0 = null
            hx = hash32_int(consts[tbl][x], xp)
        else:
            nulls = env["nulls"].get(f)
            if nulls is not None:
                valid = valid & ~nulls
            if x.dtype.kind == "f":
                xi = _float_bits(x)
            elif x.dtype.itemsize == 8:
                # fold all 64 bits before narrowing so values differing
                # only in high bits don't collide structurally
                xi = (x ^ (x >> 32)).astype(xp.int32)
            else:
                xi = x.astype(xp.int32)
            hx = hash32_int(xi, xp)
        h = hx if h is None else hash_combine(h, hx, xp)
    return h, valid


def _float_bits(x):
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
