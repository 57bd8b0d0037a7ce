"""Pallas TPU kernel: fused filter -> group key -> one-hot MXU reduce.

The in-tree "native tier" replacing Druid's segment-engine hot loop
(SURVEY.md §3.7): where the jnp path lowers the grouped reduce to XLA
scatter-adds (`jax.ops.segment_sum`), this kernel rides the MXU instead —
a masked one-hot of the dense group key contracted against the aggregate
inputs — and fuses the whole per-row pipeline (validity/filter masks,
mixed-radix key build, virtual-column arithmetic, half-plane decomposition)
into one pass over VMEM-resident row chunks.

Exact int64 sums via fixed-point byte planes
--------------------------------------------
The MXU has no integer matmul wide enough for longSum semantics, and f32
accumulation is only exact below 2^24. Each int32 aggregate input v >= 0 is
decomposed into 8-bit planes  v = sum_j h_j * 256^j  (h_j in [0, 255] —
exact in bf16, whose 8 mantissa bits represent every integer up to 256).
The plane COUNT is sized per query from the column-metadata value span
(round-5 roofline fix: the round-4 kernel burned a fixed 8 planes of 4
bits on every sum; byte planes + span sizing cut the accumulator lane
count 2-4x and the one-hot FLOPs with it). Per grid step the kernel
computes

    partial[K, H] = onehotT[K, RB] . valsT[H, RB]^T      (bf16 x bf16 -> f32)

whose entries are integer-valued and bounded by RB * 255 < 2^24, so the
f32 result is exact; it is cast to int32 and accumulated across grid
steps in the int32 output. Accumulation overflow is handled by a CHUNK
axis instead of an eligibility row cap: the output carries one [K, H]
buffer per run of `steps_per_chunk` grid steps (sized so each chunk's
accumulated plane sums stay under 2^31), and the host recombines chunks
with an exact f64 sum (each chunk value < 2^31, totals < 2^53). Planes
then recombine as sum_j out[:, j] << 8j in two f64 half-sums. Counts
ride the same matmul as columns of ones.

Eligibility (checked by `eligible()`, anything else falls back to the XLA
scatter path — mirroring the planner's structural-fallback rule, SURVEY.md
§2 property 2): dims lowered to codes/numeric-offset/remap (compare +
small-table gather only), aggs are count / integer sums whose value bounds
fit int32 (interval arithmetic over virtual-column exprs), no DOUBLE
inputs, no float or over-int32 constants *read inside the kernel*.

Time handling (round-3 widening): granularity buckets and interval masks
are computed OUTSIDE the kernel (plain XLA over the int64 time column —
cheap elementwise work XLA fuses anyway) and enter the kernel as an int32
bucket-id input folded into the mixed-radix key / ANDed into the validity
mask. The int64-free kernel interior stays int32. Only a query that reads
__time *inside* the kernel (a filter or aggregate on raw time) is
ineligible. Group spaces past pallas_k_per_block tile over a second grid
axis (K-blocks × row-blocks), so K is bounded by pallas_group_cap, not by
one onehot tile.

Float sums stay on the XLA scatter path BY DESIGN: doubleSum's contract
is f64 accumulation (exact parity with the fallback), and no bf16-plane
decomposition keeps f32 dot-products exact once the accumulation inside
the MXU rounds — the half-plane trick works for ints only because plane
values are small integers whose partial sums stay below 2^24. With
filter-constrained dim domains every SSB query's sums are integer and
Pallas-eligible, so the float tier has no benchmark pressure; revisit
only with a tolerance-based parity contract.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from tpu_olap.ir import aggregations as A
from tpu_olap.ir import filters as F
from tpu_olap.ir.expr import BinOp, Col, Lit
from tpu_olap.kernels.exprs import materialize_virtuals
from tpu_olap.segments.segment import ColumnType, TIME_COLUMN

# the kernel's name in the compiled program and in a device profile
KERNEL_NAME = "onehot_group_reduce"

N_PLANE_BITS = 8
PLANE_MASK = (1 << N_PLANE_BITS) - 1
MAX_VALUE = (1 << 31) - 1           # aggregate inputs must fit int32
# The chunked accumulator removes the int32 per-chip row cap; what
# remains is f64 exactness of the host-side half-sum recombination:
# each half-sum is below n_rows * 255 * 257 and must stay under 2^53.
MAX_ROWS = (1 << 53) // (PLANE_MASK * (PLANE_MASK + 2))


def expr_int_bounds(expr, col_bounds):
    """Conservative integer interval of an expression, or None if unbounded
    / non-integer (division, functions, unknown columns) — or if ANY
    intermediate result can leave int32 (the kernel evaluates the whole
    tree in int32, so every node must fit, not just the root)."""
    def fits(b):
        return b if (b is not None and -MAX_VALUE <= b[0]
                     and b[1] <= MAX_VALUE) else None

    if isinstance(expr, Lit):
        v = expr.value
        if isinstance(v, (int, np.integer)) and not isinstance(v, bool):
            return fits((int(v), int(v)))
        return None
    if isinstance(expr, Col):
        return fits(col_bounds.get(expr.name))
    if isinstance(expr, BinOp) and expr.op in ("+", "-", "*", "%"):
        a = expr_int_bounds(expr.left, col_bounds)
        b = expr_int_bounds(expr.right, col_bounds)
        if a is None or b is None:
            return None
        if expr.op == "+":
            return fits((a[0] + b[0], a[1] + b[1]))
        if expr.op == "-":
            return fits((a[0] - b[1], a[1] - b[0]))
        if expr.op == "%":
            # floored modulo (Python/numpy/jnp/pandas all agree): with a
            # positive constant modulus the result is in [0, m-1] for
            # ANY lhs sign; other moduli stay off the device path
            if not (isinstance(expr.right, Lit) and b[0] == b[1]
                    and b[0] > 0):
                return None
            return fits((0, b[0] - 1))
        prods = [x * y for x in a for y in b]
        return fits((min(prods), max(prods)))
    return None


class _Ineligible(Exception):
    pass


# Dimension kinds whose ids are computed INSIDE the kernel (pure int32
# arithmetic). remap/timeformat ids need a dynamic gather, which Mosaic
# does not lower for 1-D operands ("Only 2D gather is supported", v5e) —
# the host wrapper precomputes those in fused XLA and streams the int32
# ids in like granularity buckets.
IN_KERNEL_DIM_KINDS = ("codes", "numeric")


def _kernel_refs(plan) -> set:
    """Column NAMES (physical or virtual) referenced inside the kernel:
    filter + agg + in-kernel dim inputs. Gather-needing dims
    (remap/timeformat) are precomputed on the host side; their source
    columns (possibly __time) never enter the kernel unless a filter/agg
    also reads them."""
    q = plan.query
    cols: set = set()
    if q.filter is not None:
        cols |= q.filter.columns()
    for p in plan.agg_plans:
        cols |= set(p.fields)
    for dp in plan.dim_plans:
        if dp.source_col and dp.kind in IN_KERNEL_DIM_KINDS:
            cols.add(dp.source_col)

    def agg_filter_cols(spec):
        if isinstance(spec, A.FilteredAggregation):
            return spec.filter.columns() | agg_filter_cols(spec.aggregator)
        return set()

    for a in q.aggregations:
        cols |= agg_filter_cols(a)
    return cols


def kernel_virtuals(plan) -> dict:
    """The subset of plan.virtual_exprs the kernel must materialize."""
    refs = _kernel_refs(plan)
    return {c: e for c, e in plan.virtual_exprs.items() if c in refs}


def kernel_columns(plan) -> tuple:
    """Physical columns read INSIDE the kernel: _kernel_refs expanded
    through virtual columns. If __time appears here, the query reads raw
    time in-kernel and is ineligible (the kernel interior is int32-only;
    host-precomputed bucket ids / interval masks / dim ids are not
    in-kernel reads)."""
    phys: set = set()
    for c in _kernel_refs(plan):
        phys |= (plan.virtual_exprs[c].columns()
                 if c in plan.virtual_exprs else {c})
    return tuple(sorted(phys))


class _ConstTracker:
    """consts-dict wrapper recording which ConstPool names the kernel's
    compiled closures actually read (filters, dim id maps, agg filters) —
    only those enter the Pallas kernel and must fit int32; host-side
    consts (interval edges, bucket origins: int64 epoch millis) do not."""

    def __init__(self, consts):
        self._c = consts
        self.used: set = set()

    def __getitem__(self, k):
        self.used.add(k)
        return self._c[k]


def traced_const_names(plan, table, filter_fn) -> list:
    """Names of pool consts the kernel closures read, discovered by running
    them once on a tiny all-zeros numpy environment (the closures are
    xp-generic and total on any int input). Memoized on the plan —
    eligible() and build_kernel() both need it for the same lowering."""
    cached = getattr(plan, "_pallas_const_names", None)
    if cached is not None:
        return cached
    n = 8
    kcols = kernel_columns(plan)
    cols = {c: np.zeros(n, np.int64) for c in kcols}
    # filter-derived streams are present in the real kernel env, so the
    # trace must offer them too — otherwise the columnComparison closure
    # would take its gather branch here and record a const the kernel
    # never reads at runtime
    for token, _, _ in plan.filter_streams:
        cols["\0d:" + token] = np.zeros(n, np.int32)
    nulls = {c: np.zeros(n, bool) for c in plan.null_cols if c in kcols}
    materialize_virtuals(kernel_virtuals(plan), cols, nulls, np,
                         wide_ints=False)
    env = {"cols": cols, "nulls": nulls}
    tc = _ConstTracker(plan.pool.consts)
    if filter_fn is not None:
        filter_fn(env, tc)
    for dp in plan.dim_plans:
        if dp.kind in IN_KERNEL_DIM_KINDS:
            dp.ids(env, tc, np)
    for p in plan.agg_plans:
        if p.filter_fn is not None:
            p.filter_fn(env, tc)
    plan._pallas_const_names = sorted(tc.used)
    return plan._pallas_const_names


def column_bounds(plan, table) -> dict:
    """Integer [min, max] of every numeric column the kernel reads; raises
    _Ineligible for DOUBLE columns or ranges that cannot load as int32.
    Memoized on the table (segments are immutable after ingest), so
    repeated queries over the same columns pay the metadata scan once."""
    cache = getattr(table, "_pallas_bounds_cache", None)
    if cache is None:
        cache = table._pallas_bounds_cache = {}
    key = kernel_columns(plan)
    cached = cache.get(key)
    if cached is not None:
        if isinstance(cached, _Ineligible):
            raise cached
        return cached
    if not key:  # e.g. count(*) grouped only by precomputed dims
        cache[key] = {}
        return {}
    md = table.column_metadata(set(key))
    bounds = {}
    for c in key:
        typ = table.schema[c]
        if typ is ColumnType.STRING:
            continue
        if typ is ColumnType.DOUBLE:
            err = _Ineligible(f"DOUBLE column {c!r}")
            cache[key] = err
            raise err
        m = md.get(c, {})
        if m.get("min") is None:
            bounds[c] = (0, 0)  # empty table
        else:
            lo, hi = int(m["min"]), int(m["max"])
            if lo < -MAX_VALUE or hi > MAX_VALUE:
                err = _Ineligible(f"column {c!r} range exceeds int32")
                cache[key] = err
                raise err
            bounds[c] = (lo, hi)
    cache[key] = bounds
    return bounds


def sum_bounds(plan, table) -> dict:
    """Per-sum-aggregation input bounds (post eligibility: always bounded)."""
    bounds = column_bounds(plan, table)
    out = {}
    for p in plan.agg_plans:
        if p.kind != "sum":
            continue
        f = p.fields[0]
        b = (expr_int_bounds(plan.virtual_exprs[f], bounds)
             if f in plan.virtual_exprs else bounds.get(f))
        out[p.name] = b
    return out


_SIMPLE_FILTERS = (F.SelectorFilter, F.BoundFilter, F.InFilter,
                   F.RegexFilter, F.LikeFilter, F.ColumnComparisonFilter)


def _colcmp_nodes(spec):
    """Every ColumnComparisonFilter in the tree."""
    if spec is None:
        return
    if isinstance(spec, F.ColumnComparisonFilter):
        yield spec
    elif isinstance(spec, (F.AndFilter, F.OrFilter)):
        for f in spec.fields:
            yield from _colcmp_nodes(f)
    elif isinstance(spec, F.NotFilter):
        yield from _colcmp_nodes(spec.field)


def _filter_ok(spec) -> bool:
    if isinstance(spec, F.ColumnComparisonFilter) and spec.op != "==":
        return False  # ordered row-vs-row: the generic kernel's
    if spec is None or isinstance(spec, _SIMPLE_FILTERS):
        return True
    if isinstance(spec, (F.AndFilter, F.OrFilter)):
        return all(_filter_ok(f) for f in spec.fields)
    if isinstance(spec, F.NotFilter):
        return _filter_ok(spec.field)
    return False


@dataclass
class Factorization:
    """Large-K lane packing: the dense key splits into (key >> s,
    key & (k2 - 1)) and k2 groups' aggregate columns share one lane tile,
    so the MXU tile product tracks K*H instead of K*128 (the direct
    layout pads H to a full 128-lane tile — a ~12x FLOP waste at H ~ 10).
    k2 is a power of two >= 8 so every sublane concat stays 8-aligned
    (Mosaic relayouts on misaligned sublane offsets are the alternative).
    Output entry (k1, h*k2 + k2v) holds agg column h of group k1*k2+k2v."""
    k2: int        # groups packed per lane tile (power of two, >= 8)
    shift: int     # log2(k2)
    width: int     # lane-padded k2 * H
    k1_pad: int    # padded row count of the [k1, width] output
    kb: int        # K1 rows per grid block
    n_kb: int      # grid blocks over the k1 axis


def factorization(K, H, n_mm, config) -> Factorization | None:
    """Pick the lane packing minimizing the output tile product, or None
    when the direct layout is no worse (small K) or inapplicable: min/max
    aggs key their VPU buffer on the full K (n_mm > 0), and H > 32 would
    spill past two lane tiles per group batch."""
    if n_mm or K < 2 or H > 32:
        return None
    kb_d = min(K, config.pallas_k_per_block)
    direct = -(-K // kb_d) * kb_d * max(128, -(-H // 128) * 128)
    best = None
    for k2 in (8, 16, 32, 64):
        width = -(-k2 * H // 128) * 128
        k1 = -(-K // k2)
        kb = min(-(-k1 // 8) * 8, config.pallas_k_per_block)
        n_kb = -(-k1 // kb)
        k1_pad = n_kb * kb
        prod = k1_pad * width
        # tie -> larger k2: fewer k1 rows means fewer passes over the
        # row stream once K1 exceeds one grid block
        if best is None or prod <= best[0]:
            best = (prod, Factorization(k2, k2.bit_length() - 1, width,
                                        k1_pad, kb, n_kb))
    return best[1] if best and best[0] < direct else None


def _layout_for(plan, table) -> "PallasLayout":
    """plan_layout memoized on the plan (same pattern as
    traced_const_names): eligible(), the FLOP budget gate, and
    build_kernel all need the identical layout during one lowering."""
    cached = getattr(plan, "_pallas_layout", None)
    if cached is None:
        cached = plan._pallas_layout = plan_layout(
            plan.agg_plans, sum_bounds(plan, table))
    return cached


def tile_product(plan, table, config) -> int:
    """K_pad * lane_width of the accumulator the kernel would build —
    the one-hot reduce costs 2 * n_rows * tile_product FLOPs. Shared by
    build_kernel and the auto-policy FLOP budget gate in lowering."""
    layout = _layout_for(plan, table)
    K = plan.total_groups
    fact = factorization(K, layout.n_cols, layout.n_minmax, config)
    if fact is not None:
        return fact.k1_pad * fact.width
    kb = min(K, config.pallas_k_per_block)
    return -(-K // kb) * kb * max(128, -(-layout.n_cols // 128) * 128)


@dataclass
class PallasLayout:
    """Half-plane column layout of the [K, H] accumulator."""
    n_cols: int                   # H (before lane padding)
    rows_slot: int                # column index of the _rows count
    agg_slots: tuple              # per agg: (name, kind, start, n_planes,
    #                               bias) — bias < 0 means inputs are
    #                               shifted by -bias into [0, hi-lo] and an
    #                               extra per-agg row-count column sits at
    #                               start + n_planes for the un-shift.
    #                               min/max aggs use `start` for their
    #                               non-null COUNT column (riding the
    #                               matmul) and n_planes as the column
    #                               index into the second (VPU min)
    #                               output buffer
    n_minmax: int = 0             # columns of the second output buffer


def _sum_plane_spec(lo: int, hi: int) -> tuple:
    """(n_planes, bias) for a sum whose inputs lie in [lo, hi]: the
    minimal byte-plane count covering the value span. bias != 0 shifts
    inputs into [0, hi - lo] (mandatory for lo < 0, since planes are
    unsigned); a non-negative range is biased only when the shift saves
    more planes than the one extra row-count column the un-shift needs."""
    def planes(top):
        return max(1, -(-max(int(top), 1).bit_length() // N_PLANE_BITS))

    shifted = planes(hi - lo)
    if lo < 0:
        return shifted, lo
    if shifted + 1 < planes(hi):
        return shifted, lo
    return planes(hi), 0


def plan_layout(agg_plans, sum_bounds) -> PallasLayout:
    slots = []
    h = 1  # slot 0: _rows
    n_mm = 0
    for p in agg_plans:
        if p.kind == "count":
            slots.append((p.name, "count", h, 1, 0))
            h += 1
        elif p.kind in ("min", "max"):
            # non-null count column in the matmul buffer + one column in
            # the min-accumulated VPU buffer (max rides negated)
            slots.append((p.name, p.kind, h, n_mm, 0))
            h += 1
            n_mm += 1
        else:  # sum
            n, bias = _sum_plane_spec(*sum_bounds[p.name])
            slots.append((p.name, "sum", h, n, bias))
            h += n + (1 if bias else 0)
    return PallasLayout(h, 0, tuple(slots), n_minmax=n_mm)


def eligible(query, plan, table, config, filter_fn=None) -> str | None:
    """None if the plan can run on the Pallas kernel, else the reason."""
    if plan.kind != "agg":
        return "not an aggregate plan"
    kcols = kernel_columns(plan)
    if TIME_COLUMN in kcols:
        return "raw __time read inside the kernel"
    if plan.total_groups > config.pallas_group_cap:
        # past the direct cap, only the factorized lane packing keeps
        # the tile product (and the VPU compare cost) in the win regime
        # — and computing the layout needs the bounds scan, so do the
        # cheap hard-cap check first
        if plan.total_groups > config.pallas_group_cap_factorized:
            return (f"group space {plan.total_groups} exceeds pallas "
                    f"cap {config.pallas_group_cap_factorized}")
        bad = next((p.kind for p in plan.agg_plans
                    if p.kind not in ("count", "sum", "min", "max")), None)
        if bad is not None:  # plan_layout would KeyError on e.g. HLL
            return f"aggregation kind {bad!r}"
        try:
            # plan_layout subscripts sum-input bounds — an unboundable
            # sum stores None there (the under-cap path rejects it
            # later with its own reason), so probe the bounds first
            sb = sum_bounds(plan, table)
            missing = next((k for k, v in sb.items() if v is None), None)
            if missing is not None:
                return f"cannot bound sum input of {missing!r}"
            layout = _layout_for(plan, table)
        except _Ineligible as e:
            return str(e)
        if factorization(plan.total_groups, layout.n_cols,
                         layout.n_minmax, config) is None:
            return (f"group space {plan.total_groups} exceeds pallas "
                    f"cap {config.pallas_group_cap} and the layout "
                    "does not factorize")
    if table.block_rows % 128 != 0:
        return f"block_rows {table.block_rows} not a multiple of 128"
    rb = min(table.block_rows, config.pallas_rows_per_block)
    if table.block_rows % rb != 0:
        return (f"pallas_rows_per_block {rb} does not divide block_rows "
                f"{table.block_rows}")
    if rb * PLANE_MASK >= 1 << 24:
        # per-step f32 matmul partials must stay exact: byte planes bound
        # each lane's per-row worth at 255, so rb caps at 65792
        return f"rows-per-block {rb} breaks f32 plane-sum exactness"
    if table.num_rows > MAX_ROWS:
        return (f"row count {table.num_rows} exceeds f64 recombination "
                "headroom")
    for dp in plan.dim_plans:
        if dp.kind not in ("codes", "numeric", "remap", "timeformat"):
            return f"dimension kind {dp.kind!r}"
    if not _filter_ok(query.filter):
        return "filter tree has non-simple members"
    for cc in _colcmp_nodes(query.filter):
        # string pairs read the precomputed translation stream (int32 by
        # construction); numeric pairs compare loaded columns — both need
        # PHYSICAL columns (virtuals would evaluate un-bounded in-kernel)
        for c in cc.dimensions:
            if c not in table.schema:
                return f"columnComparison over virtual column {c!r}"

    try:
        bounds = column_bounds(plan, table)
    except _Ineligible as e:
        return str(e)

    specs = {a.name: a for a in query.aggregations}

    def base_spec(spec):
        if isinstance(spec, A.FilteredAggregation):
            if not _filter_ok(spec.filter):
                return None
            return base_spec(spec.aggregator)
        return spec

    for p in plan.agg_plans:
        spec = base_spec(specs[p.name])
        if spec is None:
            return f"aggregator {p.name!r} has a non-simple filter"
        if p.kind == "count":
            continue
        if p.kind not in ("sum", "min", "max"):
            return f"aggregation kind {p.kind!r}"
        if p.kind == "sum" and np.dtype(p.acc_dtype).kind != "i":
            return f"non-integer sum {p.name!r}"
        f = p.fields[0]
        if f in plan.virtual_exprs:
            b = expr_int_bounds(plan.virtual_exprs[f], bounds)
        else:
            b = bounds.get(f)
        if b is None:
            return f"cannot bound {p.kind} input {f!r}"
        if p.kind == "sum" and b[1] - b[0] > MAX_VALUE:
            return f"sum input {f!r} span {b} exceeds int32"

    for name in traced_const_names(plan, table, filter_fn):
        v = plan.pool.consts[name]
        if v.dtype.kind == "f":
            return f"float literal const {name}"
        if v.dtype.kind == "i" and v.size and (
                v.min() < -MAX_VALUE or v.max() > MAX_VALUE):
            return f"const {name} exceeds int32"
    return None


def build_kernel(plan, table, config, filter_fn, interpret: bool,
                 imask_fn=None):
    """The Pallas replacement for lowering's generic agg kernel closure.

    Same contract: fn(env, valid, seg_mask, consts) -> partial dict with
    "_rows" plus one int64 [K] array per aggregation. Interval masks and
    granularity bucket ids are evaluated on the int64 time column OUTSIDE
    the pallas_call (plain fused XLA) and enter as mask / int32 key input;
    group spaces wider than pallas_k_per_block tile over grid axis 0.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    layout = _layout_for(plan, table)
    K = plan.total_groups
    H = layout.n_cols
    H_pad = max(128, -(-H // 128) * 128)
    sizes = plan.sizes
    dim_plans = plan.dim_plans
    agg_plans = plan.agg_plans
    vexprs = kernel_virtuals(plan)
    bucket_plan = plan.bucket_plan
    has_buckets = bucket_plan.kind != "all"
    pre_dims = [dp.kind not in IN_KERNEL_DIM_KINDS for dp in dim_plans]
    n_pre = (1 if has_buckets else 0) + sum(pre_dims)
    block_rows = table.block_rows
    rb = min(block_rows, config.pallas_rows_per_block)
    fact = factorization(K, H, layout.n_minmax, config)
    if fact is not None:
        KB, n_kb, K_pad = fact.kb, fact.n_kb, fact.k1_pad
        W = fact.width
    else:
        KB = min(K, config.pallas_k_per_block)
        n_kb = -(-K // KB)
        K_pad = n_kb * KB
        W = H_pad

    const_names = traced_const_names(plan, table, filter_fn)
    col_names = [c for c in kernel_columns(plan) if c != TIME_COLUMN] \
        + ["\0d:" + t for t, _, _ in plan.filter_streams]
    n_mm = layout.n_minmax
    MM_pad = max(128, -(-n_mm // 128) * 128) if n_mm else 0
    # Chunked accumulation: the int32 output accumulates per-step f32
    # partials whose per-row worth is PLANE_MASK (byte planes) or 1
    # (count-only layouts). Every `spc` grid steps the output block index
    # advances, flushing a fresh [KB, W] chunk, so per-chunk sums stay
    # under 2^31 for ANY per-chip row count; the host recombines chunks
    # with an exact f64 sum. spc is static (rb is), n_chunks is shape-
    # derived inside fn.
    per_row = PLANE_MASK if any(
        s[1] == "sum" for s in layout.agg_slots) else 1
    spc = max(1, MAX_VALUE // (rb * per_row))

    def make_kernel_fn(null_names):
        def kernel_fn(*refs):
            (col_refs, pre_refs, null_refs, valid_ref, const_refs,
             outs) = _split_refs(refs, len(col_names), n_pre,
                                 len(null_names), len(const_names),
                                 n_outs=2 if n_mm else 1)
            out_ref = outs[0]
            mm_ref = outs[1] if n_mm else None
            kb = pl.program_id(0)
            step = pl.program_id(1)

            env = {"cols": {}, "nulls": {}}
            for name, r in zip(col_names, col_refs):
                # loads may be narrower than int32 (int8/int16 segment
                # storage); compute in int32 — eligibility bounded every
                # expression node to int32, narrower products would wrap
                v = r[0, :]
                if v.dtype != jnp.int32 and jnp.issubdtype(v.dtype,
                                                           jnp.integer):
                    v = v.astype(jnp.int32)
                env["cols"][name] = v
            for name, r in zip(null_names, null_refs):
                env["nulls"][name] = r[0, :]
            materialize_virtuals(vexprs, env["cols"], env["nulls"], jnp,
                                 wide_ints=False)
            consts = {n: r[0, :] for n, r in zip(const_names, const_refs)}

            mask = valid_ref[0, :]
            if filter_fn is not None:
                mask = mask & filter_fn(env, consts)

            # mixed-radix dense group key [rb]; the precomputed granularity
            # bucket id is the most-significant digit (radix sizes[0]);
            # gather-needing dim ids arrive precomputed in dim order
            pi = 0
            key = None
            if has_buckets:
                key = pre_refs[pi][0, :]
                pi += 1
            for dp, is_pre, size in zip(dim_plans, pre_dims, sizes[1:]):
                if is_pre:
                    i = pre_refs[pi][0, :]
                    pi += 1
                else:
                    i = dp.ids(env, consts, jnp).astype(jnp.int32)
                key = i if key is None else key * jnp.int32(size) + i
            if key is None:
                key = jnp.zeros((rb,), jnp.int32)

            # transposed masked one-hot [KB, rb] for this K-block — built
            # directly in K-major orientation so every op stays 2-D. Under
            # factorization the row axis indexes k1 = key >> s; garbage
            # keys on masked-out rows shift to negative k1 and never match
            kk = jax.lax.broadcasted_iota(jnp.int32, (KB, rb), 0) \
                + kb * np.int32(KB)
            if fact is not None:
                k1 = jnp.right_shift(key, jnp.int32(fact.shift))
                k2v = jnp.bitwise_and(key, jnp.int32(fact.k2 - 1))
                onehot = ((kk == k1[None, :])
                          & mask[None, :]).astype(jnp.bfloat16)
            else:
                onehot = ((kk == key[None, :])
                          & mask[None, :]).astype(jnp.bfloat16)

            # value planes [H_pad, rb]
            rows = [mask.astype(jnp.bfloat16)[None, :]]
            mm_cols = []
            for p, (name, kind, start, n_planes, bias) in zip(
                    agg_plans, layout.agg_slots):
                m = mask if p.filter_fn is None else \
                    (mask & p.filter_fn(env, consts))
                if kind == "count":
                    rows.append(m.astype(jnp.bfloat16)[None, :])
                    continue
                f = p.fields[0]
                v = env["cols"][f].astype(jnp.int32)
                nm = env["nulls"].get(f)
                if nm is not None:
                    m = m & ~nm
                if kind in ("min", "max"):
                    # non-null count rides the matmul; the value is a
                    # masked VPU min over this K-block (max rides
                    # NEGATED so one minimum-accumulate serves both)
                    rows.append(m.astype(jnp.bfloat16)[None, :])
                    vv = -v if kind == "max" else v
                    sel = (kk == key[None, :]) & m[None, :]
                    mm_cols.append(jnp.min(
                        jnp.where(sel, vv[None, :], jnp.int32(MAX_VALUE)),
                        axis=1))
                    continue
                if bias:
                    v = v - jnp.int32(bias)  # shift into [0, hi-lo]
                # strongly-typed zero: under x64 a Python 0 enters the
                # where as a weak i64 scalar, and Mosaic's scalar i64->i32
                # conversion recurses forever (observed on v5e; the CPU
                # interpret path never lowers through Mosaic and hides it)
                v = jnp.where(m, v, jnp.int32(0))
                for j in range(n_planes):
                    h = (v >> (N_PLANE_BITS * j)) & PLANE_MASK
                    rows.append(h.astype(jnp.bfloat16)[None, :])
                if bias:  # per-agg masked row count for the un-shift
                    rows.append(m.astype(jnp.bfloat16)[None, :])
            if fact is not None:
                # pack k2 groups per lane tile: each [1, rb] agg row h
                # expands through onehot2 into rows [h*k2, (h+1)*k2) —
                # h-major so every concat part is k2 (>= 8) sublanes
                oh2 = (jax.lax.broadcasted_iota(
                    jnp.int32, (fact.k2, rb), 0)
                    == k2v[None, :]).astype(jnp.bfloat16)
                parts = [oh2 * r for r in rows]
                pad = W - fact.k2 * len(rows)
                if pad:
                    parts.append(jnp.zeros((pad, rb), jnp.bfloat16))
                vals = jnp.concatenate(parts, axis=0)
            else:
                pad = H_pad - len(rows)
                if pad:
                    rows.append(jnp.zeros((pad, rb), jnp.bfloat16))
                vals = jnp.concatenate(rows, axis=0)

            partial = jax.lax.dot_general(
                onehot, vals, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(jnp.int32)

            # np.int32, not the Python int: under x64 a weak int scalar
            # enters the remainder as i64 and Mosaic's i64->i32 scalar
            # conversion recurses (same hazard as the typed zero above)
            @pl.when(step % np.int32(spc) == 0)  # first step of this chunk
            def _():
                out_ref[0, :, :] = jnp.zeros((KB, W), jnp.int32)
            out_ref[0, :, :] += partial

            if mm_ref is not None:
                pad = MM_pad - len(mm_cols)
                cols2 = [c[:, None] for c in mm_cols]
                if pad:
                    cols2.append(jnp.full((KB, pad), jnp.int32(MAX_VALUE),
                                          jnp.int32))
                upd = jnp.concatenate(cols2, axis=1)

                @pl.when(step == np.int32(0))
                def _():
                    mm_ref[:, :] = jnp.full((KB, MM_pad),
                                            jnp.int32(MAX_VALUE),
                                            jnp.int32)
                mm_ref[:, :] = jnp.minimum(mm_ref[:, :], upd)
        return kernel_fn

    # index maps return strongly-typed int32 zeros: under x64 a literal 0
    # traces as i64, and Mosaic rejects the index-map func.return with
    # 64-bit operands ("failed to legalize func.return", v5e)
    _z = np.int32(0)

    def row_spec():
        return pl.BlockSpec((1, rb), lambda kb, i: (_z, i))

    def const_spec(n):
        return pl.BlockSpec((1, n), lambda kb, i: (_z, _z))

    def pallas_agg(env, valid, seg_mask, consts):
        n_segments = valid.shape[0]
        n = n_segments * block_rows
        grid_rows = n // rb
        cset = set(col_names)
        null_names = sorted(c for c in env["nulls"]
                            if c != TIME_COLUMN and c in cset)
        # the stages XLA runs before the kernel: `filter` is the interval
        # mask, `key` the precomputed id streams; the per-row filter and
        # the remaining key arithmetic run inside the kernel (`reduce`)
        with jax.named_scope("filter"):
            mask = (valid & seg_mask[:, None]).reshape(-1)
        pre_in = []
        if imask_fn is not None or n_pre:
            flat_env = {
                "cols": {c: a.reshape(-1) for c, a in env["cols"].items()},
                "nulls": {c: a.reshape(-1)
                          for c, a in env["nulls"].items()}}
            if imask_fn is not None:
                with jax.named_scope("filter"):
                    mask = mask & imask_fn(flat_env, consts)
            with jax.named_scope("key"):
                if has_buckets:
                    b = flat_env["cols"].get(bucket_plan.derived_name) \
                        if bucket_plan.cache_token else None
                    # cached uniform streams are TABLE-anchored; rebase
                    # to this plan's origin bucket
                    # (timebucket.ids_from_cached)
                    b = bucket_plan.ids(flat_env["cols"][TIME_COLUMN],
                                        consts) if b is None else \
                        bucket_plan.ids_from_cached(b, consts, jnp)
                    pre_in.append(b.astype(jnp.int32).reshape(1, n))
                for dp, is_pre in zip(dim_plans, pre_dims):
                    if is_pre:
                        ids = dp.ids(flat_env, consts, jnp)
                        pre_in.append(
                            ids.astype(jnp.int32).reshape(1, n))
        # the kernel's operands in its [1, n] layout are the reduce's too:
        # where XLA copies a column to get it, the copy carries the stage
        with jax.named_scope("reduce"):
            mask2 = mask.reshape(1, n)
            col_in = [_narrow(env["cols"][c].reshape(1, n), jnp)
                      for c in col_names]
            null_in = [env["nulls"][c].reshape(1, n) for c in null_names]
            const_in = [_narrow(jnp.asarray(consts[c]).reshape(1, -1), jnp)
                        for c in const_names]

        n_chunks = -(-grid_rows // spc)
        _spc = np.int32(spc)
        out_specs = pl.BlockSpec((1, KB, W),
                                 lambda kb, i: (i // _spc, kb, _z))
        out_shape = jax.ShapeDtypeStruct((n_chunks, K_pad, W), jnp.int32)
        if n_mm:
            # the min/max VPU buffer accumulates a minimum — no overflow,
            # so it stays unchunked (one block per K-block, all steps)
            out_specs = [out_specs,
                         pl.BlockSpec((KB, MM_pad), lambda kb, i: (kb, _z))]
            out_shape = [out_shape,
                         jax.ShapeDtypeStruct((K_pad, MM_pad), jnp.int32)]
        with jax.named_scope("reduce"):
            out = pl.pallas_call(
                make_kernel_fn(null_names),
                grid=(n_kb, grid_rows),
                in_specs=([row_spec() for _ in col_in]
                          + [row_spec() for _ in pre_in]
                          + [row_spec() for _ in null_in]
                          + [row_spec()]
                          + [const_spec(c.shape[1]) for c in const_in]),
                out_specs=out_specs,
                out_shape=out_shape,
                interpret=interpret,
                name=KERNEL_NAME,
            )(*col_in, *pre_in, *null_in, mask2, *const_in)
            mm = None
            if n_mm:
                out, mm = out
                mm = mm[:K]
            if n_chunks > 1:
                # exact: each chunk entry < 2^31, chunk totals < 2^53 (the
                # MAX_ROWS eligibility bound); f64 keeps the consumer out of
                # the fused int pipeline (see the recombination note below)
                out = out.astype(jnp.float64).sum(axis=0)
            else:
                out = out[0]
            if fact is not None:
                # entry (k1, h*k2 + k2v) -> row k1*k2 + k2v == dense key,
                # column h: plain XLA reshuffle outside the pallas_call
                out = (out[:, :fact.k2 * H]
                       .reshape(K_pad, H, fact.k2)
                       .transpose(0, 2, 1)
                       .reshape(K_pad * fact.k2, H))
            out = out[:K]

            res = {"_rows": out[:, layout.rows_slot].astype(jnp.int64)}
            for p, (name, kind, start, n_planes, bias) in zip(
                    agg_plans, layout.agg_slots):
                if kind == "count":
                    res[name] = out[:, start].astype(p.acc_dtype)
                elif kind in ("min", "max"):
                    v = mm[:, n_planes]  # n_planes doubles as the mm column
                    if kind == "max":
                        v = -v
                    # empty groups carry the identity; finalize renders them
                    # NULL via the non-null count
                    res[name] = v.astype(p.acc_dtype)
                    res[f"_nn_{name}"] = out[:, start].astype(jnp.int32)
                else:
                    # Plane recombination rides f64, NOT int64 shifts: on the
                    # v5e sandbox, a jit-fused  custom_call -> convert(i64) ->
                    # shift/mul  chain miscompiles (the converted values read
                    # as ZERO for a deterministic subset of rows; eager or
                    # plain-array runs of the identical expression are
                    # correct, and multiplies instead of shifts change
                    # nothing). f64 math forces the consumer out of the fused
                    # int pipeline and is exact here: each half-sum is below
                    # 255*MAX_ROWS*(256+1) < 2^53 (the MAX_ROWS bound).
                    half = (n_planes + 1) // 2  # [0, half) and [half, n)
                    lo = jnp.zeros((K,), jnp.float64)
                    hi = jnp.zeros((K,), jnp.float64)
                    for j in range(n_planes):
                        w = float(1 << (N_PLANE_BITS *
                                        (j if j < half else j - half)))
                        v = out[:, start + j].astype(jnp.float64) * w
                        if j < half:
                            lo = lo + v
                        else:
                            hi = hi + v
                    acc = lo.astype(jnp.int64) + (
                        hi.astype(jnp.int64) << (N_PLANE_BITS * half))
                    if bias:
                        # same split for the bias un-shift: bias*n can exceed
                        # 2^53, so do it in 16-bit halves of |bias|. True sum
                        # = plane sum + n_masked * bias (inputs were shifted
                        # by -bias), so the adjustment adds for bias > 0 and
                        # subtracts for bias < 0.
                        n_masked = out[:, start + n_planes].astype(jnp.float64)
                        b = abs(bias)
                        b_lo, b_hi = b & 0xFFFF, b >> 16
                        adj = (n_masked * float(b_lo)).astype(jnp.int64) + (
                            (n_masked * float(b_hi)).astype(jnp.int64) << 16)
                        acc = acc + adj if bias > 0 else acc - adj
                    res[name] = acc.astype(p.acc_dtype)
            return res

    return pallas_agg


def _split_refs(refs, n_cols, n_pre, n_nulls, n_consts, n_outs=1):
    """n_pre: host-precomputed int32 id streams — the granularity bucket
    (if any) followed by one stream per gather-needing dimension
    (remap/timeformat), in dimension order. n_outs: trailing output refs
    (the matmul accumulator, plus the min/max buffer when present)."""
    refs = list(refs)
    cols = refs[:n_cols]
    pre = refs[n_cols:n_cols + n_pre]
    nulls = refs[n_cols + n_pre:n_cols + n_pre + n_nulls]
    valid = refs[n_cols + n_pre + n_nulls]
    consts = refs[n_cols + n_pre + n_nulls + 1:
                  n_cols + n_pre + n_nulls + 1 + n_consts]
    outs = refs[-n_outs:]
    return cols, pre, nulls, valid, consts, outs


def _narrow(x, jnp):
    """i64 -> i32 (eligibility guarantees the values fit); bool stays."""
    if x.dtype == jnp.int64:
        return x.astype(jnp.int32)
    if x.dtype == jnp.float64:  # pragma: no cover — eligibility rejects
        return x.astype(jnp.float32)
    return x
