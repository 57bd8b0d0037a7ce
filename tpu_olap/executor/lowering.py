"""Query lowering: QuerySpec + TableSegments -> PhysicalPlan.

The analog of DruidStrategy's physical planning + Druid's per-query engine
setup (SURVEY.md §4.2), redesigned for XLA's trace-once model: the lowered
kernel closure only reads literals from a named ConstPool dict, so one
jitted program serves every query sharing the same *template* (same spec
structure, different literals) — the compile-cache requirement that makes
sub-500ms p50 possible (SURVEY.md §8.4 #3). Anything the dense device path
can't express raises Unsupported*, which the planner treats as "not
rewritable" -> fallback (SURVEY.md §2 property 2).
"""

from __future__ import annotations

import os.path
from dataclasses import dataclass, field, replace

import numpy as np

from tpu_olap.ir.interval import ETERNITY
from tpu_olap.ir.query import (GroupByQuerySpec, ScanQuerySpec,
                               SearchQuerySpec, SelectQuerySpec,
                               TimeseriesQuerySpec, TopNQuerySpec)
from tpu_olap.kernels.exprs import materialize_virtuals
from tpu_olap.kernels.filtereval import ConstPool, compile_filter
from tpu_olap.kernels.groupby import (UnsupportedAggregation,
                                      build_group_key, compile_aggregations,
                                      group_reduce, stage_scope)
from tpu_olap.kernels.timebucket import compile_granularity
from tpu_olap.executor.dimplan import compile_dimension
from tpu_olap.segments.segment import ColumnType, TIME_COLUMN


@dataclass
class PhysicalPlan:
    query: object
    table: object
    kind: str                  # "agg" | "mask" (scan/select)
    pool: ConstPool = None
    kernel: object = None      # unjitted fn(env, valid, segmask, consts)
    statics: tuple = ()        # part of the compile-cache key
    dim_plans: list = field(default_factory=list)
    bucket_plan: object = None
    agg_plans: list = field(default_factory=list)
    sizes: tuple = ()          # (n_buckets, dim sizes...) radix order
    total_groups: int = 1
    pruned_ids: list = field(default_factory=list)
    t_min: int = 0
    t_max: int = 0
    empty: bool = False        # intervals don't touch the table at all
    columns: tuple = ()        # physical columns the kernel reads
    null_cols: tuple = ()
    virtual_exprs: dict = field(default_factory=dict)
    # (token, source_col, const_name) derived streams the compiled
    # filters need (columnComparison code translation); the runner
    # materializes each once per content token (see dataset.derived)
    filter_streams: tuple = ()
    pallas_reason: str | None = "not attempted"  # None = pallas kernel active
    sparse: bool = False       # sort-based path for huge group spaces
    make_sparse_kernel: object = None   # SparseProgram -> kernel fn
    # the group key's words: a tuple a word of the positions of `sizes`
    # packed into it, ascending, and each position's radix in its word.
    # One word of every position that carries an id, in the mixed radix
    # of `sizes`, but for a sparse plan whose group space is 2^62 or
    # more: its key is several int64 words
    # (sparse_groupby.pack_key_words, key_radix)
    key_words: tuple = ()
    key_radix: tuple = ()
    # (test, names) of a GroupBy's HAVING where the sparse program can
    # decide it (sparse_groupby.compile_having), else None: the host does
    having: object = None
    # fn(env, valid, seg_mask, consts) -> (fenv, mask, key): the plan's
    # filter+dim front half WITHOUT the reduce, so the batch executor
    # can fuse N legs' reduces over one shared scan (dense agg only;
    # always the generic jnp/numpy path even when plan.kernel is Pallas)
    key_fn: object = None

    def fingerprint(self) -> tuple:
        # memoized: plans are immutable once lowered and (round 3) cached
        # across executions, so the template serialization — a couple ms
        # of json for wide queries — is paid once, not per dispatch
        fp = getattr(self, "_fp", None)
        if fp is None:
            import json
            t = _template(self.query.to_json())
            fp = self._fp = (
                self.table.name, json.dumps(t, sort_keys=True),
                self.statics,
                self.pool.signature() if self.pool is not None else ())
        return fp


_LITERAL_KEYS = {"value", "values", "lower", "upper", "pattern", "intervals"}


def _template(j):
    """Strip literal values from a query-JSON tree, keep structure.

    Expression subtrees (virtual columns, expression filters) are kept
    VERBATIM including their literals: those literals are traced into the
    jitted program as XLA constants (they never ride the ConstPool), so
    stripping them would alias distinct programs in the compile cache —
    `sum(x*2)` vs `sum(x*3)` must not share a fingerprint.
    """
    if isinstance(j, dict):
        if j.get("type") == "expression":
            return j
        return {k: ("?" if k in _LITERAL_KEYS else _template(v))
                for k, v in j.items()}
    if isinstance(j, list):
        return [_template(x) for x in j]
    return j


def lower(query, table, config) -> PhysicalPlan:
    if isinstance(query, (TimeseriesQuerySpec, GroupByQuerySpec,
                          TopNQuerySpec)):
        return _lower_agg(query, table, config)
    if isinstance(query, (ScanQuerySpec, SelectQuerySpec)):
        return _lower_mask(query, table, config)
    if isinstance(query, SearchQuerySpec):
        raise AssertionError("search queries lower via runner._run_search")
    raise UnsupportedAggregation(
        f"no device lowering for {type(query).__name__}")


def _sparse_reject_reason(query, total, config) -> str | None:
    """None when the sort-based sparse path can serve this shape, else
    why not — the single source of truth for both the over-budget
    routing decision and the in-branch rejections. Refused: a timeseries
    (its assembler indexes the dense bucket space); any shape without
    64-bit lanes (the key's words are int64); and, on a mesh, a group
    space of 2^62 or more: one chip packs such a key into several int64
    words (`sparse_groupby.pack_key_words`), but the chips' tables are
    merged by a sort of ONE int64 key (`sharding.mesh_merge_kernel`,
    `merge_device`, the broker's `merge_sparse`)."""
    if not isinstance(query, (GroupByQuerySpec, TopNQuerySpec)):
        return f"{query.query_type} has no sparse path"
    if not config.enable_x64:
        return "sparse group-by needs int64 keys (enable_x64=False)"
    if total >= (1 << 62) and _mesh_size(config) > 1:
        return ("the group space is past 2^62, whose sparse key is more "
                "than one int64 word, and that needs one chip: the "
                "mesh's merge sorts one int64 key")
    return None


def topn_takes_sparse(query, plan, config) -> bool:
    """The one rule that sends a TopN whose group space is UNDER the dense
    budget to the sparse path all the same (past the budget every shape
    goes there, or is refused), from the lowered dense plan's static
    facts. All of:
    - the generic kernel would run it as XLA's scatter over the [K] space
      (Pallas turned the plan down and `groupby.reduce_form` says so: K is
      past the compare form) and hand the host K rows an aggregate to rank;
    - no aggregate is a sketch: the dense plan keeps a theta sketch at the
      query's own width, the compact table clamps it to
      `sparse_theta_k_cap`, a coarser answer;
    - an integer count or sum is among the aggregates, which the sparse
      path reads as a difference of prefix sums at the sorted runs'
      boundaries (one sort whose cost does not depend on K, a fifth of one
      scattered int64 sum: PERF.md section 6) and can rank on the device;
      a plan of float sums alone scatters there as here and gains nothing
      from the sort, and whether a plan of integer min / max alone, which
      the sparse path reads at the runs' last rows since PR 37, should
      leave the dense kernel is not measured: it stays;
    - the compact table can hold every group of the space
      (K <= sparse_group_budget), so nothing the dense plan serves is
      refused."""
    from tpu_olap.kernels.groupby import reduce_form
    from tpu_olap.kernels.sparse_groupby import prefix_summed
    kinds = [p.kind for p in plan.agg_plans]
    return (isinstance(query, TopNQuerySpec)
            and plan.pallas_reason is not None
            and reduce_form(plan.total_groups, kinds) == "scatter"
            and not any(k in ("hll", "theta") for k in kinds)
            and any(prefix_summed(p) for p in plan.agg_plans)
            and plan.total_groups <= config.sparse_group_budget
            and _sparse_reject_reason(query, plan.total_groups,
                                      config) is None)


def _mesh_size(config) -> int:
    """Devices the runner will shard over. QueryRunner builds a mesh
    ONLY when num_shards > 1 is explicitly configured (runner.mesh);
    unsharded runs must not have their sketch state budgeted at
    device-count multiples they never allocate."""
    return int(config.num_shards) if config.num_shards else 1


def _radix(p) -> int:
    """Per-group state width of an aggregation plan: HLL register file,
    theta value table, or 1 for scalar accumulators. Shared by the
    sketch-state budget and the no-x64 int32 index guard."""
    from tpu_olap.kernels.hll import NUM_REGISTERS
    if p.kind == "hll":
        return NUM_REGISTERS
    return p.theta_k if p.kind == "theta" else 1


def _time_range(query, table):
    intervals = query.intervals or (ETERNITY,)
    t0, t1 = table.time_boundary
    lo = max(t0, min(iv.start for iv in intervals))
    hi = min(t1, max(iv.end for iv in intervals) - 1)
    return intervals, lo, hi, hi < lo


def _interval_mask_fn(intervals, t0, t1, pool):
    """None if intervals cover the whole table; else fn(env,c)->mask."""
    covered = any(iv.start <= t0 and iv.end > t1 for iv in intervals)
    if covered:
        return None
    starts = pool.add(np.asarray([iv.start for iv in intervals], np.int64))
    ends = pool.add(np.asarray([iv.end for iv in intervals], np.int64))

    def fn(env, c):
        t = env["cols"][TIME_COLUMN]
        return ((t[..., None] >= c[starts]) & (t[..., None] < c[ends])
                ).any(axis=-1)
    return fn


def _filter_numeric_bounds(spec, table, vexprs=None) -> dict:
    """Per-column [lo, hi] requirements implied by top-level AND
    conjuncts of the filter, for manifest pruning (SURVEY.md §3.5 P4's
    numeric-bounds leg — the denormalized-dim analog of interval
    pruning: with time-partitioned ingest a selector like d_year = 1993
    sees tight per-segment min/max and drops whole partitions before
    dispatch). Conservative: plain LONG columns only, no extraction fns,
    numeric-ordered bounds; OR/NOT shapes contribute nothing; strict
    bounds prune with their inclusive envelope (a superset scan is
    always correct — the kernel's filter stays exact)."""
    from tpu_olap.ir.filters import (AndFilter, BoundFilter, InFilter,
                                     SelectorFilter)

    def _num(v):
        try:
            return int(v)
        except (TypeError, ValueError):
            try:
                return float(v)
            except (TypeError, ValueError):
                return None

    out: dict = {}

    def add(col, lo, hi):
        # a virtual column shadows any same-named physical column in
        # filter evaluation — its values are an expression, so the
        # physical manifest's min/max say nothing about it
        if vexprs and col in vexprs:
            return
        if table.schema.get(col) is not ColumnType.LONG:
            return
        plo, phi = out.get(col, (None, None))
        if lo is not None:
            plo = lo if plo is None else max(plo, lo)
        if hi is not None:
            phi = hi if phi is None else min(phi, hi)
        out[col] = (plo, phi)

    def walk(f):
        if isinstance(f, AndFilter):
            for g in f.fields:
                walk(g)
        elif isinstance(f, SelectorFilter) and f.extraction_fn is None:
            v = _num(f.value)
            if v is not None:
                add(f.dimension, v, v)
        elif isinstance(f, InFilter) \
                and getattr(f, "extraction_fn", None) is None:
            vs = [_num(v) for v in f.values]
            if vs and all(v is not None for v in vs):
                add(f.dimension, min(vs), max(vs))
        elif isinstance(f, BoundFilter) and f.extraction_fn is None \
                and f.ordering == "numeric":
            add(f.dimension, _num(f.lower), _num(f.upper))

    if spec is not None:
        walk(spec)
    return out


def _elide_covered_imask(imask_fn, pruned_segs, intervals):
    """Residual interval-mask elision (SURVEY.md §3.5 P4 extended to row
    level): ingest globally time-sorts rows, so a scanned segment's
    [time_min, time_max] usually sits entirely inside one query interval
    — the row-level mask is then constant-true over every scanned block,
    and the kernel neither evaluates it nor reads __time for it (8
    bytes/row of HBM scan traffic on a v5e, typically the single widest
    column a filtered aggregate touches). Segments straddling an
    interval edge keep the device mask. Compile-time decision: pruning
    is static per plan, so the elision caches with the template."""
    if imask_fn is None or not pruned_segs:
        return imask_fn
    if all(any(iv.start <= s.meta.time_min and iv.end > s.meta.time_max
               for iv in intervals) for s in pruned_segs):
        return None
    return imask_fn


def _collect_columns(table, query, dim_plans, agg_plans, vexprs,
                     need_time: bool):
    cols: set[str] = set()
    if query.filter is not None:
        cols |= query.filter.columns()
    for p in agg_plans:
        cols |= set(p.fields)
    for dp in dim_plans:
        if dp.source_col:
            cols.add(dp.source_col)
    # expand virtual column references to their physical inputs
    phys: set[str] = set()
    for c in cols:
        if c in vexprs:
            phys |= vexprs[c].columns()
        else:
            phys.add(c)
    # filters on agg-inside filters already included via p.fields? filtered
    # agg filters reference columns through compile-time closures; collect
    for a in query.aggregations if hasattr(query, "aggregations") else ():
        from tpu_olap.ir.aggregations import FilteredAggregation
        if isinstance(a, FilteredAggregation):
            for c in a.filter.columns():
                phys |= vexprs[c].columns() if c in vexprs else {c}
    if need_time:
        phys.add(TIME_COLUMN)
    unknown = [c for c in phys if c not in table.schema]
    if unknown:
        from tpu_olap.kernels.filtereval import UnsupportedFilter
        raise UnsupportedFilter(f"unknown columns {unknown}")
    null_cols = tuple(sorted(
        c for c in phys if table.schema[c] is not ColumnType.STRING))
    return tuple(sorted(phys)), null_cols


def _filter_value_sets(filter_spec) -> dict:
    """Literal restrictions implied by top-level AND conjuncts:
    {column: allowed value set}. Plain selector / IN / OR-of-selectors
    only (no extraction fns) — the shapes whose passing rows provably
    carry one of the listed values in that column."""
    from tpu_olap.ir import filters as F
    conjs = list(filter_spec.fields) \
        if isinstance(filter_spec, F.AndFilter) else [filter_spec]
    out: dict = {}
    for c in conjs:
        col = vs = None
        if isinstance(c, F.SelectorFilter) and c.extraction_fn is None \
                and c.value is not None:
            col, vs = c.dimension, {c.value}
        elif isinstance(c, F.InFilter) and c.extraction_fn is None:
            # extraction-IN values are post-extraction strings, NOT raw
            # column values — they must not restrict the dim domain
            col = c.dimension
            vs = {v for v in c.values if v is not None}
        elif isinstance(c, F.OrFilter):
            cols, vals, ok = set(), set(), True
            for f in c.fields:
                if isinstance(f, F.SelectorFilter) \
                        and f.extraction_fn is None \
                        and f.value is not None:
                    cols.add(f.dimension)
                    vals.add(f.value)
                else:
                    ok = False
                    break
            if ok and len(cols) == 1:
                col, vs = next(iter(cols)), vals
        if col is not None:
            out[col] = vs if col not in out else (out[col] & vs)
    return out


def _restrict_dims(dim_plans, filter_spec, table, pool):
    """Shrink grouped string dims whose domain a filter restricts to a
    literal set: the dense id space drops from |dictionary| to |set|+1
    via a code remap (rows outside the set are masked by the same filter
    anyway, so they may map to the null slot). Two restriction sources:

    - direct: the filter names the grouped column itself (Q3.3/Q3.4's
      city IN (...) — 113k-slot tables drop to single digits);
    - FD hop: the filter names a column the grouped one determines
      (declared star FD, SURVEY.md §3.4), e.g. s_nation='US' restricting
      grouped s_city to the cities observed with that nation — verified
      against the data (fd_code_map), never trusted blindly.
    """
    if filter_spec is None:
        return dim_plans
    sets = _filter_value_sets(filter_spec)
    if not sets:
        return dim_plans
    from tpu_olap.executor.dimplan import DimPlan
    fds = table.star.functional_dependencies if table.star else ()
    out = []
    for dp in dim_plans:
        if dp.kind != "codes":
            out.append(dp)
            continue
        d = table.dictionaries[dp.source_col]
        allowed = None  # None = unrestricted; else set of codes (> 0)

        vs = sets.get(dp.source_col)
        if vs is not None:
            allowed = {c for v in vs if (c := d.id_of(v)) > 0}
        for fd in fds:
            if fd.determinant != dp.source_col:
                continue
            dvs = sets.get(fd.dependent)
            if dvs is None:
                continue
            m = table.fd_code_map(dp.source_col, fd.dependent)
            if m is None:
                continue
            dep_dict = table.dictionaries[fd.dependent]
            dep_codes = np.array(
                sorted(c for v in dvs if (c := dep_dict.id_of(v)) > 0),
                np.int64)
            codes = set(np.nonzero(np.isin(m, dep_codes))[0].tolist())
            codes.discard(0)
            allowed = codes if allowed is None else (allowed & codes)

        if allowed is None or len(allowed) + 1 >= dp.size:
            out.append(dp)
            continue
        codes = sorted(allowed)
        remap = np.zeros(dp.size, np.int32)
        labels = np.empty(len(codes) + 1, object)
        labels[0] = None
        for i, c in enumerate(codes):
            remap[c] = i + 1
            labels[i + 1] = d.values[c - 1]
        from tpu_olap.executor.dimplan import _dim_token
        out.append(DimPlan(dp.name, len(codes) + 1, labels,
                           dp.source_col, "remap",
                           remap_name=pool.add(remap),
                           cache_token=_dim_token("rs", dp.source_col,
                                                  remap)))
    return out


def _lower_agg(query, table, config) -> PhysicalPlan:
    pool = ConstPool()
    intervals, t_min, t_max, empty = _time_range(query, table)
    vexprs = {v.name: v.expression for v in query.virtual_columns}

    bucket_plan = compile_granularity(query.granularity, t_min, t_max,
                                      pool, table.time_boundary)

    if isinstance(query, GroupByQuerySpec):
        dim_specs = query.dimensions
    elif isinstance(query, TopNQuerySpec):
        dim_specs = (query.dimension,)
    else:
        dim_specs = ()
    dim_plans = [compile_dimension(s, table, pool, t_min, t_max,
                                   numeric_dim_budget=config
                                   .numeric_dim_label_budget,
                                   vexprs=vexprs)
                 for s in dim_specs]
    dim_plans = _restrict_dims(dim_plans, query.filter, table, pool)

    agg_plans = compile_aggregations(
        query.aggregations, table, pool, vexprs,
        long_dtype=config.long_dtype, double_dtype=config.double_dtype,
        theta_k_cap=config.theta_k_cap)

    filter_fn = (compile_filter(query.filter, table, pool, vexprs)
                 if query.filter is not None else None)
    imask_fn = _interval_mask_fn(intervals, *table.time_boundary, pool)

    sizes = (bucket_plan.n_buckets,) + tuple(dp.size for dp in dim_plans)
    total = 1
    for s in sizes:
        total *= s
    # the sparse key's words, over the positions of `sizes` that carry an
    # id (granularity "all" has none): one word under 2^62
    from tpu_olap.kernels.sparse_groupby import key_radix, pack_key_words
    id_pos = ([0] if bucket_plan.kind != "all" else []) \
        + list(range(1, len(sizes)))
    packed = pack_key_words([sizes[i] for i in id_pos])
    key_words = tuple(tuple(id_pos[i] for i in w) for w in packed)
    if len(packed) > 1:
        # a wide key's program holds each domain's width in bits, not its
        # size (`pack_key_words`): a numeric dimension's bound rides the
        # pool beside its offset
        dim_plans = [replace(dp, size_name=pool.add(dp.size, np.int32))
                     if dp.kind == "numeric" else dp for dp in dim_plans]
    # sketch aggregates keep [groups × radix] state PER AGGREGATION: at
    # large K their TOTAL dominates memory long before the group COUNT
    # exceeds the dense budget (observed: a 1M-group theta query
    # allocating >100 GB). The mesh's per-chip partials ([D·K, k]
    # theta tables, executor/sharding.py::mesh_agg_kernel) multiply
    # that state by the mesh size — a fuzz-found sharded theta query
    # ground a host to 100 GB and an XLA rendezvous abort with
    # per-sketch state that looked safe unscaled. Budget the summed,
    # mesh-scaled element count — over budget, the sparse path
    # (clamped sketch width, per-chip fan-out + broker merge) serves it
    # when it can; shapes with no sparse path decline legibly, never
    # allocate
    theta_radix = sum(p.theta_k for p in agg_plans if p.kind == "theta")
    other_radix = sum(_radix(p) for p in agg_plans
                      if p.kind != "theta" and _radix(p) > 1)
    state_radix = other_radix + theta_radix * max(1, _mesh_size(config))
    sketch_over = (state_radix > 0
                   and total * state_radix
                   > config.dense_sketch_state_budget)
    sparse = total > config.dense_group_budget
    if sketch_over and not sparse:
        reject = _sparse_reject_reason(query, total, config)
        if reject is not None:
            raise UnsupportedAggregation(
                f"per-group sketch state {total}×{state_radix} exceeds "
                f"dense_sketch_state_budget "
                f"{config.dense_sketch_state_budget} and {reject}")
        sparse = True
    if sparse:
        # sort-based sparse path (SURVEY.md §8.4 #1)
        reject = _sparse_reject_reason(query, total, config)
        if reject is not None:
            raise UnsupportedAggregation(
                f"group space {total} exceeds dense budget "
                f"{config.dense_group_budget} and {reject}")
    if not sparse and not config.enable_x64:
        # sketch state is [groups × radix]; without 64-bit lanes the flat
        # scatter index must fit int32
        for p in agg_plans:
            radix = _radix(p)
            if radix > 1 and total * radix > (1 << 31) - 1:
                raise UnsupportedAggregation(
                    f"sketch index space {total}×{radix} overflows int32 "
                    "without x64")

    pruned_segs = table.prune(
        intervals, _filter_numeric_bounds(query.filter, table, vexprs))
    imask_fn = _elide_covered_imask(imask_fn, pruned_segs, intervals)
    # __time (int64, the widest column) is read only when something
    # actually consumes raw timestamps on device: an un-elided interval
    # mask, or bucketing/timeformat WITHOUT a cached derived id stream
    # (the runner materializes cached streams once per table, so those
    # kernels read [S,R] int32 ids instead of recomputing from millis)
    need_time = ((bucket_plan.kind != "all"
                  and bucket_plan.cache_token is None)
                 or imask_fn is not None
                 or any(dp.kind == "timeformat" and dp.cache_token is None
                        for dp in dim_plans))
    columns, null_cols = _collect_columns(table, query, dim_plans, agg_plans,
                                          vexprs, need_time)
    pruned = [s.meta.segment_id for s in pruned_segs]

    def _masked_key(env, valid, seg_mask, consts, key_builder):
        xp = _jnp()
        flat = {c: a.reshape(-1) for c, a in env["cols"].items()}
        nulls = {c: a.reshape(-1) for c, a in env["nulls"].items()}
        materialize_virtuals(vexprs, flat, nulls, xp)
        fenv = {"cols": flat, "nulls": nulls}
        with stage_scope("filter", xp):
            mask = (valid & seg_mask[:, None]).reshape(-1)
            if filter_fn is not None:
                mask = mask & filter_fn(fenv, consts)
            if imask_fn is not None:
                mask = mask & imask_fn(fenv, consts)
        ids, radix = [], []
        with stage_scope("key", xp):
            if bucket_plan.kind != "all":
                cached = flat.get(bucket_plan.derived_name) \
                    if bucket_plan.cache_token else None
                ids.append(bucket_plan.ids_from_cached(cached, consts, xp)
                           if cached is not None
                           else bucket_plan.ids(flat[TIME_COLUMN], consts))
                radix.append(sizes[0])
            for dp, size in zip(dim_plans, sizes[1:]):
                ids.append(dp.ids(fenv, consts, xp))
                radix.append(size)
            if ids:
                key, _ = key_builder(ids, radix, xp)
            else:
                key = xp.zeros(mask.shape, xp.int32)
        return fenv, mask, key

    def kernel(env, valid, seg_mask, consts):
        fenv, mask, key = _masked_key(env, valid, seg_mask, consts,
                                      build_group_key)
        return group_reduce(key, mask, fenv, agg_plans, total, consts)

    def key_fn(env, valid, seg_mask, consts):
        return _masked_key(env, valid, seg_mask, consts, build_group_key)

    # theta rides the sparse path with a clamped sketch width (the
    # [cap, k] table and its merge transients are per-group state; see
    # EngineConfig.sparse_theta_k_cap)
    import dataclasses as _dc
    sparse_agg_plans = tuple(
        _dc.replace(p, theta_k=min(p.theta_k, config.sparse_theta_k_cap))
        if p.kind == "theta" else p for p in agg_plans)

    # a HAVING the sparse program can decide (its literals join the pool)
    having = None
    if sparse and isinstance(query, GroupByQuerySpec) \
            and query.having is not None:
        from tpu_olap.kernels.sparse_groupby import compile_having
        having = compile_having(query.having, sparse_agg_plans, pool)

    def make_sparse_kernel(program):
        """The plan's sparse kernel of `program` (a
        `sparse_groupby.SparseProgram`): `sparse_group_reduce`'s table
        program of its `cap` slots, cuts and spellings or, `cap` None,
        `sparse_group_count`'s. Either sorts by every word of the plan's
        key (`key_words`; a word int32 where its ids fit 31 bits,
        `sparse_groupby.key_word_dtypes`). The window's slice is the
        caller's (`QueryRunner._window_kernel`)."""
        from tpu_olap.kernels.sparse_groupby import (build_group_key64,
                                                     sparse_group_count,
                                                     sparse_group_reduce)

        def key_builder(ids, radix, xp):
            return build_group_key64(ids, radix, words=packed)

        def sparse_kernel(env, valid, seg_mask, consts):
            fenv, mask, key = _masked_key(env, valid, seg_mask, consts,
                                          key_builder)
            if program.cap is None:
                return sparse_group_count(key, mask)
            return sparse_group_reduce(key, mask, fenv, sparse_agg_plans,
                                       consts, program, having)
        return sparse_kernel

    def build(sparse: bool) -> PhysicalPlan:
        plans = sparse_agg_plans if sparse else agg_plans
        statics = ("agg", sizes, bucket_plan.kind,
                   tuple(dp.kind for dp in dim_plans),
                   tuple((p.kind, p.name) for p in plans),
                   filter_fn is not None, imask_fn is not None,
                   "sparse" if sparse else "dense")
        return PhysicalPlan(
            query=query, table=table, kind="agg", pool=pool,
            kernel=None if sparse else kernel,
            statics=statics, dim_plans=dim_plans, bucket_plan=bucket_plan,
            agg_plans=plans, sizes=sizes, total_groups=total,
            pruned_ids=pruned, t_min=t_min, t_max=t_max, empty=empty,
            columns=columns, null_cols=null_cols, virtual_exprs=vexprs,
            filter_streams=_dedupe_streams(pool),
            sparse=sparse, make_sparse_kernel=make_sparse_kernel if sparse
            else None, having=having,
            key_words=key_words, key_radix=key_radix(sizes, key_words),
            key_fn=None if sparse else key_fn)

    plan = build(sparse)
    if not sparse:
        _maybe_use_pallas(plan, query, table, config, filter_fn, imask_fn)
        if topn_takes_sparse(query, plan, config):
            plan = build(True)
    return plan


def _maybe_use_pallas(plan, query, table, config, filter_fn, imask_fn=None):
    """Swap the generic jnp kernel for the fused Pallas one-hot MXU reduce
    when the plan fits its envelope (kernels.pallas_reduce). "auto"
    additionally requires the TPU backend — interpret mode is for tests
    ("force"), not production."""
    if config.use_pallas not in ("auto", "force", "never"):
        raise ValueError(
            f"use_pallas must be 'auto', 'force', or 'never'; got "
            f"{config.use_pallas!r}")
    if config.use_pallas == "never":
        return
    # cheap backend gate first: under "auto" off-TPU, skip the eligibility
    # scan entirely (it reads per-column min/max metadata)
    on_tpu = _default_backend() == "tpu"
    if config.use_pallas == "auto" and not on_tpu:
        plan.pallas_reason = "auto: backend is not tpu"
        return
    from tpu_olap.kernels import pallas_reduce

    reason = pallas_reduce.eligible(query, plan, table, config, filter_fn)
    if reason is not None:
        plan.pallas_reason = reason
        return
    tuning = _tuned_pallas_policy()
    if (config.use_pallas == "auto" and plan.total_groups == 1
            and tuning.get("auto_ungrouped_pallas") is False):
        # hardware-fitted: with no grouping there is no scatter to beat —
        # XLA's fused masked reduce wins by a fixed dispatch margin
        # (tools/fit_pallas_budget.py, first on-chip A/B)
        plan.pallas_reason = ("auto: ungrouped reduce is faster on the "
                              "generic kernel (hardware-fitted policy)")
        return
    budget = config.pallas_auto_flop_budget
    if budget is None:
        budget = tuning.get("auto_flop_budget")
    if config.use_pallas == "auto" and budget is not None:
        # the one-hot reduce is O(K·n): 2 * n * tile_product FLOPs, where
        # the tile product accounts for the factorized lane packing
        # (docs/PERF_MODEL.md). Past the budget the XLA scatter kernel
        # wins — its work is n-bound and K-free.
        n = len(table.segments) * table.block_rows
        flops = 2.0 * n * pallas_reduce.tile_product(plan, table, config)
        if flops > budget:
            plan.pallas_reason = (
                f"auto: one-hot reduce needs {flops:.2e} FLOPs for "
                f"K={plan.total_groups}; over the auto flop budget")
            return
    plan.kernel = pallas_reduce.build_kernel(plan, table, config, filter_fn,
                                             interpret=not on_tpu,
                                             imask_fn=imask_fn)
    plan.statics = plan.statics + ("pallas", config.pallas_k_per_block)
    plan.pallas_reason = None


def _default_backend() -> str:
    import jax
    return jax.default_backend()


_tuning_cache: dict | None = None
# module constant so tests can monkeypatch the location instead of
# rewriting the shipped fitted file in place
_TUNING_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "planner", "pallas_tuning.json")


def _tuned_pallas_policy() -> dict:
    """Hardware-fitted defaults for the 'auto' Pallas policy:
    tools/fit_pallas_budget.py writes planner/pallas_tuning.json from
    the on-chip A/B pair (docs/PERF_MODEL.md decision procedure #1).
    Keys: auto_ungrouped_pallas (False = K==1 queries take the generic
    fused reduce) and auto_flop_budget (upper cap on the one-hot FLOP
    product; an explicit EngineConfig.pallas_auto_flop_budget overrides
    it). Absent file = empty policy (pre-A/B behavior)."""
    global _tuning_cache
    if _tuning_cache is None:
        import json
        path = _TUNING_PATH
        data = {}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    data = json.load(f)
            except Exception:  # noqa: BLE001 — a bad file must not
                data = {}      # break query planning
        _tuning_cache = data
    return _tuning_cache


def _lower_mask(query, table, config) -> PhysicalPlan:
    """Scan/Select: device computes the row mask; rows assemble host-side."""
    pool = ConstPool()
    intervals, t_min, t_max, empty = _time_range(query, table)
    vexprs = {v.name: v.expression for v in query.virtual_columns}
    filter_fn = (compile_filter(query.filter, table, pool, vexprs)
                 if query.filter is not None else None)
    imask_fn = _interval_mask_fn(intervals, *table.time_boundary, pool)
    pruned_segs = table.prune(
        intervals, _filter_numeric_bounds(query.filter, table, vexprs))
    imask_fn = _elide_covered_imask(imask_fn, pruned_segs, intervals)

    cols: set[str] = set()
    if query.filter is not None:
        cols |= query.filter.columns()
    phys: set[str] = set()
    for c in cols:
        phys |= vexprs[c].columns() if c in vexprs else {c}
    if imask_fn is not None:
        phys.add(TIME_COLUMN)
    unknown = [c for c in phys if c not in table.schema]
    if unknown:
        from tpu_olap.kernels.filtereval import UnsupportedFilter
        raise UnsupportedFilter(f"unknown columns {unknown}")
    null_cols = tuple(sorted(
        c for c in phys if table.schema[c] is not ColumnType.STRING))

    def kernel(env, valid, seg_mask, consts):
        xp = _jnp()
        flat = {c: a.reshape(-1) for c, a in env["cols"].items()}
        nulls = {c: a.reshape(-1) for c, a in env["nulls"].items()}
        materialize_virtuals(vexprs, flat, nulls, xp)
        fenv = {"cols": flat, "nulls": nulls}
        with stage_scope("filter", xp):
            mask = (valid & seg_mask[:, None]).reshape(-1)
            if filter_fn is not None:
                mask = mask & filter_fn(fenv, consts)
            if imask_fn is not None:
                mask = mask & imask_fn(fenv, consts)
        return {"mask": mask}

    statics = ("mask", filter_fn is not None, imask_fn is not None)
    pruned = [s.meta.segment_id for s in pruned_segs]
    return PhysicalPlan(
        query=query, table=table, kind="mask", pool=pool, kernel=kernel,
        statics=statics, pruned_ids=pruned, t_min=t_min, t_max=t_max,
        empty=empty, columns=tuple(sorted(phys)), null_cols=null_cols,
        virtual_exprs=vexprs, filter_streams=_dedupe_streams(pool))


def _dedupe_streams(pool: ConstPool) -> tuple:
    """Unique filter-derived stream requests in first-seen order (the
    same column pair can appear in several conjuncts of one query)."""
    seen, out = set(), []
    for token, src, cname in pool.streams:
        if token not in seen:
            seen.add(token)
            out.append((token, src, cname))
    return tuple(out)


def _jnp():
    import jax.numpy as jnp
    return jnp
