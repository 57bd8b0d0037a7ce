"""Dimension lowering: DimensionSpec -> dense ids + labels.

Every grouped dimension becomes a dense id in [0, size): dictionary codes
for string dims (0 = null), value-offset for bounded numeric dims, and a
host-computed remap table for extraction dims (substring/regex/lookup over
the dictionary; timeFormat over bucket starts). This is what makes the
group key mixed-radix (kernels.groupby) and group tables mergeable across
chips without string exchange.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from tpu_olap.ir.dimensions import (DefaultDimensionSpec,
                                    ExtractionDimensionSpec,
                                    TimeFormatExtractionFn)
from tpu_olap.kernels.filtereval import _extraction_callable
from tpu_olap.kernels.timebucket import compile_time_format
from tpu_olap.segments.segment import ColumnType, TIME_COLUMN


class UnsupportedDimension(Exception):
    pass


class NumericLabels:
    """Labels of a dense numeric dimension by arithmetic: id 0 is null,
    id i is the value lo - 1 + i. Indexed like the [size] object array it
    stands for (one id or an array of ids), so the width of the domain
    costs nothing on the host: TPC-H's l_orderkey spans 60,000,000 at SF10
    and is grouped by on the sparse path."""

    def __init__(self, lo: int, size: int):
        self.lo, self.size = int(lo), int(size)

    def __len__(self) -> int:
        return self.size

    def __getitem__(self, ids):
        if np.ndim(ids) == 0:
            return None if int(ids) == 0 else self.lo - 1 + int(ids)
        ids = np.asarray(ids)
        out = (ids.astype(np.int64) + (self.lo - 1)).astype(object)
        out[ids == 0] = None
        return out


@dataclass
class DimPlan:
    name: str          # output name
    size: int          # dense id space size
    labels: object     # [size] output values by id (None=null): an np
    #                    object array, or NumericLabels
    source_col: str | None   # column whose array feeds ids() (None = time)
    kind: str          # "codes" | "numeric" | "remap" | "timeformat"
    remap_name: str | None = None   # ConstPool name for remap/offset consts
    offset_name: str | None = None
    # ConstPool name of `size` where a numeric dimension's bound is to
    # ride the pool and not the program's text (a wide sparse key:
    # lowering._lower_agg); None: the bound is the literal
    size_name: str | None = None
    time_plan: object = None        # BucketPlan for timeformat dims
    # content hash for gather-needing kinds (remap/timeformat): the
    # runner precomputes these id streams ONCE per table as
    # device-resident derived columns (a per-dispatch 1-D gather over
    # every row costs ~60 ms on a v5e through XLA; resident ids cost
    # one HBM read like any column). ids() consumes the cached stream
    # when the env carries it under "\0d:<token>".
    cache_token: str | None = None

    @property
    def derived_name(self) -> str | None:
        return None if self.cache_token is None else "\0d:" + self.cache_token

    def ids(self, env, consts, xp):
        if self.cache_token is not None:
            hit = env["cols"].get("\0d:" + self.cache_token)
            if hit is not None:
                return hit
        if self.kind == "codes":
            return env["cols"][self.source_col]
        if self.kind == "numeric":
            v = env["cols"][self.source_col]
            i = (v - consts[self.offset_name]).astype(xp.int32)
            # out-of-range/null -> slot 0 (null); executor masks via labels
            # np.int32 zero, not a Python 0: under x64 a weak scalar enters
            # jnp.where as i64 and Mosaic's scalar i64->i32 lowering
            # recurses when this runs inside the Pallas kernel
            z = np.int32(0)
            size = self.size if self.size_name is None \
                else consts[self.size_name]
            i = xp.where((i >= 1) & (i < size), i, z)
            nm = env["nulls"].get(self.source_col)
            if nm is not None:
                i = xp.where(nm, z, i)
            return i
        if self.kind == "remap":
            codes = env["cols"][self.source_col]
            return consts[self.remap_name][codes]
        if self.kind == "timeformat":
            fine = self.time_plan.ids(env["cols"][TIME_COLUMN], consts)
            return consts[self.remap_name][fine]
        raise AssertionError(self.kind)


def compile_dimension(spec, table, pool, t_min, t_max,
                      numeric_dim_budget=1 << 20, vexprs=None) -> DimPlan:
    if isinstance(spec, DefaultDimensionSpec):
        col = spec.dimension
        if col not in table.schema:
            if vexprs and col in vexprs:
                # GROUP BY <integer expression>: a virtual column whose
                # id domain comes from interval arithmetic over its
                # inputs' min/max metadata (the expression itself is
                # materialized in the kernel env like any virtual)
                return _virtual_numeric_dim(spec, col, vexprs[col], table,
                                            pool)
            raise UnsupportedDimension(f"unknown dimension {col!r}")
        typ = table.schema[col]
        if typ is ColumnType.STRING:
            d = table.dictionaries[col]
            labels = np.empty(d.size + 1, object)
            labels[0] = None
            labels[1:] = d.values
            return DimPlan(spec.name, d.size + 1, labels, col, "codes")
        if typ is ColumnType.LONG:
            md = table.column_metadata([col])[col]
            lo = md.get("min")
            return _dense_numeric_plan(
                spec.name, col, None if lo is None else int(lo),
                None if lo is None else int(md["max"]), pool)
        raise UnsupportedDimension(
            f"cannot group by DOUBLE column {col!r} densely")
    if isinstance(spec, ExtractionDimensionSpec):
        col = spec.dimension
        ex = spec.extraction_fn
        if isinstance(ex, TimeFormatExtractionFn):
            if col != TIME_COLUMN:
                raise UnsupportedDimension(
                    "timeFormat extraction only on __time")
            plan, remap_name, values = compile_time_format(
                ex.format, ex.time_zone, t_min, t_max, pool,
                bucket_budget=numeric_dim_budget)
            labels = np.array(values, object)
            return DimPlan(spec.name, len(values), labels, None,
                           "timeformat", remap_name=remap_name,
                           time_plan=plan,
                           cache_token=_dim_token(
                               "tf", ex.format, ex.time_zone, t_min, t_max,
                               pool.consts[remap_name]))
        if col not in table.schema or table.schema[col] is not ColumnType.STRING:
            raise UnsupportedDimension(
                f"extraction dimension over non-string column {col!r}")
        d = table.dictionaries[col]
        fn = _extraction_callable(ex)
        extracted = [None] + [fn(v) for v in d.values]
        values = sorted({v for v in extracted if v is not None})
        index = {v: i + 1 for i, v in enumerate(values)}
        remap = np.asarray([0 if v is None else index[v] for v in extracted],
                           np.int32)
        labels = np.empty(len(values) + 1, object)
        labels[0] = None
        labels[1:] = values
        return DimPlan(spec.name, len(values) + 1, labels, col, "remap",
                       remap_name=pool.add(remap),
                       cache_token=_dim_token("rm", col, remap))
    raise UnsupportedDimension(f"unknown dimension spec {type(spec).__name__}")


def _dim_token(*parts) -> str:
    """Content hash over everything the derived id stream depends on:
    the remap table bytes + the source identity (+ time params for
    timeformat). Two queries with the same restriction share one cached
    stream; different restrictions cache separately."""
    import hashlib
    h = hashlib.sha1()
    for p in parts:
        if isinstance(p, np.ndarray):
            h.update(p.tobytes())
        else:
            h.update(repr(p).encode())
        h.update(b"\x1f")
    return h.hexdigest()[:16]


def _dense_numeric_plan(name, source_col, lo, hi, pool) -> DimPlan:
    """Dense numeric dimension over values in [lo, hi] (slot 0 = null;
    ids = v - (lo - 1)). lo=None means an empty domain. The labels are
    arithmetic, so no budget bounds the domain here: the group space it
    makes is held to dense_group_budget, or to the sparse path's key and
    budget, by lowering."""
    if lo is None:
        return DimPlan(name, 1, np.array([None], object), source_col,
                       "numeric", offset_name=pool.add(0, np.int64))
    size = hi - lo + 2  # +1 null slot at 0
    return DimPlan(name, size, NumericLabels(lo, size), source_col,
                   "numeric", offset_name=pool.add(lo - 1, np.int64))


def _virtual_numeric_dim(spec, col, expr, table, pool) -> DimPlan:
    from tpu_olap.kernels.pallas_reduce import expr_int_bounds
    phys = sorted(expr.columns())
    for c in phys:
        if c not in table.schema:
            raise UnsupportedDimension(
                f"virtual dimension {col!r} references unknown {c!r}")
        if table.schema[c] is not ColumnType.LONG:
            raise UnsupportedDimension(
                f"virtual dimension {col!r} over non-LONG column {c!r}")
    md = table.column_metadata(set(phys))
    col_bounds = {}
    for c in phys:
        m = md.get(c, {})
        if m.get("min") is None:
            return _dense_numeric_plan(spec.name, col, None, None, pool)
        col_bounds[c] = (int(m["min"]), int(m["max"]))
    b = expr_int_bounds(expr, col_bounds)
    if b is None:
        raise UnsupportedDimension(
            f"virtual dimension {col!r} is not integer-bounded")
    return _dense_numeric_plan(spec.name, col, b[0], b[1], pool)
