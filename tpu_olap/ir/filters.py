"""Filter specs: selector, bound, in, regex, like, and/or/not, expression.

Mirrors the reference's FilterSpec family (SURVEY.md §3.3 "Filters"); the
javascript escape hatch is replaced by ExpressionFilter over the typed
expression AST. Evaluation strategy lives in tpu_olap.kernels.filtereval:
string-dimension predicates compile to boolean lookup tables over the
dictionary, so selector/in/regex/like/bound-lexicographic all lower to one
gather kernel.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from tpu_olap.ir.expr import Expr
from tpu_olap.ir.serde import register, from_json


class FilterSpec:
    def columns(self) -> set[str]:
        raise NotImplementedError


def _reject_extraction_fn(d: dict, kind: str) -> None:
    """Refuse rather than silently drop an extractionFn we don't evaluate."""
    if d.get("extractionFn") is not None:
        raise ValueError(
            f"extractionFn on {kind!r} filter is not supported "
            "(supported on 'selector', 'in', and 'bound'); rewrite via a "
            "virtual column or an extraction filter")


@register("filter", "selector")
@dataclass(frozen=True)
class SelectorFilter(FilterSpec):
    dimension: str
    value: str | int | float | None
    extraction_fn: object | None = None

    def columns(self):
        return {self.dimension}

    def to_json(self):
        d = {"type": "selector", "dimension": self.dimension, "value": self.value}
        if self.extraction_fn is not None:
            d["extractionFn"] = self.extraction_fn.to_json()
        return d

    @staticmethod
    def from_json(d):
        ef = from_json("extractionFn", d.get("extractionFn"))
        return SelectorFilter(d["dimension"], d.get("value"), ef)


@register("filter", "in")
@dataclass(frozen=True)
class InFilter(FilterSpec):
    dimension: str
    values: tuple
    extraction_fn: object = None  # ExtractionFunctionSpec | None

    def columns(self):
        return {self.dimension}

    def to_json(self):
        out = {"type": "in", "dimension": self.dimension,
               "values": list(self.values)}
        if self.extraction_fn is not None:
            out["extractionFn"] = self.extraction_fn.to_json()
        return out

    @staticmethod
    def from_json(d):
        ef = from_json("extractionFn", d.get("extractionFn"))
        return InFilter(d["dimension"], tuple(d["values"]), ef)


@register("filter", "bound")
@dataclass(frozen=True)
class BoundFilter(FilterSpec):
    dimension: str
    lower: str | int | float | None = None
    upper: str | int | float | None = None
    lower_strict: bool = False
    upper_strict: bool = False
    ordering: str = "lexicographic"  # or "numeric"
    extraction_fn: object = None     # ExtractionFunctionSpec | None

    def columns(self):
        return {self.dimension}

    def to_json(self):
        d = {"type": "bound", "dimension": self.dimension,
             "ordering": self.ordering}
        if self.lower is not None:
            d["lower"] = self.lower
            d["lowerStrict"] = self.lower_strict
        if self.upper is not None:
            d["upper"] = self.upper
            d["upperStrict"] = self.upper_strict
        if self.extraction_fn is not None:
            d["extractionFn"] = self.extraction_fn.to_json()
        return d

    @staticmethod
    def from_json(d):
        ef = from_json("extractionFn", d.get("extractionFn"))
        return BoundFilter(d["dimension"], d.get("lower"), d.get("upper"),
                           bool(d.get("lowerStrict", False)),
                           bool(d.get("upperStrict", False)),
                           d.get("ordering", "lexicographic"), ef)


@register("filter", "regex")
@dataclass(frozen=True)
class RegexFilter(FilterSpec):
    dimension: str
    pattern: str

    def columns(self):
        return {self.dimension}

    def to_json(self):
        return {"type": "regex", "dimension": self.dimension, "pattern": self.pattern}

    @staticmethod
    def from_json(d):
        _reject_extraction_fn(d, "regex")
        return RegexFilter(d["dimension"], d["pattern"])


@register("filter", "like")
@dataclass(frozen=True)
class LikeFilter(FilterSpec):
    dimension: str
    pattern: str  # SQL LIKE: % and _

    def columns(self):
        return {self.dimension}

    def to_json(self):
        return {"type": "like", "dimension": self.dimension, "pattern": self.pattern}

    @staticmethod
    def from_json(d):
        _reject_extraction_fn(d, "like")
        return LikeFilter(d["dimension"], d["pattern"])


@register("filter", "and")
@dataclass(frozen=True)
class AndFilter(FilterSpec):
    fields: tuple = field(default_factory=tuple)

    def columns(self):
        out = set()
        for f in self.fields:
            out |= f.columns()
        return out

    def to_json(self):
        return {"type": "and", "fields": [f.to_json() for f in self.fields]}

    @staticmethod
    def from_json(d):
        return AndFilter(tuple(from_json("filter", f) for f in d["fields"]))


@register("filter", "or")
@dataclass(frozen=True)
class OrFilter(FilterSpec):
    fields: tuple = field(default_factory=tuple)

    def columns(self):
        out = set()
        for f in self.fields:
            out |= f.columns()
        return out

    def to_json(self):
        return {"type": "or", "fields": [f.to_json() for f in self.fields]}

    @staticmethod
    def from_json(d):
        return OrFilter(tuple(from_json("filter", f) for f in d["fields"]))


@register("filter", "not")
@dataclass(frozen=True)
class NotFilter(FilterSpec):
    field: FilterSpec

    def columns(self):
        return self.field.columns()

    def to_json(self):
        return {"type": "not", "field": self.field.to_json()}

    @staticmethod
    def from_json(d):
        return NotFilter(from_json("filter", d["field"]))


@register("filter", "columnComparison")
@dataclass(frozen=True)
class ColumnComparisonFilter(FilterSpec):
    """Row-vs-row equality across two (or more, chained pairwise) columns
    — the reference family's columnComparison filter (SURVEY.md §3.3),
    the shape TPC-H Q5/Q7 need (`c_nation = s_nation` on the denormalized
    fact). Divergence from Druid, by design: a NULL operand never matches
    (engine-wide boolean rule, see kernels.filtereval module docstring;
    Druid treats two missing values as equal). SQL `a <> b` composes as
    NotFilter(ColumnComparisonFilter), under which NULL rows match — the
    same inversion semantics every other NOT shape has here.

    String/string pairs compare via a cross-dictionary code translation
    map built host-side and hoisted to a device-resident derived stream
    (executor/dataset.py::derived), so the device cost is one elementwise
    int32 compare, not a per-dispatch gather.

    `op` "<" or "<=" (an extension: Druid's filter is equality only) is
    the ORDERED comparison of exactly two columns, `a op b`, the shape of
    TPC-H Q12's `l_commitdate < l_receiptdate`: two string columns compare
    by their values' ranks in the merged dictionary, carried by the same
    derived streams; the time column against a string column compares
    epoch millis with the dictionary's values read as ISO dates (a value
    that is no date matches nothing), as a literal beside the time column
    is read. `>`/`>=` are the same filter with the columns swapped."""
    dimensions: tuple  # >= 2 column names
    op: str = "=="     # "==" | "<" | "<="

    def __post_init__(self):
        if self.op not in ("==", "<", "<="):
            raise ValueError(f"columnComparison op {self.op!r}")
        if self.op != "==" and len(self.dimensions) != 2:
            raise ValueError(
                "an ordered columnComparison takes exactly 2 dimensions")

    def columns(self):
        return set(self.dimensions)

    def to_json(self):
        out = {"type": "columnComparison",
               "dimensions": list(self.dimensions)}
        if self.op != "==":
            out["op"] = self.op
        return out

    @staticmethod
    def from_json(d):
        dims = tuple(d["dimensions"])
        if len(dims) < 2:
            raise ValueError("columnComparison needs >= 2 dimensions")
        return ColumnComparisonFilter(dims, d.get("op", "=="))


@register("filter", "expression")
@dataclass(frozen=True)
class ExpressionFilter(FilterSpec):
    expression: Expr

    def columns(self):
        return self.expression.columns()

    def to_json(self):
        return {"type": "expression", "expression": self.expression.to_json()}

    @staticmethod
    def from_json(d):
        return ExpressionFilter(from_json("expr", d["expression"]))


def filter_from_json(d):
    return from_json("filter", d)


def and_of(*specs) -> FilterSpec | None:
    specs = [s for s in specs if s is not None]
    if not specs:
        return None
    if len(specs) == 1:
        return specs[0]
    flat = []
    for s in specs:
        if isinstance(s, AndFilter):
            flat.extend(s.fields)
        else:
            flat.append(s)
    return AndFilter(tuple(flat))
