"""The plain reference that decides `correct` for the ten TPC-H templates.

Each template is a numpy mask, group keys and int64 sums over the columns
the generator has in memory, computed per chunk in the generator's workers
(`chunk_partials`), merged exactly (`merge`) and turned into the answer's
rows in ORDER BY order, cut at the LIMIT (`answers`). It imports nothing of
the program and takes nothing the program has made.

The templates are the specification's queries (clause 2.4) with its
validation parameters, on the flat table. Where an answer departs from the
published text, `queries.json`'s neighbour `README` note and the
configuration's `assumed` say so: no averages or ratios (the comparison is
by equality and refuses a float), so Q1 has no `avg` columns and Q8 and Q14
return numerator and denominator; money products come back as integers in
1e-4 (cents x percent) and 1e-6 units; `l_orderkey` and `c_custkey` close
the ORDER BY of Q3 and Q10 so that the LIMIT is determinate. Sums pass 2^31
(Q1's `sum_charge` is ~1e18 a group at SF10), so an int32 accumulation does
not equal it.
"""

from __future__ import annotations

import numpy as np

from . import datagen as g

T = g.TIME_COL
D = g.day_of


def _code(vocab: list, name: str) -> int:
    return vocab.index(name)


def _in(col, values):
    m = col == values[0]
    for v in values[1:]:
        m |= col == v
    return m


def _between(col, lo: str, hi: str):
    """lo <= date < hi, on day indices."""
    return (col >= D(lo)) & (col < D(hi))


def _volume(c):
    return c["l_extendedprice"] * (100 - c["l_discount"])


def _charge(c):
    return _volume(c) * (100 + c["l_tax"])


def _when(cond, value):
    """sum(CASE WHEN cond THEN value ELSE 0 END)"""
    return lambda c: np.where(cond(c), value(c), 0)


def _one(c):
    return np.ones(len(c["l_quantity"]), np.int64)  # count(*)


_FRANCE, _GERMANY = _code(g.NATIONS, "FRANCE"), _code(g.NATIONS, "GERMANY")
_PROMO = [i for i, t in enumerate(g.TYPES) if t.startswith("PROMO")]
_URGENT = [_code(g.PRIORITIES, "1-URGENT"), _code(g.PRIORITIES, "2-HIGH")]


def _q19_branch(c, brand, containers, q_lo, q_hi, size_hi):
    return ((c["p_brand"] == _code(g.BRANDS, brand))
            & _in(c["p_container"],
                  [_code(g.CONTAINERS, x) for x in containers])
            & (c["l_quantity"] >= q_lo) & (c["l_quantity"] <= q_hi)
            & (c["p_size"] >= 1) & (c["p_size"] <= size_hi)
            # 'AIR REG' is the published text; no row carries it
            & (c["l_shipmode"] == _code(g.SHIPMODES, "AIR"))
            & (c["l_shipinstruct"]
               == _code(g.INSTRUCTIONS, "DELIVER IN PERSON")))


# keys: (answer column, source column or fn(c) -> int array); aggs: (answer
# column, fn(c) -> int array, summed); columns: the answer's SELECT order;
# order: ORDER BY; own_chunk: a group's rows all lie in one chunk, so a
# chunk may cut its part to the first rows of the ORDER BY
SPECS = {
    "q1": {
        "where": lambda c: c[T] <= D("1998-09-02"),
        "keys": (("l_returnflag", "l_returnflag"),
                 ("l_linestatus", "l_linestatus")),
        "aggs": (("sum_qty", lambda c: c["l_quantity"]),
                 ("sum_base_price", lambda c: c["l_extendedprice"]),
                 ("sum_disc_price", _volume), ("sum_charge", _charge),
                 ("count_order", _one)),
        "columns": ("l_returnflag", "l_linestatus", "sum_qty",
                    "sum_base_price", "sum_disc_price", "sum_charge",
                    "count_order"),
        "order": (("l_returnflag", "asc"), ("l_linestatus", "asc")),
    },
    "q3": {
        "where": lambda c: (
            (c["c_mktsegment"] == _code(g.SEGMENTS, "BUILDING"))
            & (c["o_orderdate"] < D("1995-03-15"))
            & (c[T] > D("1995-03-15"))),
        "keys": (("l_orderkey", "l_orderkey"),
                 ("o_orderdate", "o_orderdate"),
                 ("o_shippriority", "o_shippriority")),
        "aggs": (("revenue", _volume),),
        "columns": ("l_orderkey", "revenue", "o_orderdate",
                    "o_shippriority"),
        "order": (("revenue", "desc"), ("o_orderdate", "asc"),
                  ("l_orderkey", "asc")),
        "limit": 10, "own_chunk": True,
    },
    "q5": {
        "where": lambda c: (
            (c["c_nation"] == c["s_nation"])
            & (c["c_region"] == _code(g.REGIONS, "ASIA"))
            & _between(c["o_orderdate"], "1994-01-01", "1995-01-01")),
        "keys": (("s_nation", "s_nation"),),
        "aggs": (("revenue", _volume),),
        "columns": ("s_nation", "revenue"),
        "order": (("revenue", "desc"),),
    },
    "q6": {
        "where": lambda c: (
            _between(c[T], "1994-01-01", "1995-01-01")
            & (c["l_discount"] >= 5) & (c["l_discount"] <= 7)
            & (c["l_quantity"] < 24)),
        "keys": (),
        "aggs": (("revenue",
                  lambda c: c["l_extendedprice"] * c["l_discount"]),),
        "columns": ("revenue",), "order": (),
    },
    "q7": {
        "where": lambda c: (
            (((c["s_nation"] == _FRANCE) & (c["c_nation"] == _GERMANY))
             | ((c["s_nation"] == _GERMANY) & (c["c_nation"] == _FRANCE)))
            & _between(c[T], "1995-01-01", "1997-01-01")),
        "keys": (("supp_nation", "s_nation"), ("cust_nation", "c_nation"),
                 ("l_year", lambda c: g.DAY_YEAR[c[T]])),
        "aggs": (("revenue", _volume),),
        "columns": ("supp_nation", "cust_nation", "l_year", "revenue"),
        "order": (("supp_nation", "asc"), ("cust_nation", "asc"),
                  ("l_year", "asc")),
    },
    "q8": {
        "where": lambda c: (
            (c["c_region"] == _code(g.REGIONS, "AMERICA"))
            & _between(c["o_orderdate"], "1995-01-01", "1997-01-01")
            & (c["p_type"] == _code(g.TYPES, "ECONOMY ANODIZED STEEL"))),
        # o_year as the datasource has it: the first four characters
        "keys": (("o_year", lambda c: g.DAY_YEAR[c["o_orderdate"]]),),
        "aggs": (("brazil_volume",
                  _when(lambda c: c["s_nation"] == _code(g.NATIONS,
                                                         "BRAZIL"),
                        _volume)),
                 ("total_volume", _volume)),
        "columns": ("o_year", "brazil_volume", "total_volume"),
        "order": (("o_year", "asc"),),
        "as_string": ("o_year",),
    },
    "q10": {
        "where": lambda c: (
            (c["l_returnflag"] == _code(g.RETURNFLAGS, "R"))
            & _between(c["o_orderdate"], "1993-10-01", "1994-01-01")),
        "keys": (("c_custkey", "o_custkey"), ("c_name", "c_name"),
                 ("c_nation", "c_nation")),
        "aggs": (("revenue", _volume),),
        "columns": ("c_custkey", "c_name", "revenue", "c_nation"),
        "order": (("revenue", "desc"), ("c_custkey", "asc")),
        "limit": 20,
    },
    "q12": {
        "where": lambda c: (
            _in(c["l_shipmode"], [_code(g.SHIPMODES, "MAIL"),
                                  _code(g.SHIPMODES, "SHIP")])
            & (c["l_commitdate"] < c["l_receiptdate"])
            & (c[T] < c["l_commitdate"])
            & _between(c["l_receiptdate"], "1994-01-01", "1995-01-01")),
        "keys": (("l_shipmode", "l_shipmode"),),
        "aggs": (("high_line_count",
                  _when(lambda c: _in(c["o_orderpriority"], _URGENT),
                        _one)),
                 ("low_line_count",
                  _when(lambda c: ~_in(c["o_orderpriority"], _URGENT),
                        _one))),
        "columns": ("l_shipmode", "high_line_count", "low_line_count"),
        "order": (("l_shipmode", "asc"),),
    },
    "q14": {
        "where": lambda c: _between(c[T], "1995-09-01", "1995-10-01"),
        "keys": (),
        "aggs": (("promo_revenue",
                  _when(lambda c: _in(c["p_type"], _PROMO), _volume)),
                 ("total_revenue", _volume)),
        "columns": ("promo_revenue", "total_revenue"), "order": (),
    },
    "q19": {
        "where": lambda c: (
            _q19_branch(c, "Brand#12",
                        ("SM CASE", "SM BOX", "SM PACK", "SM PKG"),
                        1, 11, 5)
            | _q19_branch(c, "Brand#23",
                          ("MED BAG", "MED BOX", "MED PKG", "MED PACK"),
                          10, 20, 10)
            | _q19_branch(c, "Brand#34",
                          ("LG CASE", "LG BOX", "LG PACK", "LG PKG"),
                          20, 30, 15)),
        "keys": (),
        "aggs": (("revenue", _volume),),
        "columns": ("revenue",), "order": (),
    },
}
KEEP_BEYOND_LIMIT = 8  # rows a chunk keeps past the LIMIT: the tie test's


class _Take(dict):
    """The chunk's columns at the rows a template keeps, gathered when a
    column is first asked for."""

    def __init__(self, cols: dict, idx: np.ndarray):
        super().__init__()
        self.cols, self.idx = cols, idx

    def __missing__(self, name):
        v = self[name] = self.cols[name][self.idx]
        return v


def _source(spec, column):
    """The stored column a key of the answer decodes by, or None."""
    for name, src in spec["keys"]:
        if name == column:
            return src if isinstance(src, str) else None
    return None


def _key_arrays(spec, c):
    return [np.asarray(c[src] if isinstance(src, str) else src(c))
            .astype(np.int64) for _name, src in spec["keys"]]


def _group(keys: np.ndarray, vals: np.ndarray):
    """Sum the rows of `vals` [n, a] over equal rows of `keys` [n, k]."""
    if keys.shape[1] == 0:
        return keys[:1], vals.sum(axis=0, dtype=np.int64)[None, :]
    bases = keys.max(axis=0) + 1
    packed = np.zeros(len(keys), np.int64)
    for j in range(keys.shape[1]):
        packed = packed * int(bases[j]) + keys[:, j]
    uniq, first, inv = np.unique(packed, return_index=True,
                                 return_inverse=True)
    sums = np.zeros((len(uniq), vals.shape[1]), np.int64)
    np.add.at(sums, inv, vals)
    return keys[first], sums


def _order_index(spec, keys: np.ndarray, vals: np.ndarray) -> np.ndarray:
    """Row order by the ORDER BY, from integers that sort as the decoded
    values do (a vocabulary's codes by its strings' rank)."""
    key_names = [n for n, _s in spec["keys"]]
    agg_names = [n for n, _f in spec["aggs"]]
    by = []
    for col, direction in reversed(spec["order"]):
        if col in agg_names:
            v = vals[:, agg_names.index(col)]
        else:
            v = keys[:, key_names.index(col)]
            src = _source(spec, col)
            if src in g.VOCAB:
                rank = np.argsort(np.argsort(np.array(g.VOCAB[src])))
                v = rank[v]
        by.append(-v if direction == "desc" else v)
    return np.lexsort(by) if by else np.arange(len(keys))


def chunk_partials(cols: dict) -> dict:
    """One chunk's part of every answer, of the totals and of the rows per
    calendar month of the ship date (what a time filter leaves to be read:
    bytes.py)."""
    out = {"templates": {}, "groups": {}}
    for name, spec in SPECS.items():
        idx = np.flatnonzero(spec["where"](cols))
        sub = _Take(cols, idx)
        keys = np.stack(_key_arrays(spec, sub), axis=1) \
            if spec["keys"] else np.zeros((len(idx), 0), np.int64)
        vals = np.stack([np.asarray(f(sub)).astype(np.int64)
                         for _n, f in spec["aggs"]], axis=1)
        if len(idx):
            keys, vals = _group(keys, vals)
        out["groups"][name] = len(keys)
        if spec.get("own_chunk") and "limit" in spec:
            keep = _order_index(spec, keys, vals)[
                :spec["limit"] + KEEP_BEYOND_LIMIT]
            keys, vals = keys[keep], vals[keep]
        out["templates"][name] = (keys, vals)
    out["rows"] = len(cols["l_quantity"])
    out["sum_l_extendedprice"] = int(
        cols["l_extendedprice"].sum(dtype=np.int64))
    out["rows_by_shipmonth"] = np.bincount(
        g.DAY_MONTH[cols[T]], minlength=g.N_MONTHS).tolist()
    return out


def merge(partials: list) -> dict:
    """{"templates": {name: {key tuple: [sums]}}, "groups": {name: present
    groups}, "rows", "sum_l_extendedprice", "rows_by_shipmonth"}."""
    total = {"templates": {}, "groups": {}, "rows": 0,
             "sum_l_extendedprice": 0,
             "rows_by_shipmonth": [0] * g.N_MONTHS}
    for name, spec in SPECS.items():
        keys = np.concatenate([p["templates"][name][0] for p in partials])
        vals = np.concatenate([p["templates"][name][1] for p in partials])
        if len(keys):
            keys, vals = _group(keys, vals)
        total["templates"][name] = {
            tuple(k): v for k, v in zip(keys.tolist(), vals.tolist())}
        total["groups"][name] = len(keys) if not spec.get("own_chunk") \
            else sum(p["groups"][name] for p in partials)
    for p in partials:
        total["rows"] += p["rows"]
        total["sum_l_extendedprice"] += p["sum_l_extendedprice"]
        total["rows_by_shipmonth"] = [
            a + b for a, b in zip(total["rows_by_shipmonth"],
                                  p["rows_by_shipmonth"])]
    return total


def ranked(total: dict, name: str, n: int | None = None) -> list:
    """The template's groups as answer rows in ORDER BY order, the first n
    of them (all when n is None), whatever its LIMIT."""
    spec = SPECS[name]
    groups = total["templates"][name]
    if not spec["keys"]:
        # SQL: a sum over no rows is NULL; a CASE sum over them too
        vals = groups[()] if () in groups else [None] * len(spec["aggs"])
        return [dict(zip([a for a, _f in spec["aggs"]], vals))]
    if not groups:
        return []
    keys = np.array(list(groups), np.int64)
    vals = np.array(list(groups.values()), np.int64)
    order = _order_index(spec, keys, vals)[:n]
    rows = []
    for i in order.tolist():
        row = {}
        for j, (col, src) in enumerate(spec["keys"]):
            v = g.decode(src, keys[i, j]) if isinstance(src, str) \
                else int(keys[i, j])
            row[col] = str(v) if col in spec.get("as_string", ()) else v
        for j, (col, _f) in enumerate(spec["aggs"]):
            row[col] = int(vals[i, j])
        rows.append(row)
    return rows


def answers(total: dict) -> dict:
    """{template: {"columns", "rows" (dicts, in ORDER BY order), "order"}}."""
    return {name: {"columns": list(spec["columns"]),
                   "rows": ranked(total, name, spec.get("limit")),
                   "order": [list(o) for o in spec["order"]]}
            for name, spec in SPECS.items()}
