"""The plain reference that decides `correct` for the 13 SSB queries.

Each query is a numpy mask, a combined group key and an int64 sum over the
columns the generator has in memory, computed per chunk in the generator's
workers (`chunk_partials`), merged exactly as Python integers (`merge`) and
turned into the answer's rows in ORDER BY order (`answers`). It imports
nothing of the program and takes nothing the program has made. Sums at
75,000,000 rows pass 2^24 and 2^31, so an f32 or int32 accumulation does not
equal it.
"""

from __future__ import annotations

import numpy as np

from . import datagen as g


def _code(vocab: list, name: str) -> int:
    return vocab.index(name)


def _in(col, values):
    m = col == values[0]
    for v in values[1:]:
        m |= col == v
    return m


_UK = [_code(g.CITIES, "UNITED KI1"), _code(g.CITIES, "UNITED KI5")]


def _YEARS_92_97(c):
    return (c["d_year"] >= 1992) & (c["d_year"] <= 1997)


def _Y97_98(c):
    return _in(c["d_year"], [1997, 1998])


def _REVENUE(c):
    return c["lo_revenue"]


def _PROFIT(c):
    return c["lo_revenue"] - c["lo_supplycost"]


def _DISCOUNTED(c):
    return c["lo_extendedprice"] * c["lo_discount"]



def _flight1(where):
    return {"where": where, "keys": (), "value": _DISCOUNTED,
            "columns": ("revenue",), "agg": "revenue", "order": ()}


def _grouped(where, keys, value, columns, agg, order):
    return {"where": where, "keys": keys, "value": value,
            "columns": columns, "agg": agg, "order": order}


_BY_YEAR_BRAND = (("d_year", "asc"), ("p_brand1", "asc"))
_BY_YEAR_REV = (("d_year", "asc"), ("revenue", "desc"))

# where: mask over the chunk; keys: group-by columns; columns: the answer's
# columns in SELECT order; agg: the name of the sum; order: ORDER BY
SPECS = {
    "q1.1": _flight1(lambda c: (c["d_year"] == 1993)
                     & (c["lo_discount"] >= 1) & (c["lo_discount"] <= 3)
                     & (c["lo_quantity"] < 25)),
    "q1.2": _flight1(lambda c: (c["d_yearmonthnum"] == 199401)
                     & (c["lo_discount"] >= 4) & (c["lo_discount"] <= 6)
                     & (c["lo_quantity"] >= 26) & (c["lo_quantity"] <= 35)),
    "q1.3": _flight1(lambda c: (c["d_weeknuminyear"] == 6)
                     & (c["d_year"] == 1994)
                     & (c["lo_discount"] >= 5) & (c["lo_discount"] <= 7)
                     & (c["lo_quantity"] >= 26) & (c["lo_quantity"] <= 35)),
    "q2.1": _grouped(
        lambda c: (c["p_category"] == _code(g.CATEGORIES, "MFGR#12"))
        & (c["s_region"] == _code(g.REGIONS, "AMERICA")),
        ("d_year", "p_brand1"), _REVENUE,
        ("revenue", "d_year", "p_brand1"), "revenue", _BY_YEAR_BRAND),
    "q2.2": _grouped(
        # BRANDS is in sorted string order: a string range is a code range
        lambda c: (c["p_brand1"] >= _code(g.BRANDS, "MFGR#2221"))
        & (c["p_brand1"] <= _code(g.BRANDS, "MFGR#2228"))
        & (c["s_region"] == _code(g.REGIONS, "ASIA")),
        ("d_year", "p_brand1"), _REVENUE,
        ("revenue", "d_year", "p_brand1"), "revenue", _BY_YEAR_BRAND),
    "q2.3": _grouped(
        lambda c: (c["p_brand1"] == _code(g.BRANDS, "MFGR#2239"))
        & (c["s_region"] == _code(g.REGIONS, "EUROPE")),
        ("d_year", "p_brand1"), _REVENUE,
        ("revenue", "d_year", "p_brand1"), "revenue", _BY_YEAR_BRAND),
    "q3.1": _grouped(
        lambda c: (c["c_region"] == _code(g.REGIONS, "ASIA"))
        & (c["s_region"] == _code(g.REGIONS, "ASIA")) & _YEARS_92_97(c),
        ("c_nation", "s_nation", "d_year"), _REVENUE,
        ("c_nation", "s_nation", "d_year", "revenue"), "revenue",
        _BY_YEAR_REV),
    "q3.2": _grouped(
        lambda c: (c["c_nation"] == _code(g.NATIONS, "UNITED STATES"))
        & (c["s_nation"] == _code(g.NATIONS, "UNITED STATES"))
        & _YEARS_92_97(c),
        ("c_city", "s_city", "d_year"), _REVENUE,
        ("c_city", "s_city", "d_year", "revenue"), "revenue", _BY_YEAR_REV),
    "q3.3": _grouped(
        lambda c: _in(c["c_city"], _UK) & _in(c["s_city"], _UK)
        & _YEARS_92_97(c),
        ("c_city", "s_city", "d_year"), _REVENUE,
        ("c_city", "s_city", "d_year", "revenue"), "revenue", _BY_YEAR_REV),
    "q3.4": _grouped(
        lambda c: _in(c["c_city"], _UK) & _in(c["s_city"], _UK)
        & (c["d_yearmonth"] == _code(g.YEARMONTHS, "Dec1997")),
        ("c_city", "s_city", "d_year"), _REVENUE,
        ("c_city", "s_city", "d_year", "revenue"), "revenue", _BY_YEAR_REV),
    "q4.1": _grouped(
        lambda c: (c["c_region"] == _code(g.REGIONS, "AMERICA"))
        & (c["s_region"] == _code(g.REGIONS, "AMERICA"))
        & _in(c["p_mfgr"], [_code(g.MFGRS, "MFGR#1"),
                            _code(g.MFGRS, "MFGR#2")]),
        ("d_year", "c_nation"), _PROFIT,
        ("d_year", "c_nation", "profit"), "profit",
        (("d_year", "asc"), ("c_nation", "asc"))),
    "q4.2": _grouped(
        lambda c: (c["c_region"] == _code(g.REGIONS, "AMERICA"))
        & (c["s_region"] == _code(g.REGIONS, "AMERICA")) & _Y97_98(c)
        & _in(c["p_mfgr"], [_code(g.MFGRS, "MFGR#1"),
                            _code(g.MFGRS, "MFGR#2")]),
        ("d_year", "s_nation", "p_category"), _PROFIT,
        ("d_year", "s_nation", "p_category", "profit"), "profit",
        (("d_year", "asc"), ("s_nation", "asc"), ("p_category", "asc"))),
    "q4.3": _grouped(
        lambda c: (c["c_region"] == _code(g.REGIONS, "AMERICA"))
        & (c["s_nation"] == _code(g.NATIONS, "UNITED STATES")) & _Y97_98(c)
        & (c["p_category"] == _code(g.CATEGORIES, "MFGR#14")),
        ("d_year", "s_city", "p_brand1"), _PROFIT,
        ("d_year", "s_city", "p_brand1", "profit"), "profit",
        (("d_year", "asc"), ("s_city", "asc"), ("p_brand1", "asc"))),
}

_KEY_BASE = 1 << 12  # every group-by column's values are below this


def group_sums(mask, keys: list, value) -> dict:
    """{key tuple: [int64 sum, rows]} over the rows the mask keeps."""
    idx = np.flatnonzero(mask)
    if not len(idx):
        return {}
    v = np.asarray(value)[idx].astype(np.int64)
    if not keys:
        return {(): [int(v.sum(dtype=np.int64)), len(idx)]}
    combined = np.zeros(len(idx), dtype=np.int64)
    for k in keys:
        combined = combined * _KEY_BASE + np.asarray(k)[idx].astype(np.int64)
    uniq, inv = np.unique(combined, return_inverse=True)
    sums = np.zeros(len(uniq), dtype=np.int64)
    np.add.at(sums, inv, v)
    counts = np.bincount(inv, minlength=len(uniq))
    out = {}
    for u, s, n in zip(uniq.tolist(), sums.tolist(), counts.tolist()):
        key = []
        for _ in keys:
            key.append(u % _KEY_BASE)
            u //= _KEY_BASE
        out[tuple(reversed(key))] = [s, n]
    return out


def chunk_partials(cols: dict) -> dict:
    """One chunk's part of every answer, of the totals and of the rows per
    calendar month (what a time filter leaves to be read: bytes.py)."""
    out = {"templates": {}}
    for name, spec in SPECS.items():
        out["templates"][name] = group_sums(
            spec["where"](cols), [cols[k] for k in spec["keys"]],
            spec["value"](cols))
    out["rows"] = len(cols["lo_revenue"])
    out["sum_lo_revenue"] = int(cols["lo_revenue"].sum(dtype=np.int64))
    out["rows_by_yearmonth"] = np.bincount(
        cols["d_yearmonth"], minlength=len(g.YEARMONTHS)).tolist()
    return out


def merge(partials: list) -> dict:
    total = {"templates": {name: {} for name in SPECS}, "rows": 0,
             "sum_lo_revenue": 0,
             "rows_by_yearmonth": [0] * len(g.YEARMONTHS)}
    for p in partials:
        for name, groups in p["templates"].items():
            acc = total["templates"][name]
            for key, (s, n) in groups.items():
                cur = acc.setdefault(key, [0, 0])
                cur[0] += s
                cur[1] += n
        total["rows"] += p["rows"]
        total["sum_lo_revenue"] += p["sum_lo_revenue"]
        total["rows_by_yearmonth"] = [
            a + b for a, b in zip(total["rows_by_yearmonth"],
                                  p["rows_by_yearmonth"])]
    return total


def _decode(column: str, code: int):
    return g.VOCAB[column][code] if column in g.VOCAB else code


def _sort_rows(rows: list, order) -> list:
    # stable sorts from the last key to the first
    for col, direction in reversed(order):
        rows.sort(key=lambda r: r[col], reverse=direction == "desc")
    return rows


def answers(total: dict) -> dict:
    """{template: {"columns", "rows" (dicts, in ORDER BY order), "order"}}."""
    out = {}
    for name, spec in SPECS.items():
        groups = total["templates"][name]
        if not spec["keys"]:
            # SQL: a sum over no rows is NULL
            s = groups[()][0] if () in groups else None
            rows = [{spec["agg"]: s}]
        else:
            rows = []
            for key, (s, _n) in groups.items():
                row = {k: _decode(k, c) for k, c in zip(spec["keys"], key)}
                row[spec["agg"]] = s
                rows.append(row)
            rows = _sort_rows(rows, spec["order"])
        out[name] = {"columns": list(spec["columns"]), "rows": rows,
                     "order": [list(o) for o in spec["order"]]}
    return out
