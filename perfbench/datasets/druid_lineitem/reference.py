"""The plain reference that decides `correct` for Druid's nine queries.

Numpy over the columns the generator has in memory, a chunk at a time in
the generator's workers (`chunk_partials`), merged exactly (`merge_into`, in
each worker over its chunks and then over the workers: integer sums, minima
and maxima commute, so how the chunks are shared out does not matter) and
turned into the answers' rows (`answers`). It imports
nothing of the program and takes nothing the program has made.

A top-100 is cut by (metric descending, dimension ascending): the rows the
threshold keeps where the metric ties are the first by the dimension's own
order (a part key as a number, a commit date as a date), which is the
engine's stated rule for a TopN and part of what is compared. At 30 rows a
part `sum(l_quantity)` ties at rank 100 for certain.

Everything is an integer: money in cents, discount and tax in percent, so
the comparison is by equality. `sum(l_extendedprice)` over the table passes
2^31 cents thousands of times over; an int32 accumulation does not equal it.
"""

from __future__ import annotations

import numpy as np

from . import datagen as g
from perfbench.datasets.tpch_flat import datagen as _t

T = g.TIME_COL
D = _t.day_of
LIMIT = 100
SUMS = ("l_extendedprice", "l_discount", "l_tax", "l_quantity")
SUM_NAMES = ("sum_price", "sum_discount", "sum_tax", "sum_quantity")
# l_shipmode LIKE '%AIR%': the vocabulary's codes that contain it
AIR = [i for i, m in enumerate(_t.SHIPMODES) if "AIR" in m]
# BETWEEN on the ship date takes both ends (a ship date is a midnight)
STAR_FIRST, STAR_LAST = D("1992-01-03"), D("1998-11-30")
WINDOW_FIRST, WINDOW_LAST = D("1996-01-15"), D("1998-03-15")
DETAILS = ("l_partkey", "sum_quantity", "sum_price", "min_discount",
           "max_discount")
NO_MIN, NO_MAX = np.int8(127), np.int8(-128)


def _four_sums(c, mask=None) -> list:
    return [int((c[col] if mask is None else c[col][mask])
                .sum(dtype=np.int64)) for col in SUMS]


def _by_part(c, n_part: int, mask=None) -> dict:
    """Rows, sum(l_quantity), sum(l_extendedprice), min and max of
    l_discount of every part key 0..n_part (0 is never drawn), over the
    chunk's rows that `mask` keeps. A chunk is under a million rows: its row
    counts fit int16 and its quantities int32."""
    pk, qty, price, disc = (c[k] if mask is None else c[k][mask]
                            for k in ("l_partkey", "l_quantity",
                                      "l_extendedprice", "l_discount"))
    size = n_part + 1
    # bincount sums in float64: exact here, a chunk's sums are far under 2^53
    out = {"rows": np.bincount(pk, minlength=size).astype(np.int16),
           "qty": np.bincount(pk, weights=qty, minlength=size)
           .astype(np.int32),
           "price": np.bincount(pk, weights=price, minlength=size)
           .astype(np.int64),
           "dmin": np.full(size, NO_MIN, np.int8),
           "dmax": np.full(size, NO_MAX, np.int8)}
    np.minimum.at(out["dmin"], pk, disc.astype(np.int8))
    np.maximum.at(out["dmax"], pk, disc.astype(np.int8))
    return out


def chunk_partials(c: dict, n_part: int) -> dict:
    """One chunk's part of every answer, of the totals, and of the rows per
    calendar month of the ship date (what a time filter leaves to be read:
    bytes.py)."""
    ship = c[T]
    year = _t.DAY_YEAR[ship]
    return {
        "rows": len(ship),
        "rows_by_shipmonth": np.bincount(_t.DAY_MONTH[ship],
                                         minlength=_t.N_MONTHS),
        "count_star_interval": int(((ship >= STAR_FIRST)
                                    & (ship <= STAR_LAST)).sum()),
        "sum_all": _four_sums(c),
        "sum_all_filter": _four_sums(c, np.isin(c["l_shipmode"], AIR)),
        "sum_all_year": {int(y): _four_sums(c, year == y)
                         for y in np.unique(year)},
        "parts": _by_part(c, n_part),
        "parts_window": _by_part(c, n_part, (ship >= WINDOW_FIRST)
                                 & (ship <= WINDOW_LAST)),
        "commit_rows": np.bincount(c["l_commitdate"], minlength=_t.N_DAYS),
        "commit_qty": np.bincount(c["l_commitdate"],
                                  weights=c["l_quantity"],
                                  minlength=_t.N_DAYS).astype(np.int64),
    }


def _add_parts(total: dict | None, part: dict) -> dict:
    if total is None:
        return {"rows": part["rows"].astype(np.int32),
                "qty": part["qty"].astype(np.int64),
                "price": part["price"].copy(), "dmin": part["dmin"].copy(),
                "dmax": part["dmax"].copy()}
    total["rows"] += part["rows"]
    total["qty"] += part["qty"]
    total["price"] += part["price"]
    np.minimum(total["dmin"], part["dmin"], out=total["dmin"])
    np.maximum(total["dmax"], part["dmax"], out=total["dmax"])
    return total


def merge_into(total: dict | None, p: dict) -> dict:
    """`total` (None: nothing yet) with one more part in it: a chunk's
    partial, or what a worker has merged of its chunks'."""
    if total is None:
        total = {"rows": 0, "count_star_interval": 0,
                 "rows_by_shipmonth": [0] * _t.N_MONTHS,
                 "sum_all": [0] * 4, "sum_all_filter": [0] * 4,
                 "sum_all_year": {}, "parts": None, "parts_window": None,
                 "commit_rows": np.zeros(_t.N_DAYS, np.int64),
                 "commit_qty": np.zeros(_t.N_DAYS, np.int64)}
    total["rows"] += p["rows"]
    total["count_star_interval"] += p["count_star_interval"]
    total["rows_by_shipmonth"] = [
        a + int(b) for a, b in zip(total["rows_by_shipmonth"],
                                   p["rows_by_shipmonth"])]
    for name in ("sum_all", "sum_all_filter"):
        total[name] = [a + b for a, b in zip(total[name], p[name])]
    for y, sums in p["sum_all_year"].items():
        have = total["sum_all_year"].get(y, [0] * 4)
        total["sum_all_year"][y] = [a + b for a, b in zip(have, sums)]
    for name in ("parts", "parts_window"):
        total[name] = _add_parts(total[name], p[name])
    total["commit_rows"] += p["commit_rows"]
    total["commit_qty"] += p["commit_qty"]
    total["sum_l_extendedprice"] = total["sum_all"][0]
    return total


def top(rows: np.ndarray, metric: np.ndarray, n: int | None = LIMIT,
        ascending: bool = False) -> np.ndarray:
    """Keys (indices with rows) of the first n by (metric descending, key
    ascending); with `ascending`, by (metric ascending, key ascending)."""
    keys = np.flatnonzero(rows)
    m = metric[keys]
    return keys[np.lexsort((keys, m if ascending else -m))][:n]


def ranked_parts(by_part: dict, n: int | None = LIMIT) -> list:
    return [{"l_partkey": int(k), "sum_quantity": int(by_part["qty"][k]),
             "sum_price": int(by_part["price"][k]),
             "min_discount": int(by_part["dmin"][k]),
             "max_discount": int(by_part["dmax"][k])}
            for k in top(by_part["rows"], by_part["qty"], n)]


def ranked_commitdates(total: dict, n: int | None = LIMIT) -> list:
    return [{"l_commitdate": _t.DAY_STRINGS[k],
             "sum_quantity": int(total["commit_qty"][k])}
            for k in top(total["commit_rows"], total["commit_qty"], n)]


def _sums_row(sums: list) -> dict:
    return dict(zip(SUM_NAMES, sums))


def answers(total: dict) -> dict:
    """{template: {"columns", "rows" (dicts, in ORDER BY order), "order"}}."""
    by_qty = [["sum_quantity", "desc"]]
    details = ranked_parts(total["parts"])
    return {
        "count_star_interval": {
            "columns": ["cnt"], "order": [],
            "rows": [{"cnt": total["count_star_interval"]}]},
        "sum_price": {
            "columns": ["sum_price"], "order": [],
            "rows": [{"sum_price": total["sum_all"][0]}]},
        "sum_all": {
            "columns": list(SUM_NAMES), "order": [],
            "rows": [_sums_row(total["sum_all"])]},
        "sum_all_year": {
            "columns": ["l_year", *SUM_NAMES], "order": [],
            "rows": [{"l_year": y, **_sums_row(sums)}
                     for y, sums in sorted(total["sum_all_year"].items())]},
        "sum_all_filter": {
            "columns": list(SUM_NAMES), "order": [],
            "rows": [_sums_row(total["sum_all_filter"])]},
        "top_100_parts": {
            "columns": ["l_partkey", "sum_quantity"], "order": by_qty,
            "rows": [{"l_partkey": r["l_partkey"],
                      "sum_quantity": r["sum_quantity"]} for r in details]},
        "top_100_parts_details": {
            "columns": list(DETAILS), "order": by_qty, "rows": details},
        "top_100_parts_filter": {
            "columns": list(DETAILS), "order": by_qty,
            "rows": ranked_parts(total["parts_window"])},
        "top_100_commitdate": {
            "columns": ["l_commitdate", "sum_quantity"], "order": by_qty,
            "rows": ranked_commitdates(total)},
    }
