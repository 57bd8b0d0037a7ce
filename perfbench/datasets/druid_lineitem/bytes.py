"""Bytes each of the nine queries has to read: the rooflines' numerator.

The rule of `datasets/ssb/bytes.py` and `datasets/tpch_flat/bytes.py`: the
columns a template references (filter, group key, aggregated columns) at the
narrowest power-of-two integer width that holds the column's domain at the
published scale, times the rows that its filter on the time column
(`l_shipdate`) leaves to a store partitioned by calendar month. The ship
date itself is read only in a month that the interval cuts in two. A lower
bound on purpose, and the same whatever path serves the query: wider
encodings, the sort's passes over its operands, a [K] table written and read
again all count against the share.
"""

from __future__ import annotations

# narrowest width in bytes of each column's domain (l_partkey: 20,000,000
# at the published SF100, 2,000,000 at SF10: four bytes both)
WIDTH = {"l_partkey": 4, "l_quantity": 1, "l_extendedprice": 4,
         "l_discount": 1, "l_tax": 1, "l_shipdate": 2, "l_commitdate": 2,
         "l_shipmode": 1}
_SUMS = ["l_extendedprice", "l_discount", "l_tax", "l_quantity"]
_DETAILS = ["l_partkey", "l_quantity", "l_extendedprice", "l_discount"]


def _months(first: str, last: str) -> list:
    """Month indices (from January 1992) of first..last, 'YYYY-MM'."""
    def index(ym):
        return (int(ym[:4]) - 1992) * 12 + int(ym[5:7]) - 1
    return list(range(index(first), index(last) + 1))


_ALL = _months("1992-01", "1998-12")

# template -> (columns read, months of l_shipdate they are read in)
SCAN = {
    # whole months inside the interval are counted from the store's own
    # row counts; only January 1992, cut at its third day, is read
    "count_star_interval": (["l_shipdate"], _months("1992-01", "1992-01")),
    "sum_price": (["l_extendedprice"], _ALL),
    "sum_all": (_SUMS, _ALL),
    "sum_all_year": (_SUMS, _ALL),
    "sum_all_filter": (_SUMS + ["l_shipmode"], _ALL),
    "top_100_parts": (["l_partkey", "l_quantity"], _ALL),
    "top_100_parts_details": (_DETAILS, _ALL),
    "top_100_parts_filter": (_DETAILS, _months("1996-01", "1998-03")),
    "top_100_commitdate": (["l_commitdate", "l_quantity"], _ALL),
}
TOPN = tuple(t for t in SCAN if t.startswith("top_100_"))


def needed_rows(template: str, total: dict) -> int:
    """Rows the template's time filter leaves, from the reference's count
    of rows per calendar month of the ship date."""
    return sum(total["rows_by_shipmonth"][i] for i in SCAN[template][1])


def needed_bytes(template: str, total: dict,
                 rows_scanned: int | None = None) -> int:
    """Least bytes the template reads. `rows_scanned`, where the program's
    record gives it, caps the rows: rows the program pruned by other means
    are never counted as read."""
    rows = needed_rows(template, total)
    if rows_scanned is not None:
        rows = min(rows, rows_scanned)
    return rows * sum(WIDTH[c] for c in SCAN[template][0])
