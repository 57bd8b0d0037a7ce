"""Bytes each SSB template has to read: the roofline's numerator.

For a template, the columns it references (filters, group keys, the summed
expression) at the narrowest whole-byte integer width that holds the
column's published domain, times the rows that its filter on time leaves: a
store partitioned by calendar month or coarser has to read only those. A
time attribute that is only filtered on, and that whole months decide
(`d_year`, `d_yearmonthnum`, `d_yearmonth`), is not read at all. It is a
lower bound on purpose: whatever wider encodings, padding, masks or second
passes a program reads count against its share, and the share cannot pass
100% because bytes were counted that need not be read. The scan is
memory-bound by construction: a filter, a key and an add per row are a few
integer operations per 5-12 bytes, far under the chip's operations per byte.
"""

from __future__ import annotations

from . import datagen as g

# narrowest width in bytes of each column's domain
WIDTH = {
    "lo_quantity": 1, "lo_discount": 1, "lo_extendedprice": 4,
    "lo_revenue": 4, "lo_supplycost": 4, "d_year": 2,
    "d_weeknuminyear": 1, "p_mfgr": 1, "p_category": 1, "p_brand1": 2,
    "c_city": 1, "c_nation": 1, "c_region": 1, "s_city": 1, "s_nation": 1,
    "s_region": 1,
}

_ALL_MONTHS = list(range(len(g.YEARMONTHS)))


def _months(years) -> list:
    return [i for i in _ALL_MONTHS if 1992 + i // 12 in years]


# template -> (columns read, month indices its time filter keeps)
SCAN = {
    "q1.1": (["lo_extendedprice", "lo_discount", "lo_quantity"],
             _months({1993})),
    "q1.2": (["lo_extendedprice", "lo_discount", "lo_quantity"],
             [g.YEARMONTHS.index("Jan1994")]),
    "q1.3": (["lo_extendedprice", "lo_discount", "lo_quantity",
              "d_weeknuminyear"], _months({1994})),
    "q2.1": (["lo_revenue", "d_year", "p_brand1", "p_category", "s_region"],
             _ALL_MONTHS),
    "q2.2": (["lo_revenue", "d_year", "p_brand1", "s_region"], _ALL_MONTHS),
    "q2.3": (["lo_revenue", "d_year", "p_brand1", "s_region"], _ALL_MONTHS),
    "q3.1": (["lo_revenue", "d_year", "c_nation", "s_nation", "c_region",
              "s_region"], _months(set(range(1992, 1998)))),
    "q3.2": (["lo_revenue", "d_year", "c_city", "s_city", "c_nation",
              "s_nation"], _months(set(range(1992, 1998)))),
    "q3.3": (["lo_revenue", "d_year", "c_city", "s_city"],
             _months(set(range(1992, 1998)))),
    "q3.4": (["lo_revenue", "d_year", "c_city", "s_city"],
             [g.YEARMONTHS.index("Dec1997")]),
    "q4.1": (["lo_revenue", "lo_supplycost", "d_year", "c_nation",
              "c_region", "s_region", "p_mfgr"], _ALL_MONTHS),
    "q4.2": (["lo_revenue", "lo_supplycost", "d_year", "s_nation",
              "p_category", "c_region", "s_region", "p_mfgr"],
             _months({1997, 1998})),
    "q4.3": (["lo_revenue", "lo_supplycost", "d_year", "s_city", "p_brand1",
              "c_region", "s_nation", "p_category"], _months({1997, 1998})),
}


def needed_rows(template: str, total: dict) -> int:
    """Rows the template's time filter leaves, from the reference's count
    of rows per calendar month."""
    return sum(total["rows_by_yearmonth"][i] for i in SCAN[template][1])


def needed_bytes(template: str, total: dict,
                 rows_scanned: int | None = None) -> int:
    """Least bytes the template reads. `rows_scanned`, where the program's
    record gives it, caps the rows: rows the program pruned by other means
    are never counted as read."""
    rows = needed_rows(template, total)
    if rows_scanned is not None:
        rows = min(rows, rows_scanned)
    return rows * sum(WIDTH[c] for c in SCAN[template][0])
