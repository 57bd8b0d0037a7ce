"""Bytes each TPC-H template has to read: the rooflines' numerator.

The rule of `datasets/ssb/bytes.py`: for a template, the columns it
references (filters, group keys, the summed expressions) at the narrowest
power-of-two integer width that holds the column's published domain, times
the rows that its filter on the time column (`l_shipdate`) leaves to a store
partitioned by calendar month. The ship date itself is read only where a
row's own date decides something whole months cannot (Q12 compares it with
the commit date); a filter on another date (`o_orderdate`, `l_receiptdate`)
prunes nothing. It is a lower bound on purpose: wider encodings, padding,
masks, the sort's passes and what a sparse path writes and reads again all
count against the share, and the share cannot pass 100% because bytes were
counted that need not be read.
"""

from __future__ import annotations

# narrowest width in bytes of each column's domain at SF10
WIDTH = {
    "l_orderkey": 4, "l_quantity": 1, "l_extendedprice": 4, "l_discount": 1,
    "l_tax": 1, "l_returnflag": 1, "l_linestatus": 1, "l_shipdate": 2,
    "l_commitdate": 2, "l_receiptdate": 2, "l_shipinstruct": 1,
    "l_shipmode": 1, "o_custkey": 4, "o_orderdate": 2, "o_orderpriority": 1,
    "o_shippriority": 1, "c_name": 4, "c_mktsegment": 1, "c_nation": 1,
    "c_region": 1, "s_nation": 1, "p_brand": 1, "p_type": 1, "p_size": 1,
    "p_container": 1,
}
_PRICE = ["l_extendedprice", "l_discount"]


def _months(first: str, last: str) -> list:
    """Month indices (from January 1992) of first..last, 'YYYY-MM'."""
    def index(ym):
        return (int(ym[:4]) - 1992) * 12 + int(ym[5:7]) - 1
    return list(range(index(first), index(last) + 1))


_ALL = _months("1992-01", "1998-12")

# template -> (columns read, months of l_shipdate its time filter keeps)
SCAN = {
    "q1": (["l_returnflag", "l_linestatus", "l_quantity", "l_tax"] + _PRICE,
           _months("1992-01", "1998-09")),
    "q3": (["c_mktsegment", "o_orderdate", "l_orderkey", "o_shippriority"]
           + _PRICE, _months("1995-03", "1998-12")),
    "q5": (["c_nation", "s_nation", "c_region", "o_orderdate"] + _PRICE,
           _ALL),
    "q6": (["l_quantity"] + _PRICE, _months("1994-01", "1994-12")),
    "q7": (["s_nation", "c_nation"] + _PRICE, _months("1995-01", "1996-12")),
    "q8": (["c_region", "o_orderdate", "p_type", "s_nation"] + _PRICE, _ALL),
    "q10": (["l_returnflag", "o_orderdate", "o_custkey", "c_name",
             "c_nation"] + _PRICE, _ALL),
    "q12": (["l_shipmode", "l_commitdate", "l_receiptdate", "l_shipdate",
             "o_orderpriority"], _ALL),
    "q14": (["p_type"] + _PRICE, _months("1995-09", "1995-09")),
    "q19": (["p_brand", "p_container", "l_quantity", "p_size", "l_shipmode",
             "l_shipinstruct"] + _PRICE, _ALL),
}


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
