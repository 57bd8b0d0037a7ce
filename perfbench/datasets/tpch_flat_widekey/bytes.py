"""Bytes each template has to read: the rooflines' numerator.

The rule of `datasets/tpch_flat/bytes.py`: the columns the template
references (filters, group keys, the summed expressions) at the narrowest
power-of-two integer width that holds the column's published domain at
SF10, once over the rows scanned. No template filters on the time column
(`l_shipdate`; `q10p`'s window is on `o_orderdate`, which prunes nothing),
so every row is read. It is a lower bound on purpose and the same work
whatever implements it: a key of two words, the sort's passes, the prefix
sums, the [cap] tables and the HAVING's cut count against the share, never
into the numerator.
"""

from __future__ import annotations

# narrowest width in bytes of each column's domain at SF10
WIDTH = {"o_custkey": 4, "l_orderkey": 4, "o_orderdate": 2, "c_name": 4,
         "o_totalprice": 4, "l_quantity": 1, "c_acctbal": 4, "c_nation": 1,
         "l_returnflag": 1, "l_extendedprice": 4, "l_discount": 1}
# template -> columns read
SCAN = {
    "q18p": ("c_name", "o_custkey", "l_orderkey", "o_orderdate",
             "o_totalprice", "l_quantity"),
    "q10p": ("o_custkey", "c_name", "c_acctbal", "c_nation", "l_returnflag",
             "o_orderdate", "l_extendedprice", "l_discount"),
}


def needed_rows(template: str, total: dict) -> int:
    """Rows the template has to read: all of them."""
    if template not in SCAN:
        raise KeyError(template)
    return total["rows"]


def needed_bytes(template: str, total: dict,
                 rows_scanned: int | None = None) -> int:
    """Least bytes the template reads. `rows_scanned`, where the program's
    record gives it, caps the rows: rows the program pruned are never
    counted as read."""
    rows = needed_rows(template, total)
    if rows_scanned is not None:
        rows = min(rows, rows_scanned)
    return rows * sum(WIDTH[c] for c in SCAN[template])
