"""Bytes each Q18 template has to read: the rooflines' numerator.

The rule of `datasets/tpch_flat/bytes.py`: the columns the template
references (the three group keys, the summed `l_quantity`, `o_totalprice`)
at the narrowest power-of-two integer width that holds the column's
published domain at SF10, once over the rows scanned. No template filters
on the time column, so every row is read. It is a lower bound on purpose
and the same work whatever implements it: the sort's passes, the prefix
sums, the [cap] tables and the HAVING's cut count against the share, never
into the numerator.
"""

from __future__ import annotations

# narrowest width in bytes of each column's domain at SF10
WIDTH = {"o_custkey": 4, "l_orderkey": 4, "o_orderdate": 2,
         "l_quantity": 1, "o_totalprice": 4}
TEMPLATES = ("q18", "q18_312", "q18_315")


def needed_rows(template: str, total: dict) -> int:
    """Rows the template has to read: all of them."""
    if template not in TEMPLATES:
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
    return rows * sum(WIDTH.values())
