"""The plain reference that decides `correct` for the three Q18 templates.

TPC-H Q18 (clause 2.4.18) on the flat table is one group-by of one table:
`sum(l_quantity)` of every order, the orders whose sum passes the
template's QUANTITY, the 100 largest of them by `o_totalprice`. This
reference walks the generator's order skeleton, not sorted keys: the
generator writes an order's lineitems one after the other and never splits
an order between chunks, so an order is a stretch of consecutive rows with
one `l_orderkey`, and its sum is `np.add.reduceat` of `l_quantity` over the
stretches' first rows. Nothing is sorted until the few hundred orders that
pass are ranked, no key is packed, no prefix sum is read at a boundary: the
program's sort-and-boundary idea is not this one under another spelling.
It imports nothing of the program and nothing of JAX, and takes nothing the
program has made.

Per chunk (`chunk_partials`, in the generator's workers): the orders whose
sum passes the smallest QUANTITY of the templates, with their customer key,
order date, total price and sum. `merge` concatenates the chunks' orders;
`answers` keeps, a template, those past its own QUANTITY in ORDER BY order
(`o_totalprice` descending, `o_orderdate`, `o_orderkey`), cut at the
LIMIT. Every value is an integer or a date string: the comparison is by
equality.
"""

from __future__ import annotations

import numpy as np

from perfbench.datasets.tpch_flat import datagen as g

# template -> QUANTITY: the specification's validation value, and the two
# ends of the range [312..315] it draws from in a run (clause 2.4.18.3)
QUANTITY = {"q18": 300, "q18_312": 312, "q18_315": 315}
LIMIT = 100
COLUMNS = ("c_custkey", "o_orderkey", "o_orderdate", "o_totalprice",
           "sum_quantity")
ORDER = (("o_totalprice", "desc"), ("o_orderdate", "asc"),
         ("o_orderkey", "asc"))
_FIELDS = ("o_custkey", "l_orderkey", "o_orderdate", "o_totalprice")


def chunk_partials(cols: dict) -> dict:
    """One chunk's part: its large orders, its order and row counts and its
    part of the totals the harness prints."""
    key = cols["l_orderkey"]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    qty = np.add.reduceat(cols["l_quantity"].astype(np.int64), first)
    keep = first[qty > min(QUANTITY.values())]
    orders = {c: np.asarray(cols[c][keep]).astype(np.int64) for c in _FIELDS}
    orders["sum_quantity"] = qty[qty > min(QUANTITY.values())]
    return {"orders": orders, "n_orders": len(first),
            "rows": len(key),
            "sum_l_extendedprice": int(
                cols["l_extendedprice"].sum(dtype=np.int64))}


def merge(partials: list) -> dict:
    """{"orders": {column: int64 array} of every order past the smallest
    QUANTITY, "n_orders" (the group-by's present groups), "rows",
    "sum_l_extendedprice", "groups": {template: orders past its QUANTITY}}."""
    orders = {c: np.concatenate([p["orders"][c] for p in partials])
              for c in _FIELDS + ("sum_quantity",)}
    return {"orders": orders,
            "n_orders": sum(p["n_orders"] for p in partials),
            "rows": sum(p["rows"] for p in partials),
            "sum_l_extendedprice": sum(p["sum_l_extendedprice"]
                                       for p in partials),
            "groups": {t: int((orders["sum_quantity"] > q).sum())
                       for t, q in QUANTITY.items()}}


def ranked(total: dict, name: str, n: int | None = LIMIT) -> list:
    """The template's orders as answer rows in ORDER BY order, the first n
    of them (all when n is None)."""
    o = total["orders"]
    idx = np.flatnonzero(o["sum_quantity"] > QUANTITY[name])
    idx = idx[np.lexsort((o["l_orderkey"][idx], o["o_orderdate"][idx],
                          -o["o_totalprice"][idx]))][:n]
    return [{"c_custkey": int(o["o_custkey"][i]),
             "o_orderkey": int(o["l_orderkey"][i]),
             "o_orderdate": g.DAY_STRINGS[int(o["o_orderdate"][i])],
             "o_totalprice": int(o["o_totalprice"][i]),
             "sum_quantity": int(o["sum_quantity"][i])}
            for i in idx.tolist()]


def answers(total: dict) -> dict:
    """{template: {"columns", "rows" (dicts, in ORDER BY order), "order"}}."""
    return {name: {"columns": list(COLUMNS), "rows": ranked(total, name),
                   "order": [list(o) for o in ORDER]}
            for name in QUANTITY}
