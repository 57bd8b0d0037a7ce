"""The plain reference that decides `correct` for `q10p` and `q18p`: TPC-H
Q10 and Q18 with the GROUP BY lists the specification publishes.

Both lists hold columns that one of their number determines: a customer key
fixes the customer's name, balance and nation, an order key fixes the
order's customer, date and total price. So this reference groups by the
determining column alone and reads the others off a row of the group, and
says so where it would not hold (`merge` raises if one customer shows two
balances): the answer over the full list is then the same rows. It never
builds a key of several columns, packs nothing into words and sorts no row
until the few rows that are answered get ranked; it imports nothing of the
program and nothing of JAX, and takes nothing the program has made.

`q18p` walks the generator's order skeleton as `tpch_flat_having`'s
reference does: the generator writes an order's lineitems one after the
other and never splits an order between chunks, so an order is a stretch of
consecutive rows with one `l_orderkey`, and its sum is `np.add.reduceat` of
`l_quantity` over the stretches' first rows. `q10p` adds each passing row's
revenue into a table a customer key (`np.add.at`), a chunk at a time; the
chunks' tables of the customers they saw are added up in `merge`.

Every value is an integer or a string: the comparison is by equality.
"""

from __future__ import annotations

import numpy as np

from perfbench.datasets.tpch_flat import datagen as g

QUANTITY = 300                  # clause 2.4.18.3's validation value
FIRST_DAY, END_DAY = g.day_of("1993-10-01"), g.day_of("1994-01-01")
RETURNED = g.RETURNFLAGS.index("R")
LIMIT = {"q10p": 20, "q18p": 100}
COLUMNS = {
    "q10p": ("c_custkey", "c_name", "revenue", "c_acctbal", "c_nation"),
    "q18p": ("c_name", "c_custkey", "o_orderkey", "o_orderdate",
             "o_totalprice", "sum_quantity"),
}
ORDER = {
    "q10p": (("revenue", "desc"), ("c_custkey", "asc")),
    "q18p": (("o_totalprice", "desc"), ("o_orderdate", "asc"),
             ("o_orderkey", "asc")),
}
_ORDER_FIELDS = ("o_custkey", "l_orderkey", "o_orderdate", "o_totalprice")


def chunk_partials(cols: dict) -> dict:
    """One chunk's part: its large orders, the customers with a returned
    item ordered in the quarter and their revenue, its order and row counts
    and its part of the totals the harness prints."""
    key = cols["l_orderkey"]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    qty = np.add.reduceat(cols["l_quantity"].astype(np.int64), first)
    keep = first[qty > QUANTITY]
    orders = {c: np.asarray(cols[c][keep]).astype(np.int64)
              for c in _ORDER_FIELDS}
    orders["sum_quantity"] = qty[qty > QUANTITY]

    rows = np.flatnonzero((cols["l_returnflag"] == RETURNED)
                          & (cols["o_orderdate"] >= FIRST_DAY)
                          & (cols["o_orderdate"] < END_DAY))
    cust = cols["o_custkey"][rows].astype(np.int64)
    revenue = np.zeros(int(cust.max(initial=0)) + 1, np.int64)
    np.add.at(revenue, cust, cols["l_extendedprice"][rows].astype(np.int64)
              * (100 - cols["l_discount"][rows].astype(np.int64)))
    # one row of each customer seen, for what the customer determines
    seen = np.zeros(len(revenue), bool)
    seen[cust] = True
    row_of = np.zeros(len(revenue), np.int64)
    row_of[cust] = rows
    who = np.flatnonzero(seen)
    customers = {"o_custkey": who, "revenue": revenue[who],
                 "c_acctbal": cols["c_acctbal"][row_of[who]]
                 .astype(np.int64),
                 "c_nation": cols["c_nation"][row_of[who]].astype(np.int64)}
    return {"orders": orders, "customers": customers,
            "n_orders": len(first), "rows": len(key),
            "sum_l_extendedprice": int(
                cols["l_extendedprice"].sum(dtype=np.int64))}


def merge(partials: list) -> dict:
    """{"orders": {column: int64 array} of every order past QUANTITY,
    "customers": {column: int64 array} of every customer with a returned
    item of the quarter, "n_orders" (`q18p`'s present groups), "rows",
    "sum_l_extendedprice", "groups": {template: its answer's groups before
    the LIMIT}}."""
    orders = {c: np.concatenate([p["orders"][c] for p in partials])
              for c in _ORDER_FIELDS + ("sum_quantity",)}
    parts = {c: np.concatenate([p["customers"][c] for p in partials])
             for c in ("o_custkey", "revenue", "c_acctbal", "c_nation")}
    n = int(parts["o_custkey"].max(initial=0)) + 1
    revenue = np.zeros(n, np.int64)
    np.add.at(revenue, parts["o_custkey"], parts["revenue"])
    who = np.flatnonzero(np.bincount(parts["o_custkey"], minlength=n))
    customers = {"o_custkey": who, "revenue": revenue[who]}
    for c in ("c_acctbal", "c_nation"):
        table = np.zeros(n, np.int64)
        table[parts["o_custkey"]] = parts[c]
        if (table[parts["o_custkey"]] != parts[c]).any():
            raise ValueError(f"a customer with two values of {c}: the "
                             "published GROUP BY would split it")
        customers[c] = table[who]
    return {"orders": orders, "customers": customers,
            "n_orders": sum(p["n_orders"] for p in partials),
            "rows": sum(p["rows"] for p in partials),
            "sum_l_extendedprice": sum(p["sum_l_extendedprice"]
                                       for p in partials),
            "groups": {"q10p": len(who),
                       "q18p": len(orders["sum_quantity"])}}


def ranked(total: dict, name: str, n: int | None = None) -> list:
    """The template's groups as answer rows in ORDER BY order, the first n
    of them (all when n is None)."""
    if name == "q18p":
        o = total["orders"]
        idx = np.lexsort((o["l_orderkey"], o["o_orderdate"],
                          -o["o_totalprice"]))[:n]
        return [{"c_name": g.decode("c_name", int(o["o_custkey"][i])),
                 "c_custkey": int(o["o_custkey"][i]),
                 "o_orderkey": int(o["l_orderkey"][i]),
                 "o_orderdate": g.DAY_STRINGS[int(o["o_orderdate"][i])],
                 "o_totalprice": int(o["o_totalprice"][i]),
                 "sum_quantity": int(o["sum_quantity"][i])}
                for i in idx.tolist()]
    c = total["customers"]
    idx = np.lexsort((c["o_custkey"], -c["revenue"]))[:n]
    return [{"c_custkey": int(c["o_custkey"][i]),
             "c_name": g.decode("c_name", int(c["o_custkey"][i])),
             "revenue": int(c["revenue"][i]),
             "c_acctbal": int(c["c_acctbal"][i]),
             "c_nation": g.decode("c_nation", int(c["c_nation"][i]))}
            for i in idx.tolist()]


def answers(total: dict) -> dict:
    """{template: {"columns", "rows" (dicts, in ORDER BY order), "order"}}."""
    return {name: {"columns": list(COLUMNS[name]),
                   "rows": ranked(total, name, LIMIT[name]),
                   "order": [list(o) for o in ORDER[name]]}
            for name in COLUMNS}
