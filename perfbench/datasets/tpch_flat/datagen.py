"""TPC-H data from a seed, flattened to one row per lineitem: the benchmark's
own generator for the datasource `orderLineItemPartSupplier`.

TPC-H specification rev. 2.17, clause 4.2.3 (the value rules dbgen follows),
written as numpy over whole chunks; it imports nothing of the program and
nothing of JAX, and its workers are spawned processes, so the parent may own
the chip. The flat row is lineitem joined to orders, customer, part,
supplier, partsupp, nation and region on their keys, as the upstream
project's index of the same name holds it: 38 columns, no free text
(comments, addresses, phones, `p_name`, `o_clerk`).

What follows the specification, letter for letter:

- table sizes by scale factor (SF1 = 1,500,000 orders, 150,000 customers,
  10,000 suppliers, 200,000 parts, 4 partsupp rows a part);
- sparse order keys: of every 32 consecutive keys only the first 8 are
  used, so SF10's l_orderkey spans 1..~60,000,000;
- `o_custkey` is never a multiple of 3 (a third of the customers have no
  order), uniform over the rest;
- 1 to 7 lineitems an order; `l_partkey` uniform, `l_suppkey` one of the
  part's four suppliers by the specification's formula;
- `p_retailprice = (90000 + ((p_partkey / 10) mod 20001) + 100 *
  (p_partkey mod 1000)) / 100`, `l_extendedprice = l_quantity *
  p_retailprice`; quantity 1..50, discount 0..10%, tax 0..8%;
- `o_orderdate` in 1992-01-01..1998-08-02, `l_shipdate = o_orderdate +
  1..121` days, `l_commitdate = o_orderdate + 30..90`, `l_receiptdate =
  l_shipdate + 1..30`; `l_returnflag` is R or A where the receipt date is
  not after 1995-06-17 and N otherwise; `l_linestatus` is O where the ship
  date is after 1995-06-17 and F otherwise; `o_orderstatus` from the
  order's line statuses; `o_totalprice` the sum over the order's lines of
  extendedprice * (1 + tax) * (1 - discount);
- the 25 nations and 5 regions, the 150 part types, 40 containers, 25
  brands, 5 segments, 5 priorities, 7 ship modes, 4 ship instructions;
  `c_name = Customer#<9 digits>`, `s_name = Supplier#<9 digits>`.

What is this generator's own (the configuration lists it under `assumed`):

1. Decimals are int64 in their smallest unit: money in cents, discount and
   tax in percent. The engine has no exact decimal type.
2. `l_shipdate` is the time column (a millisecond timestamp); the other
   three dates are ISO `YYYY-MM-DD` strings, the way a Druid datasource
   holds a date that is not its `__time`.
3. The dates and the lineitems-per-order of a chunk (its "skeleton") come
   from a stream keyed by the chunk alone; the seed permutes the chunk's
   orders and draws every key, measure and attribute. So every seed gives
   the same number of rows in every calendar month, the engine cuts the
   same segments, and every program keeps its shape from seed to seed (the
   SSB generator's departure 7 says what happens otherwise); the lineitem
   counts are each of 1..7 equally often, so a chunk of orders is exactly
   four times as many rows, and the row count asked for is met exactly by
   cutting the last order short.
4. Sizes for a row count that is not a whole scale factor: scale factor =
   rows / 5,998,605.2 (SF10 = 59,986,052 lineitems), every table by it.
5. The table's first orders, as many as there are customers with orders,
   take each of those customers once (a seeded one-to-one scatter); every
   later order draws its customer uniformly, as the specification does.
   Drawn independently, a customer in 3,000,000 has no order at all (15
   orders a customer), so one seed in six had a `c_name` dictionary of
   599,999 entries and not 600,000; the engine compiles a dictionary's
   size into the group key's radix, and that seed compiled Q10's two sort
   programs again, three minutes inside `setup_s` (my chip run, PR 27).
   Every seed now gives every dictionary the same size.
"""

from __future__ import annotations

import functools
import math
import os
import time

import numpy as np

ORDERS_PER_CHUNK = 245_000          # a multiple of 7: 980,000 rows a chunk
ROW_GROUP_ROWS = 1 << 18
TIME_COL = "l_shipdate"
ROWS_PER_SF = 5_998_605.2

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
# clause 4.2.3: nation key order, with each nation's region key
_NATION_REGION = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2),
    ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0), ("MOZAMBIQUE", 0),
    ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3), ("SAUDI ARABIA", 4),
    ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1)]
NATIONS = [n for n, _r in _NATION_REGION]
NATION_REGION = np.array([r for _n, r in _NATION_REGION], np.int8)
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                "TAKE BACK RETURN"]
RETURNFLAGS = ["R", "A", "N"]
LINESTATUSES = ["O", "F"]
ORDERSTATUSES = ["F", "O", "P"]
MFGRS = [f"Manufacturer#{m}" for m in range(1, 6)]
BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
TYPES = [f"{a} {b} {c}"
         for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO")
         for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
         for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]

FIRST_DAY = np.datetime64("1992-01-01")
LAST_DAY = np.datetime64("1998-12-31")
N_DAYS = int((LAST_DAY - FIRST_DAY).astype(int)) + 1
N_ORDER_DAYS = N_DAYS - 151        # o_orderdate: STARTDATE..ENDDATE - 151
CURRENT_DAY = int((np.datetime64("1995-06-17") - FIRST_DAY).astype(int))
DAY_STRINGS = [str(FIRST_DAY + i) for i in range(N_DAYS)]
DAY_YEAR = np.array([int(s[:4]) for s in DAY_STRINGS], np.int16)
# calendar month of each day, counted from January 1992
DAY_MONTH = ((FIRST_DAY + np.arange(N_DAYS)).astype("datetime64[M]")
             .astype(int) - (1992 - 1970) * 12).astype(np.int16)
N_MONTHS = int(DAY_MONTH[-1]) + 1
DAY_MS = (FIRST_DAY + np.arange(N_DAYS)).astype("datetime64[ms]") \
    .astype(np.int64)


def day_of(iso: str) -> int:
    """Day index of an ISO date (may lie outside the calendar)."""
    return int((np.datetime64(iso) - FIRST_DAY).astype(int))


def scale_factor(rows: int) -> float:
    return rows / ROWS_PER_SF


def dim_sizes(rows: int) -> tuple[int, int, int]:
    """(customers, suppliers, parts) for a `rows`-row flat table."""
    sf = scale_factor(rows)
    return (max(300, round(150_000 * sf)), max(40, round(10_000 * sf)),
            max(400, round(200_000 * sf)))


def order_key(index):
    """Clause 4.2.3's sparse order keys: the index-th order (from 0)."""
    index = np.asarray(index, np.int64)
    return (index // 8) * 32 + index % 8 + 1


def retail_price(partkey):
    """p_retailprice in cents, by the specification's formula."""
    pk = np.asarray(partkey, np.int64)
    return 90_000 + (pk // 10) % 20_001 + 100 * (pk % 1000)


def dimension_codes(rows: int, seed: int) -> dict:
    """Attributes of customer, supplier, part and partsupp by key - 1, as
    integers (string attributes as codes into the vocabularies above). One
    stream from the seed."""
    n_cust, n_supp, n_part = dim_sizes(rows)
    rng = np.random.default_rng((seed, 104_729))
    mfgr = rng.integers(0, 5, n_part, dtype=np.int8)
    return {
        "c_nation": rng.integers(0, 25, n_cust, dtype=np.int8),
        "c_acctbal": rng.integers(-99_999, 1_000_000, n_cust,
                                  dtype=np.int32),
        "c_mktsegment": rng.integers(0, 5, n_cust, dtype=np.int8),
        "s_nation": rng.integers(0, 25, n_supp, dtype=np.int8),
        "s_acctbal": rng.integers(-99_999, 1_000_000, n_supp,
                                  dtype=np.int32),
        "p_mfgr": mfgr,
        "p_brand": (mfgr * 5 + rng.integers(0, 5, n_part, dtype=np.int8)
                    ).astype(np.int8),
        "p_type": rng.integers(0, len(TYPES), n_part, dtype=np.int16),
        "p_size": rng.integers(1, 51, n_part, dtype=np.int8),
        "p_container": rng.integers(0, len(CONTAINERS), n_part,
                                    dtype=np.int8),
        "ps_availqty": rng.integers(1, 10_000, (n_part, 4), dtype=np.int16),
        "ps_supplycost": rng.integers(100, 100_001, (n_part, 4),
                                      dtype=np.int32),
    }


def _coprime_to(n: int) -> int:
    """A multiplier that scatters 0..n-1 over 0..n-1 one to one."""
    a = 1_000_003
    while math.gcd(a, n) != 1:
        a += 2
    return a


def skeleton(chunk: int, n: int = ORDERS_PER_CHUNK) -> dict:
    """The dates and lineitem counts of a chunk of n orders (a multiple of
    7), before the seed arranges them: per order its date and count, per
    line its three offsets. Keyed by the chunk alone (departure 3)."""
    rng = np.random.default_rng((7_368_787, n, chunk))
    counts = rng.permutation(np.arange(n, dtype=np.int32) % 7 + 1)
    # every order day gets the same number of the chunk's orders, within 1
    odate = rng.permutation(np.arange(n, dtype=np.int32) % N_ORDER_DAYS)
    m = int(counts.sum())
    return {"counts": counts, "odate": odate,
            "ship": rng.integers(1, 122, m, dtype=np.int32),
            "commit": rng.integers(30, 91, m, dtype=np.int32),
            "receipt": rng.integers(1, 31, m, dtype=np.int32)}


def fact_columns(rows: int, seed: int, chunk: int, dims: dict,
                 opc: int = ORDERS_PER_CHUNK) -> dict:
    """One chunk (of `opc` orders) of the flat table as numpy arrays:
    integers as they are stored, string attributes as codes into the
    vocabularies, dates as day indices from 1992-01-01."""
    n_cust, n_supp, n_part = dim_sizes(rows)
    sk = skeleton(chunk, opc)
    rng = np.random.default_rng((seed, 7919, chunk))
    # the table ends after `rows` rows: the last chunk keeps the orders of
    # its skeleton that fit, and the first lines of the next one. Which
    # orders those are does not depend on the seed (departure 3)
    left = rows - chunk * 4 * opc
    ends = np.cumsum(sk["counts"])
    n_full = int(np.searchsorted(ends, left, side="right"))
    cut = left - (int(ends[n_full - 1]) if n_full else 0) \
        if n_full < opc else 0
    # the seed's arrangement of the chunk's whole orders
    perm = rng.permutation(n_full)
    if cut:
        perm = np.r_[perm, n_full]
    counts = sk["counts"][perm]
    if cut:
        counts[-1] = cut
    n_orders = len(perm)
    old_start = (ends - sk["counts"])[perm]
    new_start = np.cumsum(counts) - counts
    m = int(counts.sum())
    order_of = np.repeat(np.arange(n_orders, dtype=np.int32), counts)
    linenumber = (np.arange(m, dtype=np.int32)
                  - np.repeat(new_start, counts).astype(np.int32))
    src = np.repeat(old_start, counts).astype(np.int32) + linenumber

    def draw(lo, hi, n=m):  # every range fits 32 bits
        return rng.integers(lo, hi, n, dtype=np.int32)

    # orders of the chunk
    o_index = chunk * opc + np.arange(n_orders, dtype=np.int64)
    o_date = sk["odate"][perm]
    # 1..n_cust without the multiples of 3, uniform; the table's first
    # `usable` orders take every customer once (departure 5)
    usable = n_cust - n_cust // 3
    j = draw(0, usable, n_orders)
    covering = o_index < usable
    if covering.any():
        j[covering] = (o_index[covering] * _coprime_to(usable) + seed) \
            % usable
    o_custkey = (j // 2) * 3 + j % 2 + 1
    o_priority = draw(0, 5, n_orders).astype(np.int8)

    # lineitems
    partkey = draw(1, n_part + 1)
    supp_i = draw(0, 4)
    pk = partkey.astype(np.int64)
    suppkey = ((pk + supp_i * (n_supp // 4 + (pk - 1) // n_supp)) % n_supp
               + 1).astype(np.int32)
    quantity = draw(1, 51)
    discount = draw(0, 11)
    tax = draw(0, 9)
    shipmode = draw(0, 7).astype(np.int8)
    instruct = draw(0, 4).astype(np.int8)
    r_or_a = draw(0, 2).astype(np.int8)
    price = retail_price(partkey)
    extended = quantity * price
    odate_l = o_date[order_of]
    ship = odate_l + sk["ship"][src]
    commit = odate_l + sk["commit"][src]
    receipt = ship + sk["receipt"][src]
    returnflag = np.where(receipt <= CURRENT_DAY, r_or_a, 2).astype(np.int8)
    linestatus = np.where(ship > CURRENT_DAY, 0, 1).astype(np.int8)

    # per order, from its lines: status and total price
    first = np.flatnonzero(np.r_[True, order_of[1:] != order_of[:-1]])
    n_open = np.add.reduceat((linestatus == 0).astype(np.int32), first)
    n_lines = np.diff(np.r_[first, m])
    o_status = np.where(n_open == 0, 0, np.where(n_open == n_lines, 1, 2)) \
        .astype(np.int8)
    charge = extended * (100 + tax) * (100 - discount)
    o_total = (np.add.reduceat(charge, first) + 5000) // 10_000

    custkey = o_custkey[order_of]
    c_nation = dims["c_nation"][custkey - 1]
    s_nation = dims["s_nation"][suppkey - 1]
    return {
        "l_orderkey": order_key(o_index)[order_of],
        "l_partkey": partkey, "l_suppkey": suppkey,
        "l_linenumber": linenumber + 1,
        "l_quantity": quantity, "l_extendedprice": extended,
        "l_discount": discount, "l_tax": tax,
        "l_returnflag": returnflag, "l_linestatus": linestatus,
        TIME_COL: ship, "l_commitdate": commit, "l_receiptdate": receipt,
        "l_shipinstruct": instruct, "l_shipmode": shipmode,
        "o_custkey": custkey, "o_orderstatus": o_status[order_of],
        "o_totalprice": o_total[order_of], "o_orderdate": odate_l,
        "o_orderpriority": o_priority[order_of],
        "o_shippriority": np.zeros(m, np.int8),
        "c_name": custkey, "c_acctbal": dims["c_acctbal"][custkey - 1],
        "c_mktsegment": dims["c_mktsegment"][custkey - 1],
        "c_nation": c_nation, "c_region": NATION_REGION[c_nation],
        "s_name": suppkey, "s_acctbal": dims["s_acctbal"][suppkey - 1],
        "s_nation": s_nation, "s_region": NATION_REGION[s_nation],
        "p_mfgr": dims["p_mfgr"][partkey - 1],
        "p_brand": dims["p_brand"][partkey - 1],
        "p_type": dims["p_type"][partkey - 1],
        "p_size": dims["p_size"][partkey - 1],
        "p_container": dims["p_container"][partkey - 1],
        "p_retailprice": price,
        "ps_availqty": dims["ps_availqty"][partkey - 1, supp_i],
        "ps_supplycost": dims["ps_supplycost"][partkey - 1, supp_i],
    }


# string columns: column -> vocabulary its codes index
VOCAB = {
    "l_returnflag": RETURNFLAGS, "l_linestatus": LINESTATUSES,
    "l_shipinstruct": INSTRUCTIONS, "l_shipmode": SHIPMODES,
    "o_orderstatus": ORDERSTATUSES, "o_orderpriority": PRIORITIES,
    "c_mktsegment": SEGMENTS, "c_nation": NATIONS, "c_region": REGIONS,
    "s_nation": NATIONS, "s_region": REGIONS, "p_mfgr": MFGRS,
    "p_brand": BRANDS, "p_type": TYPES, "p_container": CONTAINERS,
}
DATE_COLS = ("l_commitdate", "l_receiptdate", "o_orderdate")
NAME_COLS = {"c_name": "Customer#", "s_name": "Supplier#"}
# near-unique integers: no parquet dictionary pages for them
HIGH_CARDINALITY = ("l_orderkey", "l_partkey", "l_extendedprice",
                    "o_custkey", "o_totalprice", "c_acctbal")


def decode(column: str, value):
    """A stored value as the served answer has it."""
    if column in VOCAB:
        return VOCAB[column][value]
    if column in DATE_COLS:
        return DAY_STRINGS[value]
    if column in NAME_COLS:
        return f"{NAME_COLS[column]}{value:09d}"
    return int(value)


def _dict_array(codes: np.ndarray, vocab: list):
    import pyarrow as pa
    width = np.int8 if len(vocab) <= 127 else np.int16
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(width, copy=False)),
        pa.array(vocab, pa.string()))


def _name_array(keys: np.ndarray, prefix: str):
    """`<prefix><9 digits>` of the keys present, as an arrow dictionary."""
    import pyarrow as pa
    uniq, inv = np.unique(keys, return_inverse=True)
    names = np.char.add(prefix, np.char.zfill(uniq.astype(str), 9))
    return pa.DictionaryArray.from_arrays(
        pa.array(inv.astype(np.int32, copy=False)),
        pa.array(names, pa.string()))


def to_arrow(cols: dict):
    """The chunk as the arrow table that is written: int64 integers, a
    millisecond timestamp, dictionary strings."""
    import pyarrow as pa
    arrays, names = [], []
    for name, v in cols.items():
        if name in VOCAB:
            arr = _dict_array(v, VOCAB[name])
        elif name in DATE_COLS:
            arr = _dict_array(v, DAY_STRINGS)
        elif name in NAME_COLS:
            arr = _name_array(v, NAME_COLS[name])
        elif name == TIME_COL:
            arr = pa.array(DAY_MS[v], pa.timestamp("ms"))
        else:
            arr = pa.array(v.astype(np.int64, copy=False))
        arrays.append(arr)
        names.append(name)
    return pa.table(arrays, names=names)


def chunk_tasks(rows: int, seed: int, out_dir: str,
                opc: int = ORDERS_PER_CHUNK) -> list:
    if opc % 7:
        raise ValueError("orders per chunk must be a multiple of 7")
    n_chunks = -(-rows // (4 * opc))
    return [(out_dir, rows, seed, c, opc) for c in range(n_chunks)]


@functools.lru_cache(maxsize=1)
def _worker_dims(rows: int, seed: int):
    """A worker builds the dimension attributes once, not once per chunk."""
    return dimension_codes(rows, seed)


def write_chunk(task) -> dict:
    """One parquet file of the flat table and its part of the reference.
    Runs in a spawned worker: plain values in, plain values out."""
    import pyarrow.parquet as pq

    from . import reference

    out_dir, rows, seed, chunk, opc = task
    cols = fact_columns(rows, seed, chunk, _worker_dims(rows, seed), opc)
    path = os.path.join(out_dir, f"olps-{chunk:05d}.parquet")
    table = to_arrow(cols)
    pq.write_table(table, path, row_group_size=ROW_GROUP_ROWS,
                   use_dictionary=[c for c in table.schema.names
                                   if c not in HIGH_CARDINALITY])
    t1 = time.perf_counter()
    partial = reference.chunk_partials(cols)
    t2 = time.perf_counter()
    return {"path": path, "partial": partial, "reference_s": t2 - t1}
