"""Star Schema Benchmark data from a seed: the benchmark's own generator.

A copy of `tpu_olap/bench/ssb.py` (generator part), kept under the
benchmark's directory so that no later PR can change the data a cell is
measured on. It imports nothing of the program and nothing of JAX: its
workers are spawned processes, so the parent may own the chip.

Same as the original: table sizes by SF ratios (`dim_sizes`), uniform
foreign keys, uniform dates 1992-01-01..1998-12-31, int64 money columns,
`lo_revenue = lo_extendedprice * (100 - lo_discount) // 100`, the extra
city mass on 'UNITED KI1'/'UNITED KI5' (~6% each, so that Q3.3/Q3.4 find
rows at every size), the 29-column denormalized fact (the "Druid
datasource") written as parquet in chunks of 2,000,000 rows with row groups
of 2^18 rows, one random stream per chunk keyed `(seed, 7919, chunk)` so
that the files are the same for any number of workers, and dimension
streams keyed by the seed alone.

Where it departs from the original, and why:

1. Dimension attributes are integer codes into fixed vocabularies (city ->
   nation -> region by arithmetic, `MFGR#abc` from three digits), never
   Python strings per row. The original builds them with list
   comprehensions and f-strings: ~2.5M parts at 75M fact rows, seconds of
   set-up in every run of every check.
2. The fact is denormalized by indexing those code arrays with the foreign
   keys (`codes[fk - 1]`), not by four pandas merges per chunk, and string
   columns are written as arrow dictionary arrays over the full vocabulary.
   Parquet stores them as it stores the original's strings (dictionary
   pages), and the program's ingest reads both through its arrow-dictionary
   path, so the table the engine holds is the same shape. Generation was
   ~60 s of 8 workers for 75M rows with the merges (PERF.md, PR 23).
3. `lo_shipmode`, `c_mktsegment`: drawn as integer codes with
   `rng.integers` instead of `rng.choice(strings)`, and every fact column
   is drawn as int32 (all ranges fit) and widened when written: the same
   distributions, another stream, half the generator's time.
4. `p_brand1` digits: the original draws the brand number 1..40; so does
   this. The vocabulary is the 1,000 possible `MFGR#abc` names in sorted
   string order, so that a code range is a string range (Q2.2's BETWEEN).
5. Each worker also computes its chunk's part of the plain reference
   (`reference.py`) from the integer columns it has in memory, and returns
   it with the path. The time that takes is reported apart and is not
   counted in `setup_s`.
7. `lo_orderdate` is spread exactly evenly over the 2,557 days within each
   chunk (a seeded permutation of `arange(rows) % days`) where the original
   draws days independently. Independent draws move the rows per calendar
   month by a few hundred from seed to seed; the engine partitions by month
   into blocks of 65,536 rows, so the segment count moved between 1,169 and
   1,170 (my chip runs, PR 24) and with it the shape of every compiled
   program: a run on a new seed then compiled for a minute inside
   `setup_s`. Every seed now gives the same sizes in another order.
6. The four dimension tables handed to the engine (`dimension_tables`) are
   arrow tables with dictionary columns and without the `*_name` columns
   (`c_name`, `s_name`: one distinct string per row, referenced by no
   query). The engine registers them `accelerate=False`, for the planner's
   star-join collapse only; no query of the 13 reads them.
"""

from __future__ import annotations

import functools
import os
import time

import numpy as np

CHUNK_ROWS = 2_000_000
ROW_GROUP_ROWS = 1 << 18
TIME_COL = "lo_orderdate_ts"

REGION_NATIONS = {
    "AFRICA": ["ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE"],
    "AMERICA": ["ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES"],
    "ASIA": ["CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM"],
    "EUROPE": ["FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM"],
    "MIDDLE EAST": ["EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA"],
}
REGIONS = list(REGION_NATIONS)
NATIONS = [n for ns in REGION_NATIONS.values() for n in ns]
# SSB: city = first 9 characters of the nation, space-padded, + digit 0-9.
# city code // 10 is the nation code, nation code // 5 the region code
CITIES = [f"{n[:9]:<9}{i}" for n in NATIONS for i in range(10)]
MONTHS = ["January", "February", "March", "April", "May", "June", "July",
          "August", "September", "October", "November", "December"]
MONTH_ABBR = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
              "Oct", "Nov", "Dec"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
MFGRS = [f"MFGR#{a}" for a in range(1, 6)]
CATEGORIES = [f"MFGR#{a}{b}" for a in range(1, 6) for b in range(1, 6)]
# brand names in sorted STRING order; BRAND_CODE[a-1, b-1, c-1] is the code
_BRAND_NAMES = {(a, b, c): f"MFGR#{a}{b}{c}" for a in range(1, 6)
                for b in range(1, 6) for c in range(1, 41)}
BRANDS = sorted(_BRAND_NAMES.values())
BRAND_CODE = np.zeros((5, 5, 40), dtype=np.int16)
for (_a, _b, _c), _name in _BRAND_NAMES.items():
    BRAND_CODE[_a - 1, _b - 1, _c - 1] = BRANDS.index(_name)

FIRST_DAY = np.datetime64("1992-01-01")
N_DAYS = int((np.datetime64("1998-12-31") - FIRST_DAY).astype(int)) + 1
YEARS = list(range(1992, 1999))
YEARMONTHS = [f"{MONTH_ABBR[m]}{y}" for y in YEARS for m in range(12)]


def dim_sizes(n: int) -> tuple[int, int, int]:
    """(customers, suppliers, parts) for an n-row lineorder (SF ratios:
    SF1 = 6M lineorder, 30k customers, 2k suppliers, 200k parts)."""
    return max(200, n // 200), max(150, n // 3000), max(500, n // 30)


def city_probs() -> np.ndarray:
    p = np.ones(len(CITIES))
    for i, c in enumerate(CITIES):
        if c in ("UNITED KI1", "UNITED KI5"):
            p[i] = len(CITIES) * 0.06
    return p / p.sum()


def date_codes() -> dict:
    """Per-day attribute arrays of the SSB `date` dimension, by day index."""
    days = FIRST_DAY + np.arange(N_DAYS)
    y = days.astype("datetime64[Y]").astype(int) + 1970
    m = days.astype("datetime64[M]").astype(int) % 12 + 1
    dom = (days - days.astype("datetime64[M]")).astype(int) + 1
    doy = (days - days.astype("datetime64[Y]")).astype(int) + 1
    return {
        "d_datekey": (y * 10000 + m * 100 + dom).astype(np.int64),
        "d_year": y.astype(np.int64),
        "d_yearmonthnum": (y * 100 + m).astype(np.int64),
        "d_yearmonth": ((y - 1992) * 12 + m - 1).astype(np.int16),
        "d_weeknuminyear": ((doy - 1) // 7 + 1).astype(np.int64),
        "d_month": (m - 1).astype(np.int8),
        "d_monthnuminyear": m.astype(np.int64),
        "ts_ms": days.astype("datetime64[ms]").astype(np.int64),
    }


def dimension_codes(rows: int, seed: int) -> dict:
    """Integer attribute codes of customer, supplier and part, by key - 1.
    One stream from the seed, in the original's order of draws."""
    n_cust, n_supp, n_part = dim_sizes(rows)
    rng = np.random.default_rng(seed)
    p = city_probs()
    c_city = rng.choice(len(CITIES), n_cust, p=p).astype(np.int16)
    c_seg = rng.integers(0, len(SEGMENTS), n_cust).astype(np.int8)
    s_city = rng.choice(len(CITIES), n_supp, p=p).astype(np.int16)
    a = rng.integers(1, 6, n_part)
    b = rng.integers(1, 6, n_part)
    c = rng.integers(1, 41, n_part)
    return {
        "c_city": c_city, "c_mktsegment": c_seg, "s_city": s_city,
        "p_mfgr": (a - 1).astype(np.int8),
        "p_category": ((a - 1) * 5 + (b - 1)).astype(np.int8),
        "p_brand1": BRAND_CODE[a - 1, b - 1, c - 1],
        "p_size": rng.integers(1, 51, n_part).astype(np.int64),
    }


def fact_columns(rows: int, seed: int, chunk: int, start: int, m: int,
                 dims: dict, dates: dict) -> dict:
    """One chunk of the denormalized fact as numpy arrays: integers as they
    are stored, string attributes as codes into the vocabularies above."""
    n_cust, n_supp, n_part = dim_sizes(rows)
    rng = np.random.default_rng((seed, 7919, chunk))

    def draw(lo, hi):  # every range fits 32 bits: half the generator's work
        return rng.integers(lo, hi, m, dtype=np.int32)

    quantity = draw(1, 51)
    discount = draw(0, 11)
    price = draw(90_000, 10_000_000).astype(np.int64)
    custkey = draw(1, n_cust + 1)
    partkey = draw(1, n_part + 1)
    suppkey = draw(1, n_supp + 1)
    # every day of the calendar gets the same number of the chunk's rows
    # (within one), in an order drawn from the seed: rows per month, and so
    # the engine's segment count and every program's shape, are the same
    # for every seed (departure 7)
    day = rng.permutation(np.arange(m, dtype=np.int32) % N_DAYS)
    supplycost = draw(50_000, 6_000_000)
    tax = draw(0, 9)
    shipmode = draw(0, len(SHIPMODES))
    c_city = dims["c_city"][custkey - 1]
    s_city = dims["s_city"][suppkey - 1]
    return {
        "lo_orderkey": np.arange(start, start + m, dtype=np.int64),
        "lo_custkey": custkey, "lo_partkey": partkey, "lo_suppkey": suppkey,
        "lo_orderdate": dates["d_datekey"][day],
        "lo_quantity": quantity, "lo_discount": discount,
        "lo_extendedprice": price,
        "lo_revenue": price * (100 - discount) // 100,
        "lo_supplycost": supplycost, "lo_tax": tax, "lo_shipmode": shipmode,
        "d_year": dates["d_year"][day],
        "d_yearmonthnum": dates["d_yearmonthnum"][day],
        "d_yearmonth": dates["d_yearmonth"][day],
        "d_weeknuminyear": dates["d_weeknuminyear"][day],
        "d_month": dates["d_month"][day],
        "d_monthnuminyear": dates["d_monthnuminyear"][day],
        "c_city": c_city, "c_nation": (c_city // 10).astype(np.int8),
        "c_region": (c_city // 50).astype(np.int8),
        "c_mktsegment": dims["c_mktsegment"][custkey - 1],
        "s_city": s_city, "s_nation": (s_city // 10).astype(np.int8),
        "s_region": (s_city // 50).astype(np.int8),
        "p_mfgr": dims["p_mfgr"][partkey - 1],
        "p_category": dims["p_category"][partkey - 1],
        "p_brand1": dims["p_brand1"][partkey - 1],
        TIME_COL: dates["ts_ms"][day],
    }


HIGH_CARDINALITY = ("lo_orderkey", "lo_custkey", "lo_partkey",
                    "lo_extendedprice", "lo_revenue", "lo_supplycost")

# string columns of the fact: column -> vocabulary its codes index
VOCAB = {
    "lo_shipmode": SHIPMODES, "d_yearmonth": YEARMONTHS, "d_month": MONTHS,
    "c_city": CITIES, "c_nation": NATIONS, "c_region": REGIONS,
    "c_mktsegment": SEGMENTS, "s_city": CITIES, "s_nation": NATIONS,
    "s_region": REGIONS, "p_mfgr": MFGRS, "p_category": CATEGORIES,
    "p_brand1": BRANDS,
}


def _dict_array(codes: np.ndarray, vocab: list):
    import pyarrow as pa
    width = np.int8 if len(vocab) <= 127 else np.int16
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(width, copy=False)), pa.array(vocab, pa.string()))


def to_arrow(cols: dict):
    """The chunk as the arrow table that is written: int64 integers, a
    millisecond timestamp, dictionary strings."""
    import pyarrow as pa
    arrays, names = [], []
    for name, v in cols.items():
        if name in VOCAB:
            arr = _dict_array(v, VOCAB[name])
        elif name == TIME_COL:
            arr = pa.array(v, pa.timestamp("ms"))
        else:
            arr = pa.array(v.astype(np.int64, copy=False))
        arrays.append(arr)
        names.append(name)
    return pa.table(arrays, names=names)


def chunk_tasks(rows: int, seed: int, out_dir: str,
                chunk_rows: int = CHUNK_ROWS) -> list:
    starts = range(1, rows + 1, chunk_rows)
    return [(out_dir, rows, seed, i, s, min(chunk_rows, rows - s + 1))
            for i, s in enumerate(starts)]


@functools.lru_cache(maxsize=1)
def _worker_dims(rows: int, seed: int):
    """A worker builds the dimension codes once, not once per chunk."""
    return dimension_codes(rows, seed), date_codes()


def write_chunk(task) -> dict:
    """One parquet file of the fact and its part of the reference. Runs in
    a spawned worker: plain values in, plain values out."""
    import pyarrow.parquet as pq

    from . import reference

    out_dir, rows, seed, chunk, start, m = task
    dims, dates = _worker_dims(rows, seed)
    cols = fact_columns(rows, seed, chunk, start, m, dims, dates)
    path = os.path.join(out_dir, f"lineorder-{chunk:05d}.parquet")
    table = to_arrow(cols)
    # no parquet dictionary pages for the near-unique integers: writing and
    # reading them costs three times the plain encoding and saves nothing
    pq.write_table(table, path, row_group_size=ROW_GROUP_ROWS,
                   use_dictionary=[c for c in table.schema.names
                                   if c not in HIGH_CARDINALITY])
    t1 = time.perf_counter()
    partial = reference.chunk_partials(cols)
    t2 = time.perf_counter()
    return {"path": path, "partial": partial, "reference_s": t2 - t1}


def dimension_tables(rows: int, seed: int) -> dict:
    """The four dimension tables as arrow tables (see departure 6)."""
    import pyarrow as pa
    d = dimension_codes(rows, seed)
    dates = date_codes()
    n_cust, n_supp, n_part = dim_sizes(rows)
    customer = pa.table({
        "c_custkey": pa.array(np.arange(1, n_cust + 1, dtype=np.int64)),
        "c_city": _dict_array(d["c_city"], CITIES),
        "c_nation": _dict_array(d["c_city"] // 10, NATIONS),
        "c_region": _dict_array(d["c_city"] // 50, REGIONS),
        "c_mktsegment": _dict_array(d["c_mktsegment"], SEGMENTS),
    })
    supplier = pa.table({
        "s_suppkey": pa.array(np.arange(1, n_supp + 1, dtype=np.int64)),
        "s_city": _dict_array(d["s_city"], CITIES),
        "s_nation": _dict_array(d["s_city"] // 10, NATIONS),
        "s_region": _dict_array(d["s_city"] // 50, REGIONS),
    })
    part = pa.table({
        "p_partkey": pa.array(np.arange(1, n_part + 1, dtype=np.int64)),
        "p_mfgr": _dict_array(d["p_mfgr"], MFGRS),
        "p_category": _dict_array(d["p_category"], CATEGORIES),
        "p_brand1": _dict_array(d["p_brand1"], BRANDS),
        "p_size": pa.array(d["p_size"]),
    })
    date = pa.table({
        "d_datekey": pa.array(dates["d_datekey"]),
        "d_year": pa.array(dates["d_year"]),
        "d_yearmonthnum": pa.array(dates["d_yearmonthnum"]),
        "d_yearmonth": _dict_array(dates["d_yearmonth"], YEARMONTHS),
        "d_weeknuminyear": pa.array(dates["d_weeknuminyear"]),
        "d_month": _dict_array(dates["d_month"], MONTHS),
        "d_monthnuminyear": pa.array(dates["d_monthnuminyear"]),
    })
    return {"date": date, "customer": customer, "supplier": supplier,
            "part": part}
