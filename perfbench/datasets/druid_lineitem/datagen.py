"""TPC-H `lineitem` from a seed, as Druid's own benchmark holds it: one
datasource, `l_shipdate` its time column, no other table.

The rows are the lineitem columns of `datasets/tpch_flat/datagen.py`, whose
value rules are TPC-H's clause 4.2.3 (that module's docstring lists them and
its own departures: decimals as int64 hundredths and percent, the seed-free
skeleton of dates so that every seed fills the calendar months alike, sizes
by rows / 5,998,605.2). Reused by import, nothing copied; like it, this
module imports nothing of the program and nothing of JAX, and its workers
are spawned processes. `l_comment` is left out: free text that none of the
nine queries reads.
"""

from __future__ import annotations

import functools
import os
import time

from perfbench.datasets.tpch_flat import datagen as _t

TIME_COL = _t.TIME_COL                      # l_shipdate
ORDERS_PER_CHUNK = _t.ORDERS_PER_CHUNK
COLUMNS = ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
           "l_quantity", "l_extendedprice", "l_discount", "l_tax",
           "l_returnflag", "l_linestatus", TIME_COL, "l_commitdate",
           "l_receiptdate", "l_shipinstruct", "l_shipmode")
HIGH_CARDINALITY = ("l_orderkey", "l_partkey", "l_extendedprice")


def parts(rows: int) -> int:
    """Rows of the part table behind a `rows`-row lineitem: 200,000 a scale
    factor, so `l_partkey` is uniform over 1..parts(rows)."""
    return _t.dim_sizes(rows)[2]


def lineitem_columns(rows: int, seed: int, chunk: int, opc: int) -> dict:
    """One chunk of lineitem as numpy arrays: integers as stored, strings
    as codes into the vocabularies, dates as day indices from 1992-01-01."""
    flat = _t.fact_columns(rows, seed, chunk, _worker_dims(rows, seed), opc)
    return {c: flat[c] for c in COLUMNS}


@functools.lru_cache(maxsize=1)
def _worker_dims(rows: int, seed: int):
    """The flat generator draws part and supplier attributes beside the
    lineitem columns; a worker builds them once, not once a chunk."""
    return _t.dimension_codes(rows, seed)


chunk_tasks = _t.chunk_tasks


def write_chunks(tasks: list) -> dict:
    """One worker's share of the chunks: a parquet file of lineitem each,
    and the worker's part of the reference, its chunks' parts merged here
    (a part holds five arrays as long as the part table, twice: sixty-two
    of them through the pool's pipe and merged one after another in the
    parent took longer than generating the table). Runs in a spawned
    worker: plain values in, plain values and numpy arrays out."""
    import pyarrow.parquet as pq

    from . import reference

    paths, partial, reference_s = [], None, 0.0
    for out_dir, rows, seed, chunk, opc in tasks:
        cols = lineitem_columns(rows, seed, chunk, opc)
        path = os.path.join(out_dir, f"lineitem-{chunk:05d}.parquet")
        table = _t.to_arrow(cols)
        pq.write_table(table, path, row_group_size=_t.ROW_GROUP_ROWS,
                       use_dictionary=[c for c in table.schema.names
                                       if c not in HIGH_CARDINALITY])
        paths.append(path)
        t1 = time.perf_counter()
        partial = reference.merge_into(
            partial, reference.chunk_partials(cols, parts(rows)))
        reference_s += time.perf_counter() - t1
    return {"paths": paths, "partial": partial, "reference_s": reference_s}
