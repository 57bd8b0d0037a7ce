"""Dataset `tpch_flat_widekey`: the flattened TPC-H datasource
`orderLineItemPartSupplier` of dataset `tpch_flat`, file for file, under
TPC-H Q10 "Returned Item Reporting" (clause 2.4.10) and Q18 "Large Volume
Customer" (clause 2.4.18) with the GROUP BY lists the specification
publishes: group spaces of 4.1e19 and 1.2e31 at SF10, past what one int64
word holds.

The table is the sibling's: `generate` drives its `datagen.fact_columns` and
`to_arrow` through a `write_chunk` of this dataset's own, which writes the
same 38-column parquet files byte for byte and computes this dataset's part
of the reference in the sibling's place.

What the harness asks of a dataset directory (perfbench/README.md), as
`datasets/ssb/__init__.py` documents it:

    TABLE                      the fact table's name
    templates()                {name: sql}
    generate(rows, seed, out_dir, workers)
                               -> {"paths", "reference", "reference_s"}
    register(engine, paths, rows, seed)
                               the data through Engine.register_table
    answers(reference)         {template: {"columns", "rows", "order"}}
    needed_bytes(template, reference, rows_scanned)
    totals(reference)          {"rows", "sum_lo_revenue"}
"""

from __future__ import annotations

import json
import os
import time

from perfbench.datasets.tpch_flat import datagen as _g

from . import reference as _r
from .bytes import needed_bytes, needed_rows  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE = "orderLineItemPartSupplier"
answers = _r.answers


def templates() -> dict:
    with open(os.path.join(HERE, "queries.json")) as f:
        return json.load(f)


def write_chunk(task) -> dict:
    """One parquet file of the flat table, written as the sibling's
    `datagen.write_chunk` writes it, and this dataset's part of the
    reference. Runs in a spawned worker: plain values in, plain values
    out."""
    import pyarrow.parquet as pq

    out_dir, rows, seed, chunk, opc = task
    cols = _g.fact_columns(rows, seed, chunk, _g._worker_dims(rows, seed),
                           opc)
    path = os.path.join(out_dir, f"olps-{chunk:05d}.parquet")
    table = _g.to_arrow(cols)
    pq.write_table(table, path, row_group_size=_g.ROW_GROUP_ROWS,
                   use_dictionary=[c for c in table.schema.names
                                   if c not in _g.HIGH_CARDINALITY])
    t1 = time.perf_counter()
    partial = _r.chunk_partials(cols)
    return {"path": path, "partial": partial,
            "reference_s": time.perf_counter() - t1}


def generate(rows: int, seed: int, out_dir: str, workers: int,
             orders_per_chunk: int = _g.ORDERS_PER_CHUNK) -> dict:
    """Write the flat table as parquet files under out_dir from `workers`
    spawned JAX-free processes, and merge their parts of the reference. The
    files and the reference are the same for any number of workers."""
    import multiprocessing

    os.makedirs(out_dir, exist_ok=True)
    tasks = _g.chunk_tasks(rows, seed, out_dir, orders_per_chunk)
    if workers <= 1:
        done = [write_chunk(t) for t in tasks]
    else:
        pool = multiprocessing.get_context("spawn").Pool(
            min(workers, len(tasks)))
        try:
            done = pool.map(write_chunk, tasks, chunksize=1)
            pool.close()
        except BaseException:
            pool.terminate()
            raise
        finally:
            pool.join()  # every worker has ended before this returns
    n = max(1, min(workers, len(tasks)))
    return {"paths": [d["path"] for d in done],
            "reference": _r.merge([d["partial"] for d in done]),
            # the wall-clock share of the reference: workers ran in parallel
            "reference_s": sum(d["reference_s"] for d in done) / n}


def _stop_unless_key_words(engine, path: str) -> None:
    """End the run here, with an exit code and no result line, where the
    program has no plan for a template or does not say how many words its
    sparse key takes. Asked through `Engine.explain` over the first
    thousand rows, before the table is ingested: the driver also runs this
    cell's files over the parent commit, which refuses a group space past
    2^62 ("the group space overflows the int64 sparse key") and hands both
    templates to the pandas interpreter over 60M rows, minutes a query;
    its `explain` does not know the word. Over a thousand rows `q10p`'s
    space fits one int64 and that commit too calls it `rewritten`, so what
    decides is the word: only its presence is read, never its value.
    Where `explain` says the word with no number (`key_words` null: no
    device plan at all, a control's lower precision), the run goes on and
    its check shows what such a program answers. Nothing of the program is
    named here but what `register` already uses: the engine it is
    handed."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    probe = TABLE + "_probe"
    first = next(pq.ParquetFile(path).iter_batches(batch_size=1024))
    engine.register_table(probe, pa.Table.from_batches([first]),
                          time_column=_g.TIME_COL)
    try:
        for name, sql in sorted(templates().items()):
            said = engine.explain(sql.replace(TABLE, probe))
            if not said.get("rewritten"):
                why = said.get("reason", "no plan")
            elif "key_words" not in said:
                why = "it does not say how many words its group key takes"
            else:
                continue
            raise SystemExit("this program cannot serve dataset "
                             f"tpch_flat_widekey: {name}: {why}")
    finally:
        engine.drop_table(probe)


def register(engine, paths: list, rows: int, seed: int) -> None:
    """The program's normal path: the one flat table streams from parquet
    into time-partitioned segments. No dimension tables: the datasource is
    already joined, and no template names one."""
    _stop_unless_key_words(engine, paths[0])
    engine.register_table(TABLE, list(paths), time_column=_g.TIME_COL)


def totals(reference: dict) -> dict:
    """The harness prints `totals()["sum_lo_revenue"]` by that name
    (lib/harness.py's check line, written for SSB); here the key holds
    sum(l_extendedprice) over all rows, in cents, as in `tpch_flat`."""
    return {"rows": reference["rows"],
            "sum_lo_revenue": reference["sum_l_extendedprice"]}
