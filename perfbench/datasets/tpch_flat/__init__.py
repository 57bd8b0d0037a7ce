"""Dataset `tpch_flat`: TPC-H flattened to one row per lineitem, the
upstream project's datasource `orderLineItemPartSupplier`.

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

from . import datagen as _g
from . import reference as _r
from .bytes import needed_bytes, needed_rows  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE = "orderLineItemPartSupplier"
answers = _r.answers


def templates() -> dict:
    with open(os.path.join(HERE, "queries.json")) as f:
        return json.load(f)


def generate(rows: int, seed: int, out_dir: str, workers: int,
             orders_per_chunk: int = _g.ORDERS_PER_CHUNK) -> dict:
    """Write the flat table as parquet files under out_dir from `workers`
    spawned JAX-free processes, and merge their parts of the reference. The
    files and the reference are the same for any number of workers."""
    import multiprocessing

    os.makedirs(out_dir, exist_ok=True)
    tasks = _g.chunk_tasks(rows, seed, out_dir, orders_per_chunk)
    if workers <= 1:
        done = [_g.write_chunk(t) for t in tasks]
    else:
        pool = multiprocessing.get_context("spawn").Pool(
            min(workers, len(tasks)))
        try:
            done = pool.map(_g.write_chunk, tasks, chunksize=1)
            pool.close()
        except BaseException:
            pool.terminate()
            raise
        finally:
            pool.join()  # every worker has ended before this returns
    n = max(1, min(workers, len(tasks)))
    return {
        "paths": [d["path"] for d in done],
        "reference": _r.merge([d["partial"] for d in done]),
        # the wall-clock share of the reference: workers ran in parallel
        "reference_s": sum(d["reference_s"] for d in done) / n,
    }


def _stop_unless_planned(engine, path: str) -> None:
    """End the run here, with an exit code and no result line, where the
    program's planner has no device plan for a template. Asked through
    `Engine.explain` over the first thousand rows, before the table is
    ingested: the driver also runs this cell's files over the parent
    commit, which would ingest 60M rows for eight minutes, answer Q3 and
    Q12 with errors in milliseconds, and report the latencies of a round
    two templates shorter, to be compared with a program that serves
    them. Only the planner's verdict is read: what a plan then does on
    the device (a control's lower precision included) is for the run and
    its check to show. Nothing of the program is named here but what
    `register` already uses: the engine it is handed."""
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
                raise SystemExit(
                    f"this program cannot serve dataset tpch_flat: "
                    f"{name}: {said.get('reason', 'no plan')}")
    finally:
        engine.drop_table(probe)


def register(engine, paths: list, rows: int, seed: int) -> None:
    """The program's normal path: the one flat table streams from parquet
    into time-partitioned segments. No dimension tables: the datasource is
    already joined, and no template names one."""
    _stop_unless_planned(engine, paths[0])
    engine.register_table(TABLE, list(paths), time_column=_g.TIME_COL)


def totals(reference: dict) -> dict:
    """The harness prints `totals()["sum_lo_revenue"]` by that name
    (lib/harness.py's check line, written for SSB); this dataset has no
    such column, so the key holds sum(l_extendedprice) over all rows, in
    cents. Renaming the key is a `benchmark` PR's."""
    return {"rows": reference["rows"],
            "sum_lo_revenue": reference["sum_l_extendedprice"]}
