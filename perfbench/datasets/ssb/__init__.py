"""Dataset `ssb`: the Star Schema Benchmark's denormalized lineorder.

What the harness asks of a dataset directory (perfbench/README.md):

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
TABLE = "lineorder"
answers = _r.answers


def templates() -> dict:
    with open(os.path.join(HERE, "queries.json")) as f:
        return json.load(f)


def star_schema() -> dict:
    with open(os.path.join(HERE, "star.json")) as f:
        return json.load(f)


def generate(rows: int, seed: int, out_dir: str, workers: int,
             chunk_rows: int = _g.CHUNK_ROWS) -> dict:
    """Write the fact as parquet files under out_dir from `workers` spawned
    JAX-free processes, and merge their parts of the reference. The files
    and the reference are the same for any number of workers."""
    import multiprocessing

    os.makedirs(out_dir, exist_ok=True)
    tasks = _g.chunk_tasks(rows, seed, out_dir, chunk_rows)
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


def register(engine, paths: list, rows: int, seed: int) -> None:
    """The program's normal path: the fact streams from parquet into
    time-partitioned segments; the dimension tables are registered for the
    planner's star-join collapse only (accelerate=False)."""
    engine.register_table(TABLE, list(paths), time_column=_g.TIME_COL,
                          star_schema=star_schema())
    for name, table in _g.dimension_tables(rows, seed).items():
        engine.register_table(name, table, accelerate=False)


def totals(reference: dict) -> dict:
    return {"rows": reference["rows"],
            "sum_lo_revenue": reference["sum_lo_revenue"]}
