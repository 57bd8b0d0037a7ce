"""Dataset `druid_lineitem`: TPC-H `lineitem` alone as one datasource, the
table of Druid's own published benchmark ("Benchmarking Druid", 2014;
druid-io/druid-benchmark), under its nine queries.

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
from .bytes import TOPN, needed_bytes, needed_rows  # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE = "lineitem"
answers = _r.answers


def templates() -> dict:
    with open(os.path.join(HERE, "queries.json")) as f:
        return json.load(f)


def generate(rows: int, seed: int, out_dir: str, workers: int,
             orders_per_chunk: int = _g.ORDERS_PER_CHUNK) -> dict:
    """Write lineitem as parquet files under out_dir from `workers` spawned
    JAX-free processes, each taking every `workers`-th chunk and merging
    its own chunks' parts of the reference; their parts are merged here.
    The files and the reference are the same for any number of workers
    (integer sums, minima and maxima commute)."""
    import multiprocessing

    os.makedirs(out_dir, exist_ok=True)
    tasks = _g.chunk_tasks(rows, seed, out_dir, orders_per_chunk)
    n = max(1, min(workers, len(tasks)))
    shares = [tasks[i::n] for i in range(n)]
    if n == 1:
        done = [_g.write_chunks(shares[0])]
    else:
        pool = multiprocessing.get_context("spawn").Pool(n)
        try:
            done = pool.map(_g.write_chunks, shares, chunksize=1)
            pool.close()
        except BaseException:
            pool.terminate()
            raise
        finally:
            pool.join()  # every worker has ended before this returns
    reference = None
    for d in done:
        reference = _r.merge_into(reference, d["partial"])
    return {"paths": sorted(p for d in done for p in d["paths"]),
            "reference": reference,
            # the wall-clock share of the reference: workers ran in parallel
            "reference_s": sum(d["reference_s"] for d in done) / n}


def register(engine, paths: list, rows: int, seed: int) -> None:
    """The program's normal path: the one table streams from parquet into
    time-partitioned segments. No dimension tables: the datasource has
    none, and no query names one. A program that answers `min(l_discount)`
    with a float ends the run here, with an exit code and no result line:
    the comparison that decides `correct` is by equality and refuses a
    float, so such a program (any commit before this dataset came, which
    the driver also runs these files over) could only report two templates
    wrong in every run."""
    engine.register_table(TABLE, list(paths), time_column=_g.TIME_COL)
    lo = engine.sql(f"SELECT min(l_discount) AS lo FROM {TABLE}")["lo"]
    if lo.dtype.kind not in "iu":
        raise SystemExit(
            "this program cannot serve dataset druid_lineitem exactly: "
            f"min(l_discount) comes back as {lo.dtype}, not as an integer")


def totals(reference: dict) -> dict:
    """The harness prints `totals()["sum_lo_revenue"]` by that name
    (lib/harness.py's check line, written for SSB); here the key holds
    sum(l_extendedprice) over all rows, in cents, as in `tpch_flat`."""
    return {"rows": reference["rows"],
            "sum_lo_revenue": reference["sum_l_extendedprice"]}
