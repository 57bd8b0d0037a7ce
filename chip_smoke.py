#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path starts on the chip.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # the mesh path only, on four chips

SQL in -> planner -> lowered program -> device -> result frame out, through
`Engine.sql` and the HTTP `QueryServer`, on Star-Schema-Benchmark data made
from `--seed`. Every engine is built with fallback_on_device_failure=False
and the breaker off, so a device failure is an ERROR here, never a slow
answer from the pandas interpreter under a TPU name. Nothing is retried,
caught and passed over, or sent to a CPU backend.

Phases, one chip (default):
  device   jax.devices()[0].platform must be "tpu"
  answers  6,000,000 lineorder rows; all 13 SSB queries, one min/max and
           one HLL query (float64 finals: the packed buffer's f32-pair
           slabs, TPU only) and a TopN over a 200,000-wide key (sparse
           sort, threshold on the device, ties at the threshold) against
           the pandas interpreter on the same data
           (bench/parity.py::check_query)
  size     75,000,000 rows (SF100 on a v5e-8 / 8 chips) written as parquet,
           stream-ingested, then a QueryServer answers all 13 over POST /sql
           twice each; totals vs pyarrow, HTTP frame sha256 == Engine.sql's,
           and every record served by the device on the planned path; then
           POST /debug/profile?ms=N under load: the capture holds the
           program's spans and no Python-tracer events
With --chips 4, only: one-device engine vs num_shards=4 engine on 24,000,000
rows — sha256 parity, per-chip window, sparse fan-out (a group-by and a
TopN whose metric ties at the threshold), sys.devices, bytes resident on
every chip.

The LAST stdout line is one JSON object, {"ok": ..., "device": {...}}; exit
code 0 only with "ok": true. `--allow-cpu` is the CPU REHEARSAL of the
script (Pallas in interpret mode, tiny --rows): it runs every phase, prints
"rehearsal: all phases passed", and still ends "ok": false with a non-zero
exit — with it the script can never report success, without it the device
phase fails first on a machine with no chip.
"""

import argparse
import hashlib
import http.client
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

ANSWER_ROWS = 6_000_000     # SF1
SIZE_ROWS = 75_000_000      # SF100 on a v5e-8 (BASELINE.json) / 8 chips
MESH_ROWS = 24_000_000      # 6M per chip on four
EXIT_FAILED, EXIT_REHEARSAL = 1, 10

# float64 finals. SSB's 13 pack int64 fields only; on the TPU a float64
# slab of the packed buffer travels as an f32 pair (packing.PackLayout),
# a branch no CPU run takes. min/max also drives the Pallas kernel's
# second output buffer. (sql, approx columns)
F64_QUERIES = {
    "minmax": ("""
        SELECT d_year, min(lo_revenue) AS lo, max(lo_supplycost) AS hi,
               sum(lo_revenue) AS revenue
        FROM lineorder GROUP BY d_year""", ()),
    "hll": ("""
        SELECT s_region, approx_count_distinct(lo_custkey) AS u
        FROM lineorder JOIN supplier ON lo_suppkey = s_suppkey
        GROUP BY s_region""", ("u",)),
}


# A TopN over a key too wide for the Pallas kernel and the compare form:
# the sparse sort group-by serves it, one chip applies the threshold on
# the device (100 group rows leave it), a mesh's broker merges whole tables
# and ranks on the host. sum(lo_quantity) over ~30 rows a part ties at the
# threshold for certain: the rows kept there are the first by part key.
WIDE_TOPN_SQL = """
    SELECT lo_partkey, sum(lo_quantity) AS qty FROM lineorder
    GROUP BY lo_partkey ORDER BY qty DESC LIMIT 100"""


class SmokeFailure(AssertionError):
    pass


def say(msg: str):
    print(f"[chip-smoke] {msg}", flush=True)


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def digest(frame) -> str:
    return hashlib.sha256(
        frame.to_csv(index=False, float_format="%.6g").encode()).hexdigest()


def timed_ms(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return (time.perf_counter() - t0) * 1000


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def make_engine(use_pallas: str, **cfg):
    from tpu_olap import Engine
    from tpu_olap.executor import EngineConfig
    return Engine(EngineConfig(
        fallback_on_device_failure=False, breaker_failure_threshold=0,
        use_pallas=use_pallas, **cfg))


def last_record(eng, query_id: str | None = None) -> dict:
    for rec in reversed(list(eng.runner.history)):
        if query_id is None or rec.get("query_id") == query_id:
            return dict(rec)
    raise SmokeFailure(f"no history record for query {query_id}")


def check_device_record(rec: dict, name: str, num_shards: int):
    """The record of one served statement: it ran on the device."""
    check(rec.get("query_type") != "fallback" and not rec.get("failed")
          and "fallback_reason" not in rec,
          f"{name}: not served by the device: {rec}")
    check(rec.get("num_shards") == num_shards,
          f"{name}: num_shards={rec.get('num_shards')} != {num_shards}")


# ------------------------------------------------------------------ phases

def phase_answers(rows: int, seed: int, use_pallas: str):
    """Parity with the pandas interpreter on the same data. Mosaic
    miscompiles that interpret mode hides (docs/TPU_NOTES.md) land here."""
    from tpu_olap.bench import QUERIES, check_query, generate_tables, \
        register_ssb
    t0 = time.perf_counter()
    eng = make_engine(use_pallas)
    register_ssb(eng, generate_tables(rows, seed))
    say(f"answers: {rows:,} rows generated+registered in "
        f"{time.perf_counter() - t0:.1f}s")
    queries = {n: (sql, ()) for n, sql in QUERIES.items()} | F64_QUERIES
    try:
        for name, (sql, approx) in queries.items():
            t0 = time.perf_counter()
            frame = check_query(eng, sql, approx_cols=approx, label=name)
            rec = last_record(eng)
            check_device_record(rec, name, 1)
            check(rec.get("packed"), f"{name}: not the single-fetch packed "
                                     f"program: {rec}")
            say(f"answers: {name} parity OK rows={len(frame)} "
                f"path={rec.get('path')} packed={rec.get('packed')} "
                f"device+oracle={time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        frame = check_query(eng, WIDE_TOPN_SQL, label="wide-topn")
        rec = last_record(eng)
        check_device_record(rec, "wide-topn", 1)
        # at a rehearsal's size the key is narrow and the dense plan stays
        wide = rec.get("topn_group_space", 0) > 65_536
        check(rec.get("query_type") == "topN" and len(frame) == 100
              and (not wide or (rec.get("reduce_path") == "sparse"
                                and rec.get("topn_rows_fetched") == 100)),
              f"wide-topn: not a TopN with the threshold on the device: "
              f"{rec}")
        say(f"answers: wide-topn parity OK K={rec.get('topn_group_space')} "
            f"path={rec.get('reduce_path')} rows_fetched="
            f"{rec.get('topn_rows_fetched')} cap={rec.get('sparse_cap')} "
            f"sha256={digest(frame)[:12]} "
            f"device+oracle={time.perf_counter() - t0:.1f}s")
    finally:
        eng.clear_cache()
        eng.close()
    say(f"answers: {len(queries) + 1}/{len(queries) + 1} queries match the "
        f"pandas interpreter ({len(QUERIES)} SSB + {sorted(F64_QUERIES)} + "
        "wide-topn)")


def post_sql(conn, sql: str):
    """POST /sql -> (frame, query id, wall ms)."""
    import pandas as pd
    t0 = time.perf_counter()
    conn.request("POST", "/sql", json.dumps({"query": sql}),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    ms = (time.perf_counter() - t0) * 1000
    check(resp.status == 200, f"POST /sql -> {resp.status}: {body[:500]!r}")
    out = json.loads(body)
    return (pd.DataFrame(out["rows"], columns=out["columns"]),
            resp.getheader("X-Query-Id"), ms)


def check_device_capture(server, conn, sql: str, ms: int = 1500):
    """POST /debug/profile?ms=N while this thread keeps querying: the
    capture must hold the program's spans beside the device's operations
    (host events named by the span, the device call named by the query id)
    and nothing of the profiler's Python tracer (events named "$file:line
    function"), which `obs.profile.start_capture` leaves off."""
    import glob
    import threading

    import jax
    from jax.profiler import ProfileData

    out = {}

    def capture():
        c = http.client.HTTPConnection(server.host, server.port, timeout=120)
        try:
            c.request("POST", f"/debug/profile?ms={ms}", "")
            out.update(json.loads(c.getresponse().read()))
        finally:
            c.close()

    t = threading.Thread(target=capture)
    t.start()
    qids, deadline = [], time.perf_counter() + 60
    while t.is_alive() and time.perf_counter() < deadline:
        qids.append(post_sql(conn, sql)[1])
    t.join(timeout=120)
    check(out.get("ok") is True, f"POST /debug/profile: {out}")
    paths = glob.glob(os.path.join(out["trace_dir"], "plugins", "profile",
                                   "*", "*.xplane.pb"))
    check(len(paths) == 1, f"capture files under {out['trace_dir']}: {paths}")
    host, device_ops = {}, 0
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            if plane.name.startswith("/host:"):
                for e in line.events:
                    host[e.name] = host.get(e.name, 0) + 1
            elif plane.name.startswith("/device:") and line.name == "XLA Ops":
                device_ops += len(list(line.events))
    shutil.rmtree(out["trace_dir"], ignore_errors=True)
    spans = ("sql", "http-read", "parse", "plan", "execute", "device-call",
             "prepare", "record", "render", "serialize", "http-write")
    missing = [n for n in spans if n not in host]
    python_events = sum(n for name, n in host.items()
                        if name.startswith("$"))
    annotated = sum(1 for q in qids if q in host)
    check(not missing, f"capture lacks the spans {missing}")
    check(python_events == 0,
          f"capture holds {python_events} Python-tracer events")
    check(annotated > 0, "no device call of the window is annotated")
    check(device_ops > 0 or jax.devices()[0].platform != "tpu",
          "capture holds no device operation")
    say(f"capture: POST /debug/profile?ms={ms} -> {sum(host.values())} host "
        f"events under {len(host)} names, {python_events} of the Python "
        f"tracer; {len(qids)} queries sent, {annotated} device calls "
        f"annotated, serialize x{host['serialize']}, device-call "
        f"x{host['device-call']}; {device_ops} device operations")


def phase_size(rows: int, seed: int, use_pallas: str, data_dir: str,
               workers: int):
    import jax
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    from tpu_olap.api.server import QueryServer
    from tpu_olap.bench import QUERIES, register_ssb_parquet, \
        write_ssb_parquet

    t0 = time.perf_counter()
    paths, dims = write_ssb_parquet(data_dir, rows, seed=seed,
                                    workers=workers)
    gen_s = time.perf_counter() - t0
    parquet_mb = sum(os.path.getsize(p) for p in paths) / 2**20
    say(f"size: {rows:,} rows -> {len(paths)} parquet files, "
        f"{parquet_mb:.0f} MB, generated in {gen_s:.1f}s "
        f"({workers} workers)")

    eng = make_engine(use_pallas)
    t0 = time.perf_counter()
    register_ssb_parquet(eng, paths, dims)
    ingest_s = time.perf_counter() - t0
    seg = eng.catalog.get("lineorder").segments
    stored_mb = sum(c.nbytes for s in seg.segments
                    for c in s.columns.values()) / 2**20
    say(f"size: stream-ingested in {ingest_s:.1f}s, "
        f"{len(seg.segments)} segments, stored {stored_mb:.0f} MB")

    # reference totals straight from the files, independent of the engine
    n_ref = rev_ref = 0
    for p in paths:
        col = pq.read_table(p, columns=["lo_revenue"]).column(0)
        n_ref += len(col)
        rev_ref += pc.sum(col).as_py()
    check(n_ref == rows, f"parquet holds {n_ref} rows, wanted {rows}")

    server = QueryServer(eng, port=0).start()
    conn = http.client.HTTPConnection(server.host, server.port, timeout=600)
    grouped_off_pallas = []
    try:
        frame, qid, ms = post_sql(
            conn, "SELECT count(*) AS n, sum(lo_revenue) AS rev "
                  "FROM lineorder")
        check_device_record(last_record(eng, qid), "totals", 1)
        got = (int(frame.n[0]), int(frame.rev[0]))
        check(got == (n_ref, rev_ref),
              f"totals {got} != pyarrow's {(n_ref, rev_ref)}")
        say(f"size: count(*)={got[0]:,} sum(lo_revenue)={got[1]:,} "
            f"equal pyarrow's ({ms:.0f} ms cold)")

        served = 0
        for name, sql in QUERIES.items():
            runs = []
            for leg in ("cold", "warm"):
                frame, qid, ms = post_sql(conn, sql)
                rec = last_record(eng, qid)
                check_device_record(rec, f"{name} {leg}", 1)
                runs.append((frame, rec, ms))
                served += 1
            (cold_f, cold, cold_ms), (warm_f, warm, warm_ms) = runs
            t0 = time.perf_counter()
            direct = eng.sql(sql)
            direct_ms = (time.perf_counter() - t0) * 1000
            check_device_record(last_record(eng), f"{name} Engine.sql", 1)
            check(digest(cold_f) == digest(warm_f) == digest(direct),
                  f"{name}: HTTP frame sha256 != Engine.sql's")
            phys = eng.runner._lower_cached(
                eng.last_plan.query, eng.last_plan.entry.segments)
            grouped = not name.startswith("q1.")
            want = "pallas" if phys.pallas_reason is None else "dense"
            for rec in (cold, warm):
                check(rec.get("path") == want
                      and bool(rec.get("pallas")) == (want == "pallas"),
                      f"{name}: path={rec.get('path')} "
                      f"pallas={rec.get('pallas')}, plan says {want}")
            if grouped and want != "pallas":
                grouped_off_pallas.append(name)
                say(f"size: {name} NOT on pallas at this size: "
                    f"{phys.pallas_reason}")
            # the 2nd run may compile again: the packed buffer's cap is
            # re-sized from the 1st run's group count (runner._run_packed)
            say(f"size: {name} cold={cold_ms:.1f}ms warm={warm_ms:.1f}ms "
                f"engine_sql_3rd={direct_ms:.1f}ms "
                f"compile_ms={cold.get('compile_ms', 0):.1f} "
                f"warm_compile_ms={warm.get('compile_ms', 0):.1f} "
                f"path={warm.get('path')} groups={len(direct)} "
                f"sha256={digest(direct)[:12]}")
        check_device_capture(server, conn, QUERIES["q2.1"])
        hbm = eng.runner.device_snapshot()[0]
        stats = jax.devices()[0].memory_stats() or {}
        say(f"size: {served} HTTP records served by the device, none "
            f"fallback; grouped queries off pallas: "
            f"{grouped_off_pallas or 'none'}")
        say(f"size: ingest_s={ingest_s:.1f} generate_s={gen_s:.1f} "
            f"stored_mb={stored_mb:.0f} "
            f"hbm_ledger_bytes_in_use={hbm['hbm_bytes']} "
            f"resident_bytes={hbm['resident_bytes']} "
            f"memory_stats_peak_bytes="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")
    finally:
        conn.close()
        server.stop()  # joins the engine's background threads too
        eng.clear_cache()


def phase_mesh(rows: int, seed: int, use_pallas: str, chips: int,
               data_dir: str, workers: int):
    """tools/multichip_smoke.py's checks on real chips: one engine on
    device 0 vs one with num_shards=chips over the same tables."""
    import jax

    from tools.multichip_smoke import SMOKE_QUERIES
    from tpu_olap.bench import register_ssb_parquet, write_ssb_parquet

    check(len(jax.devices()) >= chips,
          f"--chips {chips} needs {chips} devices, JAX reports "
          f"{len(jax.devices())}")
    # written once, stream-ingested per engine: four in-memory
    # registrations of 24M rows would cost minutes of four chips each
    t0 = time.perf_counter()
    paths, dims = write_ssb_parquet(data_dir, rows, seed=seed,
                                    workers=workers)
    say(f"mesh: {rows:,} rows -> {len(paths)} parquet files in "
        f"{time.perf_counter() - t0:.1f}s")
    # rehearsal sizes: a block small enough that every chip still owns
    # several segments, so the per-chip window check has something to cut
    block = None if rows >= (chips * 16) << 16 else 1 << 11

    def pair(**cfg):
        one = make_engine(use_pallas, **cfg)
        many = make_engine(use_pallas, num_shards=chips, **cfg)
        t0 = time.perf_counter()
        for e in (one, many):
            register_ssb_parquet(e, paths, dims, block_rows=block)
        say(f"mesh: two engines ingested in {time.perf_counter() - t0:.1f}s")
        return one, many

    e1, em = pair()
    for name, sql in SMOKE_QUERIES.items():
        a = e1.sql(sql)
        rec1 = last_record(e1)
        check_device_record(rec1, f"{name} 1-chip", 1)
        t0 = time.perf_counter()
        b = em.sql(sql)
        cold_ms = (time.perf_counter() - t0) * 1000
        rec = last_record(em)
        check_device_record(rec, f"{name} mesh", chips)
        check(digest(a) == digest(b), f"{name}: mesh sha256 != 1-chip's")
        # the mesh program is one chip's program on every chip
        # (sharding.mesh_agg_kernel): the Mosaic kernel runs there
        # whenever one chip would run it
        check(rec.get("mesh_program") == "per_chip"
              and rec.get("path") == rec1.get("path"),
              f"{name} mesh: mesh_program={rec.get('mesh_program')} "
              f"path={rec.get('path')}, 1-chip path={rec1.get('path')}")
        if name == "groupby":
            check(rec1.get("path") == "pallas" and rec.get("pallas"),
                  f"groupby: 1-chip path={rec1.get('path')} mesh "
                  f"pallas={rec.get('pallas')}, not pallas on both")
        # the 2nd one-chip run may compile again (packed cap re-size):
        # warm is the best of runs 2-4 on either side
        warm1, warm_m = (
            min(timed_ms(lambda: e.sql(sql)) for _ in range(3))
            for e in (e1, em))
        say(f"mesh: {name} sha256 OK num_shards={rec.get('num_shards')} "
            f"merge={rec.get('merge')} "
            f"mesh_program={rec.get('mesh_program')} "
            f"win/chip={rec.get('segments_window_per_chip')} "
            f"pallas={bool(rec.get('pallas'))} cold={cold_ms:.0f}ms "
            f"warm mesh={warm_m:.1f}ms 1-chip={warm1:.1f}ms "
            f"ratio={warm_m / warm1:.2f}")
        if name == "windowed":
            per_chip = -(-len(em.catalog.get("lineorder")
                              .segments.segments) // chips)
            w = rec.get("segments_window_per_chip")
            check(w and w < per_chip,
                  f"windowed: no per-chip window (w={w}, of {per_chip})")
    say(f"mesh: mesh_program={em.runner.mesh_program} "
        f"(backend={jax.default_backend()}, one process)")

    rows_dev = em.sql("SELECT count(*) AS n FROM sys.devices")
    check(int(rows_dev.n[0]) == chips,
          f"sys.devices has {int(rows_dev.n[0])} rows, wanted {chips}")
    snap = em.runner.device_snapshot()
    check(len(snap) == chips and all(r["resident_bytes"] > 0 for r in snap),
          f"device_snapshot: bytes not resident on every chip: {snap}")
    # the snapshot divides evenly by construction; ask the chips too
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:chips]]
    check(all(b is None or b > 0 for b in in_use),
          f"a chip holds no bytes: memory_stats bytes_in_use={in_use}")
    say(f"mesh: resident_bytes/chip={[int(r['resident_bytes']) for r in snap]}"
        f" memory_stats bytes_in_use/chip={in_use}")
    for e in (e1, em):
        e.clear_cache()
        e.close()

    # lo_suppkey, not multichip_smoke's lo_custkey: its ~rows/3000 groups
    # stay under the first sparse cap (2^15) at 24M rows, so each of the
    # five programs (one device + one per chip) compiles once — the sort
    # kernel takes 1.5-2 min to compile for the v5e whatever its size
    sparse_sql = ("SELECT lo_suppkey, sum(lo_revenue) AS rev, "
                  "count(*) AS n FROM lineorder GROUP BY lo_suppkey "
                  "ORDER BY lo_suppkey LIMIT 20")
    s1, sm = pair(dense_group_budget=64)
    t0 = time.perf_counter()
    a = s1.sql(sparse_sql)
    t1 = time.perf_counter()
    b = sm.sql(sparse_sql)
    t2 = time.perf_counter()
    check_device_record(last_record(s1), "sparse 1-chip", 1)
    rec = last_record(sm)
    check_device_record(rec, "sparse fan-out", chips)
    check(rec.get("sparse") and digest(a) == digest(b),
          f"sparse fan-out: parity or path wrong: {rec}")
    say(f"mesh: sparse fan-out sha256 OK groups={rec.get('result_groups')} "
        f"merge={rec.get('sparse_merge')} cold 1-chip={t1 - t0:.1f}s "
        f"mesh={t2 - t1:.1f}s (compile included)")
    # the same table ranked: one chip applies the threshold in its program,
    # the mesh's broker merges four whole tables and ranks on the host; the
    # row count ties at the threshold, so equal frames mean the fan-out and
    # the merge keep the tie rule (the key's own order). The mesh's
    # per-chip program is the group-by's above, found in the compile cache
    topn_sql = sparse_sql.replace("ORDER BY lo_suppkey LIMIT 20",
                                  "ORDER BY n DESC LIMIT 100")
    a, b = s1.sql(topn_sql), sm.sql(topn_sql)
    rec1, rec = last_record(s1), last_record(sm)
    check_device_record(rec1, "sparse topN 1-chip", 1)
    check_device_record(rec, "sparse topN fan-out", chips)
    check(rec1.get("query_type") == rec.get("query_type") == "topN"
          and rec1.get("sparse") and rec.get("sparse")
          and rec1.get("topn_rows_fetched") == 100
          and len(a) == 100 and digest(a) == digest(b),
          f"sparse topN: parity or path wrong: 1-chip {rec1}, mesh {rec}")
    n = a["n"].to_numpy()
    say(f"mesh: sparse topN sha256 OK K={rec.get('topn_group_space')} "
        f"rows fetched 1-chip={rec1.get('topn_rows_fetched')} "
        f"mesh={rec.get('topn_rows_fetched')} "
        f"ties among the 100 kept={100 - len(set(n.tolist()))}")
    for e in (s1, sm):
        e.clear_cache()
        e.close()


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="every phase's row count; only with --allow-cpu "
                         "(a chip run is never cut)")
    ap.add_argument("--data-dir", default=None,
                    help="where generated parquet goes (default: a new "
                         "directory under the system temp dir); removed "
                         "at the end")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="CPU rehearsal of the script: never reports ok")
    args = ap.parse_args(argv)
    if args.rows is not None and not args.allow_cpu:
        ap.error("--rows is for the CPU rehearsal: give --allow-cpu too")

    # everything the run writes outside the checkout: libtpu's logs
    # (otherwise under the fixed /tmp/tpu_logs) and the generated parquet
    work_dir = tempfile.mkdtemp(prefix="tpu_olap_smoke_")
    os.makedirs(os.path.join(work_dir, "tpu_logs"))
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(work_dir, "tpu_logs"))
    import jax

    dev = jax.devices()[0]
    # count: the chips this run drives, of those JAX reports
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": min(args.chips, len(jax.devices()))}
    say(f"device: {device} (JAX reports {len(jax.devices())})")
    on_tpu = dev.platform == "tpu"
    passed = False
    data_dir = args.data_dir or os.path.join(work_dir, "data")
    t_start = time.perf_counter()
    try:
        check(on_tpu or args.allow_cpu,
              f"device: platform is {dev.platform!r}, not 'tpu' — no CPU "
              "carry-on (--allow-cpu is the rehearsal)")
        # on the chip only the product default "auto" runs, so no kernel
        # there can be an interpret-mode one; the CPU rehearsal forces
        # the Pallas kernel (interpret) to walk the same code
        use_pallas = "auto" if on_tpu else "force"
        from tpu_olap.utils.platform import configure_compile_cache
        cache_dir = configure_compile_cache()
        n0 = cache_entries(cache_dir)
        say(f"compile cache: {cache_dir} entries at start: {n0}")
        # parquet chunks are written by spawned workers that never touch
        # JAX; leave cores for the parent and the runtime
        workers = max(1, min(8, (os.cpu_count() or 2) - 2))
        if args.chips == 1:
            phase_answers(args.rows or ANSWER_ROWS, args.seed, use_pallas)
            phase_size(args.rows or SIZE_ROWS, args.seed, use_pallas,
                       data_dir, workers)
        else:
            phase_mesh(args.rows or MESH_ROWS, args.seed, use_pallas,
                       args.chips, data_dir, workers)
        say(f"compile cache: {cache_dir} entries at end: "
            f"{cache_entries(cache_dir)} (start: {n0})")
        passed = True
    except Exception:  # noqa: BLE001 — the boundary: report, then fail
        traceback.print_exc()
        say("FAILED — see the traceback on stderr")
    finally:
        for d in (data_dir, work_dir):
            shutil.rmtree(d, ignore_errors=True)
    say(f"total {time.perf_counter() - t_start:.0f}s")
    if passed and args.allow_cpu:
        say("rehearsal: all phases passed")
    ok = passed and on_tpu and not args.allow_cpu
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    if ok:
        return 0
    return EXIT_REHEARSAL if passed else EXIT_FAILED


if __name__ == "__main__":
    sys.exit(main())
