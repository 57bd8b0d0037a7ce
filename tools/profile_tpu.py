"""Per-query profile of the SSB suite on the live backend.

For each of the 13 queries: two warm-up runs (compile + packed-buffer
resize), then ITERS timed runs recording wall time next to the engine's
own per-query history metrics (execute/lower/assemble breakdown, result
group counts, packed-path cache hits). Also measures the raw
dispatch+fetch round-trip floor (a trivial jitted op fetched back) so
query times can be read net of the host round trip. Writes one JSON object to
PROFILE_TPU.json (or PROFILE_CPU.json off-hardware).

Usage: python tools/profile_tpu.py    [SSB_ROWS=... BENCH_ITERS=...]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def main():
    force_cpu = bool(os.environ.get("PROFILE_FORCE_CPU"))
    if force_cpu:
        from tpu_olap.utils.platform import force_cpu_platform
        force_cpu_platform()
    import jax
    import jax.numpy as jnp

    if jax.default_backend() == "cpu" and not force_cpu:
        # invoked expecting hardware: with no chip, refuse rather than
        # spend minutes on a CPU profile that would be filed as a TPU one
        print("backend resolved to cpu without PROFILE_FORCE_CPU; refusing",
              file=sys.stderr)
        sys.exit(3)

    backend = jax.default_backend()
    rows = int(os.environ.get("SSB_ROWS", 6_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 5))

    import bench as B
    from tpu_olap import Engine
    from tpu_olap.bench import QUERIES, register_ssb_parquet
    from tpu_olap.executor import EngineConfig

    paths, dims = B._prepare_dataset(rows, 0)
    eng = Engine(EngineConfig(hbm_budget_bytes=8 * 2**30))
    t0 = time.perf_counter()
    register_ssb_parquet(eng, paths, dims)
    ingest_s = time.perf_counter() - t0

    # raw round-trip floor: dispatch a trivial compiled op and fetch it
    one = jnp.ones((8, 128), jnp.float32)
    tiny = jax.jit(lambda x: x.sum())
    np.asarray(tiny(one))  # compile
    rtts = []
    for _ in range(7):
        t0 = time.perf_counter()
        np.asarray(tiny(one))
        rtts.append((time.perf_counter() - t0) * 1000)
    rtt_ms = float(np.percentile(rtts, 50))

    keep = ("execute_ms", "lower_ms", "assemble_ms", "result_groups",
            "result_cap", "packed", "jit_cache_hit", "query_type",
            "hbm_bytes", "strategy", "pallas")
    prof = {}
    for qname in sorted(QUERIES):
        sql = QUERIES[qname]
        eng.sql(sql)
        eng.sql(sql)
        walls = []
        for _ in range(iters):
            t0 = time.perf_counter()
            eng.sql(sql)
            walls.append((time.perf_counter() - t0) * 1000)
        h = eng.history[-1]
        plan = eng.planner.plan(sql)
        from tpu_olap.executor.lowering import lower
        phys = lower(plan.query, plan.entry.segments, eng.config)
        prof[qname] = {
            "wall_p50_ms": round(float(np.percentile(walls, 50)), 2),
            "wall_min_ms": round(min(walls), 2),
            "pallas_reason": phys.pallas_reason,
            "total_groups": phys.total_groups
            if phys.kind == "agg" else None,
            **{k: (round(v, 2) if isinstance(v, float) else v)
               for k, v in h.items() if k in keep},
        }
        print(f"[profile] {qname}: {prof[qname]}", file=sys.stderr)

    # non-aggregate / auxiliary paths: the SSB 13 are all aggregates, so
    # exercise scan, paged select, search, raw-IR passthrough, and theta
    # set ops on the live backend too (smoke + timing, oracle-light)
    aux = {}

    def run_aux(name, fn):
        # failures must not discard the already-collected 13-query
        # profile (these raw-IR paths bypass Engine.sql's structural
        # fallback, and chip time is too scarce to lose the run)
        try:
            fn()  # warm
            t0 = time.perf_counter()
            r = fn()
            aux[name] = {
                "wall_ms": round((time.perf_counter() - t0) * 1000, 2),
                "rows": len(r) if hasattr(r, "__len__") else None}
        except Exception as e:  # noqa: BLE001 — recorded, not fatal
            aux[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
        print(f"[profile] aux {name}: {aux[name]}", file=sys.stderr)

    run_aux("scan_limit", lambda: eng.sql(
        "SELECT lo_orderkey, lo_revenue FROM lineorder "
        "WHERE lo_discount = 5 LIMIT 100"))
    run_aux("select_page", lambda: eng.select_page(
        "lineorder", columns=("lo_orderkey", "lo_revenue"),
        page_size=64)[0])
    run_aux("search", lambda: eng.sql(
        "SEARCH DRUID DATASOURCE lineorder FOR 'MFGR#12' "
        "IN p_category LIMIT 10"))
    spec = json.dumps({
        "queryType": "timeseries", "granularity": "all",
        "aggregations": [
            {"type": "filtered", "name": "ta",
             "filter": {"type": "selector", "dimension": "lo_discount",
                        "value": 1},
             "aggregator": {"type": "thetaSketch", "name": "ta",
                            "fieldName": "lo_custkey", "size": 4096}},
            {"type": "filtered", "name": "tb",
             "filter": {"type": "selector", "dimension": "lo_discount",
                        "value": 2},
             "aggregator": {"type": "thetaSketch", "name": "tb",
                            "fieldName": "lo_custkey", "size": 4096}}],
        "postAggregations": [{
            "type": "thetaSketchEstimate", "name": "both",
            "field": {"type": "thetaSketchSetOp", "func": "INTERSECT",
                      "fields": [
                          {"type": "fieldAccess", "fieldName": "ta"},
                          {"type": "fieldAccess", "fieldName": "tb"}]}}]})
    run_aux("theta_setop", lambda: eng.sql(
        f"ON DRUID DATASOURCE lineorder EXECUTE QUERY '{spec}'"))

    out = {
        "backend": backend, "rows": rows, "ingest_s": round(ingest_s, 1),
        "rtt_floor_ms": round(rtt_ms, 2), "queries": prof, "aux": aux,
    }
    name = f"PROFILE_{'TPU' if backend != 'cpu' else 'CPU'}.json"
    with open(os.path.join(REPO, name), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"profile": name, "rtt_floor_ms": out["rtt_floor_ms"]}))


if __name__ == "__main__":
    main()
