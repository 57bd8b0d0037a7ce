"""Benchmark entry point. Prints ONE JSON line:
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Flagship metric: worst-case (max) p50 latency across the 13 SSB queries
Q1.1-Q4.3, executed end-to-end through the engine (SQL -> planner ->
lowered jitted program -> device -> result frame). The north-star target is
<500 ms p50 for EVERY query (BASELINE.json:2), so the binding statistic is
the max; vs_baseline = 500 / max_p50 (>1.0 beats the target).

Scale: SF1 by default (6M lineorder rows, BASELINE.json SF100's data path
at 1/100th rows) via the multi-file-parquet streaming-ingest path under an
ENFORCED host-RAM cap (RLIMIT_AS, BENCH_RAM_CAP_GB, default 24) and an
explicit HBM budget, with ingest wall time, process peak RSS, and ledger
eviction counts recorded in the detail — the at-scale data-path proof
(SURVEY.md §8.4 #4). Row count via SSB_ROWS, iterations via BENCH_ITERS.
Generated parquet is cached under .ssb_data/ keyed by (rows, seed) so
repeat runs skip generation.

On the chip machine JAX uses the TPU by default and this process owns it.
With no accelerator the bench EXITS NON-ZERO: it never carries on on the CPU
under the same metric name. `BENCH_FORCE_CPU=1` is the stated CPU rehearsal
(host platform, virtual devices for --mesh); its artifacts carry
"tpu_unavailable" so a CPU number self-explains why it is not a TPU number.
`python chip_smoke.py` is the quicker proof that the path starts on the chip.
"""

import json
import os
import resource
import sys
import time

import numpy as np

TARGET_MS = 500.0
REPO = os.path.dirname(os.path.abspath(__file__))


def _require_backend(n_devices: int = 1) -> str | None:
    """The backend gate of every bench mode. BENCH_FORCE_CPU=1 — the
    stated CPU rehearsal — pins the host platform (with `n_devices`
    virtual devices) and returns the reason stamped into artifacts as
    "tpu_unavailable". Otherwise the default backend must be an
    accelerator: with none the bench exits non-zero instead of printing
    a CPU number under the device metric's name. Must run before jax
    initializes its backends."""
    from tpu_olap.utils.platform import (ensure_host_device_count,
                                         env_flag, force_cpu_platform)
    if env_flag("BENCH_FORCE_CPU"):
        ensure_host_device_count(n_devices)
        force_cpu_platform()
        return "BENCH_FORCE_CPU=1 (explicit CPU run)"
    import jax
    if jax.default_backend() == "cpu":
        raise SystemExit(
            "bench.py: the default JAX backend is cpu — no accelerator "
            "to measure. Run it on the chip, or set BENCH_FORCE_CPU=1 "
            "for the CPU rehearsal (never a device number).")
    return None


def _peak_rss_mb() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024


def _prepare_dataset(rows: int, seed: int) -> tuple[list, dict]:
    """Generate (or reuse cached) multi-file SSB parquet at `rows` scale.
    Dimension tables are persisted alongside the fact files so the
    cache-hit path reloads the exact frames the fact's foreign keys were
    drawn against (no re-derivation that could drift)."""
    import pandas as pd

    from tpu_olap.bench.ssb import write_ssb_parquet

    data_dir = os.environ.get(
        "SSB_DATA_DIR",
        os.path.join(REPO, ".ssb_data", f"rows{rows}-seed{seed}"))
    manifest = os.path.join(data_dir, "MANIFEST.json")

    def dim_path(t):
        return os.path.join(data_dir, f"dim-{t}.parquet")

    if os.path.exists(manifest):
        with open(manifest) as f:
            m = json.load(f)
        if m.get("rows") == rows and m.get("seed") == seed \
                and m.get("dims") and all(
                os.path.exists(p) for p in m["paths"]) and all(
                os.path.exists(dim_path(t)) for t in m["dims"]):
            dims = {t: pd.read_parquet(dim_path(t)) for t in m["dims"]}
            return m["paths"], dims
    paths, dims = write_ssb_parquet(data_dir, rows, seed=seed)
    for t, df in dims.items():
        df.to_parquet(dim_path(t), index=False)
    with open(manifest, "w") as f:
        json.dump({"rows": rows, "seed": seed, "paths": paths,
                   "dims": sorted(dims)}, f)
    return paths, dims


def _setup(extra_cfg: dict | None = None):
    """Shared bench preamble: backend gate, RAM-capped dataset prep +
    streaming ingest, engine construction. Returns (engine, ctx) where
    ctx carries the numbers both bench modes stamp into artifacts.
    `extra_cfg` overlays EngineConfig fields (the cache bench enables
    the semantic result cache; the latency/throughput benches keep the
    default-off caches so every timed execution measures real
    compute)."""
    tpu_unavailable = _require_backend()
    import jax

    backend = jax.default_backend()
    # progress breadcrumbs on STDERR (stdout stays one JSON line): a
    # run cut at its time limit still shows how far it got
    def note(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    note(f"backend={backend}")
    rows = int(os.environ.get("SSB_ROWS", 6_000_000))
    iters = int(os.environ.get("BENCH_ITERS", 5))
    seed = 0

    # Enforced host-RAM cap over the DATA PATH — generation and streaming
    # ingest run under it, so an unbounded materialization crashes the
    # bench rather than silently leaning on a 125 GB host (VERDICT
    # round-2 task #1). The soft limit is restored before the query
    # phase: a finite RLIMIT_AS makes XLA:CPU's arena reservation fail
    # into small-chunk mode, slowing query execution ~1.7x — the cap
    # proves ingest boundedness, not query-allocator behavior.
    cap_gb = float(os.environ.get("BENCH_RAM_CAP_GB", 24))
    cap = int(cap_gb * 2**30)
    soft0, hard0 = resource.getrlimit(resource.RLIMIT_AS)
    if hard0 != resource.RLIM_INFINITY:
        cap = min(cap, hard0)  # soft may never exceed a finite hard limit
    resource.setrlimit(resource.RLIMIT_AS, (cap, hard0))

    from tpu_olap import Engine
    from tpu_olap.bench import QUERIES, register_ssb_parquet
    from tpu_olap.executor import EngineConfig

    t0 = time.perf_counter()
    paths, dims = _prepare_dataset(rows, seed)
    gen_s = time.perf_counter() - t0
    note(f"dataset ready ({gen_s:.1f}s)")

    # HBM budget: enough for the SSB working set but bounded, so the
    # ledger's accounting (and eviction under pressure) is always live.
    hbm_budget = int(os.environ.get(
        "BENCH_HBM_BUDGET_BYTES", 8 * 2**30))
    # SSB_USE_PALLAS=never|force|auto: a Pallas-vs-XLA-scatter
    # comparison on the same data (auto = Pallas on TPU where eligible).
    # Validated HERE: failing after a full ingest on a typo would waste
    # the chip call.
    use_pallas = os.environ.get("SSB_USE_PALLAS", "auto")
    if use_pallas not in ("auto", "force", "never"):
        raise SystemExit(
            f"SSB_USE_PALLAS={use_pallas!r}: must be auto|force|never")
    # history_limit raised: the bench slices eng.history by saved offsets
    # (per-phase batch attribution), which a steady-state ring eviction
    # would shift mid-run; the bench process is short-lived anyway
    eng = Engine(EngineConfig(hbm_budget_bytes=hbm_budget,
                              use_pallas=use_pallas,
                              history_limit=1_000_000,
                              **(extra_cfg or {})))
    t0 = time.perf_counter()
    register_ssb_parquet(eng, paths, dims)
    ingest_s = time.perf_counter() - t0
    note(f"ingest done ({ingest_s:.1f}s)")
    ingest_peak_rss_mb = _peak_rss_mb()
    resource.setrlimit(resource.RLIMIT_AS, (soft0, hard0))  # query phase
    seg = eng.catalog.get("lineorder").segments
    stored_mb = sum(c.nbytes for s in seg.segments
                    for c in s.columns.values()) // 2**20
    return eng, {
        "note": note, "backend": backend, "rows": rows, "iters": iters,
        "tpu_unavailable": tpu_unavailable, "use_pallas": use_pallas,
        "cap_gb": cap_gb, "gen_s": gen_s, "ingest_s": ingest_s,
        "ingest_peak_rss_mb": ingest_peak_rss_mb, "stored_mb": stored_mb,
        "hbm_budget": hbm_budget, "paths": paths, "dims": dims,
    }


class _OneDispatchFault:
    """bench --inject-faults: when armed, fail exactly the FIRST
    dispatch attempt of the next query — the retry layer answers, and
    the wall-clock difference vs the clean run is the recovery cost
    (cache purge + re-upload + recompile where needed)."""

    stages = ("dispatch",)

    def __init__(self):
        self.armed = False

    def __call__(self, stage, attempt):
        if self.armed and attempt == 0:
            self.armed = False
            raise RuntimeError("bench-injected dispatch fault")


def _fault_overhead(eng, iters: int, note):
    """Per-query p50 with one injected dispatch fault per execution
    (banked next to the clean p50 so robustness cost shows up in the
    perf trajectory instead of being invisible). Requires
    dispatch_retries >= 1 (the engine default) so the retry — not the
    pandas fallback — answers."""
    from tpu_olap.bench import QUERIES

    inj = _OneDispatchFault()
    prev = eng.config.fault_injector
    eng.config.fault_injector = inj
    fault_ms, fell_back = {}, {}
    try:
        for qname in sorted(QUERIES):
            sql = QUERIES[qname]
            times = []
            n_fb = 0
            for _ in range(iters):
                n0 = len(eng.history)
                inj.armed = True
                t0 = time.perf_counter()
                eng.sql(sql)
                times.append((time.perf_counter() - t0) * 1000)
                n_fb += sum(1 for m in eng.history[n0:]
                            if m.get("query_type") == "fallback")
            fault_ms[qname] = round(float(np.percentile(times, 50)), 3)
            if n_fb:
                fell_back[qname] = n_fb
            note(f"{qname} faulted p50={fault_ms[qname]}ms"
                 + (f" (fallback x{n_fb})" if n_fb else ""))
    finally:
        eng.config.fault_injector = prev
    return fault_ms, fell_back


def main(span_summary: bool = False, inject_faults: int | None = None,
         trace_out: str | None = None,
         pipeline_depth: int | None = None):
    eng, ctx = _setup(
        {} if pipeline_depth is None
        else {"pipeline_depth": pipeline_depth})
    note = ctx["note"]
    backend, rows, iters = ctx["backend"], ctx["rows"], ctx["iters"]
    tpu_unavailable, use_pallas = ctx["tpu_unavailable"], ctx["use_pallas"]

    from tpu_olap.bench import QUERIES
    from tpu_olap.utils.platform import env_flag
    import jax

    # BENCH_RESULT_DIGEST=1 records a per-query sha256 over the rendered
    # result frame — lets two runs of the same scale prove identical
    # answers (e.g. an eviction-churn run vs the default-budget run)
    # without shipping result rows in the artifact.
    want_digest = env_flag("BENCH_RESULT_DIGEST")
    digests = {}

    # Dispatch+fetch round-trip floor: a trivial compiled op, fetched
    # back (~66-68 ms host round trip in the 2026-07-31 setup; not
    # re-measured since). Banking it per-artifact makes device-only
    # compute a first-class metric (wall p50 minus the floor) so compute
    # regressions cannot hide under the round-trip term.
    import jax.numpy as jnp
    tiny = jax.jit(lambda x: x + 1)
    one = jnp.zeros((8,), jnp.int32)
    np.asarray(tiny(one))  # compile
    rtts = []
    for _ in range(max(iters, 5)):
        t0 = time.perf_counter()
        np.asarray(tiny(one))
        rtts.append((time.perf_counter() - t0) * 1000)
    rtt_floor = round(float(np.percentile(rtts, 50)), 3)
    note(f"rtt_floor={rtt_floor}ms")

    detail = {}
    spread = {}  # per-query min/max over the timed iters (VERDICT r3
    #              weak #2: single-sample artifacts need variance data)
    exec_ms = {}  # per-query engine-recorded execute phase (device
    #               dispatch+fetch, excludes plan/lower/assemble)
    over_floor = {}  # execute minus the transport floor: the honest
    #                  per-query compute term
    phase_ms = {}  # --span-summary: per-query per-phase p50 from the
    #                span tree (obs.trace) — parse/plan/prepare/dispatch/
    #                host-transfer/assemble attribution in the artifact
    slow_traces = {}  # --trace-out: (ms, Trace) of each query's slowest
    #                   timed iteration, exported as one Chrome trace so
    #                   profiles get banked alongside the numbers
    for qname in sorted(QUERIES):
        sql = QUERIES[qname]
        # Warm twice: the first run compiles and observes the true group
        # count, which re-sizes the packed result buffer; the second run
        # compiles the re-sized template so timed runs are all cache hits.
        eng.sql(sql)
        res = eng.sql(sql)
        assert eng.last_plan.rewritten, (qname,
                                         eng.last_plan.fallback_reason)
        if want_digest:
            import hashlib
            digests[qname] = hashlib.sha256(
                res.to_csv(float_format="%.6g").encode()).hexdigest()[:16]
        times = []
        execs = []
        phases: dict = {}
        for _ in range(iters):
            n0 = len(eng.history)
            t0 = time.perf_counter()
            eng.sql(sql)
            times.append((time.perf_counter() - t0) * 1000)
            if trace_out is not None and eng.tracer.last is not None:
                prev = slow_traces.get(qname)
                if prev is None or times[-1] > prev[0]:
                    slow_traces[qname] = (times[-1], eng.tracer.last)
            # only records THIS dispatch appended: a fallback-served
            # iteration must not re-report a stale device timing
            fresh = [m for m in eng.history[n0:] if "execute_ms" in m]
            if fresh:
                execs.append(fresh[-1]["execute_ms"])
            if span_summary and eng.tracer.last is not None:
                from tpu_olap.obs.trace import phase_totals
                for ph, ms in phase_totals(eng.tracer.last).items():
                    phases.setdefault(ph, []).append(ms)
        if span_summary:
            phase_ms[qname] = {
                ph: round(float(np.percentile(v, 50)), 3)
                for ph, v in sorted(phases.items())}
        detail[qname] = round(float(np.percentile(times, 50)), 3)
        spread[qname] = {"min": round(min(times), 3),
                         "max": round(max(times), 3)}
        if execs:
            exec_ms[qname] = round(float(np.percentile(execs, 50)), 3)
            over_floor[qname] = round(max(0.0, exec_ms[qname] - rtt_floor),
                                      3)
        note(f"{qname} p50={detail[qname]}ms "
             f"[{spread[qname]['min']}..{spread[qname]['max']}] "
             f"exec={exec_ms.get(qname)}ms")

    if trace_out is not None:
        # one Chrome-trace file with each flight's slowest query as its
        # own named row — open in Perfetto next to the BENCH json
        from tpu_olap.obs.profile import chrome_trace
        traces = [slow_traces[q][1] for q in sorted(slow_traces)]
        with open(trace_out, "w") as f:
            json.dump(chrome_trace(traces), f)
        note(f"chrome trace written: {trace_out} "
             f"({len(traces)} slowest-iteration traces)")

    fault_detail = None
    if inject_faults:
        fault_ms, fell_back = _fault_overhead(eng, inject_faults, note)
        overhead = {q: round(max(0.0, fault_ms[q] - detail[q]), 3)
                    for q in fault_ms}
        fault_detail = {
            "iters": inject_faults,
            "per_query_p50_fault_ms": fault_ms,
            "per_query_recovery_overhead_ms": overhead,
            "worst_recovery_overhead_ms": round(
                max(overhead.values()), 3),
            **({"fallback_served": fell_back} if fell_back else {}),
        }

    ledger = eng.runner._hbm_ledger
    worst = max(detail.values())
    print(json.dumps({
        "metric": "ssb_13q_p50_max_ms",
        "value": round(worst, 3),
        "unit": "ms",
        "vs_baseline": round(TARGET_MS / worst, 2),
        "detail": {
            "rows": rows, "backend": backend,
            "use_pallas": use_pallas,
            **({"tpu_unavailable": tpu_unavailable}
               if tpu_unavailable else {}),
            "rtt_floor_ms": rtt_floor,
            "per_query_p50_ms": detail,
            "per_query_spread_ms": spread,
            "per_query_execute_ms": exec_ms,
            "per_query_over_floor_ms": over_floor,
            "worst_over_floor_ms": round(max(over_floor.values()), 3)
            if over_floor else None,
            "iters": iters,
            "ram_cap_gb": ctx["cap_gb"],
            "generate_s": round(ctx["gen_s"], 1),
            "ingest_s": round(ctx["ingest_s"], 1),
            "ingest_peak_rss_mb": ctx["ingest_peak_rss_mb"],
            "segment_store_mb": ctx["stored_mb"],
            "hbm": {"budget_bytes": ctx["hbm_budget"],
                    "bytes_in_use": ledger.bytes_in_use,
                    "evictions": ledger.evictions,
                    # telemetry-plane census (ISSUE 17): high-watermark
                    # growth between runs is a regression the compare
                    # gate catches even when steady-state bytes match
                    "high_watermark_bytes": ledger.watermarks()["total"],
                    "per_chip_high_watermark_bytes":
                        ledger.watermarks()["per_chip"]},
            "alerts": eng.runner.sentinel.counts(),
            **({"per_query_phase_p50_ms": phase_ms}
               if span_summary else {}),
            **({"trace_out": trace_out} if trace_out else {}),
            **({"fault_injection": fault_detail}
               if fault_detail else {}),
            **({"result_digests": digests} if want_digest else {}),
        },
    }))


def _concurrency_main(n_clients: int) -> int:
    """`bench.py --concurrency N`: shared-scan batch throughput A/B.

    N clients replay the 13-query SSB dashboard loop concurrently — the
    broker scenario the batch executor exists for (every user's panel
    refresh re-issues the same queries). Phase A dispatches them
    sequentially (the dispatch lock serializes: N concurrent queries =
    N full scans). Phase B turns on the request coalescer
    (EngineConfig.batch_window_ms): concurrent callers ride ONE fused
    shared-scan dispatch — identical in-flight queries scan once,
    distinct compatible ones fuse into one device pass. Banks the
    throughput ratio to BENCH_BATCH.json with per-query parity checked
    against the sequential-path oracle (frame.equals — bitwise)."""
    import threading

    eng, ctx = _setup()
    note = ctx["note"]
    from tpu_olap.bench import QUERIES
    qnames = sorted(QUERIES)
    rounds = int(os.environ.get("BENCH_CONC_ROUNDS", 3))
    # window sized to re-capture the whole client cohort after each
    # batch completes (clients wake together, then spend ~10-40 ms of
    # GIL-bound frame conversion before re-submitting): ~25 ms keeps
    # the dashboard loop in lockstep, so batches stay large and mostly
    # identical (dedupe, no fresh fused compiles); 5 ms shears the
    # cohort into small mixed batches
    window_ms = float(os.environ.get("BENCH_BATCH_WINDOW_MS", 25.0))

    # warm twice (compile + packed-cap resize) and keep the sequential
    # result as the parity oracle
    ref = {}
    for qn in qnames:
        eng.sql(QUERIES[qn])
        ref[qn] = eng.sql(QUERIES[qn])
        assert eng.last_plan.rewritten, (qn,
                                         eng.last_plan.fallback_reason)

    def run_phase(tag, timed_rounds):
        errs, frames = [], {}

        def client(ci):
            for _ in range(timed_rounds):
                for qn in qnames:
                    try:
                        frames[(ci, qn)] = eng.sql(QUERIES[qn])
                    except Exception as e:  # noqa: BLE001 — banked
                        errs.append((qn, repr(e)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        n = n_clients * timed_rounds * len(qnames)
        note(f"{tag}: {n} queries in {wall:.1f}s ({n / wall:.1f} qps), "
             f"errors={len(errs)}")
        return wall, n, frames, errs

    eng.runner.set_batch_window(0)
    wall_seq, n_seq, frames_seq, errs_seq = run_phase("sequential", rounds)
    eng.runner.set_batch_window(window_ms)
    run_phase("batched-warmup", 1)  # compile common fused compositions
    h0 = len(eng.history)
    wall_bat, n_bat, frames_bat, errs_bat = run_phase("batched", rounds)
    hist = eng.history[h0:]

    bad = sorted({k[1] for k, f in frames_bat.items()
                  if not f.equals(ref[k[1]])})
    seq_bad = sorted({k[1] for k, f in frames_seq.items()
                      if not f.equals(ref[k[1]])})
    batches = {}
    for m in hist:
        if "batch_id" in m:
            batches.setdefault(m["batch_id"], []).append(m)
    n_dedup = sum(1 for m in hist if m.get("batch_dedup"))
    sizes = [recs[0]["batch_size"] for recs in batches.values()]
    shared = [recs[0].get("scan_ms_shared", 0.0)
              for recs in batches.values()]
    agg = [m.get("agg_ms", 0.0) for m in hist if "agg_ms" in m]

    qps_seq = n_seq / wall_seq
    qps_bat = n_bat / wall_bat
    speedup = qps_bat / qps_seq
    parity_ok = not bad and not seq_bad and not errs_seq and not errs_bat
    out = {
        "metric": f"ssb_batch_throughput_speedup_c{n_clients}",
        "value": round(speedup, 2),
        "unit": "x",
        # target: >= 2x aggregate throughput at this concurrency
        "vs_baseline": round(speedup / 2.0, 2),
        "detail": {
            "rows": ctx["rows"], "backend": ctx["backend"],
            **({"tpu_unavailable": ctx["tpu_unavailable"]}
               if ctx["tpu_unavailable"] else {}),
            "concurrency": n_clients, "rounds": rounds,
            "batch_window_ms": window_ms,
            "sequential": {"queries": n_seq, "wall_s": round(wall_seq, 2),
                           "qps": round(qps_seq, 2),
                           "errors": len(errs_seq)},
            "batched": {"queries": n_bat, "wall_s": round(wall_bat, 2),
                        "qps": round(qps_bat, 2),
                        "errors": len(errs_bat)},
            "parity_ok": parity_ok,
            "parity_mismatch_queries": bad,
            "batches": len(batches),
            "batch_size_mean": round(float(np.mean(sizes)), 2)
            if sizes else None,
            "batch_size_max": max(sizes) if sizes else None,
            "deduped_queries": n_dedup,
            "fused_dispatches": sum(
                1 for recs in batches.values()
                if recs[0].get("batch_legs", 1) > 1),
            "fused_compiles": sum(
                1 for recs in batches.values()
                if recs[0].get("batch_legs", 1) > 1
                and not recs[0].get("jit_cache_hit")),
            "scan_ms_shared_total": round(float(np.sum(shared)), 1),
            "agg_ms_total": round(float(np.sum(agg)), 1),
        },
    }
    with open(os.path.join(REPO, "BENCH_BATCH.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if parity_ok else 1


def _cache_main(mode: str) -> int:
    """`bench.py --cache-mode cold|warm|mixed`: the semantic-result-
    cache A/B (docs/CACHING.md). COLD is the honest baseline — both
    tiers DISABLED, so it equals the plain latency bench's execution
    and tier-1 population overhead cannot inflate the speedup. WARM
    enables the caches, primes, and times repeats (tier-2 serving);
    the headline value is worst cold p50 / worst warm p50. Mode
    `mixed` adds two more phases: a WINDOW SWEEP that slides a time
    window across the fact table with tier 2 off, so the per-segment
    tier's partial-recompute path is exercised warm (hits > 0 banked),
    and a FRESH-INGEST phase that re-registers a modified dataset (a
    file subset — genuinely different rows) and proves the
    invalidation contract: zero stale hits, recompute answers matching
    the independent pandas fallback. Parity (`bench.parity`) is
    checked in every state so a cache bug that serves a stale or
    mis-merged result fails the artifact, not just a unit test."""
    from tpu_olap.bench import QUERIES, register_ssb_parquet
    from tpu_olap.bench.parity import ParityError, check_query

    eng, ctx = _setup()  # caches start OFF: the cold phase is honest
    note = ctx["note"]
    iters = ctx["iters"]
    qnames = sorted(QUERIES)
    cfg = eng.config
    rc = eng.runner.result_cache

    def set_tiers(full: bool, segment: bool):
        # ResultCache reads the live config, so flipping the knobs
        # switches tiers between phases without rebuilding the engine
        cfg.result_cache_enabled = full
        cfg.segment_cache_enabled = segment

    # warm the compile caches so cold numbers measure scans, not XLA
    # builds (same convention as the latency bench)
    for qn in qnames:
        eng.sql(QUERIES[qn])
        eng.sql(QUERIES[qn])
        assert eng.last_plan.rewritten, (qn, eng.last_plan.fallback_reason)

    def timed_runs(qn, n):
        times, hits = [], 0
        for _ in range(n):
            n0 = len(eng.history)
            t0 = time.perf_counter()
            eng.sql(QUERIES[qn])
            times.append((time.perf_counter() - t0) * 1000)
            hits += sum(1 for m in eng.history[n0:] if m.get("cache_hit"))
        return times, hits

    cold, warm, hit_rate_cold, hit_rate_warm = {}, {}, {}, {}
    parity = {"cold": True, "warm": True, "window_sweep": None,
              "fresh_ingest": None}
    parity_errors = []

    def check_parity(tag, sql):
        try:
            check_query(eng, sql, label=tag)
            return True
        except ParityError as e:
            parity_errors.append(str(e)[:300])
            return False

    for qn in qnames:
        times, hits = timed_runs(qn, iters)
        cold[qn] = round(float(np.percentile(times, 50)), 3)
        hit_rate_cold[qn] = round(hits / iters, 3)
        if not check_parity(f"cold:{qn}", QUERIES[qn]):
            parity["cold"] = False
        note(f"{qn} cold p50={cold[qn]}ms")

    if mode in ("warm", "mixed"):
        set_tiers(True, True)
        for qn in qnames:
            eng.sql(QUERIES[qn])  # prime
            times, hits = timed_runs(qn, iters)
            warm[qn] = round(float(np.percentile(times, 50)), 3)
            hit_rate_warm[qn] = round(hits / iters, 3)
            if not check_parity(f"warm:{qn}", QUERIES[qn]):
                parity["warm"] = False
            note(f"{qn} warm p50={warm[qn]}ms "
                 f"(hit rate {hit_rate_warm[qn]})")

    sweep = None
    if mode == "mixed":
        # tier-1 window sweep: tier 2 OFF so repeats cannot shortcut to
        # the full-result tier; a monthly-advancing window over the
        # fact table makes each step a PARTIAL tier-1 hit (the overlap
        # serves from cached per-segment partials, only the new tail
        # recomputes in one device pass)
        set_tiers(False, True)
        # month-partitioned re-ingest: the sweep's month-boundary
        # windows then COVER whole segments, which is what makes the
        # per-segment tier able to store/serve them (auto partitioning
        # at small scales resolves coarser and every segment would
        # straddle the window edge)
        t0 = time.perf_counter()
        eng.register_table("lineorder", list(ctx["paths"]),
                           time_column="lo_orderdate_ts",
                           time_partition="month")
        note(f"sweep re-ingest (month partitions): "
             f"{time.perf_counter() - t0:.1f}s")
        rc.clear()
        wsql = ("SELECT d_year, sum(lo_revenue) AS rev FROM lineorder "
                "WHERE lo_orderdate_ts >= TIMESTAMP '{lo}' AND "
                "lo_orderdate_ts < TIMESTAMP '{hi}' "
                "GROUP BY d_year ORDER BY d_year")
        windows = [(f"1993-{m:02d}-01",
                    f"1994-{m:02d}-01") for m in range(1, 7)]
        steps, sweep_ok = [], True
        for i, (lo, hi) in enumerate(windows):
            sql = wsql.format(lo=lo, hi=hi)
            n0 = len(eng.history)
            t0 = time.perf_counter()
            eng.sql(sql)
            ms = (time.perf_counter() - t0) * 1000
            recs = [m for m in eng.history[n0:]
                    if "segments_computed" in m]
            rec = recs[-1] if recs else {}
            steps.append({
                "window": f"{lo}/{hi}", "ms": round(ms, 3),
                "segments_cached": rec.get("segments_cached", 0),
                "segments_computed": rec.get("segments_computed", 0)})
            if not check_parity(f"sweep:{i}", sql):
                sweep_ok = False
        served = sum(st["segments_cached"] for st in steps[1:])
        parity["window_sweep"] = sweep_ok and served > 0
        sweep = {"steps": steps,
                 "segments_served_from_cache": served,
                 "first_step_ms": steps[0]["ms"],
                 "steady_p50_ms": round(float(np.percentile(
                     [st["ms"] for st in steps[1:]], 50)), 3)}
        note(f"window sweep: {served} segment serves from cache, "
             f"first={sweep['first_step_ms']}ms "
             f"steady p50={sweep['steady_p50_ms']}ms")

    fresh = None
    if mode == "mixed":
        # fresh ingest with genuinely different data: a subset of the
        # parquet files (every SF1+ dataset has several). A stale cache
        # entry served after this would answer from the OLD rows and
        # fail parity against the fallback, which reads the new frame.
        set_tiers(True, True)
        paths = ctx["paths"]
        sub = paths[:-1] if len(paths) > 1 else paths
        t0 = time.perf_counter()
        register_ssb_parquet(eng, sub, ctx["dims"])
        reingest_s = time.perf_counter() - t0
        stale_hits = 0
        fresh_ok = True
        fresh_ms = {}
        for qn in qnames:
            n0 = len(eng.history)
            t0 = time.perf_counter()
            eng.sql(QUERIES[qn])
            fresh_ms[qn] = round((time.perf_counter() - t0) * 1000, 3)
            stale_hits += sum(1 for m in eng.history[n0:]
                              if m.get("cache_hit"))
            if not check_parity(f"fresh:{qn}", QUERIES[qn]):
                fresh_ok = False
        parity["fresh_ingest"] = fresh_ok and stale_hits == 0
        fresh = {"files": len(sub), "reingest_s": round(reingest_s, 1),
                 "stale_hits": stale_hits,
                 "per_query_p50_ms": fresh_ms}
        note(f"fresh-ingest: stale_hits={stale_hits} parity={fresh_ok}")

    worst_cold = max(cold.values())
    parity_ok = all(v for v in parity.values() if v is not None)
    if warm:
        speedup = {qn: round(cold[qn] / max(warm[qn], 1e-3), 2)
                   for qn in warm}
        worst_warm = max(warm.values())
        metric = "ssb_cache_warm_speedup"
        value = round(worst_cold / worst_warm, 2)
        vs_baseline = round(value / 5.0, 2)  # target: >= 5x (ISSUE 9)
    else:
        # cold-only mode measures the baseline, not a speedup: bank it
        # under its own metric name instead of a misleading 0x
        speedup, metric = {}, "ssb_cache_cold_p50_max_ms"
        value = round(worst_cold, 3)
        vs_baseline = round(TARGET_MS / worst_cold, 2)
    out = {
        "metric": metric,
        "value": value,
        "unit": "x" if warm else "ms",
        "vs_baseline": vs_baseline,
        "detail": {
            "mode": mode, "rows": ctx["rows"], "iters": iters,
            "backend": ctx["backend"],
            **({"tpu_unavailable": ctx["tpu_unavailable"]}
               if ctx["tpu_unavailable"] else {}),
            # cold == plain execution (caches off): comparable to the
            # latency bench's per-query p50s
            "per_query_p50_ms": cold,
            "cache": {
                "per_query_cold_p50_ms": cold,
                "per_query_warm_p50_ms": warm,
                "per_query_speedup": speedup,
                "min_speedup": min(speedup.values()) if speedup else None,
                "per_query_hit_rate": hit_rate_warm,
                "per_query_cold_hit_rate": hit_rate_cold,
            },
            "parity": parity,
            "parity_ok": parity_ok,
            **({"parity_errors": parity_errors[:5]}
               if parity_errors else {}),
            **({"segment_tier_window_sweep": sweep} if sweep else {}),
            **({"fresh_ingest": fresh} if fresh else {}),
            "cache_snapshot": rc.snapshot(),
        },
    }
    with open(os.path.join(REPO, "BENCH_CACHE.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if parity_ok else 1


def _cube_main(mode: str) -> int:
    """`bench.py --cube-mode off|auto`: the materialized-rollup A/B
    (docs/CUBES.md). BASE is the honest floor — the rewrite pass
    disabled and both semantic-cache tiers off, so every timed run is a
    real base-table execution. AUTO then closes the advisor loop on the
    bench's own traffic: the warm-up runs populate the workload
    profiler, `cube_specs_from_workload` turns its ranked rollup
    recommendations into specs, the materializer builds them, and the
    same 13 SSB queries re-run — queries the rewrite covers serve from
    cube partials (path="cube"). Banks BENCH_CUBES.json with per-query
    base-vs-cube p50, materialization cost + storage bytes, coverage,
    and parity: sha256 result digests must MATCH the base path exactly
    for the all-integer SSB aggregates, and every covered query is
    additionally checked against the independent pandas fallback."""
    import hashlib

    from tpu_olap.bench import QUERIES
    from tpu_olap.bench.parity import ParityError, check_query

    eng, ctx = _setup({"cube_auto_refresh": False})
    note = ctx["note"]
    iters = ctx["iters"]
    qnames = sorted(QUERIES)
    eng.config.cube_rewrite_enabled = False

    def digest(frame) -> str:
        return hashlib.sha256(
            frame.to_csv(float_format="%.6g").encode()).hexdigest()[:16]

    # warm compiles AND the workload profiler (the advisor's demand
    # signal is the bench's own traffic — the loop the ISSUE closes)
    for qn in qnames:
        eng.sql(QUERIES[qn])
        eng.sql(QUERIES[qn])
        assert eng.last_plan.rewritten, (qn, eng.last_plan.fallback_reason)

    def timed(qn, n):
        times = []
        cube_serves = 0
        for _ in range(n):
            n0 = len(eng.history)
            t0 = time.perf_counter()
            eng.sql(QUERIES[qn])
            times.append((time.perf_counter() - t0) * 1000)
            cube_serves += sum(1 for m in eng.history[n0:]
                               if m.get("path") == "cube")
        return times, cube_serves

    base, base_digest = {}, {}
    for qn in qnames:
        times, _ = timed(qn, iters)
        base[qn] = round(float(np.percentile(times, 50)), 3)
        base_digest[qn] = digest(eng.sql(QUERIES[qn]))
        note(f"{qn} base p50={base[qn]}ms")

    out = {
        "metric": "ssb_cube_base_p50_max_ms",
        "value": round(max(base.values()), 3),
        "unit": "ms",
        "vs_baseline": round(TARGET_MS / max(base.values()), 2),
        "detail": {
            "mode": mode, "rows": ctx["rows"], "iters": iters,
            "backend": ctx["backend"],
            **({"tpu_unavailable": ctx["tpu_unavailable"]}
               if ctx["tpu_unavailable"] else {}),
            "per_query_base_p50_ms": base,
        },
    }
    if mode == "auto":
        from tpu_olap.cubes import cube_specs_from_workload
        rows = eng.runner.workload.snapshot()
        specs, notes = cube_specs_from_workload(rows, eng,
                                                top=len(qnames))
        t0 = time.perf_counter()
        built, build_errors = [], {}
        for s in specs:
            try:
                e = eng.create_cube(s)
                built.append(s.name)
                note(f"built {s.name}: {e.data.n_rows} rows @ "
                     f"{s.granularity} in {e.build_ms:.0f}ms")
            except Exception as ex:  # noqa: BLE001 — per-spec isolation
                build_errors[s.name] = f"{type(ex).__name__}: {ex}"
                note(f"build FAILED {s.name}: {build_errors[s.name]}")
        build_s = time.perf_counter() - t0

        eng.config.cube_rewrite_enabled = True
        # the independent pandas-fallback oracle is O(full scan) per
        # query — affordable at SF1, hours at SF10+. Digest equality
        # against the base device path is checked at EVERY scale.
        deep_parity = ctx["rows"] <= 10_000_000
        cube_ms, covered, digest_ok, parity_errors = {}, [], {}, []
        speedup = {}
        for qn in qnames:
            eng.sql(QUERIES[qn])  # settle (fold layout warm)
            times, serves = timed(qn, iters)
            cube_ms[qn] = round(float(np.percentile(times, 50)), 3)
            is_covered = serves == iters
            digest_ok[qn] = digest(eng.sql(QUERIES[qn])) \
                == base_digest[qn]
            if is_covered:
                covered.append(qn)
                speedup[qn] = round(base[qn] / max(cube_ms[qn], 1e-3),
                                    2)
                if deep_parity:
                    try:
                        check_query(eng, QUERIES[qn],
                                    label=f"cube:{qn}")
                    except ParityError as e:
                        parity_errors.append(str(e)[:300])
            note(f"{qn} cube p50={cube_ms[qn]}ms covered={is_covered} "
                 f"digest_ok={digest_ok[qn]}"
                 + (f" speedup={speedup.get(qn)}x" if is_covered
                    else ""))
        parity_ok = all(digest_ok.values()) and not parity_errors \
            and bool(covered)
        worst_speedup = min(speedup.values()) if speedup else 0.0
        snap = eng.cubes.snapshot()  # after serving: serve_count live
        storage = sum(r["storage_bytes"] + r["sketch_bytes"]
                      for r in snap if r["status"] == "ready")
        out["metric"] = "ssb_cube_covered_speedup_min"
        out["value"] = worst_speedup
        out["unit"] = "x"
        out["vs_baseline"] = round(worst_speedup / 10.0, 2)  # >=10x
        out["detail"].update({
            "deep_parity_vs_fallback": deep_parity,
            "advisor_specs": len(specs),
            "advisor_notes": notes,
            "cubes_built": built,
            **({"build_errors": build_errors} if build_errors else {}),
            "materialize_s": round(build_s, 2),
            "cube_storage_bytes": storage,
            "cubes": snap,
            "per_query_cube_p50_ms": cube_ms,
            "per_query_speedup": speedup,
            "covered_queries": covered,
            "uncovered_queries": [q for q in qnames
                                  if q not in covered],
            "digest_match": digest_ok,
            "parity_ok": parity_ok,
            **({"parity_errors": parity_errors[:5]}
               if parity_errors else {}),
        })
    with open(os.path.join(REPO, "BENCH_CUBES.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    if mode != "auto":
        return 0
    return 0 if parity_ok else 1


def _mesh_main(n_devices: int) -> int:
    """`bench.py --mesh N`: the sharded-serving A/B (docs/TPU_NOTES.md
    "sharded serving"), banking MULTICHIP_r06.json. The 13 SSB queries
    run against the SAME in-memory denormalized fact on (a) one device
    and (b) an N-chip mesh (jit + NamedSharding, interleaved segment
    placement, cost-model merge strategy), with a sha256 digest over
    every rendered result frame — the mesh answers must be IDENTICAL
    (exact aggs bit-exact, sketch states losslessly merged at the
    broker). On real hardware the mesh is the physical chips; without
    one the host platform is forced to N virtual CPU devices, which
    proves placement/merge/pruning correctness but shares one socket's
    FLOPs — virtual-mesh speedups are parity evidence, not hardware
    scaling (`virtual_mesh: true` in the artifact). Knobs:
    MULTICHIP_ROWS (default 1M), BENCH_ITERS."""
    import hashlib

    tpu_unavailable = _require_backend(n_devices)
    import jax
    if len(jax.devices()) < n_devices:
        print(json.dumps({"metric": "multichip_worst_p50",
                          "value": None, "unit": "ms",
                          "error": f"only {len(jax.devices())} devices "
                                   f"for --mesh {n_devices}"}))
        return 1

    from tpu_olap import Engine
    from tpu_olap.bench import QUERIES
    from tpu_olap.bench.ssb import generate_tables, register_ssb
    from tpu_olap.executor import EngineConfig

    rows = int(os.environ.get("MULTICHIP_ROWS",
                              os.environ.get("SSB_ROWS", 1_000_000)))
    iters = int(os.environ.get("BENCH_ITERS", 3))
    t_ing = time.perf_counter()
    tables = generate_tables(rows, seed=0)
    e1 = Engine(EngineConfig())
    en = Engine(EngineConfig(num_shards=n_devices))
    for e in (e1, en):
        register_ssb(e, tables, block_rows=1 << 13)
    ingest_s = time.perf_counter() - t_ing

    def digest(frame):
        return hashlib.sha256(
            frame.to_csv(float_format="%.6g").encode()).hexdigest()[:16]

    def p50_of(eng, sql):
        eng.sql(sql)          # compile + cap observation
        res = eng.sql(sql)    # re-sized template compile
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            eng.sql(sql)
            times.append((time.perf_counter() - t0) * 1000)
        return res, round(float(np.percentile(times, 50)), 3)

    per_query = {}
    parity_ok = True
    mesh_records = {}
    for qname in sorted(QUERIES):
        sql = QUERIES[qname]
        r1, p1 = p50_of(e1, sql)
        rn, pn = p50_of(en, sql)
        rewritten = bool(en.last_plan.rewritten)
        d1, dn = digest(r1), digest(rn)
        match = d1 == dn
        parity_ok = parity_ok and match and rewritten
        m = dict(en.runner.history[-1])
        mesh_records[qname] = m
        per_query[qname] = {
            "p50_1dev_ms": p1, "p50_mesh_ms": pn,
            "speedup": round(p1 / pn, 3) if pn > 0 else None,
            "digest": dn, "digest_match": match,
            "rewritten": rewritten,
            "num_shards": m.get("num_shards"),
            "merge": m.get("merge"),
            "strategy": (m.get("cost") or {}).get("strategy"),
            "segments_window_per_chip":
                m.get("segments_window_per_chip"),
        }
        print(f"[mesh] {qname}: 1dev={p1}ms mesh={pn}ms "
              f"{'OK' if match else 'DIGEST MISMATCH'}",
              file=sys.stderr)

    # scan-bound headline: queries whose pruned set still covers the
    # table (no per-chip window) — the shapes per-chip bandwidth scales
    # directly. Flight-1 queries are PRUNING-bound instead (manifest
    # pruning + the per-chip window already cut them to a handful of
    # segments; at single-digit ms the mesh dispatch overhead
    # dominates), so they are reported but not in the scaling headline.
    sb = [v["speedup"] for v in per_query.values()
          if v["speedup"] and v.get("segments_window_per_chip") is None]
    worst = max(v["p50_mesh_ms"] for v in per_query.values())
    out = {
        "metric": "multichip_worst_p50",
        "value": worst,
        "unit": "ms",
        "vs_baseline": round(TARGET_MS / worst, 3) if worst else None,
        "mode": "multichip",
        "n_devices": n_devices,
        "rows": rows,
        "iters": iters,
        "ingest_s": round(ingest_s, 1),
        "backend": jax.default_backend(),
        "virtual_mesh": tpu_unavailable is not None,
        **({"tpu_unavailable": tpu_unavailable}
           if tpu_unavailable else {}),
        "parity_ok": parity_ok,
        "scan_bound_speedup_min": round(min(sb), 3) if sb else None,
        "per_query": per_query,
    }
    with open(os.path.join(REPO, "MULTICHIP_r06.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if parity_ok else 1


def _ingest_main() -> int:
    """`bench.py --ingest-mode`: the real-time ingest bench
    (docs/INGEST.md), banking BENCH_INGEST.json. Synthetic fact table
    (INGEST_BASE_ROWS, default 200k — append throughput and
    query-under-ingest interference do not need SF scale) with a WAL
    in a temp dir, then four phases:

    1. QUIESCED query p50/p99 — the interference baseline;
    2. SUSTAINED APPEND throughput: INGEST_BATCH_ROWS-row batches for
       INGEST_SECONDS with the background compactor live (rows/s
       includes WAL fsync + snapshot swap + backpressure waits);
    3. QUERY UNDER INGEST: the same query timed while an appender
       thread streams batches — p50/p99 vs quiesced is the write-path
       interference the enqueue-only dispatch lock is supposed to
       bound;
    4. CRASH RECOVERY: a fresh engine re-registers the base and
       replays the WAL — replay wall + rows/s, then compaction wall;
    5. CHECKPOINTED RECOVERY (docs/DURABILITY.md): checkpoint the
       recovered table (seal + spill + manifest + WAL truncation),
       append a small tail, crash again — the restart must replay
       ONLY the tail, so its replay cost is independent of the
       pre-checkpoint append volume (banked as frames full vs tail).

    Parity: the final recovered state must be sha256-identical to a
    one-shot registration of base + every acknowledged batch."""
    tpu_unavailable = _require_backend()
    import hashlib
    import shutil
    import tempfile
    import threading

    import pandas as pd

    from tpu_olap import Engine
    from tpu_olap.executor import EngineConfig
    from tpu_olap.resilience.errors import IngestBackpressure

    def note(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    base_rows = int(os.environ.get("INGEST_BASE_ROWS", 200_000))
    batch_rows = int(os.environ.get("INGEST_BATCH_ROWS", 1_000))
    run_s = float(os.environ.get("INGEST_SECONDS", 3.0))
    iters = int(os.environ.get("BENCH_ITERS", 5)) * 8
    fsync = os.environ.get("INGEST_WAL_FSYNC", "always")

    rng = np.random.default_rng(0)
    base = pd.DataFrame({
        "ts": pd.to_datetime("1993-01-01") + pd.to_timedelta(
            rng.integers(0, 86400 * 365, base_rows), unit="s"),
        "cat": rng.choice([f"c{i:02d}" for i in range(32)], base_rows),
        "v": rng.integers(0, 10_000, base_rows).astype(np.int64),
    })
    wal_dir = tempfile.mkdtemp(prefix="bench-ingest-wal-")
    store_dir = tempfile.mkdtemp(prefix="bench-ingest-store-")
    # checkpoint_on_compact stays OFF so phases 1-4 measure the pure
    # WAL-replay path (the honest O(total) baseline phase 5 is
    # compared against); phase 5 checkpoints explicitly
    mk_cfg = lambda: EngineConfig(  # noqa: E731
        ingest_wal_dir=wal_dir, ingest_wal_fsync=fsync,
        ingest_store_dir=store_dir,
        ingest_store_checkpoint_on_compact=False,
        ingest_compact_rows=1 << 15, ingest_compact_interval_s=0.25,
        history_limit=1_000_000)
    eng = Engine(mk_cfg())
    t0 = time.perf_counter()
    eng.register_table("events", base, time_column="ts",
                       block_rows=1 << 14, time_partition="month")
    note(f"base ingest: {base_rows} rows in "
         f"{time.perf_counter() - t0:.2f}s")
    q = ("SELECT cat, count(*) AS n, sum(v) AS s FROM events "
         "GROUP BY cat ORDER BY cat")

    def timed(n):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            eng.sql(q)
            ts.append((time.perf_counter() - t0) * 1000)
        return {"p50": round(float(np.percentile(ts, 50)), 3),
                "p99": round(float(np.percentile(ts, 99)), 3)}

    eng.sql(q)  # compile warm-up
    quiesced = timed(iters)
    note(f"quiesced: {quiesced}")

    def mk_batch(i):
        r = np.random.default_rng(1000 + i)
        return [{"ts": int(pd.Timestamp("1994-01-01").value // 10**6)
                 + int(x), "cat": f"c{int(c):02d}", "v": int(v)}
                for x, c, v in zip(
                    r.integers(0, 86400_000 * 30, batch_rows),
                    r.integers(0, 32, batch_rows),
                    r.integers(0, 10_000, batch_rows))]

    # --- phase 2: sustained append throughput (compactor live)
    appended_batches = []
    sheds = 0
    t_start = time.perf_counter()
    i = 0
    while time.perf_counter() - t_start < run_s:
        b = mk_batch(i)
        try:
            eng.append("events", b)
            appended_batches.append(i)
        except IngestBackpressure:
            sheds += 1
            time.sleep(0.05)
        i += 1
    append_wall = time.perf_counter() - t_start
    n_appended = len(appended_batches) * batch_rows
    append_rps = n_appended / append_wall
    note(f"sustained append: {n_appended} rows in {append_wall:.2f}s "
         f"= {append_rps:,.0f} rows/s ({sheds} sheds)")

    # --- phase 3: query under ingest
    stop = threading.Event()

    def appender():
        j = 100_000
        while not stop.is_set():
            try:
                eng.append("events", mk_batch(j))
                appended_batches.append(j)
            except IngestBackpressure:
                time.sleep(0.05)
            j += 1

    th = threading.Thread(target=appender)
    th.start()
    try:
        under_ingest = timed(iters)
    finally:
        stop.set()
        th.join()
    note(f"under ingest: {under_ingest}")
    interference = round(
        under_ingest["p50"] / max(quiesced["p50"], 1e-3), 2)

    # --- phase 4: crash recovery + compaction
    snap = eng.ingest.snapshot()["tables"]["events"]
    eng.close()  # flush WAL deterministically, then abandon the engine
    total_appended = len(appended_batches) * batch_rows
    rec = Engine(mk_cfg())
    rec.config.ingest_auto_compact = False
    t0 = time.perf_counter()
    rec.register_table("events", base, time_column="ts",
                       block_rows=1 << 14, time_partition="month")
    recover_wall = time.perf_counter() - t0
    ev = [e for e in rec.runner.events.snapshot()
          if e["event"] == "wal_replay"]
    replay_ms = ev[0]["ms"] if ev else 0.0
    replay_rows = ev[0]["rows"] if ev else 0
    note(f"recovery: register+replay {recover_wall:.2f}s "
         f"(replay {replay_ms:.0f} ms for {replay_rows} rows)")
    t0 = time.perf_counter()
    rec.compact_now("events")
    compact_s = time.perf_counter() - t0

    # --- parity: recovered state == one-shot registration
    extra = pd.DataFrame(
        [r for i in sorted(set(appended_batches)) for r in mk_batch(i)])
    extra["ts"] = pd.to_datetime(extra["ts"], unit="ms")
    ref = Engine()
    ref.register_table("events",
                       pd.concat([base, extra], ignore_index=True),
                       time_column="ts", block_rows=1 << 14,
                       time_partition="month")
    dig = lambda f: hashlib.sha256(  # noqa: E731
        f.to_csv(index=False).encode()).hexdigest()
    parity_ok = dig(rec.sql(q)) == dig(ref.sql(q))
    note(f"recovery parity: {parity_ok}")

    # --- phase 5: checkpointed recovery (docs/DURABILITY.md) — the
    # same table, but with a durable checkpoint between the appends
    # and the crash: replay cost must drop from O(total appends) to
    # O(tail), independent of the pre-checkpoint volume
    full_replay_frames = ev[0]["records"] if ev else 0
    t0 = time.perf_counter()
    ck = rec.checkpoint_now("events")
    checkpoint_s = time.perf_counter() - t0
    tail_batches = 5
    for j in range(tail_batches):
        rec.append("events", mk_batch(900_000 + j))
    dig_before = dig(rec.sql(q))
    rec.close()
    t0 = time.perf_counter()
    rec2 = Engine(mk_cfg())
    rec2.config.ingest_auto_compact = False
    rec2.register_table("events", base, time_column="ts",
                        block_rows=1 << 14, time_partition="month")
    recover_ck_wall = time.perf_counter() - t0
    ev2 = [e for e in rec2.runner.events.snapshot()
           if e["event"] == "wal_replay"]
    loads = [e for e in rec2.runner.events.snapshot()
             if e["event"] == "store_load"]
    tail_frames = ev2[0]["records"] if ev2 else 0
    tail_replay_ms = ev2[0]["ms"] if ev2 else 0.0
    ck_parity_ok = dig(rec2.sql(q)) == dig_before
    note(f"checkpointed recovery: checkpoint {checkpoint_s:.2f}s "
         f"({ck.get('bytes', 0)} bytes, status {ck.get('status')}), "
         f"restart replayed {tail_frames} frames (full replay was "
         f"{full_replay_frames}) in {tail_replay_ms:.0f} ms; "
         f"parity {ck_parity_ok}")
    parity_ok = parity_ok and ck_parity_ok and bool(loads) \
        and tail_frames == tail_batches
    rec2.close()
    shutil.rmtree(wal_dir, ignore_errors=True)
    shutil.rmtree(store_dir, ignore_errors=True)

    out = {
        "metric": "ingest_append_rows_per_s",
        "value": round(append_rps, 1),
        "unit": "rows/s",
        "vs_baseline": None,
        "detail": {
            **({"tpu_unavailable": tpu_unavailable}
               if tpu_unavailable else {}),
            "base_rows": base_rows, "batch_rows": batch_rows,
            "wal_fsync": fsync, "run_s": run_s,
            "appended_rows_total": total_appended,
            "backpressure_sheds": sheds,
            "query_quiesced_ms": quiesced,
            "query_under_ingest_ms": under_ingest,
            "under_ingest_p50_interference_x": interference,
            "recovery": {
                "register_plus_replay_s": round(recover_wall, 3),
                "replay_ms": replay_ms, "replay_rows": replay_rows,
                "replay_rows_per_s": round(
                    replay_rows / max(replay_ms / 1000, 1e-6), 1),
                "compact_s": round(compact_s, 3)},
            # docs/DURABILITY.md: replay cost with a checkpoint on
            # disk is O(tail) — frames_replayed_tail vs
            # frames_replayed_full is the independence-from-volume
            # evidence (the tail is a fixed 5 batches regardless of
            # how much was appended before the checkpoint)
            "checkpointed_recovery": {
                "checkpoint_s": round(checkpoint_s, 3),
                "checkpoint_bytes": ck.get("bytes"),
                "wal_frames_truncated": ck.get(
                    "wal_frames_truncated"),
                "register_plus_replay_s": round(recover_ck_wall, 3),
                "replay_ms": tail_replay_ms,
                "frames_replayed_tail": tail_frames,
                "frames_replayed_full": full_replay_frames,
                "parity_ok": ck_parity_ok},
            "compactions": snap["compactions"],
            "wal_bytes_final": (snap["wal"] or {}).get("bytes"),
            "parity_ok": parity_ok,
        },
    }
    with open(os.path.join(REPO, "BENCH_INGEST.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if parity_ok else 1


def _parse_args(argv=None):
    import argparse
    p = argparse.ArgumentParser(
        description="SSB benchmark: prints one JSON metric line "
                    "(worst-case p50 across the 13 SSB queries, or the "
                    "shared-scan batch throughput A/B with "
                    "--concurrency). Scale/iteration knobs are env vars "
                    "(SSB_ROWS, BENCH_ITERS, BENCH_RAM_CAP_GB, ...).")
    p.add_argument(
        "--concurrency", type=int, nargs="?", const=8, default=None,
        metavar="N",
        help="run the shared-scan batch throughput A/B with N "
             "concurrent clients (default 8) instead of the latency "
             "bench; banks BENCH_BATCH.json")
    p.add_argument(
        "--cache-mode", choices=("cold", "warm", "mixed"), default=None,
        metavar="MODE",
        help="run the semantic-result-cache bench instead of the "
             "latency bench: cold (caches cleared per run), warm "
             "(repeats served from cache), mixed (cold + warm + a "
             "fresh-ingest invalidation phase with parity in every "
             "state); banks BENCH_CACHE.json (docs/CACHING.md)")
    p.add_argument(
        "--cube-mode", choices=("off", "auto"), default=None,
        metavar="MODE",
        help="run the materialized-rollup-cube bench instead of the "
             "latency bench: off (base path only — the honest floor), "
             "auto (advisor-recommended cubes materialized from the "
             "bench's own workload profile, then base-vs-cube p50 with "
             "parity digests, materialization cost, and storage "
             "bytes); banks BENCH_CUBES.json (docs/CUBES.md)")
    p.add_argument(
        "--ingest-mode", action="store_true",
        help="run the real-time ingest bench instead of the latency "
             "bench: sustained WAL-durable append rows/s, query "
             "p50/p99 under ingest vs quiesced, crash-recovery replay "
             "time, and compaction cost, with sha256 recovery parity; "
             "banks BENCH_INGEST.json (docs/INGEST.md). Knobs: "
             "INGEST_BASE_ROWS, INGEST_BATCH_ROWS, INGEST_SECONDS, "
             "INGEST_WAL_FSYNC")
    p.add_argument(
        "--mesh", type=int, nargs="?", const=8, default=None,
        metavar="N",
        help="run the sharded-serving A/B instead of the latency "
             "bench: the 13 SSB queries on an N-chip mesh "
             "(jit + NamedSharding, interleaved placement, broker "
             "merge) vs one device over the same table, with sha256 "
             "result parity per query; banks MULTICHIP_r06.json "
             "(docs/TPU_NOTES.md). Without an accelerator the host "
             "platform is forced to N virtual CPU devices. Knobs: "
             "MULTICHIP_ROWS, BENCH_ITERS")
    p.add_argument(
        "--span-summary", action="store_true",
        help="emit per-query per-phase span timings (parse/plan/"
             "prepare/dispatch/host-transfer/assemble, from the "
             "obs.trace span tree) into the BENCH json detail as "
             "per_query_phase_p50_ms")
    p.add_argument(
        "--trace-out", type=str, default=None, metavar="PATH",
        help="write a Chrome-trace JSON (loads in Perfetto) of each "
             "SSB query's slowest timed iteration to PATH, so per-run "
             "profiles are banked next to the BENCH json "
             "(docs/OBSERVABILITY.md)")
    p.add_argument(
        "--inject-faults", type=int, nargs="?", const=3, default=None,
        metavar="N",
        help="after the clean timed runs, re-time each SSB query N "
             "times (default 3) with one injected dispatch fault per "
             "execution; banks per-query faulted p50 and the recovery "
             "overhead (faulted minus clean) into the BENCH json "
             "detail as fault_injection (docs/RESILIENCE.md)")
    p.add_argument(
        "--pipeline-depth", type=int, default=None, metavar="N",
        help="override EngineConfig.pipeline_depth for the latency "
             "bench (0 = serialized dispatch; default = engine "
             "default). The concurrency A/B lives in "
             "tools/bench_concurrency.py")
    args = p.parse_args(argv)
    if args.concurrency is not None and args.trace_out:
        p.error("--trace-out only applies to the latency bench; it is "
                "not written by the --concurrency throughput A/B")
    if args.cache_mode is not None and (args.concurrency is not None
                                        or args.trace_out
                                        or args.inject_faults):
        p.error("--cache-mode is its own bench; it does not combine "
                "with --concurrency/--trace-out/--inject-faults")
    if args.cube_mode is not None and (args.concurrency is not None
                                       or args.cache_mode is not None
                                       or args.trace_out
                                       or args.inject_faults):
        p.error("--cube-mode is its own bench; it does not combine "
                "with the other modes")
    if args.ingest_mode and (args.concurrency is not None
                             or args.cache_mode is not None
                             or args.cube_mode is not None
                             or args.trace_out or args.inject_faults):
        p.error("--ingest-mode is its own bench; it does not combine "
                "with the other modes")
    if args.mesh is not None and (args.concurrency is not None
                                  or args.cache_mode is not None
                                  or args.cube_mode is not None
                                  or args.ingest_mode
                                  or args.trace_out
                                  or args.inject_faults):
        p.error("--mesh is its own bench; it does not combine with "
                "the other modes")
    return args


if __name__ == "__main__":
    args = _parse_args()
    if args.mesh is not None:
        sys.exit(_mesh_main(args.mesh))
    if args.ingest_mode:
        sys.exit(_ingest_main())
    if args.cube_mode is not None:
        sys.exit(_cube_main(args.cube_mode))
    if args.cache_mode is not None:
        sys.exit(_cache_main(args.cache_mode))
    if args.concurrency is not None:
        sys.exit(_concurrency_main(args.concurrency))
    main(span_summary=args.span_summary, inject_faults=args.inject_faults,
         trace_out=args.trace_out, pipeline_depth=args.pipeline_depth)
