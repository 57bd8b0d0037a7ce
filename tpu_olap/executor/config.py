"""Engine configuration — the session-level half of the reference's
two-layer config (SURVEY.md §6 "Config / flag system": session SQLConf keys
`spark.sparklinedata.*`; per-table options live in catalog.TableOptions).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class EngineConfig:
    # dtype policy: int64/float64 accumulators give exact parity (x64 is
    # emulated on TPU; measured acceptable for reduce-dominated kernels).
    long_dtype: object = np.int64
    double_dtype: object = np.float64
    enable_x64: bool = True

    # dense group-by budget: max total groups (dims × buckets product) the
    # dense table may hold before the query is declared non-rewritable
    # (SURVEY.md §8.4 #1). 2^22 groups × 8B ≈ 32 MB per aggregator.
    dense_group_budget: int = 1 << 22

    # theta sketch nominal-entries cap (k × groups × 8B of HBM)
    theta_k_cap: int = 1 << 14

    # host-side cap on the fine buckets a timeFormat dimension may
    # materialize at lowering time. (A grouped NUMERIC dimension's labels
    # are arithmetic, dimplan.NumericLabels: its domain is bounded by the
    # group space's budgets, not by this.)
    numeric_dim_label_budget: int = 1 << 22

    # sort-based sparse group-by (kernels.sparse_groupby), used when the
    # dense mixed-radix space exceeds dense_group_budget: initial compact
    # table size (adapts upward pow2 on overflow) and the hard ceiling of
    # PRESENT groups before the query is declared non-rewritable.
    sparse_group_cap: int = 1 << 15
    sparse_group_budget: int = 1 << 21
    # theta sketch width on the SPARSE path: [cap, k] tables (and their
    # [cap, parts*k] merge transients) must stay HBM-modest, so k is
    # clamped below the dense-path theta_k_cap. 256 -> ~6% RSE, the
    # sketch-shrink-under-memory-pressure tradeoff Druid also makes.
    sparse_theta_k_cap: int = 256
    # max [groups × radix] element count of dense per-group sketch state
    # (theta value tables, HLL register files). Past it a GroupBy takes
    # the sparse path (clamped sketch width) and other shapes decline
    # legibly — without this, a wide-group theta/HLL query allocates
    # K × k state long before K exceeds dense_group_budget (observed:
    # >100 GB at K ≈ 1M). 2^28 elements ≈ 2 GB int64 state keeps
    # legitimately-sized dense queries (e.g. hourly-year theta
    # timeseries) on the dense path
    dense_sketch_state_budget: int = 1 << 28
    # multi-chip sparse merge strategy: both run per-chip local
    # compaction as the one-chip program mapped over the mesh
    # (shard_map, one compile a cap), then the host BROKER merges the D
    # compact tables' present rows (executor/sharding.py). "exchange"
    # lets the broker table hold
    # D x sparse_group_budget present groups (capacity scales with chip
    # count, any key skew absorbed — there are no hash owners);
    # "gather" keeps the legacy global-budget contract (all groups must
    # fit one chip's table). A multi-host (DCN) mesh hands the whole
    # sparse program to GSPMD instead (global-budget capacity).
    sparse_merge: str = "exchange"
    # where a one-process mesh merges those D compact tables. "device":
    # every chip all-gathers the others' present rows over ICI and runs
    # the merge (one sort of them, the tables read at the runs'
    # boundaries); the host fetches the merged table from one chip and
    # sorts nothing. "broker": the host fetches the D tables and merges
    # them in numpy, as Druid's broker merges its historicals' partial
    # results — 15 to 53 ms for the same 600,000 rows from one second
    # to another on the chip machine's host (PERF.md, PR 36), where the
    # device's 8 ms is 8 ms. A plan with a sketch aggregate ([cap, m]
    # state) merges at the broker whatever this says.
    mesh_merge: str = "device"

    # HBM residency budget (bytes) for device-cached column buffers across
    # all tables; least-recently-used columns evict when exceeded
    # (SURVEY.md §8.4 #4). None = unbounded (single-table dev default).
    hbm_budget_bytes: int | None = None

    # packed results: max non-empty groups shipped back per query in the
    # single-fetch compacted buffer (executor.packing). Queries whose
    # result exceeds this transparently re-run unpacked (slower transfer,
    # same answer).
    result_group_cap: int = 1 << 16

    # fallback-at-scale bounds (SURVEY.md §2 property 2 without the OOM):
    # parquet-backed tables whose footer row count exceeds
    # fallback_chunk_rows execute the fallback over streamed row-group
    # chunks (partial aggregation; bounded resident rows) instead of
    # materializing one frame; a chunked NON-aggregate result larger than
    # fallback_scan_row_cap refuses with a clear error instead of eating
    # host RAM.
    fallback_chunk_rows: int = 4_000_000
    fallback_chunk_batch_rows: int = 1 << 20
    fallback_scan_row_cap: int = 20_000_000
    # Correlation shapes the magic-set rewrite cannot serve (multi-
    # comparison conjuncts, outer refs outside WHERE, ORDER BY/LIMIT
    # inside the subquery) run a bounded nested loop instead: one
    # subquery execution per distinct outer key tuple, refused legibly
    # past this cap (SURVEY.md §2 property 2 "never an error").
    corr_nested_loop_cap: int = 2048
    # Chunked-fallback aggregate parallelism (fork pool over parquet row
    # groups): 0 = auto (min(8, cpu count)), 1 = sequential. The
    # reference's slow path was distributed Spark; this is its host-side
    # analog (SURVEY.md §2 L0, §4.4). The timeout bounds how long a
    # deadlocked fork worker can stall a query before the sequential
    # loop takes over (fork from a JAX-threaded parent can in principle
    # inherit a held allocator lock).
    # Interactive default (ADVICE round 5: a deadlocked fork pool used to
    # stall a query 15 min before the safe sequential retry; 45 s covers
    # the legitimate parallel case at bench scales). The dispatcher
    # additionally scales this UP with the estimated scan size
    # (fallback._parallel_timeout_s) so huge tables are not cut off.
    fallback_parallel_workers: int = 0
    fallback_parallel_timeout_s: float = 45.0
    # FROM/JOIN (SELECT ...) bodies route back through the engine's
    # statement executor (device path when rewritable). False keeps the
    # interpreter pure — bench.parity.pure_config() derives that oracle
    # config, and run_both uses it so the fallback side of every parity
    # check stays an independent pandas execution.
    fallback_derived_on_device: bool = True

    # shared-scan batch execution (executor.batch): compatible concurrent
    # agg queries against one table fuse into ONE device pass — each
    # segment window is read once and feeds N per-query (filter, agg)
    # legs, killing the per-query scan floor (PROFILE_CPU.json: ~65 ms
    # execute per query even for total_groups=1). batch_window_ms > 0
    # turns on the request coalescer: concurrent QueryRunner.execute()
    # callers wait up to this window and ride one fused dispatch
    # (docs/BATCH_EXECUTION.md). 0 = off (single-query behavior,
    # execute_batch() still available explicitly).
    batch_window_ms: float = 0.0
    # max logical queries per fused dispatch; larger batches split
    batch_max_queries: int = 16

    # --- semantic result caching (executor.resultcache; docs/CACHING.md)
    # Tier 2: bounded LRU full-result cache keyed by (normalized query
    # JSON, table generation) — the broker result cache. Tier 1:
    # per-segment partial-aggregate cache keyed by (generation, segment
    # id, query template minus intervals) — the historical cache: a
    # repeated aggregate over a moving window recomputes only uncached
    # segments in one device pass and merges the rest host-side via the
    # aggregators' merge semantics. Both invalidate generationally on
    # ingest/DROP and clear with CLEAR DRUID CACHE. Off by default:
    # serving deployments opt in; benches/tests that measure raw compute
    # rely on every execution dispatching.
    result_cache_enabled: bool = False
    result_cache_max_bytes: int = 256 << 20
    segment_cache_enabled: bool = False
    segment_cache_max_bytes: int = 512 << 20
    # segments with fewer valid rows than this floor are recomputed
    # rather than cached (per-entry overhead beats the recompute win)
    segment_cache_min_rows: int = 256
    # max total per-segment state elements (segments x groups x agg
    # radix) the one-pass per-segment dispatch may allocate; plans past
    # it bypass tier 1 (the plain packed/partials path serves them)
    segment_cache_state_budget: int = 1 << 22

    # --- materialized rollup cubes (tpu_olap.cubes; docs/CUBES.md) ---
    # cube_rewrite_enabled gates the planner's aggregate-rewrite pass:
    # a covered aggregate is served by folding a registered cube's
    # stored partials instead of scanning the base table. Cubes only
    # exist once created (DDL / Engine.create_cube / advisor specs), so
    # the default-on flag costs one dict probe per query until then.
    cube_rewrite_enabled: bool = True
    # background maintainer: rebuild cubes whose base table's ingest
    # generation moved (stale cubes are never served either way — the
    # rewrite pass checks the generation first, mirroring the semantic
    # result cache's invalidation contract). False = refresh only via
    # REFRESH DRUID CUBES / CubeRegistry.refresh_now (deterministic for
    # tests and bench phases).
    cube_auto_refresh: bool = True
    cube_refresh_interval_s: float = 2.0
    # serve-time fold budget: max [groups x per-agg state radix]
    # elements the host fold may allocate (HLL register files / theta
    # tables scale it exactly like segment_cache_state_budget)
    cube_serve_state_budget: int = 1 << 22
    # serve-cost bailout: only serve from a cube when its (interval-
    # kept) row count is at least this factor smaller than the base
    # rows the query would scan after pruning. Measured on the SF10
    # bench (BENCH_CUBES.json): the pruned columnar scan moves ~130k
    # rows/ms where the host fold moves ~34k rows/ms, so break-even is
    # ~4x row reduction — 16 serves only clear wins and leaves
    # marginally-covered queries (manifest pruning already made them
    # fast) un-pessimized on the base path. <= 1 disables the check.
    cube_serve_min_reduction: float = 16.0

    # --- real-time ingest (segments/delta.py, segments/wal.py;
    # docs/INGEST.md) --- Engine.append lands rows in a mutable
    # in-memory delta scope, queryable immediately alongside sealed
    # segments; a WAL makes acknowledged appends crash-durable and a
    # background compactor seals deltas into time-partitioned segments.
    # ingest_wal_dir: directory for per-table write-ahead logs; None
    # disables durability (appends remain queryable, just not
    # replayable after a crash).
    ingest_wal_dir: str | None = None
    # fsync policy: "always" (fsync before acknowledging — the full
    # durability contract), "interval" (background flusher fsyncs every
    # ingest_wal_flush_interval_s; process crashes lose nothing, power
    # loss may lose the last interval), "never" (tests/benches).
    ingest_wal_fsync: str = "always"
    ingest_wal_flush_interval_s: float = 0.05
    # replay an existing WAL when a table is first registered in this
    # process (crash recovery); re-registering a live table always
    # RESETS its log instead (the appends belonged to the old data)
    ingest_wal_replay: bool = True
    # backpressure bound: max delta rows per table before appends shed
    # with 429 + Retry-After (ingest_retry_after_s); 0 = unbounded
    ingest_max_delta_rows: int = 1 << 20
    ingest_retry_after_s: float = 1.0
    # background compactor: seal deltas >= ingest_compact_rows into
    # time-partitioned sealed segments every ingest_compact_interval_s
    # (ingest-woken). False = compact only via Engine.compact_now
    # (deterministic for tests/benches).
    ingest_auto_compact: bool = True
    ingest_compact_rows: int = 1 << 16
    ingest_compact_interval_s: float = 2.0
    # --- durable sealed-segment store (segments/store.py;
    # docs/DURABILITY.md) --- checkpointed spill of the sealed scope as
    # checksummed columnar chunk files plus an atomically-swapped
    # manifest, so recovery replays only the WAL tail past the
    # checkpoint watermark instead of the whole append history.
    # ingest_store_dir: directory for per-table checkpoint stores; None
    # disables checkpointing (recovery replays the full WAL, the PR 13
    # behavior).
    ingest_store_dir: str | None = None
    # manifests retained per table (>= 2). The WAL truncates only
    # through the watermark of the OLDEST retained manifest (lag-one),
    # so a corrupt newest checkpoint always falls back to the previous
    # one with the covering WAL tail still on disk — a single corrupt
    # chunk or torn manifest never loses an acknowledged row.
    ingest_store_keep_manifests: int = 2
    # checkpoint automatically after every compaction (the durability
    # hook: seal -> spill -> manifest advance -> WAL truncate). False =
    # checkpoint only via Engine.checkpoint_now / CHECKPOINT DRUID
    # TABLE (deterministic for tests/benches).
    ingest_store_checkpoint_on_compact: bool = True

    # multi-chip: shard the segment axis across this many devices on a
    # 1-D 'chips' mesh (None/1 = single device) — jit + NamedSharding
    # over an INTERLEAVED segment->chip placement (executor/sharding.py:
    # segment i -> chip i mod D, so any time range load-balances and
    # windowed dispatch prunes per-chip working sets). The analog of the
    # reference's queryHistoricalServers fan-out (SURVEY.md §3.5 P2).
    num_shards: int | None = None

    # emit empty time buckets in timeseries results (Druid default)
    skip_empty_buckets: bool = False

    # reference's `allowTopN` / topN threshold guard (SURVEY.md §3.2
    # LimitTransform); used by the planner
    allow_topn: bool = True
    topn_max_threshold: int = 100_000

    # reference's allowCountDistinct: push COUNT(DISTINCT) as HLL
    allow_count_distinct: bool = True

    # session timezone for granularity math (reference: tz.id conf key)
    time_zone: str = "UTC"

    # failure detection / elastic recovery (SURVEY.md §6): device dispatch
    # retries after purging device caches; with a mesh, repeated failure
    # halves the shard count (the "chip loss -> re-shard the manifest"
    # analog of the reference's Spark task retry over DruidRDD partitions).
    dispatch_retries: int = 1
    degrade_shards_on_retry: bool = False
    # structural "never an error" guarantee (SURVEY.md §2 property 2):
    # after dispatch retries exhaust on a NON-structural failure, run the
    # pandas fallback instead of raising. Off = propagate (debugging).
    fallback_on_device_failure: bool = True
    # per-query deadline (seconds) on the device dispatch; on expiry the
    # engine falls back (the analog of the reference's task-kill -> HTTP
    # query abort, SURVEY.md §3.5). None = no deadline.
    query_deadline_s: float | None = None
    # fault hook: callable(stage: str, attempt: int) -> None, may raise
    # to inject a fault (None in production). A plain callable fires only
    # at the classic "dispatch" site; declaring a `stages` attribute
    # (None = all) opts into the generalized sites — host-transfer,
    # reprobe, ingest, batch-leg (resilience.faults.maybe_inject).
    fault_injector: object = None

    # --- stage-graph execution (docs/EXECUTION.md; docs/PERF_MODEL.md
    # "execution pipeline") ---
    # Every query runs as an explicit stage graph — plan -> enqueue ->
    # transfer -> finalize -> assemble — driven by executor/stages.py.
    # Each stage class has its own bounded pool (enqueue stays width 1:
    # the chip has one program queue; the others scale with this knob),
    # so the old two-phase split generalizes: enqueue holds
    # dispatch_lock only while the device program is fired, and the
    # transfer/finalize/assemble stages of different queries overlap.
    # pipeline_depth is GRAPH ADMISSION: it bounds how many per-query
    # stage graphs are in flight engine-wide (queued device work +
    # pinned result buffers stay within the HBM budget) while the
    # per-stage queues absorb bursts inside admitted graphs; 0 restores
    # the serialized behavior (dispatch_lock held across the whole
    # query, no graph admission).
    pipeline_depth: int = 4

    # --- resilience layer (tpu_olap.resilience; docs/RESILIENCE.md) ---
    # admission control: a bounded device-dispatch queue in front of
    # dispatch_lock. At most max_inflight_dispatches hold slots at once;
    # at most admission_queue_limit wait for one; the next caller (or a
    # caller whose query_deadline_s budget cannot cover the expected
    # queue wait) is shed immediately with QueryShed -> HTTP 429,
    # instead of piling onto the lock and timing out later.
    # max_inflight_dispatches <= 0 disables admission entirely.
    max_inflight_dispatches: int = 8
    admission_queue_limit: int = 64
    # circuit breaker: this many CONSECUTIVE terminal device failures
    # (dispatch retries exhausted, deadline hits, probe failures) trip
    # it open; while open, fallback-capable queries serve from the
    # interpreter (path="fallback_breaker") and the rest refuse with
    # BreakerOpen -> HTTP 503 + Retry-After. A background healer thread
    # probes the device every breaker_open_cooldown_s and closes the
    # breaker when the probe succeeds. <= 0 disables the breaker.
    breaker_failure_threshold: int = 5
    breaker_open_cooldown_s: float = 5.0

    # observability (tpu_olap.obs): per-query span-tree tracing (obs.trace)
    # — on by default; what a span costs is in docs/OBSERVABILITY.md.
    # trace_history_limit bounds the recent-trace ring served by
    # GET /debug/queries; traces slower than slow_query_ms also land in the
    # slow-query ring (slow_log_limit entries).
    tracing_enabled: bool = True
    trace_history_limit: int = 128
    slow_query_ms: float = 250.0
    slow_log_limit: int = 64
    # QueryRunner.history ring size: per-query observability records past
    # this evict oldest-first, so a long-running server's memory is flat.
    # Engine.counters() stays exact regardless — totals are maintained
    # incrementally at record time, never re-summed from (possibly
    # evicted) history.
    history_limit: int = 1024
    # structured event log (obs.events): engine-level occurrences
    # (query completion, breaker transitions, admission sheds, cache
    # clears, ingest) land in a bounded ring served by GET /debug/events;
    # event_log_path additionally appends each event as one JSON line to
    # that file (durable sink for a log pipeline). None = ring only.
    event_log_limit: int = 2048
    event_log_path: str | None = None
    # latency SLO (obs.slo): queries completing within slo_latency_ms
    # count good, others (and failures/sheds) bad; the burn-rate gauge
    # is bad_fraction over slo_window_s divided by the error budget
    # (1 - slo_target). Defaults mirror the bench north star
    # (BASELINE.json: every SSB query < 500 ms).
    slo_latency_ms: float = 500.0
    slo_target: float = 0.99
    slo_window_s: float = 3600.0
    # workload profiler (obs.workload; ISSUE 11): every completed-query
    # record folds into bounded per-template rolling stats — the demand
    # signal behind sys.query_templates, GET /debug/workload, and the
    # cube advisor. workload_max_templates bounds distinct templates
    # (least-recently-seen evicts); workload_latency_window bounds the
    # per-template latency ring the p50/p95/p99 derive from.
    workload_profile_enabled: bool = True
    workload_max_templates: int = 512
    workload_latency_window: int = 512
    # telemetry plane (obs.timeseries + obs.sentinel; ISSUE 17): a
    # periodic `telemetry` background graph on the stage scheduler
    # snapshots every counter/gauge family into bounded per-series
    # rings (sys.metrics_history / GET /debug/timeseries) and runs the
    # regression sentinel's drift checks. interval <= 0 disables the
    # graph; retention bounds each series ring.
    telemetry_enabled: bool = True
    telemetry_interval_s: float = 5.0
    telemetry_retention: int = 360
    # regression sentinel (obs.sentinel): EWMA + moment-sketch
    # baselines per query template and per stage; a served query
    # slower than max(floor, factor * baseline) after `min_samples`
    # warmup raises a latency_drift alert attributed to the stage
    # whose busy/wait moved most. Resource alerts (hbm_pressure,
    # eviction_thrash, wal_lag, breaker_open, admission_shed) fire on
    # the telemetry tick; an alert not re-confirmed for clear_after_s
    # clears. alerts surface as events + alerts_active{kind} +
    # sys.alerts + GET /debug/health.
    sentinel_enabled: bool = True
    sentinel_min_samples: int = 8
    sentinel_ewma_alpha: float = 0.2
    sentinel_latency_factor: float = 3.0
    sentinel_latency_floor_ms: float = 10.0
    sentinel_clear_after_s: float = 60.0
    sentinel_hbm_pressure: float = 0.90   # of hbm_budget_bytes
    sentinel_eviction_thrash: int = 32    # evictions per tick
    sentinel_wal_lag_records: int = 1024  # unsynced WAL frames
    sentinel_alert_limit: int = 256       # sys.alerts history ring
    # event-log JSONL sink rotation (obs.events): when the sink file
    # exceeds max_bytes it rotates to path.1 (shifting .1 -> .2 ...,
    # keeping `keep` rotated files) and emits a sink_rotate event.
    # 0 disables rotation (the pre-ISSUE-17 unbounded behavior).
    event_log_max_bytes: int = 64 * 1024 * 1024
    event_log_rotate_keep: int = 3

    # Pallas fused one-hot MXU reduce (kernels.pallas_reduce): "auto" uses
    # it on the TPU backend for eligible plans, "force" uses it everywhere
    # eligible (interpret mode off-TPU — for tests), "never" disables.
    use_pallas: str = "auto"
    # max dense group count the Pallas kernel serves — beyond this the
    # VPU compare cost (K·N comparisons across K-blocks) beats scatter
    pallas_group_cap: int = 8192
    # factorized lane packing (kernels.pallas_reduce.Factorization) cuts
    # the tile product to ~K*H, so factorizable layouts stay profitable
    # well past the direct cap: the measured on-chip win extends through
    # 2.1e13 FLOPs with no loss observed (PALLAS_SWEEP_TPU.json,
    # BENCH_TPU_SF20.json). Non-factorizable plans (min/max aggs, wide
    # H) keep the stricter cap above.
    pallas_group_cap_factorized: int = 65536
    pallas_rows_per_block: int = 1024
    # K-block tile height: group spaces wider than this tile over a second
    # grid axis ([KB, rb] one-hot per step instead of one [K, rb] tile)
    pallas_k_per_block: int = 1024
    # the one-hot reduce does K_pad*n*H_pad*2 FLOPs — O(K·n), the wrong
    # asymptotics for large K (docs/PERF_MODEL.md). Under "auto", plans
    # whose product exceeds this budget keep the XLA scatter kernel;
    # None = no cap (pre-A/B behavior; "force" always ignores the cap).
    # Default set from the on-chip A/B once the probe banks it.
    pallas_auto_flop_budget: float | None = None

    def apply_x64(self):
        if self.enable_x64:
            import jax
            jax.config.update("jax_enable_x64", True)
