"""QueryRunner: execute a QuerySpec against a registered table.

The analog of the reference's DruidRDD.compute + broker round-trip
(SURVEY.md §4.2) collapsed into an in-process call: lower -> (cached) jit
-> device dispatch -> host assembly. Per-query observability records
(segments pruned, rows scanned, compile/execute/assemble times) mirror the
reference's DruidQueryHistory (SURVEY.md §3.2 "Query-history").
"""

from __future__ import annotations

import functools
import itertools
import time
from collections import OrderedDict
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field

import numpy as np

from tpu_olap.executor.config import EngineConfig
from tpu_olap.executor.dataset import DeviceDataset
from tpu_olap.obs.events import EventLog
from tpu_olap.obs.metrics import MetricsRegistry
from tpu_olap.obs.profile import annotate_dispatch
from tpu_olap.obs.slo import SloTracker
from tpu_olap.obs.trace import (Tracer, current_query_id,
                                current_traceparent,
                                in_nested_execution, short_str,
                                span as _span)
from tpu_olap.obs.workload import (WorkloadProfiler, fingerprint_ir,
                                   in_introspection)
from tpu_olap.resilience.admission import AdmissionController
from tpu_olap.resilience.breaker import CircuitBreaker
from tpu_olap.resilience.errors import QueryError
from tpu_olap.resilience.faults import maybe_inject
from tpu_olap.executor import lowering
from tpu_olap.executor.lowering import PhysicalPlan, lower
from tpu_olap.executor.packing import (build_packer, densify, make_layout,
                                       unpack)
from tpu_olap.executor import sparse_dispatch
from tpu_olap.executor.sharding import next_pow2 as _next_pow2
from tpu_olap.executor.results import (agg_specs_by_name, eval_having,
                                       eval_post_aggs, finalize_aggs, iso,
                                       render_value, theta_raw_fields)
from tpu_olap.ir.query import (GroupByQuerySpec, ScanQuerySpec,
                               SearchQuerySpec, SegmentMetadataQuerySpec,
                               SelectQuerySpec, TimeBoundaryQuerySpec,
                               TimeseriesQuerySpec, TopNQuerySpec)
from tpu_olap.ir.interval import ETERNITY
from tpu_olap.ir.aggregations import CountAggregation
from tpu_olap.ir.dimensions import DefaultDimensionSpec
from tpu_olap.kernels.groupby import reduce_form
from tpu_olap.segments.segment import TIME_COLUMN


@dataclass
class QueryResult:
    query: object
    rows: list                 # flat records (dims/aggs/postaggs [+timestamp])
    druid: list                # Druid-wire-shaped result
    metrics: dict = field(default_factory=dict)

    def to_pandas(self):
        import pandas as pd
        return pd.DataFrame(self.rows)


class QueryDeadlineExceeded(QueryError):
    """Raised when a query exceeds EngineConfig.query_deadline_s. The
    in-process analog of the reference's task-kill -> HTTP query abort
    (SURVEY.md §3.5): the caller falls back; the abandoned dispatch thread
    finishes (and is discarded) in the background since an in-flight XLA
    computation cannot be interrupted. Part of the resilience error
    taxonomy: HTTP surfaces map it to 504 when no fallback answered."""

    code = "deadline_exceeded"
    retriable = True
    http_status = 504


class HistoryRing(list):
    """Bounded per-query history (EngineConfig.history_limit): append
    evicts oldest-first past maxlen, so a long-running server's memory
    no longer grows per query. A list subclass on purpose — callers
    (bench.py, tests, tools) slice and len() it freely, and the ring is
    small enough that the O(maxlen) front-eviction memmove is noise
    next to any query. Aggregate counters never re-sum this structure;
    QueryRunner.record maintains them incrementally. Appends are
    internally locked: pipelined execution completes queries on
    concurrent stage-2 threads, and two racing evictions must not each
    delete a survivor."""

    def __init__(self, maxlen: int | None = None):
        import threading
        super().__init__()
        self.maxlen = maxlen if maxlen is None else max(1, int(maxlen))
        self._mu = threading.Lock()

    def append(self, item):
        with self._mu:
            super().append(item)
            if self.maxlen is not None:
                while len(self) > self.maxlen:
                    del self[0]


# core metric keys every completed-query record carries, whatever path
# served it (dense / sparse / pallas / fallback / batch leg / cache hit)
# — the stable dashboard schema (tests/test_observability.py contract)
CORE_METRIC_DEFAULTS = (
    ("total_ms", 0.0), ("rows_scanned", 0), ("segments_scanned", 0),
    ("cache_hit", False), ("query_type", "?"), ("datasource", "?"),
    ("pipelined", False),
)


def sanitize_metric_value(v, _depth=0):
    """Exception-carrying (or otherwise non-JSON) metric values -> short
    strings AT RECORD TIME, so /status, /sql responses, and
    /debug/queries never hit serialization failures on raw exception
    objects. JSON-native scalars pass through untouched."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return short_str(v) if isinstance(v, str) and len(v) > 300 else v
    if _depth < 4:
        if isinstance(v, (list, tuple)):
            return [sanitize_metric_value(x, _depth + 1) for x in v]
        if isinstance(v, dict):
            return {str(k): sanitize_metric_value(x, _depth + 1)
                    for k, x in v.items()}
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    return short_str(v)


def _evict_one(cache: dict) -> None:
    """Evict the LEAST-RECENTLY-USED entry: the runner caches are
    OrderedDicts whose hits move-to-end (_cache_lru_hit), so the first
    key is the coldest — previously this popped an arbitrary first
    entry, which under insertion order is plain FIFO and evicts hot
    compiled templates during churn. Tolerates the abandoned-deadline-
    thread concurrency (_run_with_deadline): a concurrent insert between
    iter() and next() raises RuntimeError, a concurrent pop raises
    KeyError — either just means someone else made room."""
    try:
        cache.pop(next(iter(cache), None), None)
    except (KeyError, RuntimeError):
        pass


def _cache_lru_hit(cache, key) -> None:
    """Mark a cache hit for LRU eviction: move the key to the
    OrderedDict's end, tolerating concurrent mutation by an abandoned
    deadline thread (a vanished key is just a racing purge)."""
    try:
        cache.move_to_end(key)
    except (KeyError, RuntimeError):
        pass


class QueryRunner:
    def __init__(self, config: EngineConfig | None = None):
        import threading
        self.config = config or EngineConfig()
        self.config.apply_x64()
        # Serializes device dispatch (the chip has one program queue,
        # SURVEY.md §3.5 P1). Engine.device_lock aliases this object so
        # engine-level admin ops and runner-level dispatch share one
        # lock; coalesced callers wait OUTSIDE it (executor.batch).
        self.dispatch_lock = threading.RLock()
        # pipelined execution (EngineConfig.pipeline_depth > 0): stage 1
        # (enqueue) holds dispatch_lock only while the device program is
        # fired; stage 2 (transfer/finalize/assemble) runs lock-free on
        # the caller's thread. The plan cache gets its own mutex because
        # lowering now runs outside the dispatch critical section.
        self._cache_lock = threading.Lock()
        self._tls = threading.local()   # per-thread _last_metrics
        self._inflight_seq = itertools.count(1)  # ledger pin keys
        self._transfer_count = 0        # live stage-2 transfers (gauge)
        self._coalescer = None
        self._batch_seq = 0
        if (self.config.batch_window_ms or 0) > 0:
            self.set_batch_window(self.config.batch_window_ms)
        self._datasets: dict = {}
        from tpu_olap.executor.dataset import HbmLedger
        self._hbm_ledger = HbmLedger(self.config.hbm_budget_bytes)
        # OrderedDicts so eviction is LRU: hits move-to-end
        # (_cache_lru_hit), _evict_one pops the coldest entry
        self._jit_cache: OrderedDict = OrderedDict()
        self._arg_cache: OrderedDict = OrderedDict()  # uploaded consts/
        #                                  seg-mask, content-keyed
        self._cap_hints: dict = {}   # template -> last observed group count
        self._plan_cache: OrderedDict = OrderedDict()  # lowered
        #                                  PhysicalPlans, per query JSON
        self._mesh = None
        self.mesh_program = None   # set with the mesh (the mesh property)
        self._active_shards = config.num_shards if config else None
        self._chip_dispatches: dict = {}  # chip index -> dispatches
        self._wedged = False   # a deadline expired; re-probe before trusting
        self.history = HistoryRing(self.config.history_limit)
        # observability (tpu_olap.obs): span-tree tracer + incremental
        # metrics registry, both fed through record() at query completion
        self.tracer = Tracer(enabled=self.config.tracing_enabled,
                             ring_limit=self.config.trace_history_limit,
                             slow_ms=self.config.slow_query_ms,
                             slow_limit=self.config.slow_log_limit)
        self.metrics = MetricsRegistry()
        # structured event log (obs.events): query completions, breaker
        # transitions, admission sheds, cache clears, ingest — the ring
        # behind GET /debug/events, with an optional JSONL file sink
        self.events = EventLog(limit=self.config.event_log_limit,
                               path=self.config.event_log_path,
                               max_bytes=self.config.event_log_max_bytes,
                               rotate_keep=self.config.event_log_rotate_keep)
        # latency SLO accounting (obs.slo): every record() classifies
        # good/bad against slo_latency_ms and updates the burn-rate gauge
        self.slo = SloTracker(self.config.slo_latency_ms,
                              self.config.slo_target,
                              self.config.slo_window_s,
                              metrics=self.metrics)
        self._totals_lock = threading.Lock()
        self._totals = {"queries": 0, "rows_scanned": 0,
                        "segments_scanned": 0, "segments_pruned": 0,
                        "cache_hits": 0, "total_ms": 0.0}
        self._by_query_type: dict = {}
        m = self.metrics
        self._m_queries = m.counter(
            "queries_total", "Queries completed, by type and path.",
            ("query_type", "path"))
        self._m_latency = m.histogram(
            "query_latency_ms", "End-to-end query latency (ms).",
            ("query_type", "path"))
        self._m_rows = m.counter(
            "rows_scanned_total", "Rows scanned across all queries.")
        self._m_segments = m.counter(
            "segments_scanned_total",
            "Segments scanned across all queries.")
        self._m_compile = m.counter(
            "compile_cache_requests_total",
            "Dispatches by compile-cache outcome.", ("result",))
        self._m_retries = m.counter(
            "dispatch_retries_total", "Device dispatch retries.")
        self._m_deadline = m.counter(
            "deadline_exceeded_total",
            "Queries killed by query_deadline_s.")
        self._m_hbm_bytes = m.gauge(
            "hbm_bytes_in_use", "HBM ledger bytes resident.")
        self._m_hbm_evict = m.counter(
            "hbm_evictions_total", "HBM ledger column evictions.")
        self._m_batch = m.histogram(
            "batch_size", "Logical queries per shared-scan batch.",
            buckets=(1, 2, 4, 8, 16, 32, 64))
        self._m_degraded = m.counter(
            "degraded_queries_total",
            "Queries served by the interpreter while the breaker was "
            "open (path=fallback_breaker).")
        # memory & compile accounting (ISSUE 8): live device bytes per
        # table, cache population/eviction, and executable builds — the
        # gauges are point-in-time, refreshed by refresh_resource_gauges
        # at scrape; the counters update at their event sites
        self._m_device_bytes = m.gauge(
            "device_bytes",
            "Live device bytes resident per table (segment/derived "
            "buffers + cached const/seg-mask uploads, via nbytes).",
            ("table",))
        self._m_cache_entries = m.gauge(
            "cache_entries", "Entries in the runner caches.", ("cache",))
        self._m_cache_evict = m.counter(
            "cache_evictions_total",
            "Capacity evictions from the runner caches.", ("cache",))
        self._m_cache_clears = m.counter(
            "cache_clears_total",
            "Explicit cache clears (CLEAR DRUID CACHE / recovery "
            "purges).", ("scope",))
        self._m_narrow_fallbacks = m.counter(
            "sparse_narrow_fallbacks_total",
            "Sparse dispatches whose narrow program found a group's sum "
            "past int32 and ran the wide program of the same cap.")
        self._m_wide_key = m.counter(
            "sparse_wide_key_queries_total",
            "Sparse dispatches whose group space is 2^62 or more: the key "
            "rode the sort as more than one int64 word.")
        self._m_narrow_key = m.counter(
            "sparse_narrow_key_queries_total",
            "Sparse dispatches with a key word whose ids fit 31 bits: "
            "that word rode the sort as ONE int32 operand "
            "(key_sort_bits holds a 32).")
        self._m_boundary_sorted = m.counter(
            "sparse_boundary_sorted_total",
            "Sparse dispatches whose program read its whole [cap] tables "
            "at the sorted runs' boundaries as operands of the sort that "
            "finds the runs' first rows (boundary_read: sorted), not as "
            "gathers after it.")
        self._m_recompile = m.counter(
            "recompiles_total",
            "Device executables built (jit-cache misses), by dispatch "
            "flavor.", ("kind",))
        self._m_compile_ms = m.counter(
            "compile_ms_total",
            "Milliseconds spent in cold dispatches that built an "
            "executable (trace + XLA compile + first execution).")
        # pipelined-execution observability (ISSUE 10): how long callers
        # wait for the dispatch lock (the contention the pipeline
        # shrinks) and how many stage-2 transfers are live right now
        from tpu_olap.obs.metrics import QUEUE_WAIT_BUCKETS_MS
        self._m_lock_wait = m.histogram(
            "dispatch_lock_wait_ms",
            "Wait to acquire the dispatch lock (stage-1 enqueue in "
            "pipelined mode; whole-query hold in serialized mode).",
            buckets=QUEUE_WAIT_BUCKETS_MS)
        self._m_transfers = m.gauge(
            "inflight_transfers",
            "Device->host result transfers currently in flight "
            "(stage-2 completions).")
        # resilience layer (tpu_olap.resilience; docs/RESILIENCE.md):
        # bounded admission in front of dispatch_lock, plus the device
        # circuit breaker whose healer probes via _healer_probe
        self.admission = AdmissionController(
            self.config.max_inflight_dispatches,
            self.config.admission_queue_limit, metrics=m,
            events=self.events,
            pipeline_depth=self.config.pipeline_depth)
        self.breaker = CircuitBreaker(
            self.config.breaker_failure_threshold,
            self.config.breaker_open_cooldown_s,
            probe=self._healer_probe, metrics=m, events=self.events)
        # two-tier semantic result cache (executor.resultcache;
        # docs/CACHING.md): tier 2 full results consulted at execute()
        # entry, tier 1 per-segment partials consulted inside _run_agg —
        # both generation-invalidated, cleared by clear_cache
        from tpu_olap.executor.resultcache import ResultCache
        self.result_cache = ResultCache(self.config, metrics=m,
                                        events=self.events)
        # workload profiler (obs.workload; ISSUE 11): record() folds
        # every completed-query record into per-template rolling stats —
        # the sys.query_templates / cube-advisor demand signal
        self.workload = WorkloadProfiler(
            max_templates=self.config.workload_max_templates,
            latency_window=self.config.workload_latency_window,
            enabled=self.config.workload_profile_enabled, metrics=m)
        self._attempt_local = threading.local()  # host-transfer inject
        # stage-graph scheduler (executor.stages; docs/EXECUTION.md):
        # per-stage bounded pools + graph admission for the query path,
        # and the periodic-graph ticker the background subsystems (cube
        # maintainer, compactor, WAL flusher) register with
        from tpu_olap.executor.stages import StageScheduler
        self.stages = StageScheduler(self.config, metrics=m,
                                     admission=self.admission,
                                     inject=self._inject,
                                     events=self.events)
        # telemetry plane (obs.timeseries + obs.sentinel; ISSUE 17):
        # the sampler snapshots every metric series into bounded rings
        # (sys.metrics_history / GET /debug/timeseries); the sentinel
        # keeps per-template/per-stage drift baselines fed by record()
        # and runs resource checks on the same periodic tick. Both are
        # observers only — neither executes SQL nor emits query
        # records, so the ISSUE 11 no-self-attribution contract holds.
        from tpu_olap.obs.sentinel import RegressionSentinel
        from tpu_olap.obs.timeseries import TimeseriesSampler
        self.telemetry = TimeseriesSampler(
            m, retention=self.config.telemetry_retention)
        self.sentinel = RegressionSentinel(self.config, metrics=m,
                                           events=self.events)
        ledger = self._hbm_ledger
        ledger.register_external(
            "cache_pins", lambda d: self.result_cache.shard_bytes(d))
        self.sentinel.add_probe("hbm", lambda: {
            "bytes_in_use": ledger.bytes_in_use,
            "budget": ledger.budget, "evictions": ledger.evictions})
        self.sentinel.add_probe(
            "breaker", lambda: {"state": self.breaker.state})
        shed_counter = m.counter("queries_shed_total",
                                 "Queries shed by admission control.",
                                 ("reason",))
        self.sentinel.add_probe("admission", lambda: {
            "shed_total": sum(s.value for s in
                              list(shed_counter.series.values()))})
        self._telemetry_handle = None
        if self.config.telemetry_enabled and \
                (self.config.telemetry_interval_s or 0) > 0:
            self._telemetry_handle = self.stages.register_periodic(
                "telemetry",
                lambda: self.config.telemetry_interval_s,
                self._telemetry_tick)

    def _telemetry_tick(self):
        """One telemetry-graph beat: sample the registry into the
        history rings, then run the sentinel's resource checks and
        stale-alert clearing."""
        self.telemetry.sample_once()
        self.sentinel.check()

    def _inject(self, stage: str):
        """Generalized fault-injection hook (resilience.faults): fires
        the configured injector at `stage` with the current dispatch
        attempt (thread-local, set by _dispatch), so a fault at e.g.
        host-transfer rides the same retry accounting as a dispatch
        fault."""
        maybe_inject(self.config, stage,
                     getattr(self._attempt_local, "value", 0))

    # --------------------------------------------- pipelined execution

    @property
    def _last_metrics(self) -> dict:
        """Per-THREAD current-query metrics dict: pipelined execution
        runs several queries' stages concurrently, so a shared attr
        would let one query's failure handler read another's record."""
        return getattr(self._tls, "last_metrics", {})

    @_last_metrics.setter
    def _last_metrics(self, value: dict):
        self._tls.last_metrics = value

    @property
    def _pipelined(self) -> bool:
        """Pipelined mode: dispatch_lock held only for stage-1 enqueue
        (EngineConfig.pipeline_depth > 0); 0 restores the serialized
        whole-query hold."""
        return (self.config.pipeline_depth or 0) > 0

    def _pipeline_slot(self):
        """Bound one dispatch's enqueue->complete region (admission-
        accounted, docs/PERF_MODEL.md). No-op when serialized."""
        if not self._pipelined:
            return nullcontext()
        return self.admission.pipeline_slot(self.config.query_deadline_s)

    @contextmanager
    def _enqueue_lock(self, metrics: dict | None = None):
        """The enqueue stage's critical section (width-1 stage pool +
        dispatch_lock: the chip has one program queue). Pipelined mode:
        acquire dispatch_lock (bounded by the deadline budget so an
        abandoned watchdog thread blocked here eventually exits instead
        of leaking), time the wait into dispatch_lock_wait_ms, and
        stamp the record. Serialized mode: the caller already holds the
        lock across the whole query (QueryRunner.execute) — possibly on
        the watchdog's parent thread — so only the stage accounting
        runs."""
        with self.stages.stage("enqueue", metrics):
            if not self._pipelined:
                yield
                return
            deadline = self.config.query_deadline_s
            t0 = time.perf_counter()
            ok = self.dispatch_lock.acquire(timeout=deadline) \
                if deadline is not None else self.dispatch_lock.acquire()
            waited = (time.perf_counter() - t0) * 1000
            self._m_lock_wait.observe(waited)
            if metrics is not None:
                metrics["pipelined"] = True
                metrics["lock_wait_ms"] = round(
                    metrics.get("lock_wait_ms", 0.0) + waited, 3)
            if not ok:
                raise QueryDeadlineExceeded(
                    f"dispatch lock unavailable within the {deadline}s "
                    "deadline (a dispatch is wedged holding it)") from None
            try:
                yield
            finally:
                self.dispatch_lock.release()

    @contextmanager
    def _timed_dispatch_lock(self):
        """Serialized-mode whole-query lock hold, with the wait observed
        into the same dispatch_lock_wait_ms histogram the pipelined
        sections feed — so an A/B reads lock contention from one
        series."""
        t0 = time.perf_counter()
        with self.dispatch_lock:
            self._m_lock_wait.observe((time.perf_counter() - t0) * 1000)
            yield

    def _note_transfer(self, delta: int):
        with self._totals_lock:
            self._transfer_count += delta
            self._m_transfers.set(self._transfer_count)

    def _pin_inflight(self, out):
        """Account a just-enqueued dispatch's output buffers in the HBM
        ledger until stage 2 transfers them (shapes/dtypes are known
        without blocking on the async computation). Returns the pin key
        for _fetch_tree."""
        import jax
        nbytes = sum(int(getattr(a, "nbytes", 0) or 0)
                     for a in jax.tree_util.tree_leaves(out))
        key = ("__inflight__", next(self._inflight_seq))
        self._hbm_ledger.pin_inflight(key, nbytes)
        return key

    def _fetch_tree(self, out, metrics: dict | None = None, pin=None):
        """Stage-2 device->host transfer: ONE jax.device_get round trip
        for the whole output tree (instead of one np.asarray per
        aggregate column — one host round trip, not one per array). Unpins
        the in-flight ledger entry and maintains the transfer gauge;
        the host-transfer fault site fires here."""
        import jax
        t0 = time.perf_counter()
        if pin is None:
            pin = self._pin_inflight(out)
        self._note_transfer(1)
        try:
            with self.stages.stage("transfer", metrics):
                self._inject("host-transfer")
                host = jax.device_get(out)
        finally:
            self._note_transfer(-1)
            if pin is not None:
                self._hbm_ledger.unpin_inflight(pin)
        if metrics is not None:
            metrics["transfer_ms"] = round(
                metrics.get("transfer_ms", 0.0)
                + (time.perf_counter() - t0) * 1000, 3)
        return host

    def _metric_path(self, m: dict) -> str:
        """Dashboard path label: which execution flavor served this
        record (docs/OBSERVABILITY.md)."""
        if m.get("query_type") == "fallback" or m.get("fallback"):
            # degraded-but-correct serving while the breaker is open is
            # its own first-class path (docs/RESILIENCE.md)
            if m.get("fallback_breaker"):
                return "fallback_breaker"
            return "fallback"
        if m.get("cache_tier") == "full":
            # served wholly from the full-result cache: no dispatch ran,
            # so none of the execution-flavor labels apply (tier-1
            # partial hits keep their real dispatch path — a device pass
            # still computed the uncached segments)
            return "cache"
        if m.get("cube"):
            # served by the aggregate rewrite from a materialized
            # rollup cube (planner.cuberewrite; docs/CUBES.md)
            return "cube"
        if m.get("batch_dedup") or m.get("batch_legs", 0) > 1:
            return "batch"
        if m.get("sparse"):
            return "sparse"
        if m.get("pallas"):
            return "pallas"
        return "dense"

    def record(self, m: dict) -> dict:
        """The one gate every per-query observability record passes
        through: sanitize exception-carrying values to short strings,
        stamp the core metric keys (query_id from the active trace),
        fold the record into the incremental totals (Engine.counters
        stays exact after ring eviction) and the metrics registry, then
        append to the bounded history ring. Sanitization is IN PLACE so
        a QueryResult.metrics dict sharing this object stays the
        consistent view. The `record` span times all of it: the
        workload profiler, the registry, the SLO, the event log and the
        sentinel, per query, on the serving thread."""
        with _span("record"):
            return self._record(m)

    def _record(self, m: dict) -> dict:
        # the transient fingerprint rides under `_wl` (obs.workload):
        # popped before sanitization so the object never stringifies
        fp = m.pop("_wl", None)
        had_jit_key = "jit_cache_hit" in m
        for k in list(m):
            m[k] = sanitize_metric_value(m[k])
        if in_introspection():
            # sys.* introspection statements leave NO trace of
            # themselves: no history record, no metrics/SLO, no event,
            # no profiler observation — a query over sys.queries can
            # never recurse into its own stats (ISSUE 11)
            return m
        m.setdefault("query_id",
                     current_query_id() or self.tracer.new_query_id())
        m.setdefault("ts_ms", int(time.time() * 1000))
        # W3C trace context (ISSUE 17): a validated incoming
        # traceparent (Engine._sql_traced / sql_batch_ids / append)
        # propagates by contextvar and stamps every record the request
        # produced, so the future fleet router joins one distributed
        # trace across replicas
        tp = current_traceparent()
        if tp is not None:
            m.setdefault("traceparent", tp)
        if fp is not None:
            m.setdefault("template_id", fp.template_id)
        for k, v in CORE_METRIC_DEFAULTS:
            m.setdefault(k, v)
        qt, path = m["query_type"], self._metric_path(m)
        m["path"] = path
        if qt == "?":
            # runner NOTES (healer/reprobe outcomes), not queries: they
            # log + land in history but must not inflate queries_total,
            # the latency histogram, or the /status totals — a breaker
            # outage's healer loop would otherwise add one phantom 0 ms
            # "query" per cooldown for exactly the window an operator
            # is debugging
            self.events.emit(
                "device", query_id=m["query_id"],
                **{k: v for k, v in m.items()
                   if k.startswith("device_probe")})
            self.history.append(m)
            return m
        # workload attribution (obs.workload): every real query record —
        # device, fallback, cache hit, batch leg, dedup fan-out, nested
        # leg — folds into its template's rolling stats
        self.workload.observe(m, fp)
        with self._totals_lock:
            t = self._totals
            t["queries"] += 1
            t["rows_scanned"] += m["rows_scanned"] or 0
            t["segments_scanned"] += m["segments_scanned"] or 0
            t["segments_pruned"] += max(
                0, (m.get("segments_total", 0) or 0)
                - (m["segments_scanned"] or 0))
            t["cache_hits"] += 1 if m["cache_hit"] else 0
            t["total_ms"] += m["total_ms"] or 0.0
            self._by_query_type[qt] = self._by_query_type.get(qt, 0) + 1
        self._m_queries.inc(query_type=qt, path=path)
        self._m_latency.observe(m["total_ms"] or 0.0,
                                query_type=qt, path=path)
        self._m_rows.inc(m["rows_scanned"] or 0)
        self._m_segments.inc(m["segments_scanned"] or 0)
        if had_jit_key:
            self._m_compile.inc(
                result="hit" if m["jit_cache_hit"] else "miss")
        if m.get("retries"):
            self._m_retries.inc(m["retries"])
        if m.get("deadline_exceeded"):
            self._m_deadline.inc()
        if m.get("fallback_breaker"):
            self._m_degraded.inc()
        if "hbm_bytes" in m:
            self._m_hbm_bytes.set(m["hbm_bytes"])
        if "hbm_evictions" in m:
            self._m_hbm_evict.set_total(m["hbm_evictions"])
        if m.get("recompiles"):
            # cold-dispatch wall: the miss's first call is where tracing
            # + XLA compilation happen, so a recompile storm shows up as
            # compile_ms on the records that paid it (and in the
            # compile_ms_total counter). Approximate by construction —
            # it includes the first execution (docs/OBSERVABILITY.md).
            m.setdefault("compile_ms",
                         m.get("execute_ms") or m.get("scan_ms_shared")
                         or 0.0)
            self._m_compile_ms.inc(m["compile_ms"] or 0.0)
        if in_nested_execution():
            # an internal leg of a larger statement (grouping-sets
            # union, planner subquery, fallback derived table): it
            # keeps its history record and per-path metrics, but the
            # SLO observation and `query` event belong to the OUTER
            # statement — one served response, one event
            self.history.append(m)
            return m
        # SLO classification + the structured event log: record() is the
        # one chokepoint every per-query record passes through, so both
        # see every path (dense/sparse/fallback/batch leg/failed).
        # INTERIM device failures (failed/deadline records on a
        # non-fallback path) log as `query_error`, not `query`, and are
        # never SLO-counted here: the served outcome is accounted
        # exactly once elsewhere — by the compensating fallback record
        # when the engine falls back, or at the statement/raw-IR
        # boundary (Engine._observe_failure / execute_ir) when the
        # failure propagates to the client. Everything else is a served
        # response: one `query` event + one SLO observation.
        failed = bool(m.get("failed") or m.get("deadline_exceeded"))
        interim = failed and qt != "fallback"
        if interim:
            self.events.emit(
                "query_error", query_id=m["query_id"], query_type=qt,
                path=path, datasource=m["datasource"],
                total_ms=round(m["total_ms"] or 0.0, 3),
                **({"deadline_exceeded": True}
                   if m.get("deadline_exceeded") else {}))
        else:
            # the SLO sees the USER-VISIBLE latency: a compensating
            # fallback adds the wall its query already burned on the
            # failed device attempt (deadline wait, exhausted retries).
            # Client-shaped failures (unsupported SQL -> 400) are
            # event-logged but never burn the error budget.
            if not (failed and m.get("client_error")):
                self.slo.observe((m["total_ms"] or 0.0)
                                 + (m.get("device_attempt_ms") or 0.0),
                                 failed=failed)
            self.events.emit(
                "query", query_id=m["query_id"], query_type=qt,
                path=path, datasource=m["datasource"],
                total_ms=round(m["total_ms"] or 0.0, 3),
                cache_hit=bool(m["cache_hit"]),
                **({"cache_tier": m["cache_tier"]}
                   if m.get("cache_tier") else {}),
                **({"failed": True} if failed else {}))
        # regression sentinel (obs.sentinel): served responses only —
        # introspection returned above, nested legs returned above, and
        # the sentinel itself skips failed/deadline records, so the
        # baselines see exactly the user-visible latency stream
        self.sentinel.observe(m)
        self.history.append(m)
        return m

    def _note_compile(self, kind: str, metrics: dict | None = None):
        """Called at every jit-cache miss that builds a device
        executable: bumps the recompile counter (by dispatch flavor) and
        stamps the record so record() can attribute compile_ms — the
        signal that makes a recompile storm (cap churn, layout drift,
        config flapping) visible in /metrics instead of just 'queries
        got slow'."""
        self._m_recompile.inc(kind=kind)
        if metrics is not None:
            metrics["recompiles"] = metrics.get("recompiles", 0) + 1

    def _program(self, key, build, what=None, metrics=None):
        """(the jitted program remembered under `key`, jit cache hit):
        built once a key (`build()`), which with `what` is a counted
        compile (`_note_compile`; None: the caller counts it). Call under
        the enqueue lock."""
        fn = self._jit_cache.get(key)
        if fn is not None:
            _cache_lru_hit(self._jit_cache, key)
            return fn, True
        fn = self._jit_cache[key] = build()
        if what is not None:
            self._note_compile(what, metrics)
        return fn, False

    def device_bytes_by_table(self) -> dict:
        """Live device bytes per table: each dataset's resident column/
        null/derived stacks plus this table's cached const/seg-mask
        uploads (_arg_cache keys lead with the table name). Snapshots
        tolerate the abandoned-thread concurrency the caches allow."""
        out: dict = {}
        for name, ds in list(self._datasets.items()):
            out[name] = ds.resident_bytes()
        for key, val in list(self._arg_cache.items()):
            try:
                consts_dev, seg_arg = val
                n = sum(int(getattr(a, "nbytes", 0) or 0)
                        for a in consts_dev.values())
                n += int(getattr(seg_arg, "nbytes", 0) or 0)
            except Exception:  # noqa: BLE001 — accounting, not serving
                continue
            out[key[0]] = out.get(key[0], 0) + n
        return out

    def refresh_resource_gauges(self):
        """Point-in-time memory/cache gauges, refreshed at scrape time
        (GET /metrics) rather than per query — walking every resident
        buffer is O(buffers), too heavy for the per-record hot path."""
        by_table = self.device_bytes_by_table()
        for t, b in by_table.items():
            self._m_device_bytes.set(b, table=t)
        for key in list(self._m_device_bytes.series):
            if key[0] not in by_table:  # evicted table: zero, not stale
                self._m_device_bytes.set(0.0, table=key[0])
        self._m_cache_entries.set(len(self._jit_cache), cache="jit")
        self._m_cache_entries.set(len(self._plan_cache), cache="plan")
        self._m_cache_entries.set(len(self._arg_cache), cache="arg")
        self.result_cache._refresh_gauges()
        self._refresh_hbm_chip_gauges()

    def _refresh_hbm_chip_gauges(self):
        """Per-(chip, owner-class) HBM gauges (ISSUE 17): exact ledger
        attribution plus high-watermark and headroom-vs-budget — the
        /metrics face of sys.devices' per-chip columns."""
        m = self.metrics
        g_bytes = m.gauge(
            "hbm_chip_bytes",
            "HBM-resident bytes per chip and owner class (exact "
            "HbmLedger attribution; cache_pins via the ResultCache "
            "reporter).", ("chip", "owner"))
        g_hwm = m.gauge(
            "hbm_chip_high_watermark_bytes",
            "Ledger-managed per-chip HBM high-watermark.", ("chip",))
        g_head = m.gauge(
            "hbm_chip_headroom_bytes",
            "Per-chip share of hbm_budget_bytes minus ledger-managed "
            "resident bytes (absent without a budget).", ("chip",))
        ledger = self._hbm_ledger
        snap = ledger.breakdown()
        hwm = ledger.watermarks()
        D = ledger.num_chips
        per_chip_ledger = [0] * D
        seen = set()
        for (c, owner), b in snap.items():
            if 0 <= c < D and owner != "cache_pins":
                per_chip_ledger[c] += b
            g_bytes.set(b, chip=c, owner=owner)
            seen.add((str(c), owner))
        for key in list(g_bytes.series):
            if tuple(key) not in seen:  # released class: zero, not stale
                g_bytes.set(0.0, chip=key[0], owner=key[1])
        budget = ledger.budget
        for c in range(D):
            g_hwm.set(hwm["per_chip"][c] if c < len(hwm["per_chip"])
                      else 0, chip=c)
            if budget:
                g_head.set(budget / D - per_chip_ledger[c], chip=c)
        m.gauge("hbm_high_watermark_bytes",
                "Ledger-managed total HBM high-watermark.") \
            .set(hwm["total"])

    def device_snapshot(self) -> list:
        """Per-chip serving state behind sys.devices and
        GET /debug/devices: logical segments owned under the
        interleaved placement (segment i → chip i mod D), resident
        device bytes, multi-chip dispatch participation, and tier-1
        cache-shard entries (chip of an entry = its segment's owner).

        The per-chip HBM columns (ISSUE 17) come from the ledger's
        exact per-(chip, owner-class) attribution — table columns,
        cube tables, in-flight pins sum to the ledger's bytes_in_use;
        cache_pin_bytes rides alongside from the ResultCache reporter —
        plus ledger-managed high-watermark and headroom against the
        per-chip share of the HBM budget."""
        import jax
        mesh = self.mesh
        devs = list(mesh.devices.flat) if mesh is not None \
            else jax.devices()[:1]
        D = len(devs)
        seg = [0] * D
        res_bytes = [0.0] * D
        rebased_cols = rebase_rows = 0
        for _name, ds in list(self._datasets.items()):
            n_seg = len(ds.table.segments)
            b = ds.resident_bytes()
            rebased_cols += ds.rebased_cols
            rebase_rows += ds.rebase_rows_uploaded
            if mesh is not None and D > 1:
                for c in range(D):
                    seg[c] += len(range(c, n_seg, D))
                    res_bytes[c] += b / D
            else:
                seg[0] += n_seg
                res_bytes[0] += b
        cache_by_chip = self.result_cache.shard_entries(D)
        ledger = self._hbm_ledger
        hbm = ledger.breakdown()
        hwm = ledger.watermarks()
        budget = ledger.budget
        chip_budget = (budget / D) if budget else None
        with self._totals_lock:
            disp = dict(self._chip_dispatches)
        rows = []
        for c, d in enumerate(devs):
            col_b = hbm.get((c, "table_columns"), 0)
            cube_b = hbm.get((c, "cube_tables"), 0)
            infl_b = hbm.get((c, "inflight"), 0)
            cache_b = hbm.get((c, "cache_pins"), 0)
            ledger_b = col_b + cube_b + infl_b
            chip_hwm = hwm["per_chip"][c] \
                if c < len(hwm["per_chip"]) else 0
            rows.append({
                "index": c,
                "device": str(d),
                "platform": d.platform,
                "process": d.process_index,
                "chips": D,
                "segments": seg[c],
                "resident_bytes": int(res_bytes[c]),
                "dispatches": disp.get(c, 0),
                "cache_shard_entries": cache_by_chip.get(c, 0),
                "rebased_cols": rebased_cols,
                "rebase_rows_uploaded": rebase_rows,
                "hbm_bytes": int(ledger_b),
                "table_column_bytes": int(col_b),
                "cube_table_bytes": int(cube_b),
                "inflight_bytes": int(infl_b),
                "cache_pin_bytes": int(cache_b),
                "hbm_high_watermark_bytes": int(chip_hwm),
                "hbm_headroom_bytes": (int(chip_budget - ledger_b)
                                       if chip_budget else None),
            })
        return rows

    def counters(self) -> dict:
        """Aggregate counters, maintained incrementally at record time —
        exact over the full query lifetime even after history-ring
        eviction (previously an O(history) re-sum per /status ping)."""
        with self._totals_lock:
            out = dict(self._totals)
            out["by_query_type"] = dict(self._by_query_type)
        return out

    @property
    def mesh(self):
        if self._mesh is None and (self._active_shards or 1) > 1:
            from tpu_olap.executor import sharding as sh
            self._mesh = sh.make_mesh(self._active_shards)
            # which spelling of an aggregate runs is a fact of the
            # mesh, not of a query: a host broker cannot see another
            # process's shards, so a mesh that spans processes takes
            # the GSPMD spelling (replicated outputs) for every query
            self.mesh_program = "gspmd" if sh.is_multihost(self._mesh) \
                else "per_chip"
            # the ledger learns the chip count the moment the mesh
            # exists, so every subsequent add splits per chip exactly
            # (ISSUE 17 per-chip HBM attribution)
            self._hbm_ledger.set_num_chips(self._mesh.devices.size)
        return self._mesh

    def _dispatch(self, call, metrics: dict, table_name: str):
        """Run a device dispatch with retry-based recovery (SURVEY.md §6
        failure detection): on failure, purge the query's table-scoped
        device state (its buffers/programs could be poisoned by a device
        reset — other tables' warm caches are left alone) and re-run;
        with degrade_shards_on_retry, halve the mesh — the in-process
        analog of re-sharding the segment manifest after chip loss."""
        from tpu_olap.kernels.groupby import UnsupportedAggregation

        attempts = max(1, self.config.dispatch_retries + 1)
        for attempt in range(attempts):
            try:
                self._attempt_local.value = attempt
                # `device-call` is the one instant both clocks see: the
                # span (perf_counter) has exactly the extent of the
                # annotation that a live jax.profiler capture
                # (obs.profile) puts around this dispatch under its
                # query_id, so the captured XLA ops nest under the query
                # and the two axes join on it; off capture the
                # annotation is a single module-flag probe
                with _span("device-call", attempt=attempt), \
                        annotate_dispatch(current_query_id()):
                    maybe_inject(self.config, "dispatch", attempt)
                    out = call()
                # success resets the breaker's consecutive-failure count
                self.breaker.record_success()
                return out
            except UnsupportedAggregation:
                raise  # structural, not transient: straight to fallback
            except QueryError:
                # taxonomy failures originating inside a pipelined
                # dispatch (lock unavailable within the deadline, a
                # pipeline-slot shed): lock/queue starvation, not device
                # sickness — no retry (it would re-wait the same
                # resource), no breaker failure (the holder's own
                # watchdog accounts for a real wedge)
                raise
            except Exception as e:
                # record every retried error so poisoned-device vs
                # deterministic failures are distinguishable in history
                metrics.setdefault("retry_errors", []).append(
                    f"{type(e).__name__}: {e}")
                if attempt + 1 >= attempts:
                    # terminal (retries exhausted): one breaker failure —
                    # per-attempt errors the retry layer absorbed are not
                    # breaker events
                    self.breaker.record_failure()
                    raise
                metrics["retries"] = attempt + 1
                # in pipelined mode nothing outer holds dispatch_lock,
                # and the structural purges below must not race another
                # query's stage-1 enqueue; serialized mode keeps the
                # historical behavior (caller holds the lock — or, on a
                # deadline watchdog thread, the purge is lock-free and
                # tolerated, see _run_with_deadline)
                purge_lock = self.dispatch_lock if self._pipelined \
                    else nullcontext()
                with purge_lock:
                    if self.config.degrade_shards_on_retry and \
                            (self._active_shards or 1) > 1:
                        # mesh shrink invalidates every table's shardings
                        self.clear_cache()
                        self._mesh = None
                        self._active_shards = max(
                            1, self._active_shards // 2)
                        metrics["degraded_shards"] = self._active_shards
                    else:
                        self.clear_cache(table_name)

    # ------------------------------------------------------------------ API

    def set_batch_window(self, window_ms: float | None):
        """Enable/disable the shared-scan request coalescer at runtime
        (EngineConfig.batch_window_ms sets it at construction; the
        concurrency bench A/B toggles it). With a window, concurrent
        execute() callers of agg queries ride one fused dispatch
        (executor.batch.Coalescer); 0/None restores per-call dispatch."""
        from tpu_olap.executor.batch import Coalescer
        self.config.batch_window_ms = float(window_ms or 0.0)
        self._coalescer = Coalescer(self, float(window_ms) / 1000.0) \
            if window_ms else None

    def execute_batch(self, queries, table) -> list:
        """Execute N queries against one table as a shared-scan batch
        (executor.batch.run_batch): identical queries scan once,
        compatible dense-agg legs fuse into one device pass, everything
        else runs through the single-query path. Results come back in
        input order; the first failed leg's exception raises (callers
        that need per-leg failure isolation use _execute_batch_boxed)."""
        boxed = self._execute_batch_boxed(list(queries), table)
        for b in boxed:
            if isinstance(b, BaseException):
                raise b
        return boxed

    def _execute_batch_boxed(self, queries, table, query_ids=None) -> list:
        from tpu_olap.executor.batch import run_batch
        # one admission slot per batch submission: the fused dispatch is
        # one device occupancy however many logical queries ride it.
        # Pipelined mode: no outer lock — run_batch's device sections
        # take it per dispatch, so the leader no longer holds the lock
        # during per-leg finalize/assembly (docs/BATCH_EXECUTION.md).
        with self.admission.slot(self.config.query_deadline_s):
            if self._pipelined:
                return run_batch(self, queries, table, query_ids)
            with self._timed_dispatch_lock():
                return run_batch(self, queries, table, query_ids)

    def _next_batch_id(self) -> int:
        self._batch_seq += 1
        return self._batch_seq

    def compute_partials(self, query, table):
        """Run an aggregation query and return its RAW mergeable
        partials instead of finalized rows — the cube materializer's
        entry point (tpu_olap.cubes; docs/CUBES.md). Returns
        (plan, present flat group ids [G] int64, {name: [G, ...] compact
        partial arrays}, metrics). Rides the ordinary machinery: cached
        lowering, admission slot, breaker check, the dense partials or
        sparse dispatch path — so background cube builds queue behind
        (and shed with) foreground traffic instead of around it. No
        deadline wrapping: a rollup over the whole table is legitimate
        long-running background work."""
        from tpu_olap.kernels.groupby import UnsupportedAggregation

        with self.admission.slot(self.config.query_deadline_s):
            self.breaker.check()
            metrics = self._last_metrics = {}
            with _span("lower"):
                plan = self._lower_cached(query, table)
            if plan.kind != "agg":
                raise UnsupportedAggregation(
                    f"{query.query_type} has no mergeable partials")
            if len(plan.key_words) > 1:
                raise UnsupportedAggregation(
                    f"a group space of {plan.total_groups} has no flat "
                    "int64 group id to key mergeable partials by")
            if plan.sparse:
                out, _, _ = self._dispatch(
                    lambda: sparse_dispatch.run_sparse(
                        self, plan, metrics, cut=False),
                    metrics, table.name)
                pm, present = sparse_dispatch.present_groups(out, plan)
                compact = {k: np.asarray(v)[pm] for k, v in out.items()
                           if not k.startswith("_") or k == "_rows"
                           or k.startswith("_nn_")}
            else:
                partials = self._dispatch(
                    lambda: self._run_partials(plan, metrics), metrics,
                    table.name)
                rows = np.asarray(partials["_rows"])
                present = np.nonzero(rows > 0)[0].astype(np.int64)
                compact = {k: np.asarray(v)[present]
                           for k, v in partials.items()}
        return plan, present, compact, metrics

    def _guarded_dispatch(self, call, metrics: dict, table_name: str):
        """_dispatch under the same deadline/wedge guard as the
        single-query path: with query_deadline_s set, the fused batch
        dispatch runs on a fresh daemon thread and is abandoned on
        expiry (QueryDeadlineExceeded -> every leg's caller falls back),
        and a wedged device is reprobed before being trusted again. The
        batch executor's fused pass uses this so coalesced callers are
        never hung past the deadline the single-query path honors."""
        self.breaker.check()
        deadline = self.config.query_deadline_s
        if deadline is None:
            return self._dispatch(call, metrics, table_name)
        if self._wedged:
            self._reprobe_device(deadline)
        return self._join_abandoning(
            lambda: self._dispatch(call, metrics, table_name), deadline,
            {"datasource": table_name, "batch_dispatch": True,
             "query_type": "batch"},  # a real failure record, not a
            #                           runner note (record() routes
            #                           query_type "?" to the note path)
            name="tpu-olap-batch-dispatch")

    def execute(self, query, table) -> QueryResult:
        # full-result cache first: a hit needs no admission slot, no
        # dispatch lock, and no healthy device — it keeps serving
        # repeated queries through breaker-open windows and overload
        res = self._serve_full_cache(query, table)
        if res is not None:
            return res
        # breaker next: while open, fail in microseconds (the engine
        # routes fallback-capable queries to the interpreter) instead of
        # queueing doomed work onto the sick device
        self.breaker.check()
        if self._coalescer is not None and not in_nested_execution():
            # nested statements (subqueries, derived tables) dispatch
            # directly: the coalescer's leader would record their legs
            # OUTSIDE the nested context, double-counting them in the
            # SLO/event accounting (obs.trace.nested_execution)
            from tpu_olap.executor.batch import AGG_QUERY_TYPES
            if isinstance(query, AGG_QUERY_TYPES):
                # waits OUTSIDE dispatch_lock so concurrent callers can
                # coalesce; the batch leader takes the lock to dispatch
                # (and holds the one admission slot for the batch)
                with _span("coalesce") as sp:
                    res = self._coalescer.submit(query, table)
                    sp.set(batch_id=res.metrics.get("batch_id"),
                           batch_size=res.metrics.get("batch_size"))
                return res
        with self.admission.slot(self.config.query_deadline_s):
            if self._pipelined:
                # two-stage pipeline: _execute_guarded's dispatch
                # sections take dispatch_lock for stage-1 enqueue only;
                # transfer/finalize/assembly run lock-free, so query B's
                # device compute overlaps query A's RTT + assembly
                return self._execute_guarded(query, table)
            with self._timed_dispatch_lock():
                return self._execute_guarded(query, table)

    def _execute_guarded(self, query, table) -> QueryResult:
        """Breaker + deadline/wedge guard around _execute. Serialized
        mode: the caller holds dispatch_lock across this whole call.
        Pipelined mode: no outer lock — the per-dispatch enqueue
        sections (_enqueue_lock) take it."""
        self.breaker.check()
        deadline = self.config.query_deadline_s
        if deadline is not None:
            if self._wedged:
                # a previous dispatch timed out and was abandoned; before
                # trusting the device again, prove it answers a trivial
                # computation (the analog of the reference re-resolving a
                # live broker after task kill, SURVEY.md §3.5/§6). Still
                # dead -> fail fast so the engine keeps falling back
                # without stacking another full deadline wait.
                self._reprobe_device(deadline)
            return self._run_with_deadline(query, table, deadline)
        return self._execute(query, table)

    def _run_with_deadline(self, query, table, deadline: float):
        """Dispatch on a fresh daemon thread, abandoning it on expiry.

        An abandoned dispatch cannot be interrupted mid-XLA-computation;
        it finishes (or hangs) in the background while later queries run
        on new threads. Shared cache dicts tolerate that concurrency:
        individual dict ops are atomic, structural rebuilds snapshot
        first (clear_cache), and a stale entry written by an abandoned
        thread after a recovery purge costs at most one retried dispatch
        (the _dispatch retry purges again) — mirroring the reference,
        where a killed Spark task's Druid query keeps running server-side
        while the retry proceeds."""
        import threading
        abandoned = threading.Event()
        return self._join_abandoning(
            lambda: self._execute(query, table, abandoned), deadline,
            {"query_type": query.query_type, "datasource": table.name},
            on_timeout=abandoned.set)  # its history record is discarded

    def _join_abandoning(self, work, deadline: float, rec: dict,
                         on_timeout=None, name="tpu-olap-dispatch"):
        """Run `work` on a fresh daemon thread, abandoning it on expiry:
        mark the device wedged, record `rec` (stamped with the
        deadline), and raise QueryDeadlineExceeded. The one
        deadline/wedge join shared by the single-query path
        (_run_with_deadline) and the fused batch path
        (_guarded_dispatch); `on_timeout` runs before the wedge is set
        (e.g. flagging the abandoned thread to discard its record).
        The worker runs inside a contextvars snapshot so the caller's
        active trace (obs.trace) spans the cross-thread dispatch."""
        import contextvars
        import threading
        box: dict = {}
        ctx = contextvars.copy_context()

        def run():
            try:
                box["res"] = ctx.run(work)
            except BaseException as e:  # noqa: BLE001 - relayed to caller
                box["err"] = e

        t = threading.Thread(target=run, daemon=True, name=name)
        t.start()
        t.join(deadline)
        if t.is_alive():
            if on_timeout is not None:
                on_timeout()
            self._wedged = True
            self.breaker.record_failure("deadline")
            self.record({**rec, "deadline_exceeded": True,
                         "total_ms": deadline * 1000})
            raise QueryDeadlineExceeded(
                f"query exceeded deadline of {deadline}s") from None
        if "err" in box:
            raise box["err"]
        return box["res"]

    def _probe_device(self, timeout: float) -> bool:
        """Trivial device round-trip on a watchdog thread; True iff it
        completes within `timeout`. The one probe primitive shared by
        the post-wedge reprobe and the breaker's healer thread. The
        "reprobe" fault-injection site lives here, so probe failure is
        testable without a real sick device."""
        import threading
        ok = threading.Event()

        def work():
            try:
                maybe_inject(self.config, "reprobe", 0)
                import jax.numpy as jnp
                jnp.ones((8,), jnp.int32).sum().block_until_ready()
                ok.set()
            except Exception:
                pass

        t = threading.Thread(target=work, daemon=True,
                             name="tpu-olap-probe")
        t.start()
        t.join(timeout)
        return ok.is_set()

    def _recover_after_probe(self, lock_timeout_s: float | None = None
                             ) -> bool:
        """Probe succeeded: clear the wedge and purge device-resident
        DATA (buffers a reset would poison) but keep compiled
        executables — recompiling every template would eat the next
        query's deadline; if an executable is also poisoned, the
        _dispatch retry layer purges the table's full cache anyway.
        Holds dispatch_lock itself (re-entrant for the serialized path,
        where the caller already owns it): in pipelined mode the purge
        must not race another query's stage-1 env build. Pipelined
        acquisition is BOUNDED: an abandoned stage-1 thread can strand
        the lock (it hung inside the jitted fire), and blocking here
        forever would hang every recovery path on the caller thread —
        returns False instead (callers treat it as probe failure, so
        the breaker keeps the engine on degraded serving until the
        stranded holder drains). Success also reclaims pipeline slots
        stranded by abandoned dispatch threads."""
        if self._pipelined:
            t = 5.0 if lock_timeout_s is None \
                else max(1.0, float(lock_timeout_s))
            if not self.dispatch_lock.acquire(timeout=t):
                self.record({"device_probe_lock_stranded": True})
                return False
        else:
            self.dispatch_lock.acquire()
        try:
            self._wedged = False
            for ds in list(self._datasets.values()):
                ds.evict()
            self._datasets.clear()
            self._arg_cache.clear()
        finally:
            self.dispatch_lock.release()
        # reclaim in-flight pipeline slots held by abandoned dispatch
        # threads: the device is verified healthy and its state purged,
        # so the stranded holders' slots must not zero device capacity.
        # Stage-pool slots stranded the same way (a worker abandoned
        # mid-transfer still occupies its stage) are reclaimed too.
        self.admission.reset_pipeline()
        self.stages.reclaim_stranded()
        self.record({"device_probe_recovered": True})
        return True

    def _reprobe_device(self, deadline: float):
        """Post-wedge health check: a trivial device round-trip under the
        deadline. Success clears the wedge and purges device caches (the
        hang may have been a device reset poisoning buffers); failure
        raises immediately."""
        if not self._probe_device(deadline):
            self.record({"device_probe_failed": True})
            self.breaker.record_failure("probe")
            raise QueryDeadlineExceeded(
                "device still unresponsive after a deadline-expired query")
        if not self._recover_after_probe(deadline):
            self.breaker.record_failure("probe")
            raise QueryDeadlineExceeded(
                "device answered the probe but the dispatch lock is "
                "stranded by an abandoned dispatch")

    def _healer_probe(self) -> bool:
        """The breaker healer's half-open probe (resilience.breaker):
        same round-trip; success also clears the wedge and purges
        device-resident data so the first post-recovery query starts
        from trustworthy buffers."""
        timeout = self.config.query_deadline_s or 10.0
        if not self._probe_device(timeout):
            self.record({"device_probe_failed": True})
            return False
        # _recover_after_probe takes dispatch_lock itself (bounded in
        # pipelined mode): a query that slipped through during half-open
        # may be mid-enqueue on these datasets. A stranded lock returns
        # False -> the breaker stays open and the healer retries next
        # cooldown, until the stranded holder drains.
        return self._recover_after_probe(timeout)

    def _execute(self, query, table, abandoned=None) -> QueryResult:
        t0 = time.perf_counter()
        self._last_metrics = {}
        try:
            res = self._execute_inner(query, table)
        except Exception:
            # failed queries still leave an observability record (with
            # retry_errors) so poisoned-device vs deterministic failures
            # are diagnosable from history
            m = self._last_metrics
            m["failed"] = True
            m["query_type"] = query.query_type
            m["datasource"] = table.name
            m["total_ms"] = (time.perf_counter() - t0) * 1000
            m["_wl"] = self.fingerprint(query, table.name)
            if abandoned is None or not abandoned.is_set():
                self.record(m)
            raise
        res.metrics["total_ms"] = (time.perf_counter() - t0) * 1000
        res.metrics["query_type"] = query.query_type
        res.metrics["datasource"] = table.name
        with _span("fingerprint"):
            fp = self.fingerprint(query, table.name)
        res.metrics["_wl"] = fp
        if abandoned is None or not abandoned.is_set():
            self.record(res.metrics)
            self._store_full_cache(query, table, res, fp)
        return res

    def fingerprint(self, query, table_name: str):
        """Workload template of a device-path query spec (obs.workload)
        — None (profiling off / exotic spec) just skips attribution,
        never fails the query."""
        if not self.workload.enabled:
            return None
        try:
            return fingerprint_ir(query, table_name)
        except Exception:  # noqa: BLE001 — profiling must never raise
            return None

    # --------------------------------------------- semantic result cache

    _CACHEABLE_QUERY_TYPES = ("timeseries", "groupBy", "topN")

    def _serve_full_cache(self, query, table) -> QueryResult | None:
        """Tier-2 lookup (docs/CACHING.md): a hit returns a fresh
        QueryResult sharing the cached rows, with a real observability
        record (cache_hit=True, cache_tier="full", path="cache",
        rows_scanned=0). None = miss/bypass, caller executes."""
        rc = self.result_cache
        if not rc.full_enabled or in_introspection() \
                or getattr(query, "query_type", None) \
                not in self._CACHEABLE_QUERY_TYPES \
                or getattr(table, "generation", None) is None:
            return None
        t0 = time.perf_counter()
        with _span("result-cache") as sp:
            hit = rc.get_full(query, table)
            sp.set(tier="full", hit=hit is not None)
        if hit is None:
            return None
        rows, druid, meta = hit
        m = {"query_type": query.query_type, "datasource": table.name,
             "cache_hit": True, "cache_tier": "full",
             "rows_scanned": 0, "segments_scanned": 0,
             "segments_total": meta.get("segments_total", 0),
             "rows_returned": len(rows),
             # the fingerprint is memoized on the entry's meta at store
             # time: warm serves must not pay the normalization walk
             "_wl": meta.get("_wl_fp"),
             "total_ms": (time.perf_counter() - t0) * 1000}
        res = QueryResult(query, rows, druid, m)
        # the entry's live meta dict rides along so the SQL layer can
        # memoize its rendered DataFrame on the entry
        # (Engine._frame_from): frame construction is over half the
        # warm-serve wall for small results
        res._cache_meta = meta
        self.record(m)
        return res

    def _store_full_cache(self, query, table, res: QueryResult,
                          fp=None):
        """Populate tier 2 from a successfully served result (single
        path, batch singles, and fused batch legs all funnel here).
        `fp` is the query's workload fingerprint, memoized on the entry
        meta so warm serves re-stamp it without re-normalizing."""
        rc = self.result_cache
        if not rc.full_enabled or in_introspection() \
                or getattr(query, "query_type", None) \
                not in self._CACHEABLE_QUERY_TYPES \
                or getattr(table, "generation", None) is None \
                or res.metrics.get("failed"):
            return
        rc.put_full(query, table, res.rows, res.druid, {
            "segments_total": res.metrics.get("segments_total", 0),
            "_wl_fp": fp})

    def _lower_cached(self, query, table):
        """Memoized lower(): re-lowering an unchanged query template
        costs ~5-10 ms of pure Python (dim/filter/granularity compile +
        domain restriction) per execution — a large slice of the warm
        per-query budget. Keyed on the full query JSON plus the
        lowering-relevant config knobs; a table identity check (not just
        the name) invalidates on re-registration."""
        with self.stages.stage("plan", self._last_metrics):
            return self._lower_cached_inner(query, table)

    def _lower_cached_inner(self, query, table):
        import json as _json

        c = self.config
        # exactly the config knobs lower() reads (beyond what the query
        # JSON itself captures); anything else would either mask a live
        # config change or needlessly fragment the cache
        key = (table.name,
               _json.dumps(query.to_json(), sort_keys=True, default=str),
               c.use_pallas, c.enable_x64,
               str(c.long_dtype), str(c.double_dtype),
               c.num_shards,
               c.dense_group_budget, c.numeric_dim_label_budget,
               c.theta_k_cap, c.sparse_theta_k_cap, c.pallas_group_cap,
               c.pallas_group_cap_factorized,
               c.dense_sketch_state_budget,
               c.pallas_rows_per_block, c.pallas_k_per_block,
               c.pallas_auto_flop_budget)
        # _cache_lock, not dispatch_lock: pipelined execution lowers
        # outside the dispatch critical section, concurrently across
        # threads. lower() itself runs unlocked (pure per-query work);
        # a duplicate concurrent lowering is last-write-wins.
        with self._cache_lock:
            hit = self._plan_cache.get(key)
            if hit is not None and hit[0] is table:
                _cache_lru_hit(self._plan_cache, key)
                return hit[1]
        plan = lower(query, table, self.config)
        with self._cache_lock:
            if len(self._plan_cache) > 512:
                _evict_one(self._plan_cache)
                self._m_cache_evict.inc(cache="plan")
            self._plan_cache[key] = (table, plan)
        return plan

    def _execute_inner(self, query, table) -> QueryResult:
        # one in-flight stage graph per query: pipeline_depth counts
        # graphs engine-wide (stages.StageScheduler.graph wraps the
        # admission controller's pipeline slot — re-entrant, so the
        # per-dispatch _pipeline_slot holds inside become no-ops here)
        with self.stages.graph(self.config.query_deadline_s):
            return self._execute_graph(query, table)

    def _execute_graph(self, query, table) -> QueryResult:
        if isinstance(query, TimeBoundaryQuerySpec):
            res = self._run_time_boundary(query, table)
        elif isinstance(query, SegmentMetadataQuerySpec):
            res = self._run_segment_metadata(query, table)
        elif isinstance(query, SearchQuerySpec):
            res = self._run_search(query, table)
        elif isinstance(query, (ScanQuerySpec, SelectQuerySpec)):
            res = self._run_scan(query, table)
        elif isinstance(query, (TimeseriesQuerySpec, GroupByQuerySpec,
                                TopNQuerySpec)):
            res = self._run_agg(query, table)
        else:
            raise TypeError(f"unknown query type {type(query).__name__}")
        return res

    def clear_cache(self, table_name: str | None = None):
        """Evict device-resident columns (+ compiled programs if full clear).
        The analog of `CLEAR DRUID CACHE` (SURVEY.md §4.5)."""
        self._m_cache_clears.inc(scope="table" if table_name else "full")
        purged = self.result_cache.clear(table_name)
        self.events.emit(
            "cache_clear", table=table_name or "*",
            jit_entries=len(self._jit_cache),
            plan_entries=len(self._plan_cache),
            arg_entries=len(self._arg_cache),
            result_entries=purged["full"],
            segment_entries=purged["segment"])
        # list() snapshots: an abandoned deadline thread may insert
        # concurrently (see _run_with_deadline) — never iterate live
        # dicts. Plan-cache mutation additionally takes _cache_lock:
        # pipelined lowering reads it outside dispatch_lock.
        if table_name is None:
            for ds in list(self._datasets.values()):
                ds.evict()
            self._datasets.clear()
            self._jit_cache.clear()
            self._arg_cache.clear()
            self._cap_hints.clear()
            with self._cache_lock:
                self._plan_cache.clear()
        elif table_name in self._datasets:
            self._datasets.pop(table_name).evict()
            self._jit_cache = OrderedDict(
                (k, v) for k, v in list(self._jit_cache.items())
                if k[0] != table_name)
            self._arg_cache = OrderedDict(
                (k, v) for k, v in list(self._arg_cache.items())
                if k[0] != table_name)
            self._cap_hints = {k: v for k, v in list(self._cap_hints.items())
                               if k[0] != table_name}
            # plans pin their TableSegments (host column arrays): drop
            # them too or a re-registration keeps the old data alive
            with self._cache_lock:
                self._plan_cache = OrderedDict(
                    (k, v) for k, v in list(self._plan_cache.items())
                    if k[0] != table_name)

    # ------------------------------------------------------------- dispatch

    def _dataset(self, table) -> DeviceDataset:
        key = table.name
        ds = self._datasets.get(key)
        if ds is None or ds.table is not table:
            prev = ds
            # the superseded snapshot rides in as `prev`: resident
            # columns REBASE device-side (only delta-touched segments'
            # rows upload — docs/INGEST.md "incremental re-place");
            # evict AFTER construction (the new dataset snapshots the
            # old stacks first), releasing the stale ledger accounting —
            # in-flight queries that captured its env keep their
            # buffers alive by reference
            ds = DeviceDataset(table, self.mesh, self._hbm_ledger,
                               prev=prev)
            if prev is not None:
                prev.evict()
            self._datasets[key] = ds
        return ds

    def _prepare(self, plan: PhysicalPlan, metrics: dict):
        """Dataset env + validity/segment masks + scan metrics — common
        preamble of every dispatch flavor."""
        with _span("prepare") as sp:
            out = self._prepare_inner(plan, metrics)
            sp.set(rows_scanned=metrics.get("rows_scanned"),
                   segments_scanned=metrics.get("segments_scanned"),
                   filter_streams=metrics["filter_streams"],
                   filter_stream_builds=metrics["filter_stream_builds"],
                   num_shards=self._active_shards or 1)
        return out

    def _prepare_inner(self, plan: PhysicalPlan, metrics: dict):
        table = plan.table
        ds = self._dataset(table)
        env = ds.env(plan.columns, plan.null_cols)
        bp = plan.bucket_plan
        bp_token = bp.cache_token if bp is not None else None
        # resident filter streams this dispatch reads, and how many of
        # them it had to build (0 once warm; 1 again after an eviction)
        metrics["filter_streams"] = len(plan.filter_streams)
        metrics["filter_stream_builds"] = 0
        tokens = [dp.cache_token for dp in plan.dim_plans
                  if dp.cache_token is not None] \
            + ([bp_token] if bp_token else []) \
            + [t for t, _, _ in plan.filter_streams]
        if tokens:
            # pin this query's whole working set (columns + every derived
            # stream it needs) so one derived add cannot evict another
            pinned = frozenset(
                [(table.name, "col", c) for c in plan.columns]
                + [(table.name, "null", c) for c in plan.null_cols]
                + [(table.name, "derived", t) for t in tokens])
            for dp in plan.dim_plans:
                if dp.cache_token is not None:
                    env["cols"][dp.derived_name] = ds.derived(
                        dp.cache_token,
                        lambda dp=dp: self._build_derived(ds, plan, dp),
                        pinned)
            if bp_token:
                env["cols"][bp.derived_name] = ds.derived(
                    bp_token,
                    lambda: self._build_bucket_stream(ds, plan), pinned)
            for token, src, cname in plan.filter_streams:
                def build(src=src, cname=cname):
                    metrics["filter_stream_builds"] += 1
                    return self._build_filter_stream(ds, plan, src, cname)
                env["cols"]["\0d:" + token] = ds.derived(token, build,
                                                         pinned)
        valid = ds.valid()
        seg_mask = ds.segment_mask(plan.pruned_ids if not plan.empty else [])
        metrics["segments_total"] = len(table.segments)
        metrics["segments_scanned"] = int(seg_mask.sum())
        metrics["rows_scanned"] = int(sum(
            table.segments[i].meta.n_valid for i in plan.pruned_ids)) \
            if not plan.empty else 0
        metrics["bytes_scanned"] = metrics["segments_scanned"] \
            * self._segment_scan_bytes(env, valid, table)
        if self._hbm_ledger.budget is not None:
            metrics["hbm_bytes"] = self._hbm_ledger.bytes_in_use
            metrics["hbm_evictions"] = self._hbm_ledger.evictions
        return env, valid, seg_mask

    @staticmethod
    def _segment_scan_bytes(env, valid, table) -> int:
        """Bytes one scanned segment hands the kernels — not what the
        answer needs: every array of the env (columns, null masks,
        derived streams) and the validity mask, at its resident width,
        over the segment's PADDED rows."""
        return table.block_rows * sum(
            a.dtype.itemsize
            for a in (*env["cols"].values(), *env["nulls"].values(), valid))

    def _build_derived(self, ds, plan: PhysicalPlan, dp):
        """Materialize one precomputed dim id stream [S, R] int32 on the
        device from its resident source column (dictionary codes for
        remap, __time for timeformat)."""
        src = dp.source_col if dp.source_col is not None else TIME_COLUMN
        col = ds.col(src)
        consts = plan.pool.consts
        import jax
        import jax.numpy as jnp

        def f(c):
            # no reshape: ids() is elementwise/shape-polymorphic, and
            # keeping [S, R] lets the output inherit the input's segment
            # sharding under a mesh without a gather
            env2 = {"cols": {src: c}, "nulls": {}}
            cdev = {k: jnp.asarray(v) for k, v in consts.items()}
            return dp.ids(env2, cdev, jnp).astype(jnp.int32)

        return jax.jit(f)(col)

    def _build_filter_stream(self, ds, plan: PhysicalPlan, src, cname):
        """Materialize a filter-owned derived stream [S, R] in its
        table's dtype (int32 ids and ranks, int64 millis): the
        columnComparison's gather of a dictionary-sized table by every
        row's code, paid once per (table, content token), not per
        dispatch (10 ns a row a 32-bit half on a v5e through XLA)."""
        col = ds.col(src)
        xmap = plan.pool.consts[cname]
        import jax
        import jax.numpy as jnp
        return jax.jit(lambda c: jnp.asarray(xmap)[c])(col)

    def _build_bucket_stream(self, ds, plan: PhysicalPlan):
        """Resident bucket stream [S, R] int32: the per-row pass
        (searchsorted for calendar boundary sets, floor-divide for
        uniform periods) is paid once per (table, token), not per
        dispatch — and uniform tokens are table-anchored, so a sliding
        query window re-uses the same stream (BucketPlan.build_stream /
        ids_from_cached)."""
        col = ds.col(TIME_COLUMN)
        consts = plan.pool.consts
        import jax
        import jax.numpy as jnp

        def f(c):
            cdev = {k: jnp.asarray(v) for k, v in consts.items()}
            return plan.bucket_plan.build_stream(c, cdev).astype(jnp.int32)

        return jax.jit(f)(col)

    def _segment_window(self, plan: PhysicalPlan, n_segments: int):
        """(lo, W) covering every pruned segment, or None. Interval
        pruning is mask-only inside the kernel (pruned segments multiply
        by zero but their bytes are still read); with time-partitioned
        ingest the pruned set is contiguous on the segment axis, so the
        dispatch dynamic-slices the [S, R] working set down to a pow2-
        quantized window and reads ONLY those bytes — this is what turns
        SURVEY.md §3.5 P4 pruning into real HBM savings. Safe for the
        Pallas kernel too: its grid is shape-driven and its row block
        rb divides block_rows by eligibility (pallas_reduce.eligible),
        so a window of W blocks is always an exact rb multiple >= rb.
        Mask-kind plans window too: _run_partials re-embeds the
        windowed mask into the full segment stack, so the scan/select/
        search assemblers keep indexing by global segment id. Skipped
        when a mesh shards the segment axis (per-shard windows would
        need divisibility) and when the window saves <25%."""
        if self.mesh is not None or plan.empty:
            return None
        ids = plan.pruned_ids
        if not ids:
            return None
        lo, hi = min(ids), max(ids) + 1
        W = _next_pow2(hi - lo)
        if 4 * W >= 3 * n_segments:
            return None
        return min(lo, n_segments - W), W

    @staticmethod
    def _window_kernel(kernel, W: int):
        """Wrap a partials kernel so the jitted program dynamic-slices
        every [S, ...] input to [W, ...] at `lo` before compute. One
        compile per (template, W); `lo` is traced, so interval changes
        that keep the window size re-use the executable."""
        def windowed(env, valid, seg_mask, consts, lo):
            return kernel(*_window_slice(env, valid, seg_mask, lo, W),
                          consts)
        return windowed

    @staticmethod
    def _embed_windowed_mask(out: dict, plan: PhysicalPlan, win,
                             n_seg_full: int) -> dict:
        """Windowed mask back into the full segment stack: every
        consumer (scan/select/search assembly) indexes rows by
        GLOBAL segment id; segments outside the window are pruned,
        so their rows are legitimately all-False."""
        if win is None or plan.kind != "mask":
            return out
        lo, W = win
        w = np.asarray(out["mask"]).reshape(W, -1)
        full = np.zeros((n_seg_full, w.shape[1]), bool)
        full[lo:lo + W] = w
        out["mask"] = full.reshape(-1)
        return out

    def _run_partials(self, plan: PhysicalPlan, metrics: dict) -> dict:
        import jax
        if self.mesh is not None:
            return self._run_partials_mesh(plan, metrics)
        with self._pipeline_slot():
            # stage 1 (enqueue, under dispatch_lock): env build, jit
            # cache, per-call args, and the async dispatch itself —
            # the lock releases once the device has the work and the
            # result buffers are pinned in the HbmLedger
            with self._enqueue_lock(metrics):
                env, valid, seg_mask = self._prepare(plan, metrics)
                win = self._segment_window(plan, len(seg_mask))
                if win is not None:
                    metrics["segments_window"] = win[1]
                n_seg_full = len(seg_mask)
                key = plan.fingerprint() \
                    + ((win[1],) if win else ())
                jitted, hit = self._program(
                    key, lambda: jax.jit(
                        self._window_kernel(plan.kernel, win[1])
                        if win is not None else plan.kernel),
                    "partials", metrics)
                t0 = time.perf_counter()
                with _span("dispatch", jit_cache_hit=hit, num_shards=1,
                           **_form_attr(metrics)):
                    consts_dev, seg_arg = self._args_for(plan, seg_mask,
                                                         None)
                    out = jitted(env, valid, seg_arg, consts_dev,
                                 win[0]) if win is not None \
                        else jitted(env, valid, seg_arg, consts_dev)
                pin = self._pin_inflight(out)
            # stage 2 (complete, lock-free): one device_get round trip
            # of the whole output tree
            with _span("host-transfer"):
                out = self._fetch_tree(out, metrics, pin)
        metrics["execute_ms"] = (time.perf_counter() - t0) * 1000
        metrics["jit_cache_hit"] = hit
        metrics["num_shards"] = 1
        return self._embed_windowed_mask(out, plan, win, n_seg_full)

    def _note_chip_dispatch(self, chips):
        """Per-chip dispatch-participation counters behind sys.devices /
        GET /debug/devices (dispatch occupancy)."""
        with self._totals_lock:
            for c in chips:
                self._chip_dispatches[c] = \
                    self._chip_dispatches.get(c, 0) + 1

    def _run_partials_mesh(self, plan: PhysicalPlan,
                           metrics: dict) -> dict:
        """Sharded dispatch (executor.sharding; docs/TPU_NOTES.md
        "sharded serving"): columns sit placed per chip (interleaved
        segment→chip assignment), the per-chip LOCAL window slices each
        chip's pruned working set, and an aggregate runs the mesh's one
        program (`mesh_program`, fixed when the mesh is built):
        "per_chip" runs the single-chip `plan.kernel` on every chip's
        own rows (`jax.shard_map`, no collective), brings the per-chip
        unfinalized partials back sharded and merges them at the host
        broker with the segment-cache algebra; "gspmd", the spelling of
        a mesh that spans processes, hands the whole program to GSPMD
        (replicated outputs, compiler-inserted psum/all-gather).
        Mask-kind plans (scan/select/search) fetch sharded row masks and
        inverse-permute the placed segment axis back to logical order."""
        from tpu_olap.executor import sharding as sh

        mesh = self.mesh
        program = self.mesh_program
        D = mesh.devices.size
        with self._pipeline_slot():
            with self._enqueue_lock(metrics):
                env, valid, seg_mask = self._prepare(plan, metrics)
                S = len(seg_mask)
                per_chip = S // D
                is_agg = plan.kind == "agg" and plan.key_fn is not None
                win = None
                if is_agg and not plan.empty:
                    win = sh.local_window(plan.pruned_ids, D, per_chip)
                    if win is not None:
                        metrics["segments_window"] = win[1] * D
                        metrics["segments_window_per_chip"] = win[1]
                key = plan.fingerprint() + ("mesh", D,
                                            win[1] if win else 0)
                jitted, hit = self._program(
                    key, lambda: sh.mesh_agg_kernel(
                        plan, mesh, per_chip, program, win)
                    if is_agg else sh.mesh_mask_kernel(plan, mesh),
                    "mesh", metrics)
                t0 = time.perf_counter()
                with _span("dispatch", jit_cache_hit=hit, num_shards=D,
                           mesh_program=program if is_agg else "mask",
                           **_form_attr(metrics)):
                    consts_dev, seg_arg = self._args_for(plan, seg_mask,
                                                         mesh)
                    out = jitted(env, valid, seg_arg, consts_dev,
                                 win[0]) if win is not None \
                        else jitted(env, valid, seg_arg, consts_dev)
                pin = self._pin_inflight(out)
                self._note_chip_dispatch(range(D))
            # stage 2, lock-free: ONE device_get pulls every chip's
            # shard concurrently (per-device transfers overlap)
            with _span("host-transfer"):
                out = self._fetch_tree(out, metrics, pin)
        metrics["execute_ms"] = (time.perf_counter() - t0) * 1000
        metrics["jit_cache_hit"] = hit
        metrics["num_shards"] = D
        if is_agg:
            metrics["mesh_program"] = program
            if program == "per_chip":
                with _span("broker-merge", num_shards=D):
                    out = sh.broker_merge(out, plan.agg_plans, D)
                metrics["merge"] = "broker"
            else:
                metrics["merge"] = "gspmd"
                # the GSPMD spelling runs the generic key_fn, never the
                # Mosaic call the plan was eligible for
                metrics.pop("pallas", None)
        if plan.kind == "mask":
            # placed -> logical segment order: the scan/select/search
            # assemblers index rows by GLOBAL logical segment id
            ds = self._datasets[plan.table.name]
            m = np.asarray(out["mask"]).reshape(S, -1)
            out = dict(out)
            out["mask"] = m[ds.to_place].reshape(-1)
        return out

    def _args_for(self, plan: PhysicalPlan, seg_mask: np.ndarray, mesh):
        """Device copies of the per-call inputs (const pool + segment
        mask), content-cached: a repeated query template with the same
        literals re-uses resident buffers instead of paying per-call
        host->device uploads (the BI-dashboard hot case)."""
        import jax

        consts = plan.pool.consts
        ckey = (plan.table.name,
                tuple((k, v.shape, str(v.dtype), v.tobytes())
                      for k, v in consts.items()),
                seg_mask.tobytes(),
                mesh.devices.size if mesh else 0)
        hit = self._arg_cache.get(ckey)
        if hit is not None:
            _cache_lru_hit(self._arg_cache, ckey)
            return hit
        if mesh is not None:
            from tpu_olap.executor.sharding import replicate_put, shard_put
            consts_dev = {k: replicate_put(v, mesh)
                          for k, v in consts.items()}
            seg_arg = shard_put(seg_mask, mesh)
        else:
            consts_dev = jax.device_put(consts)
            seg_arg = jax.device_put(seg_mask)
        if len(self._arg_cache) > 256:
            _evict_one(self._arg_cache)
            self._m_cache_evict.inc(cache="arg")
        self._arg_cache[ckey] = (consts_dev, seg_arg)
        return consts_dev, seg_arg

    def _packed_jit(self, plan: PhysicalPlan, cap: int, win=None):
        """(jitted packed program, layout) for a given group cap.
        Single-device only: packed buffers hold FINALIZED values, which
        cannot ride the mesh broker merge (partials must stay
        unfinalized to merge) — mesh dispatch takes _run_partials_mesh
        instead. `win` appends the segment-window slice."""
        import jax

        layout = make_layout(plan, self.config, cap,
                             lowering._default_backend())
        key = plan.fingerprint() + ("packed", layout.cap) \
            + ((win[1],) if win else ())

        def build():
            packed = build_packer(plan.kernel, plan, layout)
            if win is not None:
                packed = self._window_kernel(packed, win[1])
            return jax.jit(packed)
        # the caller counts the compile
        jitted, hit = self._program(key, build)
        return jitted, layout, hit

    def _run_packed(self, plan: PhysicalPlan, metrics: dict):
        """Single-fetch path: jit(kernel + device finalize/compact/pack),
        one buffer back. The buffer cap adapts per template: first run
        uses the config cap, later runs size from the last observed group
        count (pow2 buckets keep the jit-template space small), with a
        sized retry if a run overflows its hint. Returns None only when
        the true group count exceeds the config cap (caller re-runs the
        unpacked per-array path)."""
        with self._pipeline_slot():
            with self._enqueue_lock(metrics):
                env, valid, seg_mask = self._prepare(plan, metrics)
                win = self._segment_window(plan, len(seg_mask))
                if win is not None:
                    metrics["segments_window"] = win[1]
            cap_limit = min(self.config.result_group_cap,
                            plan.total_groups)
            base_key = plan.fingerprint() + (1,)
            hint = self._cap_hints.get(base_key)
            cap = cap_limit if hint is None else \
                min(cap_limit, max(64, _next_pow2(2 * hint)))

            t0 = time.perf_counter()
            with _span("dispatch", packed=True,
                       **_form_attr(metrics)) as dsp:
                while True:
                    # stage 1 per attempt: jit/arg caches + the async
                    # dispatch under the lock; a cap-overflow retry
                    # re-enters it (rare — the hint adapts)
                    with self._enqueue_lock(metrics):
                        consts_dev, seg_arg = self._args_for(
                            plan, seg_mask, None)
                        jitted, layout, hit = self._packed_jit(
                            plan, cap, win)
                        if not hit:
                            self._note_compile("packed", metrics)
                        buf = jitted(env, valid, seg_arg, consts_dev,
                                     win[0]) if win is not None else \
                            jitted(env, valid, seg_arg, consts_dev)
                        pin = self._pin_inflight(buf)
                    # stage 2: the packed path's transfer is already a
                    # single buffer — one round trip
                    with _span("host-transfer"):
                        buf = self._fetch_tree(buf, metrics, pin)
                        with _span("unpack"):
                            count, idx, compact = unpack(buf, layout)
                    if count <= layout.cap:
                        break
                    if count > cap_limit:
                        metrics["result_groups"] = count
                        metrics["jit_cache_hit"] = hit
                        dsp.set(jit_cache_hit=hit, overflow=True)
                        return None  # cap exceeded: unpacked re-run
                    cap = min(cap_limit, _next_pow2(count))
                dsp.set(jit_cache_hit=hit, num_shards=1)
        self._cap_hints[base_key] = count
        metrics["execute_ms"] = (time.perf_counter() - t0) * 1000
        metrics["jit_cache_hit"] = hit
        metrics["num_shards"] = 1
        metrics["result_groups"] = count
        metrics["result_cap"] = layout.cap
        metrics["packed"] = True
        return idx, compact, layout

    # ------------------------------------------------------------ agg paths

    def _run_agg(self, query, table) -> QueryResult:
        metrics = self._last_metrics = {}
        t0 = time.perf_counter()
        with _span("lower"):
            plan = self._lower_cached(query, table)
        metrics["lower_ms"] = (time.perf_counter() - t0) * 1000
        if getattr(plan, "pallas_reason", "off") is None:
            metrics["pallas"] = True  # fused Pallas reduce kernel active
        # which group-reduce implementation the plan holds, and why the
        # Pallas kernel was turned down where it was: "reduce" is the
        # generic kernel with nothing to group by (one masked reduce)
        if plan.sparse:
            metrics["reduce_path"] = "sparse"
        elif plan.pallas_reason is None:
            metrics["reduce_path"] = "pallas"
        else:
            metrics["reduce_path"] = \
                "scatter" if plan.total_groups > 1 else "reduce"
            metrics["pallas_reason"] = plan.pallas_reason
            if plan.total_groups > 1:
                # "scatter" names the generic grouped kernel; which
                # device program that is — a masked reduce a slot, or
                # XLA's scatter — is the kernel's own function of the plan
                _note_form(metrics, plan, plan.total_groups)
        specs = agg_specs_by_name(query.aggregations)
        # theta set-op post-aggs consume RAW sketch tables host-side;
        # the packed path finalizes sketches on device, so those queries
        # ride the unpacked per-array fetch instead
        keep_raw = theta_raw_fields(query.post_aggregations)

        topn = isinstance(query, TopNQuerySpec)
        if topn:
            metrics["topn_group_space"] = plan.total_groups
        having = sparse_dispatch.device_having(self.mesh, plan)
        if getattr(query, "having", None) is not None:
            metrics["having_where"] = "device" if having else "host"
        if plan.sparse:
            out, count, program = self._dispatch(
                lambda: sparse_dispatch.run_sparse(self, plan, metrics),
                metrics, table.name)
            t0 = time.perf_counter()
            with self.stages.stage("finalize", metrics):
                with _span("finalize"):
                    arrays = finalize_aggs(out, plan.agg_plans, specs,
                                           keep_raw)
                with _span("post-agg"):
                    eval_post_aggs(arrays, query.post_aggregations)
            names = self._out_names(query)
            pm, present = sparse_dispatch.present_groups(out, plan)
            sub = {n: np.asarray(arrays[n])[pm] for n in names}
            with self.stages.stage("assemble", metrics), \
                    _span("assemble"):
                if topn:
                    metrics["topn_rows_fetched"] = len(pm)
                    res = self._emit_topn(
                        query, plan, present, sub,
                        "device" if program.top else "host")
                else:
                    if "having_where" in metrics:
                        metrics["having_rows_fetched"] = len(pm)
                    if having:
                        metrics["having_groups_in"] = count
                    res = self._emit_groupby(query, plan, present, sub,
                                             decided=having)
            res.metrics = metrics
            metrics["assemble_ms"] = (time.perf_counter() - t0) * 1000
            return res
        if topn:
            # the dense paths hand the host the whole [K] space to rank
            metrics["topn_rows_fetched"] = plan.total_groups
        elif "having_where" in metrics:
            metrics["having_rows_fetched"] = plan.total_groups

        if self.result_cache.seg_enabled:
            arrays = self._run_agg_segcached(query, plan, metrics, specs,
                                             keep_raw, table)
            if arrays is not None:
                t0 = time.perf_counter()
                with self.stages.stage("finalize", metrics), \
                        _span("post-agg"):
                    eval_post_aggs(arrays, query.post_aggregations)
                with self.stages.stage("assemble", metrics), \
                        _span("assemble"):
                    res = self._assemble_agg(query, plan, arrays)
                res.metrics = metrics
                metrics["assemble_ms"] = (time.perf_counter() - t0) * 1000
                return res

        packed = None
        # mesh: unfinalized partials only (the broker merge needs them)
        use_packed = not keep_raw and self.mesh is None
        if use_packed:
            packed = self._dispatch(
                lambda: self._run_packed(plan, metrics), metrics,
                table.name)
        if packed is not None:
            idx, compact, layout = packed
            for p in plan.agg_plans:
                if p.kind == "hll" and \
                        getattr(specs.get(p.name), "round", True):
                    compact[p.name] = np.round(compact[p.name])
            t0 = time.perf_counter()
            with self.stages.stage("finalize", metrics), \
                    _span("finalize"):
                arrays = densify(idx, compact, layout, plan.agg_plans)
        else:
            if use_packed:
                metrics["packed"] = False  # cap overflow: unpacked re-run
            partials = self._dispatch(
                lambda: self._run_partials(plan, metrics), metrics,
                table.name)
            t0 = time.perf_counter()
            with self.stages.stage("finalize", metrics), \
                    _span("finalize"):
                arrays = finalize_aggs(partials, plan.agg_plans, specs,
                                       keep_raw)
        with self.stages.stage("finalize", metrics), _span("post-agg"):
            eval_post_aggs(arrays, query.post_aggregations)
        with self.stages.stage("assemble", metrics), _span("assemble"):
            res = self._assemble_agg(query, plan, arrays)
        res.metrics = metrics
        metrics["assemble_ms"] = (time.perf_counter() - t0) * 1000
        return res

    def _run_agg_segcached(self, query, plan, metrics, specs, keep_raw,
                           table):
        """Tier-1 per-segment partial-aggregate path (docs/CACHING.md):
        serve every cached, fully-interval-covered segment from the
        cache, recompute the rest in ONE device pass that keys the
        group space by (segment, group) so each computed segment's
        partials come back separately (cacheable), then fold everything
        on the host via the aggregators' merge semantics and finalize.
        Returns finalized arrays, or None when the plan bypasses the
        tier (the caller falls through to the packed/partials paths).
        The bypass reason and per-segment decision are stamped on the
        record and the `segment-cache` span (EXPLAIN ANALYZE shows
        them)."""
        import functools as _ft

        from tpu_olap.kernels.groupby import merge_partials

        rc = self.result_cache
        if in_introspection():
            # sys.* introspection must not consult, populate, or tick
            # counters on EITHER cache tier (same rule as
            # _serve_full_cache): observing the system cannot change
            # sys.caches / cache_pinned / result_cache_* metrics
            return None
        reason = rc.tier1_bypass_reason(plan, self.mesh)
        if reason is not None:
            metrics["segment_cache"] = f"bypass: {reason}"
            rc.count_bypass()
            return None
        intervals = query.intervals or (ETERNITY,)
        tkey = rc.template_key(query, table)
        floor = max(0, int(self.config.segment_cache_min_rows))
        covered, always_compute = [], []
        for sid in plan.pruned_ids:
            sm = table.segments[sid].meta
            # only segments ENTIRELY inside one query interval have
            # interval-independent partials; straddlers (and sub-floor
            # segments, where entry overhead beats the recompute win)
            # are computed fresh every time and never stored. DELTA
            # blocks (real-time appends, docs/INGEST.md) also always
            # recompute: their contents change block-in-place across
            # append snapshots, so caching them would churn the budget
            # for entries one append away from unreachable.
            if sm.n_valid >= floor and table.segment_sealed(sid) \
                    and any(
                    iv.start <= sm.time_min and iv.end > sm.time_max
                    for iv in intervals):
                covered.append(sid)
            else:
                always_compute.append(sid)
        with _span("segment-cache") as sp:
            hits = rc.get_segments(tkey, table, plan, covered)
            to_compute = sorted(
                [s for s in covered if s not in hits] + always_compute)
            sp.set(segments_cached=len(hits),
                   segments_computed=len(to_compute),
                   segments_uncovered=len(always_compute))
            if to_compute:
                fresh = self._dispatch(
                    lambda: self._run_seg_partials(plan, metrics,
                                                   to_compute),
                    metrics, table.name)
                storable = set(covered)
                for sid in to_compute:
                    if sid in storable:
                        rc.put_segment(tkey, table, plan, sid, fresh[sid])
            else:
                fresh = {}
                metrics["segments_total"] = len(table.segments)
                metrics["segments_scanned"] = 0
                metrics["rows_scanned"] = 0
                metrics["bytes_scanned"] = 0
                metrics["num_shards"] = 1
        metrics["cache_hit"] = bool(hits)
        if hits:
            metrics["cache_tier"] = "segment"
        metrics["segments_cached"] = len(hits)
        metrics["segments_computed"] = len(to_compute)
        parts = [hits[s] if s in hits else fresh[s]
                 for s in sorted(set(covered) | set(always_compute))]
        merged = _ft.reduce(
            lambda a, b: merge_partials(a, b, plan.agg_plans), parts)
        with _span("finalize"):
            return finalize_aggs(merged, plan.agg_plans, specs, keep_raw)

    def _run_seg_partials(self, plan: PhysicalPlan, metrics: dict,
                          compute_ids: list) -> dict:
        """One pass computing PER-SEGMENT partials for `compute_ids`:
        the plan's key_fn front half runs over a window covering the
        segments, the group key is extended to (local segment, group),
        and one group_reduce over W*K groups yields every segment's own
        mergeable partials dict ({segment id: partials}). One compiled
        program per (template, W) serves ANY to-compute subset — the
        subset rides in through the seg-mask runtime argument."""
        with self._pipeline_slot():
            with self._enqueue_lock(metrics):
                env, valid, _ = self._prepare(plan, metrics)
                table = plan.table
                ds = self._dataset(table)
                seg_mask = ds.segment_mask(compute_ids)
            # honest scan accounting: only the computed segments are read
            metrics["segments_scanned"] = len(compute_ids)
            metrics["rows_scanned"] = int(sum(
                table.segments[i].meta.n_valid for i in compute_ids))
            metrics["bytes_scanned"] = len(compute_ids) \
                * self._segment_scan_bytes(env, valid, table)
            S = len(seg_mask)
            K = plan.total_groups
            mesh = self.mesh
            t0 = time.perf_counter()
            if mesh is not None:
                # mesh variant (docs/CACHING.md "cache shards"): the
                # per-chip LOCAL window slices each chip's placed
                # segments, the key extends by placed window position,
                # and the [D·W·K] table comes back SHARDED per chip —
                # each (chip, segment) partials entry is cut out on the
                # host and cached per segment; serving folds cached +
                # fresh entries at the broker via merge_partials
                from tpu_olap.executor import sharding as sh
                D = mesh.devices.size
                per_chip = S // D
                lo = min(i // D for i in compute_ids)
                hi = max(i // D for i in compute_ids) + 1
                W = min(_next_pow2(hi - lo), per_chip)
                lo = min(lo, per_chip - W)
                jkey = plan.fingerprint() + ("segcache-mesh", D, W)

                def build():
                    return sh.mesh_seg_partials_kernel(plan, mesh,
                                                       per_chip, W, K)
            else:
                import jax
                D = 1
                lo, hi = min(compute_ids), max(compute_ids) + 1
                W = min(_next_pow2(hi - lo), S)
                lo = min(lo, S - W)
                jkey = plan.fingerprint() + ("segcache", W)

                def build():
                    return jax.jit(self._seg_partials_kernel(plan, W, K))
            with self._enqueue_lock(metrics):
                jitted, hit = self._program(jkey, build, "segcache",
                                            metrics)
                _note_form(metrics, plan, D * W * K)
                with _span("dispatch", jit_cache_hit=hit, segcache=True,
                           num_shards=D, **_form_attr(metrics)):
                    consts_dev, seg_arg = self._args_for(plan, seg_mask,
                                                         mesh)
                    out = jitted(env, valid, seg_arg, consts_dev, lo)
                pin = self._pin_inflight(out)
                if mesh is not None:
                    self._note_chip_dispatch(range(D))
            with _span("host-transfer"):
                out = self._fetch_tree(out, metrics, pin)
            metrics["jit_cache_hit"] = hit
            metrics["num_shards"] = D
            metrics["execute_ms"] = (time.perf_counter() - t0) * 1000
        shaped = {name: np.asarray(a).reshape(
            (D, W, K) + np.asarray(a).shape[1:]) for name, a in out.items()}
        # logical sid -> (chip sid mod D, local sid // D); one chip: (0, sid)
        return {sid: {name: a[sid % D, sid // D - lo]
                      for name, a in shaped.items()}
                for sid in compute_ids}

    @staticmethod
    def _seg_partials_kernel(plan: PhysicalPlan, W: int, K: int):
        """fn(env, valid, seg_mask, consts, lo): window-slice every
        [S, ...] input to [W, ...], run the plan's filter/dim front
        half, extend the key by the local segment index, reduce over
        W*K groups. `lo` is traced, so a sliding to-compute window of
        the same width re-uses the executable. The int32 key is safe:
        tier1_bypass_reason rejects plans whose segment-extended key
        space reaches 2^31."""
        import jax
        import jax.numpy as jnp

        from tpu_olap.kernels.groupby import group_reduce

        def fn(env, valid, seg_mask, consts, lo):
            fenv, mask, key = plan.key_fn(
                *_window_slice(env, valid, seg_mask, lo, W), consts)
            with jax.named_scope("key"):
                r = mask.shape[0] // W
                seg_local = jnp.repeat(jnp.arange(W, dtype=jnp.int32), r)
                key2 = seg_local * jnp.int32(K) + key.astype(jnp.int32)
            return group_reduce(key2, mask, fenv, plan.agg_plans, W * K,
                                consts)
        return fn

    def _assemble_agg(self, query, plan, arrays) -> QueryResult:
        """Final-arrays -> QueryResult by query type. Shared tail of the
        single-query agg path and the batch executor's per-leg finish."""
        if isinstance(query, TimeseriesQuerySpec):
            return self._assemble_timeseries(query, plan, arrays)
        if isinstance(query, GroupByQuerySpec):
            return self._assemble_groupby(query, plan, arrays)
        return self._assemble_topn(query, plan, arrays)

    def _out_names(self, query):
        names = [a.name for a in query.aggregations]
        names += [p.name for p in query.post_aggregations]
        return names

    def _bucket_emit_ids(self, query, plan):
        """Bucket ids to emit, honoring intervals and descending order."""
        if plan.empty:
            return []
        intervals = query.intervals or (ETERNITY,)
        starts = plan.bucket_plan.starts
        ids = [b for b in range(plan.bucket_plan.n_buckets)
               if any(iv.overlaps(int(starts[b]),
                                  int(starts[b + 1])
                                  if b + 1 < len(starts) else plan.t_max + 1)
                      for iv in intervals)]
        return ids

    def _assemble_timeseries(self, query, plan, arrays) -> QueryResult:
        names = self._out_names(query)
        rows, druid = [], []
        skip_empty = bool(dict(query.context).get(
            "skipEmptyBuckets", self.config.skip_empty_buckets))
        bucket_ids = self._bucket_emit_ids(query, plan)
        if query.descending:
            bucket_ids = bucket_ids[::-1]
        present = arrays["_rows"] > 0
        with _span("assemble-rows") as sp:
            for b in bucket_ids:
                if skip_empty and not present[b]:
                    continue
                vals = {n: render_value(arrays[n][b]) for n in names}
                ts = iso(plan.bucket_plan.starts[b])
                rows.append({"timestamp": ts, **vals})
                druid.append({"timestamp": ts, "result": vals})
            sp.set(rows=len(rows))
        return QueryResult(query, rows, druid)

    def _decode_groups(self, plan, idx):
        """Present flat group ids -> (bucket ids, {dim name -> dense ids}).
        A dimension's values are `dp.labels[ids]`; the caller looks up
        only the rows it emits or sorts by. `idx`: one array of ids in
        the mixed radix of `plan.sizes`, or, of a sparse plan whose key
        is several words, the tuple of the words' arrays: each position
        of `sizes` is taken from its word (`plan.key_words`: one word of
        every position that carries an id, but for such a plan) under
        its radix there (`plan.key_radix`)."""
        words = idx if isinstance(idx, tuple) else (idx,)
        radix_vals = [np.zeros(len(words[0]), np.int64) for _ in plan.sizes]
        for rem, positions in zip(words, plan.key_words):
            for i in positions[::-1]:
                radix_vals[i] = rem % plan.key_radix[i]
                rem = rem // plan.key_radix[i]
        buckets = radix_vals[0]
        dim_ids = {dp.name: ids
                   for dp, ids in zip(plan.dim_plans, radix_vals[1:])}
        return buckets, dim_ids

    def _assemble_groupby(self, query, plan, arrays) -> QueryResult:
        return self._emit_groupby(query, plan, None, arrays)

    def _decode_present(self, query, plan, present, sub):
        """-> (present, sub, bucket ids, {dim name -> dense ids}) under the
        leaf span `decode-groups`. `present` None: `sub` holds the dense
        [K] tables, cut here to the groups that have rows; a tuple: the
        words of a wide sparse key (`_decode_groups`), of which word 0
        stands for the groups from here on (their count is all that is
        read of it)."""
        with _span("decode-groups") as sp:
            if present is None:
                present = np.nonzero(sub["_rows"] > 0)[0]
                sub = {n: np.asarray(sub[n])[present]
                       for n in self._out_names(query)}
            words = present if isinstance(present, tuple) else (present,)
            sp.set(groups=len(words[0]), key_words=len(words))
            return (words[0], sub) + self._decode_groups(plan, present)

    def _emit_groupby(self, query, plan, present, sub,
                      decided=False) -> QueryResult:
        """present: flat group ids (any int width; a wide sparse key's
        words as a tuple); sub: compact per-group
        final values (present None: the dense [K] tables). Shared tail of
        the dense and sparse paths. `decided`: the groups are those the
        device's HAVING let through (`sparse_dispatch.device_having`), so
        none is tested
        here; they are decoded, ordered and limited as any."""
        names = self._out_names(query)
        present, sub, buckets, dim_ids = self._decode_present(
            query, plan, present, sub)
        labels = {dp.name: dp.labels for dp in plan.dim_plans}

        if query.having is not None and not decided:
            with _span("having", where="host",
                       groups_in=len(present)) as sp:
                hmask = eval_having(
                    query.having, sub,
                    {d: labels[d][ids] for d, ids in dim_ids.items()})
                buckets = buckets[hmask]
                dim_ids = {k: v[hmask] for k, v in dim_ids.items()}
                sub = {k: v[hmask] for k, v in sub.items()}
                sp.set(groups_out=len(buckets))
            self._last_metrics["having_groups_in"] = len(present)

        order = np.arange(len(buckets))
        ls = query.limit_spec
        if ls is not None and ls.columns:
            with _span("ordered-limit", groups=len(order), limit=ls.limit):
                order = _limit_order(ls, buckets, dim_ids, labels, sub)
        if ls is not None:
            lo = ls.offset
            hi = None if ls.limit is None else lo + ls.limit
            order = order[lo:hi]

        # labels and rows of what is emitted only: a LIMIT over a sparse
        # group-by keeps tens of rows of hundreds of thousands of groups
        with _span("assemble-rows", rows=len(order)):
            buckets = buckets[order]
            dim_vals = {d: labels[d][ids[order]]
                        for d, ids in dim_ids.items()}
            sub = {n: sub[n][order] for n in names}
            rows, druid = [], []
            starts = plan.bucket_plan.starts
            for i in range(len(order)):
                ts = iso(starts[buckets[i]])
                ev = {dp.name: render_value(dim_vals[dp.name][i])
                      for dp in plan.dim_plans}
                ev.update({n: render_value(sub[n][i]) for n in names})
                rows.append({"timestamp": ts, **ev})
                druid.append({"version": "v1", "timestamp": ts,
                              "event": ev})
        return QueryResult(query, rows, druid)

    def _assemble_topn(self, query, plan, arrays) -> QueryResult:
        return self._emit_topn(query, plan, None, arrays, "host")

    def _emit_topn(self, query, plan, present, sub, where) -> QueryResult:
        """present: flat group ids, sub: their final values — every present
        group in ascending id order (`where` "host": the threshold is
        applied here), or the rows a device threshold kept, in rank order
        (`where` "device"). Shared tail of the dense and sparse paths. A
        bucket's rows are its first `threshold` by the metric (last if
        inverted); where the metric ties they come in the order they
        arrived in, which is the dimension's own ascending order (label
        order) either way."""
        names = self._out_names(query)
        present, sub, buckets, dim_ids = self._decode_present(
            query, plan, present, sub)
        dp = plan.dim_plans[0]
        with _span("topn-threshold", where=where, groups=len(present),
                   threshold=query.threshold):
            m = np.asarray(sub[query.metric], np.float64)
            if query.inverted:
                m = -m
            # a group whose metric is null (NaN) or -inf is never ranked
            ranked = np.flatnonzero(m > -np.inf)
            # stable: by bucket, then by the metric, then as they came
            ranked = ranked[np.lexsort((-m[ranked], buckets[ranked]))]
            lo = np.searchsorted(buckets[ranked],
                                 np.arange(plan.sizes[0] + 1))
        rows, druid = [], []
        with _span("assemble-rows") as sp:
            for b in self._bucket_emit_ids(query, plan):
                keep = ranked[lo[b]:lo[b + 1]][:query.threshold]
                labels = dp.labels[dim_ids[dp.name][keep]]
                ts = iso(plan.bucket_plan.starts[b])
                result = []
                for i, g in enumerate(keep):
                    ev = {dp.name: render_value(labels[i])}
                    ev.update({n: render_value(sub[n][g]) for n in names})
                    result.append(ev)
                    rows.append({"timestamp": ts, **ev})
                druid.append({"timestamp": ts, "result": result})
            sp.set(rows=len(rows))
        return QueryResult(query, rows, druid)

    # ----------------------------------------------------------- scan paths

    def _run_scan(self, query, table) -> QueryResult:
        metrics = self._last_metrics = {}
        t0 = time.perf_counter()
        with _span("lower"):
            plan = self._lower_cached(query, table)
        metrics["lower_ms"] = (time.perf_counter() - t0) * 1000
        partials = self._dispatch(
            lambda: self._run_partials(plan, metrics), metrics, table.name)
        mask = partials["mask"].reshape(-1, table.block_rows)
        mask = mask[:len(table.segments)]  # drop shard-padding segments

        t0 = time.perf_counter()
        if isinstance(query, ScanQuerySpec):
            cols = list(query.columns) if query.columns else \
                [c for c in table.schema]
            offset, limit = query.offset, query.limit
            descending = query.order == "descending"
        else:
            dims = list(query.dimensions) or [
                c for c, t in table.schema.items() if t.is_dim]
            mets = list(query.metrics) or [
                c for c, t in table.schema.items()
                if not t.is_dim and c != TIME_COLUMN]
            cols = [TIME_COLUMN] + dims + mets
            offset, limit = query.paging_offset, query.page_size
            descending = query.descending

        with self.stages.stage("assemble", metrics), _span("assemble"):
            events = self._gather_rows(table, mask, cols, offset, limit,
                                       descending)
        metrics["assemble_ms"] = (time.perf_counter() - t0) * 1000

        if isinstance(query, ScanQuerySpec):
            druid = [{"columns": cols, "events": events}]
            res = QueryResult(query, events, druid)
        else:
            druid = [{
                "timestamp": iso(plan.t_min),
                "result": {
                    "pagingIdentifiers": {"offset": offset + len(events)},
                    "events": [{"offset": offset + i, "event": e}
                               for i, e in enumerate(events)],
                },
            }]
            res = QueryResult(query, events, druid)
        res.metrics = metrics
        return res

    def _gather_rows(self, table, mask, cols, offset, limit, descending):
        """Columnar assembly: pick (segment, row) takes under the
        offset/limit budget, then decode and convert each COLUMN once
        (dictionary decode, C-level tolist, vectorized null substitution)
        and zip into the wire's list-of-dicts at the end — O(cols)
        vectorized passes instead of a Python render per cell."""
        seg_iter = table.segments[::-1] if descending else table.segments
        takes = []       # (segment, row-index array)
        n_taken = 0
        skipped = 0
        budget = None if limit is None else offset + limit
        for s in seg_iter:
            m = mask[s.meta.segment_id]
            idx = np.nonzero(m)[0]
            if descending:
                idx = idx[::-1]
            if idx.size == 0:
                continue
            if budget is not None and skipped + n_taken + idx.size > budget:
                idx = idx[:budget - skipped - n_taken]
            take = idx
            if skipped < offset:
                drop = min(offset - skipped, take.size)
                skipped += drop
                take = take[drop:]
            if take.size:
                takes.append((s, take))
                n_taken += take.size
            if budget is not None and skipped + n_taken >= budget:
                break
        if not takes:
            return []

        out_cols = []
        for c in cols:
            v = np.concatenate([s.columns[c][take] for s, take in takes])
            d = table.dictionaries.get(c)
            if d is not None:
                out_cols.append(d.decode(v).tolist())
                continue
            vals = v.tolist()  # numpy -> plain python in C
            if any(c in s.null_masks for s, _ in takes):
                nm = np.concatenate(
                    [s.null_masks[c][take] if c in s.null_masks
                     else np.zeros(take.size, bool) for s, take in takes])
                if nm.any():
                    vals = [None if n else x for x, n in zip(vals, nm)]
            if v.dtype.kind == "f":
                vals = [None if x != x else x for x in vals]  # NaN -> null
            out_cols.append(vals)
        return [dict(zip(cols, row)) for row in zip(*out_cols)]

    # ------------------------------------------------------------- metadata

    def _run_search(self, query, table) -> QueryResult:
        """Single-pass search: ONE device dispatch computes the
        filter+interval row mask (shared across every searched
        dimension), then per-dimension value counts are host-side
        bincounts over the dictionary-coded columns — instead of one
        full GroupBy dispatch per dimension (VERDICT round-2 weak #6).
        Non-string dimensions (no dictionary) keep the GroupBy path."""
        dims = list(query.search_dimensions) or [
            c for c, t in table.schema.items() if t.is_dim]
        matcher = _search_matcher(query.query)
        hits = []

        coded = [d for d in dims if d in table.dictionaries]
        if coded:
            mask_query = ScanQuerySpec(
                data_source=query.data_source,
                intervals=query.intervals,
                filter=query.filter,
                virtual_columns=query.virtual_columns,
            )
            metrics = self._last_metrics
            plan = self._lower_cached(mask_query, table)
            partials = self._dispatch(
                lambda: self._run_partials(plan, metrics), metrics,
                table.name)
            # per-dimension masked value counts over the stacked code
            # columns, all dims packed into ONE result vector: one extra
            # jitted call (~0.2 ms of scatter-adds for all SSB dims at
            # SF1) plus one mask round-trip (_run_partials materializes
            # outputs to host; fusing the counts into the mask program
            # itself would remove that transfer — future work). Under a
            # mesh the host does the same bincounts. The dispatch mask
            # may be padded past the segment stack (shard-multiple
            # rounding) — slice, never the reverse (the kernels mask
            # pruned segments in place rather than compacting them away)
            with self._pipeline_slot():
                # the column fetch mutates the dataset cache and the
                # counts program is a device dispatch: both stage-1
                # work; the host bincounts / transfer run lock-free
                with self._enqueue_lock(metrics):
                    ds = self._dataset(table)
                    cards = tuple(table.dictionaries[d].cardinality
                                  for d in coded)
                    pins = frozenset((table.name, "col", d)
                                     for d in coded)
                    cols = tuple(ds.col(d, pins) for d in coded)
                    n_flat = cols[0].size
                    dev_mask = partials["mask"]
                    if dev_mask.size < n_flat:
                        raise AssertionError(
                            "search mask shorter than the segment stack")
                    packed_dev = None
                    if ds.to_logical is None:
                        packed_dev = _search_counts_packed(
                            cards, dev_mask.reshape(-1)[:n_flat], cols)
                if packed_dev is None:
                    m = np.asarray(dev_mask).reshape(-1)[:n_flat]
                    if ds.to_logical is not None:
                        # mesh: the fetched mask was inverse-permuted to
                        # LOGICAL segment order, but the resident column
                        # stacks sit in PLACEMENT order — re-permute so
                        # mask and codes walk the same rows (bincounts
                        # are order-insensitive, consistency is all
                        # that matters)
                        m = m.reshape(len(ds.to_logical), -1)[
                            ds.to_logical].reshape(-1)
                    packed = np.concatenate(
                        [np.bincount(np.asarray(c).reshape(-1)[m],
                                     minlength=card + 1)
                         for c, card in zip(cols, cards)])
                else:
                    packed = np.asarray(packed_dev)
            off = 0
            for dim, card in zip(coded, cards):
                d = table.dictionaries[dim]
                counts = packed[off:off + card + 1]
                off += card + 1
                for code in np.nonzero(counts[1:])[0]:
                    v = d.values[code]
                    if matcher(v):
                        hits.append({"dimension": dim, "value": v,
                                     "count": int(counts[code + 1])})

        for dim in [d for d in dims if d not in table.dictionaries]:
            inner = GroupByQuerySpec(
                data_source=query.data_source,
                intervals=query.intervals,
                filter=query.filter,
                virtual_columns=query.virtual_columns,
                dimensions=(DefaultDimensionSpec(dim),),
                aggregations=(CountAggregation("count"),),
            )
            res = self._run_agg(inner, table)
            for r in res.rows:
                v = r[dim]
                if v is not None and matcher(v):
                    hits.append({"dimension": dim, "value": v,
                                 "count": int(r["count"])})
        hits.sort(key=lambda h: (_search_sort_key(query.sort, h["value"]),
                                 h["dimension"]))
        hits = hits[:query.limit]
        t0, _ = table.time_boundary
        druid = [{"timestamp": iso(t0), "result": hits}]
        return QueryResult(query, hits, druid)

    def _run_time_boundary(self, query, table) -> QueryResult:
        t0, t1 = table.time_boundary
        intervals = query.intervals or (ETERNITY,)
        lo = max(t0, min(iv.start for iv in intervals))
        hi = min(t1, max(iv.end for iv in intervals) - 1)
        result = {}
        if query.bound in (None, "minTime"):
            result["minTime"] = iso(lo)
        if query.bound in (None, "maxTime"):
            result["maxTime"] = iso(hi)
        druid = [{"timestamp": iso(lo), "result": result}]
        return QueryResult(query, [result], druid)

    def _run_segment_metadata(self, query, table) -> QueryResult:
        cols = table.column_metadata(set(query.to_include) or None)
        t0, t1 = table.time_boundary
        record = {
            "id": f"{table.name}_merged",
            "intervals": [f"{iso(t0)}/{iso(t1 + 1)}"],
            "columns": cols,
            "numRows": table.num_rows,
            "size": int(sum(c.get("size", 0) for c in cols.values())),
        }
        return QueryResult(query, [record], [record])


def _window_slice(env, valid, seg_mask, lo, W: int):
    """(env, valid, seg_mask) with every [S, ...] input dynamic-sliced to
    the `W` segments from `lo`, inside a jitted program."""
    import jax

    def sl(a):
        return jax.lax.dynamic_slice_in_dim(a, lo, W, axis=0)
    with jax.named_scope("window"):
        wenv = {"cols": {c: sl(a) for c, a in env["cols"].items()},
                "nulls": {c: sl(a) for c, a in env["nulls"].items()}}
        return wenv, sl(valid), sl(seg_mask)


def _note_form(metrics: dict, plan, num_groups: int):
    """`reduce_form` on the record, from the group space the generic
    kernel is built with: the plan's K, or the segment-extended W*K
    (D*W*K on the mesh) of the segment-cache partials program, which
    branches on that number and not on K."""
    metrics["reduce_form"] = reduce_form(
        num_groups, [p.kind for p in plan.agg_plans])


def _form_attr(metrics: dict) -> dict:
    """The `dispatch` span's `reduce_form` attribute, where the record of
    the query has one (a generic grouped aggregate on the device), and
    beside it a sparse program's other words
    (`sparse_groupby.program_words`) and who decides a GroupBy's HAVING
    (`having_where`)."""
    return {k: metrics[k]
            for k in ("reduce_form", "ext_word_bits", "sum_word_bits",
                      "cap_tables", "boundary_read", "having_where",
                      "key_words", "key_bits", "key_sort_bits")
            if metrics.get(k) is not None}


def _limit_order(ls, buckets, dim_ids, labels, sub) -> np.ndarray:
    """Row order by the limit spec's columns (stable, like np.lexsort).
    Where a LIMIT keeps few of many groups and the first column is an
    aggregate, only the groups that can reach the limit by that column
    (ties with the last of them included) have the other keys built and
    sorted: a key over a dimension is built a Python value at a time."""
    n = len(buckets)
    cand = np.arange(n)
    first = ls.columns[0]
    if ls.limit is not None and first.dimension in sub \
            and n > 4 * (ls.offset + ls.limit) + 64:
        k = np.asarray(sub[first.dimension], np.float64)
        if first.direction == "descending":
            k = -k
        if not np.isnan(k).any():
            kth = ls.offset + ls.limit - 1
            cand = np.flatnonzero(k <= np.partition(k, kth)[kth])
    keys = []
    for c in ls.columns[::-1]:
        if c.dimension == "timestamp":
            k = np.asarray(buckets[cand], np.float64)
        elif c.dimension in dim_ids:
            v = labels[c.dimension][dim_ids[c.dimension][cand]]
            k = np.asarray([("" if x is None else str(x)) for x in v])
            if c.dimension_order == "numeric":
                k = np.asarray([float(x) if x else -np.inf for x in k])
        else:
            k = np.asarray(sub[c.dimension][cand], np.float64)
        if c.direction == "descending":
            k = _invert_sort_key(k)
        keys.append(k)
    return cand[np.lexsort(keys)]


def _invert_sort_key(k: np.ndarray):
    if k.dtype.kind in "fiu":
        return -k.astype(np.float64)
    # lexicographic descending for strings: invert via codes trick
    uniq, inv = np.unique(k, return_inverse=True)
    return -inv


_search_counts_jit = None


def _search_counts_packed(cards: tuple, mask, cols):
    """One jitted program: masked value counts for every searched
    dimension, concatenated so the host fetches a single small vector.
    Code 0 is the NULL slot (bincount layout identical to the host
    np.bincount(minlength=card+1) it replaces). The jit wrapper is
    module-cached; distinct (cards, shapes) compile once each."""
    global _search_counts_jit
    if _search_counts_jit is None:
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnums=0)
        def run(cards, mask, cols):
            m = mask.reshape(-1).astype(jnp.int32)
            outs = [jnp.zeros(c + 1, jnp.int32)
                    .at[col.reshape(-1).astype(jnp.int32)]
                    .add(m, mode="drop")
                    for c, col in zip(cards, cols)]
            return jnp.concatenate(outs)

        _search_counts_jit = run
    return _search_counts_jit(cards, mask, tuple(cols))


def _search_sort_key(sort: str, value: str):
    if sort == "strlen":
        return (len(value), value)
    if sort == "alphanumeric":
        # natural order: digit runs compare numerically
        import re
        parts = re.split(r"(\d+)", value)
        return tuple((1, int(p)) if p.isdigit() else (0, p)
                     for p in parts if p != "")
    return value  # lexicographic


def _search_matcher(sq):
    if sq.fragments:
        frags = [f if sq.case_sensitive else f.lower() for f in sq.fragments]

        def m(v):
            s = v if sq.case_sensitive else v.lower()
            return all(f in s for f in frags)
        return m
    needle = sq.value if sq.case_sensitive else sq.value.lower()

    def m(v):
        s = v if sq.case_sensitive else v.lower()
        return needle in s
    return m
