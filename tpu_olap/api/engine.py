"""Engine — the L7 surface (SURVEY.md §3.1): table registration (the
DefaultSource OPTIONS analog), SQL entry point with transparent fallback,
EXPLAIN DRUID REWRITE, raw-IR passthrough (ON DRUID DATASOURCE ... EXECUTE
QUERY), and CLEAR DRUID CACHE.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np
import pandas as pd

from tpu_olap.catalog import (Catalog, StarSchema, SysTableProvider,
                              TableEntry, stmt_uses_sys)
from tpu_olap.obs.workload import (fingerprint_sql,
                                   introspection_execution)
from tpu_olap.executor import EngineConfig, QueryRunner
from tpu_olap.obs.trace import (Trace, adopt_root, current_query_id,
                                detached_trace, in_nested_execution,
                                nested_execution,
                                parse_traceparent, span as _span,
                                use_query_id, use_traceparent)
from tpu_olap.executor.dimplan import UnsupportedDimension
from tpu_olap.executor.runner import QueryResult
from tpu_olap.ir.serde import query_from_json
from tpu_olap.kernels.filtereval import UnsupportedFilter
from tpu_olap.kernels.groupby import UnsupportedAggregation
from tpu_olap.kernels.timebucket import UnsupportedGranularity
from tpu_olap.planner import DruidPlanner
from tpu_olap.planner.fallback import FallbackError, execute_fallback
from tpu_olap.resilience.errors import (BreakerOpen, QueryShed,
                                        UserError)
from tpu_olap.resilience.faults import maybe_inject
from tpu_olap.segments.ingest import (DEFAULT_BLOCK_ROWS, ingest_arrow,
                                      ingest_pandas, ingest_parquet,
                                      ingest_parquet_stream)
from tpu_olap.utils import platform as _platform

_UNSUPPORTED = (UnsupportedAggregation, UnsupportedFilter,
                UnsupportedGranularity, UnsupportedDimension)


def _mark_slo_observed(e: BaseException):
    """Stamp an exception whose failure was already counted against the
    SLO (a recorded fallback failure, a raw-IR boundary observation) so
    the statement-boundary catch-all (Engine._observe_failure) never
    counts one served failure twice. Only set on exceptions that are
    NEVER shared across statements — the coalescer fans one exception
    object out to N callers, and each caller is its own served
    response, so those must stay unmarked."""
    try:
        e._slo_observed = True
    except Exception:  # noqa: BLE001 — slotted/exotic exceptions
        pass


def _failure_status(e: BaseException) -> int:
    """HTTP shape of a propagating failure: the taxonomy's http_status,
    or the server's legacy mapping for untyped errors (api.server:
    ValueError/KeyError -> 400, rest -> 500)."""
    status = getattr(e, "http_status", None)
    if status is None:
        return 400 if isinstance(e, (ValueError, KeyError)) else 500
    return int(status)


class Engine:
    def __init__(self, config: EngineConfig | None = None):
        self.config = config or EngineConfig()
        _platform.configure_compile_cache()  # before the first jit
        _platform.retain_freed_memory()
        self.catalog = Catalog()
        self.runner = QueryRunner(self.config)
        self.planner = DruidPlanner(self.catalog, self.config)
        # observability (tpu_olap.obs): the runner's; these are the API
        self.tracer = self.runner.tracer
        self.metrics = self.runner.metrics
        self.last_plan = None
        # Serializes device dispatch only (the runner's compile/arg caches
        # are not concurrent and the chip has one program queue anyway,
        # SURVEY.md §3.5 P1). Planning and the pandas fallback run outside
        # it, so concurrent HTTP clients aren't wedged behind one slow
        # device query (VERDICT round 1 "missing" #6). The lock LIVES
        # on the runner (QueryRunner.dispatch_lock) so the shared-scan
        # coalescer can let concurrent callers wait outside it and ride
        # one fused dispatch (executor.batch); this alias keeps the
        # engine-level admin surface (clear_cache) on the same lock.
        # With pipelined execution (EngineConfig.pipeline_depth > 0, the
        # default) the runner holds it only for stage-1 enqueue — host
        # transfer, finalize, and assembly overlap other queries'
        # device work (docs/PERF_MODEL.md "execution pipeline").
        self.device_lock = self.runner.dispatch_lock
        # planner-initiated subquery execution (uncorrelated shapes
        # inline as literals so the outer query can push down; the inner
        # aggregate itself rides the device path when rewritable)
        self.planner.run_subquery = self._run_stmt
        # fallback-initiated derived-table execution (round 5): a FROM/
        # JOIN (SELECT ...) body is usually the scan-heavy, device-
        # eligible part of a statement the outer interpreter serves —
        # route it back through the statement executor so the inner
        # aggregate rides the device path (fallback._run_inner_stmt)
        self.catalog.device_runner = self._run_stmt
        # sys.* virtual datasources (catalog.systables; ISSUE 11): the
        # engine is observable through its own SQL — sys.tables /
        # sys.segments / sys.queries / sys.query_templates / sys.metrics
        # / sys.caches / sys.cubes / sys.checkpoints / sys.devices
        # resolve through the catalog to live-state frames served on
        # the interpreter path with accounting suppressed
        self.catalog.sys_provider = SysTableProvider(self)
        # materialized rollup cubes (tpu_olap.cubes; docs/CUBES.md):
        # registry of (dim subset x grain) partial-aggregate rollups;
        # the planner's cube-rewrite pass serves covered aggregates
        # from them, the background maintainer rebuilds stale ones
        from tpu_olap.cubes import CubeRegistry
        self.cubes = CubeRegistry(self)
        # real-time ingest (segments/delta.py; docs/INGEST.md):
        # Engine.append / POST /ingest / INSERT INTO land rows in a
        # WAL-backed mutable delta scope, queryable immediately; a
        # background compactor seals deltas into time-partitioned
        # segments under the admission/breaker machinery
        from tpu_olap.segments.delta import IngestManager
        self.ingest = IngestManager(self)
        # WAL sync-lag probe for the regression sentinel (obs.sentinel;
        # ISSUE 17): per-table unsynced frame counts from the ingest
        # snapshot, consulted on the telemetry tick — wired here
        # because the runner (which owns the sentinel) predates the
        # ingest manager
        self.runner.sentinel.add_probe("wal", self._wal_lag_probe)

    def _wal_lag_probe(self) -> dict:
        """{table: unsynced WAL frames} for tables with live WALs."""
        out = {}
        snap = self.ingest.snapshot() or {}
        for name, st in (snap.get("tables") or {}).items():
            wal = st.get("wal") if isinstance(st, dict) else None
            if wal and wal.get("lag_records") is not None:
                out[name] = int(wal["lag_records"])
        return out

    # ------------------------------------------------------- registration

    def register_table(self, name: str, data, time_column: str | None = None,
                       star_schema=None, accelerate: bool = True,
                       block_rows: int = DEFAULT_BLOCK_ROWS,
                       column_map: dict | None = None,
                       columns=None, time_partition="auto", **options):
        """Register a datasource. `data`: pandas DataFrame, pyarrow Table,
        parquet path, or a list of parquet paths (a multi-file dataset).
        accelerate=False registers a plain (dimension) table served only
        by the fallback path — the reference's non-druid-backed relation.

        Parquet inputs stream row-group batches into segments under
        bounded host memory (SURVEY.md §8.4 #4); Arrow inputs ingest
        straight from the Arrow columns (no pandas detour); the fallback
        DataFrame materializes lazily on first fallback use. `columns`
        optionally prunes the ingested column set — always POST-rename
        names (after column_map), for every input type; parquet reads
        skip pruned columns entirely.

        `time_partition` is the Druid segmentGranularity analog:
        "day"/"month"/"year" buckets rows into disjoint calendar
        partitions (interval pruning then drops whole segments, and the
        residual row-level time mask — with its 8-bytes/row __time scan
        traffic — elides when every scanned segment sits inside the
        query interval); "auto" (default) picks the finest granularity
        the table can amortize; None disables partitioning.
        """
        # "ingest" fault site (resilience.faults): a raised fault aborts
        # registration before any segment state is built, so a failed
        # ingest never leaves a half-registered table behind
        maybe_inject(self.config, "ingest", 0)
        column_map = dict(column_map) if column_map else None
        if column_map and time_column in column_map:
            time_column = column_map[time_column]

        def _renamed_arrow(tbl):
            if column_map:
                tbl = tbl.rename_columns(
                    [column_map.get(c, c) for c in tbl.schema.names])
            return tbl

        segments = None
        pq_fields = {}
        if isinstance(data, str) or (
                isinstance(data, (list, tuple))
                and all(isinstance(p, str) for p in data)):
            import pyarrow.parquet as pq
            paths = [data] if isinstance(data, str) else list(data)
            inverse = {v: k for k, v in (column_map or {}).items()}
            read_cols = [inverse.get(c, c) for c in columns] \
                if columns else None

            def load_frame(_paths=tuple(paths), _cols=read_cols):
                f = pd.concat(
                    [pq.read_table(p, columns=_cols).to_pandas()
                     for p in _paths], ignore_index=True) \
                    if len(_paths) > 1 else \
                    pq.read_table(_paths[0], columns=_cols).to_pandas()
                return f.rename(columns=column_map) if column_map else f

            if accelerate:
                segments = ingest_parquet_stream(
                    name, paths, time_column, block_rows,
                    columns=columns, column_map=column_map,
                    time_partition=time_partition)
            frame_source = load_frame
            pq_fields = dict(
                parquet_paths=tuple(paths),
                parquet_read_cols=tuple(read_cols) if read_cols else None,
                parquet_column_map=column_map,
                parquet_rows=sum(pq.ParquetFile(p).metadata.num_rows
                                 for p in paths))
        elif isinstance(data, pd.DataFrame):
            frame = data.copy()
            if column_map:
                frame = frame.rename(columns=column_map)
            if columns:
                frame = frame[list(columns)]
            import pyarrow as pa
            table = pa.Table.from_pandas(frame, preserve_index=False) \
                if accelerate else None
            frame_source = frame
        else:  # pyarrow table
            table = _renamed_arrow(data)
            if columns:
                table = table.select(list(columns))

            def frame_source(_t=table):
                return _t.to_pandas()

        if accelerate and segments is None:
            segments = ingest_arrow(name, table, time_column, block_rows,
                                    time_partition=time_partition)
        star = star_schema
        if isinstance(star, dict):
            star = StarSchema.from_json(star)
        if segments is not None:
            segments.star = star  # FD-aware dim-domain restriction
        entry = TableEntry(name=name, segments=segments,
                           frame_source=frame_source,
                           time_column=time_column, star=star,
                           options=dict(options), **pq_fields)
        self.catalog.register(entry)
        # ingest invalidation (docs/CACHING.md): the fresh TableSegments
        # took the next generation, orphaning every semantic-cache entry
        # for this name at key level; purge them eagerly so the byte
        # budget doesn't stay occupied by unreachable entries
        self.runner.result_cache.invalidate_table(name)
        self.runner.events.emit(
            "ingest", table=name, accelerated=bool(accelerate),
            generation=segments.generation if segments is not None
            else None,
            rows=segments.num_rows if segments is not None else None,
            segments=len(segments.segments) if segments is not None
            else 0)
        # real-time ingest hook (docs/INGEST.md): a first registration
        # with an existing WAL is crash recovery — replay appends to
        # the exact acknowledged state; re-registering a live table
        # resets its log instead (the appends belonged to the old data)
        self.ingest.on_register(entry)
        # cube cascade (docs/CUBES.md): rollups over this table are now
        # stale — the rewrite pass stops serving them at generation-
        # check time; the maintainer wakes to rebuild
        self.cubes.on_table_registered(name)
        return entry

    def append(self, table: str, rows,
               traceparent: str | None = None) -> dict:
        """Real-time append (docs/INGEST.md): `rows` (list of dicts or
        a DataFrame, columns ⊆ the table's schema, time under the
        registered time column or ``__time``) land in the table's
        mutable in-memory delta and are queryable immediately alongside
        sealed segments — same kernels, same caches, exact results.
        With `ingest_wal_dir` set the batch is framed into the table's
        write-ahead log BEFORE acknowledgment, so a crash replays to
        the exact acknowledged state at the next registration. A delta
        at `ingest_max_delta_rows` sheds with IngestBackpressure (HTTP
        429 + Retry-After) — never a silent drop. SQL spelling:
        ``INSERT INTO t (cols) VALUES (...)``; HTTP: ``POST /ingest``.

        Returns {table, rows, generation, sealed_generation,
        delta_rows, watermark, wal_seq}. A valid W3C `traceparent`
        (ISSUE 17) is stamped into the ack and the emitted events."""
        tp = parse_traceparent(traceparent)
        with use_traceparent(tp["traceparent"] if tp else None):
            ack = self.ingest.append(table, rows)
        if tp is not None and isinstance(ack, dict):
            ack.setdefault("traceparent", tp["traceparent"])
        return ack

    def compact_now(self, table: str | None = None):
        """Synchronously seal delta rows into time-partitioned sealed
        segments (the background compactor's deterministic spelling).
        `table=None` compacts every table with a non-empty delta."""
        if table is None:
            return self.ingest.compact_all()
        return self.ingest.compact_now(table)

    def checkpoint_now(self, table: str | None = None):
        """Durably checkpoint a table's sealed scope
        (docs/DURABILITY.md): compact the delta, spill the sealed
        segments as checksummed chunk files under
        `EngineConfig.ingest_store_dir`, atomically advance the
        checkpoint manifest, and truncate the WAL through the lag-one
        watermark — after which a process restart replays only the
        post-checkpoint tail. SQL spelling: ``CHECKPOINT DRUID TABLE
        t``. `table=None` checkpoints every table with ingest state."""
        if table is None:
            return self.ingest.checkpoint_all()
        return self.ingest.checkpoint_now(table)

    def close(self):
        """Deterministically cancel every background stage graph the
        engine owns — the compactor and WAL flushers (ingest.stop),
        the cube maintainer, and the stage scheduler's ticker — and
        flush the event sink. The engine stays queryable afterwards;
        appends reopen WALs lazily and re-register the compactor/flush
        graphs on demand. Server.stop() calls this."""
        self.ingest.stop()
        self.cubes.stop(join=True)
        self.runner.stages.stop()
        self.runner.events.flush(2.0)

    def register_lookup(self, name: str, mapping: dict):
        """Register a named lookup map (Druid lookup extraction fn). SQL
        reaches it as LOOKUP(col, 'name') in projections, GROUP BY, and
        filters (SURVEY.md §3.3 lookup extraction dims)."""
        self.catalog.lookups[name] = {str(k): v for k, v in mapping.items()}

    # --------------------------------------------------------------- SQL

    def sql(self, query: str) -> pd.DataFrame:
        """Plan, execute (device or fallback), and return a DataFrame.

        Statement-level verbs beyond SELECT (the reference's extended
        parser, SURVEY.md §3.1): `CLEAR DRUID CACHE [table]`,
        `EXPLAIN DRUID REWRITE <sql>`, `EXPLAIN ANALYZE <sql>`, and
        `ON DRUID DATASOURCE <ds> EXECUTE QUERY '<json>'`.
        """
        return self._sql_traced(query)[0]

    def _sql_traced(self, query: str, traceparent: str | None = None):
        """sql() plus the completed trace (None for statement verbs or
        when tracing is off) — the EXPLAIN ANALYZE entry point.

        `traceparent` (ISSUE 17): a W3C trace-context header value from
        the HTTP edge. A valid header is stamped on the root span and
        the query record (distributed-trace join key); an invalid one
        is ignored — trace propagation must never fail a query."""
        tp = parse_traceparent(traceparent)
        with use_traceparent(tp["traceparent"] if tp else None):
            return self._sql_traced_inner(query, tp)

    @contextlib.contextmanager
    def _root(self, name: str):
        """The statement's root trace: the one the HTTP edge opened for
        this call (obs.trace.adopt_root — the edge closes it, after the
        last byte is written), else one of this call's own."""
        root = adopt_root(name)
        if root is not None:
            yield root
            return
        with self.tracer.trace(name) as root:
            yield root

    def _untraced(self, run):
        """Run a statement that leaves no trace (a statement verb,
        sys.* introspection): a root the HTTP edge opened for it is
        discarded and out of sight while it runs."""
        root = adopt_root("sql")
        if root is None:
            return run()
        root.discard()
        with detached_trace():
            return run()

    def _sql_traced_inner(self, query: str, tp: dict | None = None):
        verb = _match_verb(query)
        if verb is not None:
            return self._untraced(lambda: verb(self)), None
        from tpu_olap.planner.sqlparse import parse_sql
        pre_stmt = None
        if _SYS_HINT_RE.search(query):
            # probable sys.* introspection statement: confirm against
            # the parsed tree (a string literal mentioning "sys." must
            # not hijack a user query) and serve it outside the trace —
            # introspection appears nowhere in its own stats. A parse
            # failure defers to the traced path so the error records
            # like any other bad statement; a confirmed non-sys parse
            # is reused below (no double parse).
            try:
                pre_stmt = parse_sql(query)
            except Exception:
                pre_stmt = None
            if pre_stmt is not None \
                    and stmt_uses_sys(pre_stmt, self.catalog):
                return self._untraced(
                    lambda: self._execute_sys_stmt(pre_stmt)), None
        with self._root("sql") as root:
            root.set(sql=query)
            if tp is not None:
                root.set(traceparent=tp["traceparent"],
                         trace_id=tp["trace_id"],
                         parent_span_id=tp["parent_id"])
            try:
                with root.span("parse"):
                    stmt = pre_stmt if pre_stmt is not None \
                        else parse_sql(query)
                with root.span("plan") as sp:
                    plan = self.planner.plan_stmt(stmt, query)
                    sp.set(rewritten=plan.rewritten)
                    if plan.fallback_reason:
                        sp.set(fallback_reason=plan.fallback_reason)
                self.last_plan = plan
                out = self._execute_plan(plan)
            except Exception as e:
                # statement-boundary SLO accounting: failures that
                # escaped every inner observation site (e.g. a shed
                # grouping-sets leg, a planner-subquery refusal) still
                # count against the budget exactly once
                self._observe_failure(e)
                raise
        return out, root if isinstance(root, Trace) else None

    def _observe_failure(self, e: BaseException):
        """Count a failure propagating to the client against the SLO —
        exactly once (sites whose record already counted it marked the
        exception), never for nested statements (the outer statement
        accounts), and never for client-shaped errors (a 400 for bad
        SQL must not burn the error budget; 429+ does). Does NOT mark
        the exception itself: a coalescer-shared exception is one
        served failure PER caller, and each caller's own boundary runs
        this exactly once."""
        if getattr(e, "_slo_observed", False) or in_nested_execution():
            return
        if _failure_status(e) < 429:
            return
        self.runner.slo.observe(0.0, failed=True)

    def _execute_plan(self, plan) -> pd.DataFrame:
        stmt = getattr(plan, "stmt", None)
        if stmt is not None and getattr(stmt, "grouping_sets", None) \
                is not None and not plan.rewritten:
            out = self._try_grouping_sets_union(plan)
            if out is not None:
                return out
        device_ms = 0.0  # user-visible time burned on a failed device try
        if plan.rewritten and self.cubes.active:
            # aggregate rewrite onto a materialized rollup cube
            # (planner.cuberewrite; docs/CUBES.md): a covered query is
            # served by folding thousands of stored cube rows instead
            # of scanning the base table — None falls through to the
            # ordinary device path, never an error
            from tpu_olap.planner.cuberewrite import try_serve_cube
            res = try_serve_cube(self, plan)
            if res is not None:
                with _span("render"):
                    return self._frame_from(plan, res)
        if plan.rewritten:
            res = None
            t_dev = time.perf_counter()
            try:
                # the runner serializes dispatch internally
                # (dispatch_lock) — and with batch_window_ms set,
                # concurrent callers coalesce into one fused dispatch
                with _span("execute"):
                    res = self.runner.execute(plan.query,
                                              plan.entry.segments)
            except _UNSUPPORTED as e:
                plan.query = None
                plan.fallback_reason = f"lowering failed: {e}"
                device_ms = (time.perf_counter() - t_dev) * 1000
            except QueryShed:
                # admission shed = the system is OVERLOADED: routing the
                # query to the (slower) interpreter would amplify the
                # overload. Propagate -> HTTP 429, client retries later
                # (the statement boundary counts it against the SLO).
                raise
            except BreakerOpen as e:
                # breaker open = the DEVICE is sick, the host is fine:
                # degraded-but-correct serving from the interpreter,
                # stamped path="fallback_breaker" in the record schema.
                if not self.config.fallback_on_device_failure:
                    raise  # refusal: SLO-counted at the boundary
                plan.query = None
                plan.breaker_fallback = True
                plan.fallback_reason = f"breaker open: {e}"
            except Exception as e:
                # Structural "never an error" guarantee (SURVEY.md §2
                # property 2): dispatch retries exhausted on a
                # non-structural failure (device loss, deadline, compiler
                # bug) -> correct-but-slow fallback, not a user error.
                if not self.config.fallback_on_device_failure:
                    # the interim record never SLO-counts; the
                    # statement boundary counts this propagation
                    raise
                plan.query = None
                plan.fallback_reason = \
                    f"device failure: {type(e).__name__}: {e}"
                device_ms = (time.perf_counter() - t_dev) * 1000
            if res is not None:
                # conversion bugs in _frame_from must surface, not be
                # silently reclassified as device failures
                with _span("render"):
                    return self._frame_from(plan, res)
        return self._execute_fallback_recorded(plan, device_ms)

    def _execute_fallback_recorded(self, plan,
                                   device_ms: float = 0.0) -> pd.DataFrame:
        """Run the pandas fallback under a span AND a history record, so
        the fallback path shares the dashboard metric schema (query_id /
        total_ms / rows_scanned / ... — the observability contract) the
        device paths emit. Failures record too, then propagate.
        `device_ms` is the wall already burned on a failed device
        attempt (deadline wait, exhausted retries): stamped on the
        record so the SLO classifies the query by the latency the USER
        saw, not just the fallback's own wall."""
        stmt = plan.stmt
        entry = plan.entry if plan.entry is not None \
            else self.catalog.maybe(getattr(stmt, "table", None) or "")
        rows = 0
        if entry is not None:
            rows = (entry.segments.num_rows if entry.is_accelerated
                    else entry.materialized_rows) or 0
        m = {"query_type": "fallback",
             "datasource": getattr(stmt, "table", None) or "(derived)",
             "rows_scanned": rows, "cache_hit": False}
        if device_ms > 0:
            m["device_attempt_ms"] = round(device_ms, 3)
        if plan.fallback_reason:
            m["fallback_reason"] = plan.fallback_reason
        if getattr(plan, "breaker_fallback", False):
            m["fallback_breaker"] = True
        # workload attribution (obs.workload): fallback statements
        # fingerprint from their literal-masked SQL text, so the
        # interpreter path lands in sys.query_templates too
        if self.runner.workload.enabled:
            try:
                m["_wl"] = fingerprint_sql(plan.sql or "", stmt,
                                           m["datasource"])
            except Exception:  # noqa: BLE001 — profiling never raises
                pass
        t0 = time.perf_counter()
        with _span("fallback") as sp:
            sp.set(reason=plan.fallback_reason)
            try:
                out = execute_fallback(stmt, self.catalog, self.config)
            except Exception as e:
                m["failed"] = True
                m["total_ms"] = (time.perf_counter() - t0) * 1000
                if _failure_status(e) < 429:
                    # client-shaped failure (unsupported SQL -> 400):
                    # recorded and event-logged, but it must not burn
                    # the SLO error budget (record() honors this key)
                    m["client_error"] = True
                self.runner.record(m)
                if not in_nested_execution():
                    _mark_slo_observed(e)  # record() accounted for it
                raise
            m["total_ms"] = (time.perf_counter() - t0) * 1000
            m["rows_returned"] = len(out)
            self.runner.record(m)
        return out

    def _try_grouping_sets_union(self, plan):
        """GROUPING SETS/ROLLUP/CUBE on the device path (VERDICT r4
        missing #4): a union of per-set GROUP BY dispatches sharing the
        compile cache — each leg differs only in dimension list, so the
        legs land on the same jit template family as their plain GROUP
        BY twins. Absent group keys / GROUPING() markers are reattached
        as constant columns after each leg runs. Returns None when the
        shape cannot be unioned (SELECT *; ORDER BY not on an output
        column) — the caller then takes the whole-statement fallback."""
        from tpu_olap.planner.fallback import (FallbackError,
                                               _sort_order_items,
                                               grouping_set_legs,
                                               union_order_keys)
        stmt = plan.stmt
        # only worth decomposing when the legs can ride the device path:
        # an unaccelerated or derived source would re-run the scan/join
        # once per set where the whole-statement fallback filters once
        # (and gating here keeps that fallback an independent oracle for
        # the union path in tests)
        if stmt.derived is not None or stmt.grouping_sets == []:
            return None
        entry = self.catalog.maybe(stmt.table)
        if entry is None or not entry.is_accelerated:
            return None
        try:
            out_names, legs = grouping_set_legs(stmt)
        except FallbackError:
            return None
        order_keys = union_order_keys(stmt, out_names) \
            if stmt.order_by else []
        if order_keys is None:
            return None  # union ORDER BY must name output columns
        t0 = time.perf_counter()
        frames, leg_plans = [], []
        for leg_stmt, consts in legs:
            lp = self.planner.plan_stmt(leg_stmt)
            leg_plans.append(lp)
            with nested_execution():
                # legs are internal: one SLO observation + one `query`
                # event for the whole union, stamped below
                f = self._execute_plan(lp)
            for name, val in consts.items():
                # absent group keys reattach as np.nan (float64 NULL),
                # matching the whole-statement fallback's dtype — a bare
                # None would make an object column that breaks numeric
                # comparisons/sorts over the union
                f[name] = np.nan if val is None else val
            frames.append(f.loc[:, out_names])
        plan.grouping_legs = leg_plans
        n_dev = sum(1 for lp in leg_plans if lp.rewritten)
        plan.fallback_reason = (
            None if n_dev == len(leg_plans) else
            f"grouping-sets union: {n_dev}/{len(leg_plans)} legs "
            "device-rewritten")
        out = pd.concat(frames, ignore_index=True) if frames else \
            pd.DataFrame(columns=out_names)
        if order_keys:
            out = _sort_order_items(out, order_keys, stmt.order_by)
        lo = stmt.offset
        hi = None if stmt.limit is None else lo + stmt.limit
        out = out.iloc[lo:hi].reset_index(drop=True)
        # the union is the served response: ONE SLO observation + ONE
        # `query` event spanning every leg (the legs' own records were
        # marked nested above)
        if not in_nested_execution():
            total_ms = (time.perf_counter() - t0) * 1000
            self.runner.slo.observe(total_ms)
            self.runner.events.emit(
                "query",
                query_id=current_query_id() or self.tracer.new_query_id(),
                query_type="groupBy", path="grouping_sets",
                datasource=stmt.table, total_ms=round(total_ms, 3),
                cache_hit=False)
        return out

    def sql_batch(self, queries) -> list[pd.DataFrame]:
        """Execute several SQL statements as one submission, fusing
        rewritten device queries against the same table into shared-scan
        batch dispatches (executor.batch): identical statements scan
        once, compatible aggregations ride one fused device pass.
        Statement verbs and fallback statements run individually; any
        leg that fails on the batch path re-runs through the ordinary
        single-query path (device retry, then pandas fallback), so the
        'never an error' property holds per statement. Results come
        back in input order."""
        return self.sql_batch_ids(queries)[0]

    def sql_batch_ids(self, queries, traceparent: str | None = None):
        """sql_batch plus each statement's query_id (parallel to the
        results) — the ids the /sql/batch X-Query-Id header carries so
        clients can correlate responses with /debug/queries,
        sys.queries, and Perfetto traces. A valid W3C `traceparent`
        covers every statement in the submission (ISSUE 17)."""
        tp = parse_traceparent(traceparent)
        with use_traceparent(tp["traceparent"] if tp else None):
            return self._sql_batch_ids_inner(queries, tp)

    def _sql_batch_ids_inner(self, queries, tp: dict | None = None):
        queries = list(queries)
        outs: list = [None] * len(queries)
        plans: dict[int, object] = {}
        groups: dict[str, list[int]] = {}
        # one query_id per logical statement, minted up front so the
        # fused batch legs' records stay attributable (obs.trace)
        qids = [self.tracer.new_query_id() for _ in queries]
        with self._root("sql_batch") as root:
            root.set(statements=len(queries))
            if tp is not None:
                root.set(traceparent=tp["traceparent"],
                         trace_id=tp["trace_id"],
                         parent_span_id=tp["parent_id"])
            for i, q in enumerate(queries):
                verb = _match_verb(q)
                if verb is not None:
                    # statement verbs and sys.* introspection produce
                    # no history record: "-" in the X-Query-Id slot
                    # keeps the header positional without handing the
                    # client an id that matches nothing
                    outs[i], qids[i] = verb(self), "-"
                    continue
                if _SYS_HINT_RE.search(q):
                    from tpu_olap.planner.sqlparse import parse_sql
                    try:
                        stmt = parse_sql(q)
                    except Exception:
                        stmt = None  # the plan span raises it properly
                    if stmt is not None \
                            and stmt_uses_sys(stmt, self.catalog):
                        outs[i] = self._execute_sys_stmt(stmt)
                        qids[i] = "-"
                        continue
                with root.span("plan", query_id=qids[i]):
                    plan = self.planner.plan(q)
                if plan.rewritten and self.cubes.active:
                    # cube-covered statements serve immediately (their
                    # record carries the statement's own query_id) and
                    # never join a fused base-table scan they don't need
                    from tpu_olap.planner.cuberewrite import \
                        try_serve_cube
                    with use_query_id(qids[i]):
                        res = try_serve_cube(self, plan)
                    if res is not None:
                        outs[i] = self._frame_from(plan, res)
                        continue
                plans[i] = plan
                stmt = getattr(plan, "stmt", None)
                if plan.rewritten and not (
                        stmt is not None
                        and getattr(stmt, "grouping_sets", None)
                        is not None):
                    groups.setdefault(plan.entry.name, []).append(i)
            done = set()
            for name, idxs in groups.items():
                if len(idxs) < 2:
                    continue
                entry = self.catalog.get(name)
                try:
                    boxed = self.runner._execute_batch_boxed(
                        [plans[i].query for i in idxs], entry.segments,
                        [qids[i] for i in idxs])
                except QueryShed:
                    # a shed aborts the WHOLE submission with 429: every
                    # statement that has not yet produced a result is a
                    # user-visible failure, counted per statement like
                    # the /sql path would (statements that completed
                    # before the shed keep their good/bad observations)
                    for o in outs:
                        if o is None:
                            self.runner.slo.observe(0.0, failed=True)
                    raise
                for i, b in zip(idxs, boxed):
                    if isinstance(b, BaseException):
                        if not isinstance(b, Exception):
                            # KeyboardInterrupt/SystemExit: abort the
                            # whole submission — retrying would turn a
                            # cancel into double work
                            raise b
                        continue  # single-query path (retry+fallback)
                    outs[i] = self._frame_from(plans[i], b)
                    done.add(i)
            for i, plan in plans.items():
                if i in done:
                    continue
                # non-fused legs run inside the sql_batch trace but must
                # record under their OWN statement id, not the root's
                with use_query_id(qids[i]):
                    try:
                        outs[i] = self._execute_plan(plan)
                    except Exception as e:
                        # ANY server-shaped abort (shed, breaker
                        # refusal, device failure with fallback off)
                        # kills the whole submission: count every
                        # statement still without a result — including
                        # this one, unless its own record already
                        # counted it (marked fallback failures)
                        if _failure_status(e) >= 429:
                            for j, o in enumerate(outs):
                                if o is not None:
                                    continue
                                if j == i and getattr(
                                        e, "_slo_observed", False):
                                    continue
                                self.runner.slo.observe(0.0,
                                                        failed=True)
                        raise
            if plans:
                self.last_plan = plans[max(plans)]
        return outs, qids

    def _run_stmt(self, stmt) -> pd.DataFrame:
        """Execute one parsed statement end-to-end (device path when
        rewritable, else fallback) — the planner's subquery executor.
        Does not touch last_plan: the user-visible plan is the outer
        query's. Marked nested: the inner statement's record must not
        add a second SLO observation / `query` event to the outer
        statement's served response."""
        with nested_execution():
            return self._execute_plan(self.planner.plan_stmt(stmt))

    def _execute_sys_stmt(self, stmt) -> pd.DataFrame:
        """Serve a sys.* introspection statement (catalog.systables) on
        the host/interpreter path: a sys datasource is never device
        dispatch, never cached, and its execution is accounting-
        suppressed — no trace, no history record, no metric/SLO
        observation, no profiler template — so introspection can never
        recurse into its own stats (ISSUE 11). The statement still gets
        the planner's normalization passes, so aliases, windows over
        groups, and expression simplification behave exactly like any
        other fallback statement."""
        from tpu_olap.planner.exprutil import simplify_stmt
        from tpu_olap.planner.plan import _apply_windows_over_groups
        from tpu_olap.planner.sqlparse import UnionStmt
        # detached_trace: a sys statement inside a live trace (an
        # sql_batch submission) must not leak its fallback spans into
        # that trace's ring/Perfetto export
        with introspection_execution(), nested_execution(), \
                detached_trace():
            stmt = self.planner._resolve_aliases(stmt)
            stmt = _apply_windows_over_groups(stmt)
            if not isinstance(stmt, UnionStmt):
                stmt = simplify_stmt(stmt)
            return execute_fallback(stmt, self.catalog, self.config)

    def _frame_from(self, plan, res: QueryResult) -> pd.DataFrame:
        # full-result cache hits carry their entry's live meta dict
        # (runner._serve_full_cache): memoize the rendered DataFrame on
        # it — construction dominates the warm-serve wall for small
        # results. Always hand out copies so a caller mutating the
        # frame cannot poison the cache. Keyed on the output spec: two
        # SQL spellings can share one IR entry but project differently.
        meta = getattr(res, "_cache_meta", None)
        fkey = tuple((o.name, o.source, o.cast) for o in plan.outputs)
        if meta is not None:
            cached = meta.get("frame")
            if cached is not None and meta.get("frame_key") == fkey:
                return cached.copy()
        cols = {}
        for o in plan.outputs:
            vals = [r.get(o.source) for r in res.rows]
            if o.cast == "int":
                vals = [int(v) if v is not None else None for v in vals]
            elif o.cast == "datetime":
                # naive UTC timestamps, matching pandas semantics
                vals = pd.to_datetime(vals, utc=True).tz_localize(None)
            cols[o.name] = vals
        frame = pd.DataFrame(cols,
                             columns=[o.name for o in plan.outputs])
        if meta is not None:
            meta["frame_key"] = fkey
            meta["frame"] = frame.copy()
        return frame

    def explain(self, query: str) -> dict:
        """EXPLAIN DRUID REWRITE analog: the chosen QuerySpec (or the
        fallback reason) without executing (SURVEY.md §4.5), plus, for
        an aggregate, what its record will say of its device plan
        (`sparse_dispatch.explain_lines`: `having_where`, and of a sparse
        plan `sum_word_bits`, `key_words`, `key_bits`, `key_sort_bits`;
        `key_words` and `having_where` null where lowering finds no
        device plan for the query) and, over a mesh, the spelling the
        mesh runs it in (`mesh_program`)."""
        from tpu_olap.executor import sparse_dispatch
        from tpu_olap.executor.batch import AGG_QUERY_TYPES
        plan = self.planner.plan(query)
        out = plan.explain()
        if plan.rewritten and plan.entry.is_accelerated \
                and isinstance(plan.query, AGG_QUERY_TYPES):
            try:
                out.update(sparse_dispatch.explain_lines(
                    self.runner, plan.query, plan.entry.segments))
            except _UNSUPPORTED:
                # no device plan: the fallback answers
                out["key_words"] = None
                if getattr(plan.query, "having", None) is not None:
                    out["having_where"] = None
            if self.runner.mesh is not None:
                out["mesh_program"] = self.runner.mesh_program
        return out

    # -------------------------------------------------------- passthrough

    def execute_ir(self, query) -> QueryResult:
        """Raw query-IR passthrough (`ON DRUID DATASOURCE ds EXECUTE QUERY
        '<json>'`): accepts a QuerySpec or Druid-shaped JSON dict."""
        if isinstance(query, dict):
            query = query_from_json(query)
        entry = self.catalog.get(query.data_source)
        if not entry.is_accelerated:
            raise UserError(
                f"table {query.data_source!r} is not accelerated")
        # the runner locks (or coalesces) internally; holding the lock
        # here would deadlock a coalesced submission against its leader.
        # The root trace makes raw-IR queries first-class in
        # /debug/queries AND gives the runner's records and the
        # boundary handlers below one shared query_id, so an operator
        # can correlate a served failure with its query_error narrative
        # in /debug/events.
        with self.tracer.trace("ir", datasource=query.data_source):
            try:
                return self.runner.execute(query, entry.segments)
            except (QueryShed, BreakerOpen):
                # no record ever fires for a shed/refusal: the
                # user-visible failure counts against the SLO at this
                # boundary (the shed/breaker events tell the story).
                # Never marked: a coalescer-shared exception is one
                # failure per caller, and nothing downstream of
                # execute_ir observes this statement again.
                self.runner.slo.observe(0.0, failed=True)
                raise
            except Exception:
                # the runner's failed record is interim (query_error
                # event, no SLO count) whatever the config — the raw-IR
                # path has no fallback, so the propagated failure is
                # the served response: count it and emit its terminal
                # `query` event here (unmarked, as above)
                self.runner.slo.observe(0.0, failed=True)
                self.runner.events.emit(
                    "query",
                    query_id=current_query_id()
                    or self.tracer.new_query_id(),
                    query_type=getattr(query, "query_type", "?"),
                    path="raw_ir", datasource=query.data_source,
                    total_ms=0.0, cache_hit=False, failed=True)
                raise

    def select_page(self, table: str, columns=None, page_size: int = 100,
                    offset: int = 0, descending: bool = False,
                    filter_spec=None, intervals=()):
        """Paged Select (SURVEY.md §3.3 SelectSpec): fetch one page of
        raw rows plus the paging offset to pass back for the next page.
        Returns (rows, next_offset). The SQL spellings LIMIT/OFFSET map
        to Scan; this is the resumable-cursor flavor."""
        from tpu_olap.ir.query import SelectQuerySpec
        q = SelectQuerySpec(
            data_source=table, intervals=tuple(intervals),
            filter=filter_spec,
            dimensions=tuple(columns or ()), metrics=(),
            page_size=page_size, paging_offset=offset,
            descending=descending)
        res = self.execute_ir(q)
        return res.rows, offset + len(res.rows)

    # -------------------------------------------------------------- admin

    def clear_cache(self, table: str | None = None):
        """CLEAR DRUID CACHE analog: drop device-resident columns,
        compiled programs, and both semantic result-cache tiers
        (catalog entries stay registered)."""
        with self.device_lock:
            self.runner.clear_cache(table)

    def drop_table(self, name: str):
        """DROP the datasource: unregister it and purge every cache that
        could still serve its data (device buffers, compiled programs,
        both semantic result-cache tiers). A later re-registration under
        the same name takes a fresh generation, so even an entry that
        somehow survived could never be served."""
        with self.device_lock:
            self.runner.clear_cache(name)
        self.catalog.drop(name)
        # ingest cascade: delta state dies with the table and its WAL
        # is deleted (a later re-registration starts a fresh log)
        self.ingest.on_drop(name)
        # cube cascade: rollups over a dropped base are dropped too
        # (their storage tables unregister with them)
        self.cubes.on_table_dropped(name)
        self.runner.events.emit("drop", table=name)

    # -------------------------------------------------------------- cubes

    def create_cube(self, spec):
        """Materialize a rollup cube (docs/CUBES.md). `spec` is a
        CubeSpec or its JSON dict: {name, datasource, dimensions,
        granularity, aggregations[, virtualColumns]} — the same payload
        `CREATE DRUID CUBES FROM '<file>'` reads and
        `tools/workload_report.py --emit-cubes` writes. Builds
        synchronously on the device; returns the registry entry."""
        return self.cubes.create(spec)

    def drop_cube(self, name: str) -> bool:
        """DROP DRUID CUBE analog: unregister the cube and its backing
        segment table. Returns False when no such cube exists."""
        return self.cubes.drop(name)

    @property
    def history(self):
        """Per-query observability records (SURVEY.md §6 tracing)."""
        return self.runner.history

    def counters(self) -> dict:
        """Aggregate observability counters (SURVEY.md §6 metrics:
        'counters exported as a dict') — maintained incrementally at
        query completion (QueryRunner.record), so a /status ping is O(1)
        and the totals stay exact after history-ring eviction."""
        return self.runner.counters()


# --------------------------------------------------------------------------
# Statement-level verbs (the reference's SparklineDataParser additions)

import json as _json
import re as _re

_CLEAR_RE = _re.compile(
    r"^\s*clear\s+druid\s+cache(?:\s+(\w+))?\s*;?\s*$", _re.I)
_EXPLAIN_RE = _re.compile(
    r"^\s*explain\s+druid\s+rewrite\s+(.+?)\s*;?\s*$", _re.I | _re.S)
_EXPLAIN_ANALYZE_RE = _re.compile(
    r"^\s*explain\s+analyze\s+(.+?)\s*;?\s*$", _re.I | _re.S)
_EXEC_RE = _re.compile(
    r"^\s*on\s+druid\s+datasource\s+(\w+)\s+execute\s+query\s+"
    r"'(.+)'\s*;?\s*$", _re.I | _re.S)
_SEARCH_RE = _re.compile(
    r"^\s*search\s+druid\s+datasource\s+(\w+)\s+for\s+'((?:[^']|'')*)'"
    r"(?:\s+in\s+([\w\s,]+?))?(?:\s+limit\s+(\d+))?\s*;?\s*$", _re.I)
# rollup-cube DDL (docs/CUBES.md): CREATE DRUID CUBE <name> ON <table>
# [DIMENSIONS (a, b)] [GRANULARITY g] AGGREGATES (sum(x), ...);
# CREATE DRUID CUBES FROM '<specs.json>'; DROP DRUID CUBE <name>;
# REFRESH DRUID CUBES
_CREATE_CUBE_RE = _re.compile(
    r"^\s*create\s+druid\s+cube\s+(\w+)\s+on\s+(\w+)\s+(.*?)\s*;?\s*$",
    _re.I | _re.S)
_CREATE_CUBES_FROM_RE = _re.compile(
    r"^\s*create\s+druid\s+cubes\s+from\s+'((?:[^']|'')+)'\s*;?\s*$",
    _re.I)
_DROP_CUBE_RE = _re.compile(
    r"^\s*drop\s+druid\s+cube\s+(\w+)\s*;?\s*$", _re.I)
_REFRESH_CUBES_RE = _re.compile(
    r"^\s*refresh\s+druid\s+cubes\s*;?\s*$", _re.I)
# real-time ingest verbs (docs/INGEST.md): INSERT INTO t (a, b) VALUES
# (...), (...); COMPACT DRUID TABLE t — the SQL spellings of
# Engine.append / Engine.compact_now; CHECKPOINT DRUID TABLE t spills
# the sealed scope to the durable segment store and truncates the WAL
# (Engine.checkpoint_now; docs/DURABILITY.md)
_INSERT_RE = _re.compile(
    r"^\s*insert\s+into\s+(\w+)\s*\(([^)]*)\)\s*values\s*(.+?)\s*;?\s*$",
    _re.I | _re.S)
_COMPACT_RE = _re.compile(
    r"^\s*compact\s+druid\s+table\s+(\w+)\s*;?\s*$", _re.I)
_CHECKPOINT_RE = _re.compile(
    r"^\s*checkpoint\s+druid\s+table\s+(\w+)\s*;?\s*$", _re.I)
# cheap pre-parse hint that a statement MIGHT reference a sys.* virtual
# datasource (catalog.systables): a match still confirms against the
# parsed tree before taking the introspection path
_SYS_HINT_RE = _re.compile(r"\bsys\.[A-Za-z_]\w*", _re.I)


def _match_verb(query: str):
    m = _CLEAR_RE.match(query)
    if m:
        table = m.group(1)
        return lambda eng: _run_clear(eng, table)
    m = _EXPLAIN_RE.match(query)
    if m:
        inner = m.group(1)
        return lambda eng: _run_explain(eng, inner)
    m = _EXPLAIN_ANALYZE_RE.match(query)
    if m:
        inner = m.group(1)
        return lambda eng: _run_explain_analyze(eng, inner)
    m = _EXEC_RE.match(query)
    if m:
        ds, body = m.group(1), m.group(2).replace("''", "'")
        return lambda eng: _run_passthrough(eng, ds, body)
    m = _SEARCH_RE.match(query)
    if m:
        ds, pat = m.group(1), m.group(2).replace("''", "'")
        dims = tuple(d.strip() for d in m.group(3).split(",")) \
            if m.group(3) else ()
        limit = int(m.group(4)) if m.group(4) else 1000
        return lambda eng: _run_search_verb(eng, ds, pat, dims, limit)
    m = _CREATE_CUBE_RE.match(query)
    if m:
        name, base, clauses = m.group(1), m.group(2), m.group(3)
        return lambda eng: _run_create_cube(eng, name, base, clauses)
    m = _CREATE_CUBES_FROM_RE.match(query)
    if m:
        path = m.group(1).replace("''", "'")
        return lambda eng: _run_create_cubes_from(eng, path)
    m = _DROP_CUBE_RE.match(query)
    if m:
        name = m.group(1)
        return lambda eng: _run_drop_cube(eng, name)
    if _REFRESH_CUBES_RE.match(query):
        return _run_refresh_cubes
    m = _INSERT_RE.match(query)
    if m:
        table, cols, values = m.group(1), m.group(2), m.group(3)
        return lambda eng: _run_insert(eng, table, cols, values)
    m = _COMPACT_RE.match(query)
    if m:
        table = m.group(1)
        return lambda eng: _run_compact(eng, table)
    m = _CHECKPOINT_RE.match(query)
    if m:
        table = m.group(1)
        return lambda eng: _run_checkpoint(eng, table)
    return None


# ------------------------------------------------------------- cube DDL

_CUBE_CLAUSE_RE = _re.compile(
    r"(dimensions|aggregates|granularity)\b\s*", _re.I)


def _scan_quote(s: str, i: int) -> int:
    """Index just past the SQL string literal starting at s[i] == "'"
    ('' is the escape). Unterminated -> len(s)."""
    i += 1
    n = len(s)
    while i < n:
        if s[i] == "'":
            if i + 1 < n and s[i + 1] == "'":
                i += 2
                continue
            return i + 1
        i += 1
    return n


def _split_top_commas(s: str) -> list[str]:
    """Comma split at paren depth 0, quote-aware (aggregate lists nest
    parens, and filter literals may contain commas/parens)."""
    out, depth, cur, i, n = [], 0, [], 0, len(s)
    while i < n:
        ch = s[i]
        if ch == "'":
            j = _scan_quote(s, i)
            cur.append(s[i:j])
            i = j
            continue
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
        i += 1
    tail = "".join(cur).strip()
    if tail:
        out.append(tail)
    return out


def _parse_cube_clauses(clauses: str) -> dict:
    """DIMENSIONS (...) / GRANULARITY g / AGGREGATES (...) in any
    order -> spec fields. Parenthesized lists are matched by depth so
    aggregate expressions may contain commas and parens."""
    out = {"dimensions": (), "granularity": "all", "aggregations": ()}
    i, n = 0, len(clauses)
    while i < n:
        m = _CUBE_CLAUSE_RE.match(clauses, i)
        if m is None:
            if clauses[i].isspace():
                i += 1
                continue
            raise UserError(
                f"cannot parse CREATE DRUID CUBE clause at "
                f"{clauses[i:i + 40]!r}")
        kw = m.group(1).lower()
        i = m.end()
        if kw == "granularity":
            g = _re.match(r"\s*(\w+)", clauses[i:])
            if g is None:
                raise UserError("GRANULARITY needs a grain name")
            out["granularity"] = g.group(1)
            i += g.end()
            continue
        j = clauses.find("(", i)
        if j < 0 or clauses[i:j].strip():
            # junk between the keyword and its list must not silently
            # drop items (DIMENSIONS cat (region) would lose `cat`)
            raise UserError(f"{kw.upper()} needs a parenthesized list")
        depth, k = 0, j
        while k < n:
            c = clauses[k]
            if c == "'":
                # parens/commas inside a filter literal (e.g.
                # FILTER (WHERE cat = 'a)')) are text, not structure
                k = _scan_quote(clauses, k)
                continue
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if depth == 0:
                    break
            k += 1
        if depth != 0:
            raise UserError(f"unbalanced parens in {kw.upper()} list")
        items = _split_top_commas(clauses[j + 1:k])
        if kw == "dimensions":
            out["dimensions"] = tuple(items)
        else:
            out["aggregations"] = tuple(items)
        i = k + 1
    return out


def _cube_status_frame(rows) -> pd.DataFrame:
    return pd.DataFrame(rows, columns=["cube", "status", "detail"])


def _run_create_cube(eng: Engine, name, base, clauses) -> pd.DataFrame:
    from tpu_olap.cubes import CubeSpec
    fields = _parse_cube_clauses(clauses)
    spec = CubeSpec(name=name, datasource=base, source="ddl", **fields)
    entry = eng.create_cube(spec)
    return _cube_status_frame([{
        "cube": name, "status": entry.status,
        "detail": f"{entry.data.n_rows} rows @ {spec.granularity} "
                  f"in {entry.build_ms:.0f} ms"}])


def _run_create_cubes_from(eng: Engine, path: str) -> pd.DataFrame:
    """CREATE DRUID CUBES FROM '<file.json>': materialize every spec in
    the file (a list, or {"cubes": [...]} — the exact artifact
    tools/workload_report.py --emit-cubes writes). Per-spec isolation:
    one bad spec reports its error without aborting the rest."""
    with open(path) as f:
        payload = _json.load(f)
    specs = payload.get("cubes", payload) if isinstance(payload, dict) \
        else payload
    if not isinstance(specs, list):
        raise UserError(f"{path!r}: expected a list of cube specs")
    rows = []
    for s in specs:
        cname = (s or {}).get("name", "?") if isinstance(s, dict) else "?"
        try:
            entry = eng.create_cube(s)
            rows.append({"cube": entry.spec.name,
                         "status": entry.status,
                         "detail": f"{entry.data.n_rows} rows in "
                                   f"{entry.build_ms:.0f} ms"})
        except Exception as e:  # noqa: BLE001 — per-spec isolation
            rows.append({"cube": cname, "status": "error",
                         "detail": str(e)[:300]})
    return _cube_status_frame(rows)


def _run_drop_cube(eng: Engine, name: str) -> pd.DataFrame:
    found = eng.drop_cube(name)
    return _cube_status_frame([{
        "cube": name, "status": "dropped" if found else "absent",
        "detail": ""}])


def _run_refresh_cubes(eng: Engine) -> pd.DataFrame:
    results = eng.cubes.refresh_now()
    if not results:
        return _cube_status_frame([])
    return _cube_status_frame([
        {"cube": n, "status": "ok" if r == "ok" else "error",
         "detail": "" if r == "ok" else r}
        for n, r in sorted(results.items())])


# ------------------------------------------------- real-time ingest DDL

_TS_LITERAL_RE = _re.compile(r"^timestamp\s+'((?:[^']|'')*)'$", _re.I)


def _parse_sql_literal(tok: str):
    """One VALUES literal -> python scalar: NULL, TRUE/FALSE, numbers,
    'string' ('' escapes), TIMESTAMP 'iso'."""
    t = tok.strip()
    up = t.upper()
    if up == "NULL":
        return None
    if up == "TRUE":
        return 1
    if up == "FALSE":
        return 0
    m = _TS_LITERAL_RE.match(t)
    if m:
        return m.group(1).replace("''", "'")
    if t.startswith("'"):
        if not t.endswith("'") or len(t) < 2:
            raise UserError(f"unterminated string literal {tok!r}")
        return t[1:-1].replace("''", "'")
    try:
        return int(t)
    except ValueError:
        pass
    try:
        return float(t)
    except ValueError:
        raise UserError(
            f"cannot parse INSERT literal {tok!r}") from None


def _run_insert(eng: Engine, table: str, cols: str,
                values: str) -> pd.DataFrame:
    """INSERT INTO t (a, b, ...) VALUES (...), (...) — the SQL spelling
    of Engine.append (docs/INGEST.md). Literal lists are quote-aware
    (strings may contain commas/parens); every tuple must match the
    column list's arity."""
    names = [c.strip() for c in cols.split(",") if c.strip()]
    if not names:
        raise UserError("INSERT INTO needs a column list")
    rows = []
    for tup in _split_top_commas(values):
        t = tup.strip()
        if not (t.startswith("(") and t.endswith(")")):
            raise UserError(
                f"INSERT VALUES expects parenthesized tuples, got "
                f"{t[:40]!r}")
        items = _split_top_commas(t[1:-1])
        if len(items) != len(names):
            raise UserError(
                f"INSERT tuple has {len(items)} values for "
                f"{len(names)} columns")
        rows.append({n: _parse_sql_literal(v)
                     for n, v in zip(names, items)})
    out = eng.append(table, rows)
    return pd.DataFrame([{
        "table": table, "rows": out["rows"],
        "delta_rows": out["delta_rows"],
        "generation": out["generation"],
        "wal_seq": out["wal_seq"]}])


def _run_compact(eng: Engine, table: str) -> pd.DataFrame:
    res = eng.compact_now(table)
    if res is None:
        return pd.DataFrame([{"table": table, "status": "empty-delta",
                              "rows_sealed": 0, "ms": 0.0}])
    if res.get("status") != "compacted":
        # skipped, not empty: a compaction already in flight or the
        # breaker is open — the operator should retry
        return pd.DataFrame([{"table": table, "status": res["status"],
                              "rows_sealed": 0, "ms": 0.0}])
    return pd.DataFrame([{
        "table": table, "status": "compacted",
        "rows_sealed": res["rows_sealed"],
        "ms": round(res["ms"], 3)}])


def _run_checkpoint(eng: Engine, table: str) -> pd.DataFrame:
    """CHECKPOINT DRUID TABLE t (docs/DURABILITY.md): compact + spill
    + manifest advance + WAL truncation, reported honestly — `status`
    is `checkpointed`, `noop` (sealed scope unchanged since the last
    manifest), `busy`, `no-store` (ingest_store_dir unset), or `error`
    (from the compaction's auto-hook)."""
    res = eng.checkpoint_now(table)
    return pd.DataFrame([{
        "table": table, "status": res.get("status"),
        "checkpoint_id": res.get("checkpoint_id"),
        "segments": res.get("segments"),
        "files_written": res.get("files_written"),
        "chunks_reused": res.get("chunks_reused"),
        "bytes": res.get("bytes"),
        "wal_frames_truncated": res.get("wal_frames_truncated"),
        "ms": round(res.get("ms") or 0.0, 3)}])


def _run_clear(eng: Engine, table: str | None) -> pd.DataFrame:
    eng.clear_cache(table)
    return pd.DataFrame({"status": [
        f"cleared cache for {table}" if table else "cleared cache"]})


def _run_explain(eng: Engine, inner_sql: str) -> pd.DataFrame:
    info = eng.explain(inner_sql)
    lines = _json.dumps(info, indent=2, default=str).splitlines()
    return pd.DataFrame({"plan": lines})


def _run_explain_analyze(eng: Engine, inner_sql: str) -> pd.DataFrame:
    """EXPLAIN ANALYZE <sql> — the observability analog of EXPLAIN DRUID
    REWRITE: EXECUTES the statement and returns its span tree as rows
    (one per span, depth-indented; attrs as a JSON detail column). Stage
    durations are wall-clock children of the root, so they sum to within
    the root's total (obs.trace; docs/OBSERVABILITY.md)."""
    frame, trace = eng._sql_traced(inner_sql)
    if trace is None:
        return pd.DataFrame({
            "span": ["(no trace: tracing disabled or statement verb)"],
            "ms": [0.0], "detail": ["{}"]})
    rows = []
    for depth, s in trace.walk():
        detail = dict(s.attrs)
        if depth == 0:
            detail["query_id"] = trace.query_id
            detail["rows_returned"] = len(frame)
        rows.append({"span": ("  " * depth) + s.name,
                     "ms": round(s.duration_ms or 0.0, 3),
                     "detail": _json.dumps(detail, default=str)})
    return pd.DataFrame(rows, columns=["span", "ms", "detail"])


def _run_passthrough(eng: Engine, datasource: str, body: str) -> pd.DataFrame:
    spec = _json.loads(body)
    spec.setdefault("dataSource", datasource)
    res = eng.execute_ir(spec)
    return res.to_pandas()


def _run_search_verb(eng: Engine, datasource: str, pattern: str,
                     dims: tuple, limit: int) -> pd.DataFrame:
    """SEARCH DRUID DATASOURCE t FOR 'pat' [IN d1, d2] [LIMIT n] — the
    SQL spelling of SearchQuerySpec (SURVEY.md §3.3; VERDICT round-2
    missing #6)."""
    from tpu_olap.ir.query import SearchQueryContains, SearchQuerySpec
    q = SearchQuerySpec(
        data_source=datasource, intervals=(),
        search_dimensions=dims,
        query=SearchQueryContains(pattern, case_sensitive=False),
        limit=limit)
    return eng.execute_ir(q).to_pandas()
