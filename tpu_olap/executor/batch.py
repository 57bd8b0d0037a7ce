"""Shared-scan batch executor: fuse N compatible queries into ONE pass.

PROFILE_CPU.json shows a ~65 ms execute floor per query even when the
result is a single group — every query re-scans the full segment stream,
so N concurrent SSB queries cost N full scans. This module kills that
floor the way the reference's Druid broker did (many rewritten Spark
queries answered from one shared column store, SURVEY.md §3.1): queries
against the same table that lower to dense aggregation plans are fused
into one device pass in which each segment window is read once and feeds
N per-query (filter-mask, agg-plan) legs, each reusing the single-query
compile_aggregations/group_reduce machinery (kernels.groupby.
group_reduce_batch) and emitting its own independent partials dict.

Three entry points:

- run_batch(runner, queries, table): the boxed batch executor — dedupe
  identical queries (one physical scan serves every copy), fuse
  compatible dense-agg legs into one jitted program, run everything
  else through the ordinary single-query path. Per-leg failures are
  boxed, never collective.
- Coalescer: the micro-batching window. Concurrent QueryRunner.execute()
  callers enqueue; the first arrival leads, sleeps batch_window_ms, and
  dispatches everyone who arrived in the window as one batch
  (EngineConfig.batch_window_ms, off by default).
- fusable(plan, mesh): the compatibility rule, shared with tests.

Metrics: every leg of a fused dispatch records `batch_id` (count the
shared pass ONCE per id), `batch_size` (logical queries served),
`scan_ms_shared` (wall of the one shared pass) and `agg_ms` (this leg's
share of it — attributed by scanned-work weight: the inside of one XLA
program cannot be timed per leg). See docs/BATCH_EXECUTION.md.
"""

from __future__ import annotations

import json
import threading
import time

from tpu_olap.executor.runner import QueryResult, _next_pow2
from tpu_olap.ir.query import (GroupByQuerySpec, TimeseriesQuerySpec,
                               TopNQuerySpec)
from tpu_olap.kernels.groupby import group_reduce_batch
from tpu_olap.obs.trace import (current_query_id, span as _span,
                                use_query_id)
from tpu_olap.resilience.errors import InternalError
from tpu_olap.resilience.faults import maybe_inject

AGG_QUERY_TYPES = (TimeseriesQuerySpec, GroupByQuerySpec, TopNQuerySpec)


def fusable(plan, mesh) -> str | None:
    """None when the plan can ride a fused shared-scan dispatch, else the
    reason it must run alone (through the single-query path). Mesh legs
    fuse too: each leg's group key extends by the owning chip inside
    the ONE fused program, per-leg [D·K] partials come back sharded,
    and the host broker merges each leg (executor.sharding) — the
    shared scan happens within each chip's resident shard."""
    if plan.kind != "agg":
        return "only aggregation plans fuse"
    if plan.sparse:
        return "sparse group-by legs run alone"
    if plan.key_fn is None:
        return "plan has no batchable key_fn"
    if mesh is not None:
        if mesh.devices.size * plan.total_groups >= (1 << 31):
            return "chip-extended group key overflows int32"
        from tpu_olap.executor.sharding import is_multihost
        if is_multihost(mesh):
            return "multi-host mesh legs run alone"
    return None


def run_batch(runner, queries, table, query_ids=None) -> list:
    """Execute N queries against one table, sharing scans where possible.

    Returns a boxed list in input order: QueryResult per success,
    the exception per failed leg (the caller — Coalescer.submit or
    Engine.sql_batch — re-raises or falls back PER QUERY, preserving the
    'never an error' property query-by-query). `query_ids` (parallel to
    `queries`) carries each logical query's trace id so per-leg history
    records stay attributable across the fused dispatch; None entries
    get a fresh id at record time."""
    queries = list(queries)
    if query_ids is None:
        query_ids = [None] * len(queries)
    boxed: list = [None] * len(queries)

    # dedupe identical queries first: one physical pass serves every
    # copy (the BI dashboard-storm case — 8 users on the same panel)
    uniq: dict[str, list[int]] = {}
    for i, q in enumerate(queries):
        key = json.dumps(q.to_json(), sort_keys=True, default=str)
        uniq.setdefault(key, []).append(i)

    singles, fused = [], []   # [(query, duplicate indexes, plan)]
    for idxs in uniq.values():
        q = queries[idxs[0]]
        # batch legs consult the same full-result tier as single-query
        # dispatch: a cached leg is served (and fanned out to its
        # duplicates) without lowering, fusing, or touching the device
        with use_query_id(query_ids[idxs[0]] or None):
            cached = runner._serve_full_cache(q, table)
        if cached is not None:
            _fan_out(runner, boxed, cached, idxs, queries, query_ids)
            continue
        try:
            plan = runner._lower_cached(q, table)
            reason = fusable(plan, runner.mesh) \
                if isinstance(q, AGG_QUERY_TYPES) else "non-agg query type"
        except Exception as e:  # noqa: BLE001 — boxed per leg
            for i in idxs:
                boxed[i] = e
            continue
        (fused if reason is None else singles).append((q, idxs, plan))

    # window compatibility (the ISSUE's "same segment window" rule):
    # every leg of a fused pass computes over the UNION window, so legs
    # with disjoint pruned windows would multiply per-leg scan work
    # instead of amortizing it — fuse only overlap clusters
    clusters, alone = _window_clusters(fused)
    singles.extend(alone)
    fused_groups = []
    for cl in clusters:
        if len(cl) == 1:
            # a lone fusable leg gains nothing from the fused program:
            # run it on the richer single-query path (packed fetch,
            # per-plan window) — the dedupe above is still a shared
            # scan when it serves several copies
            singles.append(cl[0])
        else:
            fused_groups.append(cl)

    for q, idxs, plan in singles:
        try:
            # _execute_guarded, not _execute: the single-leg path keeps
            # the deadline watchdog + wedged-device reprobe of a plain
            # execute() call (serialized mode: run_batch's caller holds
            # dispatch_lock; pipelined mode: the leg's own enqueue
            # sections take it). The statement's own id is propagated
            # BEFORE record() fires, so the history record and its
            # `query` event agree (a post-hoc rewrite would leave the
            # event carrying the leader's trace id).
            with use_query_id(query_ids[idxs[0]] or None):
                res = runner._execute_guarded(q, table)
        except BaseException as e:  # noqa: BLE001 — boxed per leg
            for i in idxs:
                boxed[i] = e
            continue
        if len(idxs) > 1:
            m = res.metrics
            m["batch_id"] = runner._next_batch_id()
            m["batch_size"] = len(idxs)
            m["batch_legs"] = 1
            m["scan_ms_shared"] = m.get("execute_ms", 0.0)
            m["agg_ms"] = m.get("execute_ms", 0.0)
            runner._m_batch.observe(len(idxs))
        _fan_out(runner, boxed, res, idxs, queries, query_ids)

    maxq = max(2, int(runner.config.batch_max_queries))
    for cl in fused_groups:
        # canonical leg order => one fused program per batch COMPOSITION
        # (the jit cache is keyed on the ordered fingerprint tuple)
        cl.sort(key=lambda t: repr(t[2].fingerprint()))
        for k in range(0, len(cl), maxq):
            group = cl[k:k + maxq]
            try:
                if len(group) == 1:  # a max-size split remainder
                    q, idxs, plan = group[0]
                    results = [runner._execute_guarded(q, table)]
                else:
                    results = _run_fused(runner, table, group, query_ids)
            except BaseException as e:  # noqa: BLE001 — boxed per leg
                for _, idxs, _ in group:
                    for i in idxs:
                        boxed[i] = e
                continue
            for (q, idxs, _), res in zip(group, results):
                if query_ids[idxs[0]]:
                    res.metrics["query_id"] = query_ids[idxs[0]]
                _fan_out(runner, boxed, res, idxs, queries, query_ids)
    return boxed


def _window_clusters(fused):
    """Partition fusable legs into overlap clusters: a leg joins a
    cluster only while one union-window pass over the cluster costs no
    more than ~1.3x the legs' individual windowed passes (each fused
    leg computes over the whole union window — pruned-away segments
    multiply by zero but still cost compute). Legs with no pruned
    segments (empty intervals) come back in the second list and take
    the single-query path. Greedy over span-sorted legs, so clustering
    is deterministic and repeated workloads hit the same fused-program
    compositions in the jit cache."""
    spans, alone = [], []
    for item in fused:
        plan = item[2]
        ids = plan.pruned_ids if not plan.empty else []
        if not ids:
            alone.append(item)
            continue
        spans.append((min(ids), max(ids) + 1, item))
    spans.sort(key=lambda s: (s[0], s[1]))
    clusters = []
    cur, cur_lo, cur_hi, cur_sum = [], 0, 0, 0
    for lo, hi, item in spans:
        if cur:
            u_lo, u_hi = min(cur_lo, lo), max(cur_hi, hi)
            if (len(cur) + 1) * (u_hi - u_lo) \
                    <= 1.3 * (cur_sum + hi - lo):
                cur.append(item)
                cur_lo, cur_hi = u_lo, u_hi
                cur_sum += hi - lo
                continue
            clusters.append(cur)
        cur, cur_lo, cur_hi, cur_sum = [item], lo, hi, hi - lo
    if cur:
        clusters.append(cur)
    return clusters, alone


def _fan_out(runner, boxed, res, idxs, queries, query_ids=None):
    """First duplicate gets the computed result; the rest share its rows
    (the scan ran once) under their own QueryResult + history record
    carrying its own query_id."""
    boxed[idxs[0]] = res
    for i in idxs[1:]:
        m = {**res.metrics, "batch_dedup": True}
        # a duplicate is its own logical query: never inherit the
        # computing leg's id (record() would otherwise stamp the batch
        # leader's trace id on every fan-out copy) — nor its compile
        # attribution (one executable build must not re-increment
        # compile_ms_total once per duplicate)
        m.pop("recompiles", None)
        m.pop("compile_ms", None)
        m["query_id"] = (query_ids[i] if query_ids and query_ids[i]
                         else runner.tracer.new_query_id())
        dup = QueryResult(queries[i], res.rows, res.druid, m)
        runner.record(dup.metrics)
        boxed[i] = dup


# ------------------------------------------------------------- fused pass


def _run_fused(runner, table, group, query_ids=None):
    """group: >= 2 unique dense-agg legs against one table. Build the
    union env ONCE, run ONE fused pass, finalize/assemble per leg.
    When a trace is active (the leader's — followers' traces show only
    their coalesce wait), the fused pass appears as one `shared-scan`
    span with every logical leg nested under it."""
    from tpu_olap.executor.results import (agg_specs_by_name, eval_post_aggs,
                                           finalize_aggs, theta_raw_fields)

    t_start = time.perf_counter()
    plans = [p for _, _, p in group]
    n_logical = sum(len(idxs) for _, idxs, _ in group)
    batch_id = runner._next_batch_id()
    runner._m_batch.observe(n_logical)
    # per-leg workload fingerprints (obs.workload): fused legs are real
    # logical queries and must attribute to their own templates — the
    # `_wl` key is consumed by record(), so keep a parallel list for
    # the full-cache store below
    leg_fps = [runner.fingerprint(q, table.name) for q, _, _ in group]
    metrics_list = [{"query_type": q.query_type, "datasource": table.name,
                     "batch_id": batch_id, "batch_size": n_logical,
                     "batch_legs": len(group), "_wl": fp}
                    for (q, _, _), fp in zip(group, leg_fps)]
    if query_ids is not None:
        for (_, idxs, _), m in zip(group, metrics_list):
            if query_ids[idxs[0]]:
                m["query_id"] = query_ids[idxs[0]]

    def dispatch():
        # env build lives INSIDE the retried callable: a _dispatch retry
        # purges the table's device state, so the rebuilt attempt must
        # re-prepare (stale buffers could be poisoned by a device reset).
        # Two-staged like the single-query path (ISSUE 10): stage 1
        # (env build + fused program fire) under the enqueue lock,
        # stage 2 (transfer) lock-free — the leader no longer holds
        # dispatch_lock while it fetches or assembles.
        with runner._pipeline_slot():
            with runner._enqueue_lock(metrics_list[0]):
                leg_envs, seg_masks = [], []
                valid = None
                for plan, m in zip(plans, metrics_list):
                    env, valid, seg_mask = runner._prepare(plan, m)
                    leg_envs.append(env)
                    seg_masks.append(seg_mask)
                win = _union_window(plans, len(seg_masks[0]),
                                    runner.mesh)
                if win is not None:
                    # same units as the single-query mesh path:
                    # segments_window is the GLOBAL window (W x D under
                    # a mesh), per_chip the local width
                    D_win = runner.mesh.devices.size \
                        if runner.mesh is not None else 1
                    for m in metrics_list:
                        m["segments_window"] = win[1] * D_win
                        if runner.mesh is not None:
                            m["segments_window_per_chip"] = win[1]
                outs_dev, hit, t_fire = _enqueue_fused_device(
                    runner, table, plans, leg_envs, valid, seg_masks,
                    win)
                pin = runner._pin_inflight(outs_dev)
            if metrics_list[0].get("pipelined"):
                for m in metrics_list[1:]:
                    m["pipelined"] = True
            outs = runner._fetch_tree(outs_dev, metrics_list[0], pin)
            if runner.mesh is not None:
                # broker step: each leg's per-chip [D·K] unfinalized
                # partials fold on the host with the segment-cache
                # merge algebra (executor.sharding.broker_merge)
                from tpu_olap.executor.sharding import broker_merge
                D = runner.mesh.devices.size
                outs = [broker_merge(o, p.agg_plans, D)
                        for o, p in zip(outs, plans)]
            shared_ms = (time.perf_counter() - t_fire) * 1000
            # per-leg attribution: one XLA program cannot be timed from
            # outside per leg; split the shared wall by each leg's
            # scanned-work weight (columns read x segments scanned x
            # agg plans) — an estimate, labeled as such in
            # docs/BATCH_EXECUTION.md
            w = [max(1, (len(p.columns) + 1) * max(1, len(p.pruned_ids))
                     * (len(p.agg_plans) + 1)) for p in plans]
            tw = float(sum(w))
            agg_ms = [shared_ms * wi / tw for wi in w]
            return outs, shared_ms, agg_ms, hit

    # retry-based recovery identical to the single-query path (the
    # shared metrics of leg 0 carry any retry_errors), under the same
    # deadline/wedge guard — a wedged device must not hang every
    # coalesced caller past query_deadline_s
    with _span("shared-scan", batch_id=batch_id, batch_legs=len(group),
               batch_size=n_logical) as ssp:
        partials_list, shared_ms, agg_ms, hit = runner._guarded_dispatch(
            dispatch, metrics_list[0], table.name)
        if not hit:
            # one fused executable per batch composition: attribute the
            # build to the first leg's record (counting it on every leg
            # would multiply one compile by batch_legs in /metrics)
            runner._note_compile("batch", metrics_list[0])
        ssp.set(jit_cache_hit=hit, scan_ms_shared=round(shared_ms, 3))

        results = []
        for leg_i, ((q, idxs, plan), m, partials, leg_ms) in enumerate(
                zip(group, metrics_list, partials_list, agg_ms)):
            t0 = time.perf_counter()
            with ssp.span("leg") as lsp:
                # per-batch-leg fault site (resilience.faults): a leg
                # failure here boxes the whole group, and every logical
                # caller falls back per query — testable without a
                # device fault mid-XLA-program
                maybe_inject(runner.config, "batch-leg", leg_i)
                specs = agg_specs_by_name(q.aggregations)
                keep_raw = theta_raw_fields(q.post_aggregations)
                arrays = finalize_aggs(partials, plan.agg_plans, specs,
                                       keep_raw)
                eval_post_aggs(arrays, q.post_aggregations)
                res = runner._assemble_agg(q, plan, arrays)
            m["scan_ms_shared"] = shared_ms
            m["agg_ms"] = leg_ms
            m["jit_cache_hit"] = hit
            m["num_shards"] = runner.mesh.devices.size \
                if runner.mesh is not None else 1
            m["assemble_ms"] = (time.perf_counter() - t0) * 1000
            m["total_ms"] = (time.perf_counter() - t_start) * 1000
            res.metrics = m
            runner.record(m)
            # fused legs populate the same full-result tier the
            # single-query path serves from (docs/CACHING.md)
            runner._store_full_cache(q, table, res, leg_fps[leg_i])
            lsp.set(query_id=m["query_id"], query_type=m["query_type"],
                    agg_ms=round(leg_ms, 3), duplicates=len(idxs))
            results.append(res)
    return results


def _union_window(plans, n_segments, mesh=None):
    """(lo, W) covering every leg's pruned segments, or None — the batch
    analog of QueryRunner._segment_window. Legs whose own pruned set is
    smaller still read only the union window; their per-leg seg_mask
    zeroes the rest (adding exact zeros, so per-query results stay
    bitwise identical to the single-query windowed pass). Under a mesh
    the window is the per-chip LOCAL one (interleaved placement:
    logical [lo, hi) is local [lo//D, ceil(hi/D)) on every chip)."""
    ids = sorted({i for p in plans if not p.empty for i in p.pruned_ids})
    if not ids:
        return None
    if mesh is not None:
        from tpu_olap.executor.sharding import local_window
        D = mesh.devices.size
        return local_window(ids, D, n_segments // D)
    lo, hi = ids[0], ids[-1] + 1
    W = _next_pow2(hi - lo)
    if 4 * W >= 3 * n_segments:
        return None
    return min(lo, n_segments - W), W


def _buffer_layout(leg_envs):
    """Unique env arrays -> one flat buffer list + per-leg {name: index}
    specs. Buffers shared across legs (same ds column) appear ONCE —
    that is the 'read each column once' half of the shared scan. The
    layout is deterministic given the legs' column sets, so a cached
    fused program (keyed on the ordered fingerprint tuple) always sees
    buffers in the order its closure captured."""
    buffers, index, layouts = [], {}, []
    for env in leg_envs:
        spec = {"cols": {}, "nulls": {}}
        for kind in ("cols", "nulls"):
            for name, arr in env[kind].items():
                j = index.get(id(arr))
                if j is None:
                    j = index[id(arr)] = len(buffers)
                    buffers.append(arr)
                spec[kind][name] = j
        layouts.append(spec)
    return buffers, layouts


def _layout_key(layouts):
    """Hashable form of per-leg buffer layouts for the jit-cache key."""
    return tuple((tuple(sorted(s["cols"].items())),
                  tuple(sorted(s["nulls"].items()))) for s in layouts)


def _build_fused(plans, layouts, mesh_dims=None):
    """The fused kernel: every leg's (filter, dims, key) front half runs
    over the shared buffers, then kernels.groupby.group_reduce_batch
    emits N independent partials dicts — all traced into one program.
    mesh_dims=(D, blocks): each leg's key extends by the owning chip
    (row block b belongs to chip b // blocks in placement order), so
    per-leg [D·K] partials come back sharded and the host broker
    merges them (executor.sharding.broker_merge)."""
    def fused(buffers, valid, seg_masks, consts_list):
        legs = []
        for plan, spec, sm, consts in zip(plans, layouts, seg_masks,
                                          consts_list):
            env = {"cols": {n: buffers[j]
                            for n, j in spec["cols"].items()},
                   "nulls": {n: buffers[j]
                             for n, j in spec["nulls"].items()}}
            fenv, mask, key = plan.key_fn(env, valid, sm, consts)
            num_groups = plan.total_groups
            if mesh_dims is not None:
                from tpu_olap.executor.sharding import chip_extended_key
                D, blocks = mesh_dims
                key = chip_extended_key(key, mask, D, blocks,
                                        num_groups)
                num_groups = D * num_groups
            legs.append((key, mask, fenv, plan.agg_plans, num_groups))
        return group_reduce_batch(legs, consts_list)
    return fused


def _window_fused(fused, W: int, mesh=None, per_chip: int = 0):
    """Dynamic-slice every [S, ...] input to the union window before the
    fused compute (one compile per (composition, W); `lo` is traced).
    Under a mesh the slice is per-chip LOCAL (reshape to (chip, local),
    slice the unsharded local axis — no cross-chip movement)."""
    import jax

    if mesh is not None:
        from tpu_olap.executor.sharding import _slice_local
        D = mesh.devices.size

        def sl(a, lo):
            return _slice_local(a, D, per_chip, lo, W)
    else:
        def sl(a, lo):
            return jax.lax.dynamic_slice_in_dim(a, lo, W, axis=0)

    def fn(buffers, valid, seg_masks, consts_list, lo):
        with jax.named_scope("window"):
            args = ([sl(b, lo) for b in buffers], sl(valid, lo),
                    [sl(m, lo) for m in seg_masks])
        return fused(*args, consts_list)
    return fn


def _enqueue_fused_device(runner, table, plans, leg_envs, valid,
                          seg_masks, win):
    """Stage 1 of the fused pass (caller holds the enqueue lock): one
    jitted fused program per batch composition, fired asynchronously.
    Returns (device output trees, jit-cache hit, fire timestamp); the
    caller transfers with runner._fetch_tree outside the lock."""
    import jax

    buffers, layouts = _buffer_layout(leg_envs)
    mesh = runner.mesh
    D = mesh.devices.size if mesh is not None else 0
    per_chip = len(seg_masks[0]) // D if mesh is not None else 0
    # the layout is part of the key: a cached program's closure bakes in
    # its compile-time {name: buffer-index} maps, and the SHARING
    # structure can legitimately change between dispatches (an HBM-ledger
    # eviction between two legs' _prepare calls refetches a column as a
    # distinct object) — reusing the old closure over a differently-
    # shaped buffer list would read the wrong column
    key = (table.name, "batch", D,
           tuple(p.fingerprint() for p in plans),
           win[1] if win else 0,
           _layout_key(layouts))

    def build():
        mesh_dims = None
        if mesh is not None:
            mesh_dims = (D, win[1] if win is not None else per_chip)
        fused = _build_fused(plans, layouts, mesh_dims)
        if win is not None:
            fused = _window_fused(fused, win[1], mesh, per_chip)
        if mesh is not None:
            from tpu_olap.executor.sharding import shard_spec
            return jax.jit(fused, out_shardings=shard_spec(mesh))
        return jax.jit(fused)
    # the caller counts the compile, once a batch
    jitted, hit = runner._program(key, build)
    consts_list, seg_args = [], []
    for plan, sm in zip(plans, seg_masks):
        cdev, sarg = runner._args_for(plan, sm, mesh)
        consts_list.append(cdev)
        seg_args.append(sarg)
    t0 = time.perf_counter()
    outs = jitted(buffers, valid, seg_args, consts_list, win[0]) \
        if win is not None else jitted(buffers, valid, seg_args,
                                       consts_list)
    if mesh is not None:
        runner._note_chip_dispatch(range(D))
    return outs, hit, t0


# -------------------------------------------------------------- coalescer


class _Pending:
    __slots__ = ("query", "table", "event", "result", "error", "qid")

    def __init__(self, query, table):
        self.query = query
        self.table = table
        self.event = threading.Event()
        self.result = None
        self.error = None
        # capture the submitting caller's trace id: the leader executes
        # every follower's query on its own thread, so the fused legs'
        # history records must be re-attributed at record time
        self.qid = current_query_id()


class Coalescer:
    """Micro-batching window: the first concurrent caller leads, waits
    batch_window_ms for companions, and dispatches everyone who arrived
    as ONE run_batch call under the runner's dispatch lock. Followers
    block on an event; per-query failures propagate to their own caller
    only. A caller arriving after a leader has cut its batch becomes the
    next leader, so windows pipeline under sustained load."""

    def __init__(self, runner, window_s: float):
        self.runner = runner
        self.window_s = window_s
        self._mu = threading.Lock()
        self._queue: list = []
        self._collecting = False

    def submit(self, query, table):
        me = _Pending(query, table)
        with self._mu:
            self._queue.append(me)
            lead = not self._collecting
            if lead:
                self._collecting = True
        if not lead:
            me.event.wait()
            if me.error is not None:
                raise me.error
            return me.result
        # everything from here runs under try/finally: an async
        # exception in the leader (KeyboardInterrupt mid-sleep) must
        # still reset _collecting, drain the queue, and wake every
        # follower — else the coalescer is wedged for the process life
        batch: list = []
        try:
            try:
                if self.window_s > 0:
                    time.sleep(self.window_s)
            finally:
                with self._mu:
                    batch, self._queue = self._queue, []
                    self._collecting = False
            by_table: dict = {}
            for it in batch:
                by_table.setdefault(id(it.table), []).append(it)
            for items in by_table.values():
                try:
                    # _execute_batch_boxed = admission slot (ONE per
                    # fused submission, shed -> every caller gets the
                    # QueryShed) + dispatch_lock + run_batch
                    boxed = self.runner._execute_batch_boxed(
                        [it.query for it in items], items[0].table,
                        [it.qid for it in items])
                except BaseException as e:  # noqa: BLE001 — fan out
                    boxed = [e] * len(items)
                for it, b in zip(items, boxed):
                    if isinstance(b, BaseException):
                        it.error = b
                    else:
                        it.result = b
        finally:
            for it in batch:
                if it.result is None and it.error is None:
                    it.error = InternalError(
                        "batch leader exited without a result")
                it.event.set()
        if me.error is not None:
            raise me.error
        return me.result
