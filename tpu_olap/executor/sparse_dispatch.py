"""The sparse group-by's dispatch (the programs of kernels.sparse_groupby).

Which program of a plan runs next (`choose_program`, the one place that
knows the variants' rules), the loop that sizes its compact table
(`attempt_loop`, written once), and the three arms that build and probe a
program their own way: one chip's `jax.jit`, GSPMD's one program over a
mesh that spans processes, and the one-chip program mapped over a mesh's
chips with its merge, head and fetch. `QueryRunner` owns the state this
works on (the jit cache behind `_program`, `_cap_hints`, the HBM ledger,
the counters) and calls `run_sparse` from `_run_agg`.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from tpu_olap.executor import sharding as sh
from tpu_olap.executor.dataset import narrow_dtype
from tpu_olap.executor.sharding import next_pow2
from tpu_olap.kernels import sparse_groupby as sg
from tpu_olap.kernels.groupby import UnsupportedAggregation
from tpu_olap.obs.trace import span as _span

# rows of the smallest bucket a device HAVING compacts the passing groups
# into (and the host fetches a table): a report's HAVING keeps hundreds of
# groups out of millions, and each bucket size is a compile of the sort
HAVING_KEPT_MIN = 1024


def grown_cap(count: int, limit: int) -> int:
    """The sparse compact table's cap for `count` present groups: what an
    overflowing attempt grows to AND what the template's next run starts
    from (its hint), so the program compiled for the retry is the one
    every later run finds in the jit cache: each size is a compile of
    the sort."""
    return min(limit, max(64, next_pow2(2 * count)))


def sparse_key(plan, n_shards: int) -> tuple:
    """What a plan's sparse programs and hints (`_cap_hints`) are
    remembered under: the key itself holds the groups last seen (the next
    run's starting cap), `+ ("local",)` a mesh's largest chip count, `+
    ("kept",)` the most groups a device HAVING has let through (its
    bucket) and `+ ("wide",)` that a run found a group's sum past int32
    (the plan then starts wide)."""
    return plan.fingerprint() + ("sparse", n_shards)


def device_threshold(mesh, query, plan):
    """(metric, threshold, inverted) where one chip's sparse program
    can apply a TopN's threshold itself, else None (the host ranks the
    fetched table): one bucket, no mesh (a mesh's broker merges whole
    tables), and the metric an aggregate whose table column IS its
    final value, held as an integer (a count or a long sum): a
    post-aggregation, a sketch's estimate and a min / max's null are
    made on the host, and a float's NaN ranks differently there."""
    if mesh is not None or plan.sizes[0] != 1 \
            or getattr(query, "metric", None) is None:
        return None
    for p in plan.agg_plans:
        if p.name == query.metric and p.kind in ("count", "sum") \
                and np.issubdtype(np.dtype(p.acc_dtype), np.integer):
            return (query.metric, query.threshold, query.inverted)
    return None


def device_having(mesh, plan) -> bool:
    """Whether one chip's sparse program decides the GroupBy's HAVING
    itself (the host then fetches the rows that passed, not the
    table): the plan took the sparse path, there is no mesh (a chip's
    partial sum decides nothing, and the broker merges whole tables)
    and lowering found the predicate decidable from integer tables
    alone (`sparse_groupby.compile_having`; `device_threshold`'s
    rule, with a comparison in the rank's place)."""
    return mesh is None and plan.having is not None


def _limits(config, plan, n_shards: int):
    """-> (cap_limit, local_limit, whole_space): the most groups the
    answer may hold, the most one chip's compact table may, and whether
    the group space fits that table whole. `sparse_merge` "exchange"
    scales the answer's capacity with the mesh; local compaction and
    per-owner tables each stay within the per-chip budget. A group space
    that fits the compact table whole starts (and stays) at a cap that
    holds it: no attempt can overflow, and the sort compiles once, not
    once for the starting cap and again for the grown one (2-4 minutes a
    program at 60M rows). Not where a sketch's [cap, m] state rides: its
    cap follows the groups present."""
    budget = config.sparse_group_budget
    exchange = n_shards > 1 and config.sparse_merge == "exchange"
    cap_limit = min(budget * (n_shards if exchange else 1),
                    plan.total_groups)
    whole_space = plan.total_groups <= budget and not any(
        p.kind in ("hll", "theta") for p in plan.agg_plans)
    return cap_limit, min(budget, plan.total_groups), whole_space


def first_cap(config, plan, hint) -> int:
    """The cap a run's first table program has where `hint` groups were
    present last time (None: nothing says)."""
    _, local_limit, whole_space = _limits(config, plan, 1)
    if whole_space:
        return local_limit
    return min(local_limit, config.sparse_group_cap) if hint is None \
        else grown_cap(hint, local_limit)


def choose_program(runner, plan, stored, nullable, rows, cap, cut=True,
                   window=None) -> sg.SparseProgram:
    """The program of `cap` slots (None: the count program) that the
    plan's next run over `rows` sorted rows builds, from what the
    dispatch can observe: the mesh, the plan's query, the width each
    column is resident at (`stored`: name -> dtype; `nullable`: those with
    a null mask) and the plan's hints. Every rule that picks a variant is
    evaluated here and nowhere else: one chip's program applies a TopN's
    threshold and decides a HAVING where it can (`cut` False: the whole
    table, what the cube materializer merges), in a bucket that holds the
    most groups any literal of the template has let through; its integer
    sums of columns stored in 32 bits or fewer ride narrow until a run
    has found the plan too wide (a mesh's programs stay wide: their
    chips' partial sums are merged at the accumulator's width); and its
    whole [cap] tables ride `starts`' sort where the cap is a large
    share of the rows (`sparse_groupby.boundary_read`)."""
    if cap is None:
        return sg.SparseProgram(None, window=window)
    mesh, hints = runner.mesh, runner._cap_hints
    base = sparse_key(plan, mesh.devices.size if mesh else 1)
    top = device_threshold(mesh, plan.query, plan) if cut else None
    kept = None
    if cut and device_having(mesh, plan):
        kept = min(cap, max(HAVING_KEPT_MIN,
                            next_pow2(hints.get(base + ("kept",), 0))))
    narrow = mesh is None and base + ("wide",) not in hints \
        and sg.narrow_sums(plan.agg_plans, stored)
    read = sg.boundary_read(plan.agg_plans, stored, cap, rows, top,
                            nullable, plan.having[1] if kept else None)
    return sg.SparseProgram(cap, top, kept, narrow, read, window)


def _words(program, plan, stored, nullable) -> dict:
    return sg.program_words(
        program, plan.agg_plans, stored, nullable, plan.sizes,
        plan.key_words, plan.having[1] if plan.having else None)


def explain_lines(runner, query, table) -> dict:
    """EXPLAIN's lines of an aggregate's device plan, as its record says
    them after a run: who decides a GroupBy's HAVING (`having_where`:
    `device` | `host`) and, of a sparse plan, the words of the first
    table program its next run builds (`choose_program`): the width its
    integer sums ride at (`sum_word_bits`: 32 until a run of the plan has
    found a group's sum past int32) and the key's `key_words`, `key_bits`
    and `key_sort_bits`. Raises what lowering raises of a query with no
    device plan."""
    plan = runner._lower_cached_inner(query, table)
    out = {}
    if plan.sparse:
        stored = {c: dt for c in plan.columns
                  if (dt := narrow_dtype(table, c)) is not None}
        n_shards = runner.mesh.devices.size if runner.mesh else 1
        cap = first_cap(runner.config, plan, runner._cap_hints.get(
            sparse_key(plan, n_shards)))
        nullable = frozenset(plan.null_cols)
        program = choose_program(
            runner, plan, stored, nullable,
            len(table.segments) * table.block_rows // n_shards, cap)
        words = _words(program, plan, stored, nullable)
        out = {k: words[k] for k in ("sum_word_bits", "key_words",
                                     "key_bits", "key_sort_bits")
               if k in words}
    if getattr(query, "having", None) is not None:
        out["having_where"] = \
            "device" if device_having(runner.mesh, plan) else "host"
    return out


@dataclasses.dataclass
class _Dispatch:
    """One query's sparse dispatch: what the attempt loop and every arm
    read."""
    runner: object
    plan: object
    metrics: dict
    cut: bool
    env: dict
    valid: object
    seg_mask: object
    stored: dict        # the width each stored column is resident at:
    #                     with the plan's kinds and the cap, what the
    #                     kernel picks its reduce from
    nullable: frozenset
    win: tuple | None   # (lo, W) of the segment window (one chip only)
    base_key: tuple


@dataclasses.dataclass
class Fit:
    """What `attempt_loop` returns: the enqueued tree that fit, its pin
    (the caller's to release: a fetch unpins it), the program that made it,
    the present groups its probe read, whether the last program was a jit
    cache hit, and the attempts run."""
    out: dict
    pin: object
    program: sg.SparseProgram
    count: int
    hit: bool
    attempts: int


def attempt_loop(d: _Dispatch, rows, tag: tuple, build, probe, cap, limit,
                 noun="present groups", count_first=False, chips=()) -> Fit:
    """Run the plan's programs over `rows` sorted rows until a compact
    table holds what its probe reads, two-staged: each attempt's program
    is chosen anew (`choose_program`: a hint an attempt leaves is the
    next one's), built once a key by the arm's `build(program)` (a
    counted compile, remembered under the arm's `tag`) and enqueued
    under the enqueue lock, and its output tree is pinned like every
    other device path's (the caller blocks on the probe while the
    buffers occupy HBM; a retry or a raise unpins the superseded pin);
    the arm's `probe(out) -> present groups` (a one-element sync that
    waits for the sort) runs lock-free, so an overflow retry re-enters
    stage 1.

    A count past `cap` grows it (`grown_cap`) or, past `limit`, raises
    "exceed sparse budget". `count_first` (one chip's arm, where no hint
    tells the count of a group space past the budget): the groups are
    counted before any table is sized, by the program that builds none,
    so that the first compact table holds them: each cap is a compile of
    the sort, and the count is a run of a sort, so an attempt. A narrow
    program whose `_narrow_ok` says some group's sum may pass int32 is
    followed once by the wide program of the same cap, and the plan is
    remembered as wide (the hint `+ ("wide",)`), as a cap is. A device
    HAVING's `_kept` says how many groups passed; a bucket they do not fit
    is grown (the hint `+ ("kept",)`) and the attempt run again."""
    runner, plan, metrics, win = d.runner, d.plan, d.metrics, d.win
    hints, n_words = runner._cap_hints, len(plan.key_words)

    def enqueue(cap):
        # call under the enqueue lock
        program = choose_program(runner, plan, d.stored, d.nullable, rows,
                                 cap, d.cut, win and win[1])
        consts_dev, seg_arg = runner._args_for(plan, d.seg_mask, runner.mesh)
        fn, hit = runner._program(d.base_key + tag + (program,),
                                  lambda: build(program), "sparse", metrics)
        args = (d.env, d.valid, seg_arg, consts_dev)
        out = fn(*args, win[0]) if win is not None else fn(*args)
        return program, hit, out

    def grown(count):
        if count > limit:
            raise UnsupportedAggregation(
                f"{count} {noun} exceed sparse budget {limit}")
        return grown_cap(count, limit)

    attempts, pin = 0, None
    try:
        if count_first:
            attempts += 1
            with _span("sparse-count", key_words=n_words) as sp:
                with runner._enqueue_lock(metrics):
                    _, hit, out = enqueue(None)
                count = probe(out)
                sp.set(present_groups=count, jit_cache_hit=hit)
            cap = grown(count)
        while True:
            attempts += 1
            with _span("sparse-attempt", cap=cap, key_words=n_words) as sp:
                with runner._enqueue_lock(metrics):
                    program, hit, out = enqueue(cap)
                    prev, pin = pin, runner._pin_inflight(out)
                    if chips:
                        runner._note_chip_dispatch(chips)
                if prev is not None:
                    runner._hbm_ledger.unpin_inflight(prev)
                with _span("count-probe"):
                    count = probe(out)
                sp.set(present_groups=count, jit_cache_hit=hit)
                # a ready scalar: some group's sum may pass int32
                too_wide = program.narrow and count <= cap \
                    and not bool(out["_narrow_ok"])
                if too_wide:
                    sp.set(narrow_fallback=True)
            if count > cap:
                cap = grown(count)
                continue
            if too_wide:
                hints[d.base_key + ("wide",)] = True
                runner._m_narrow_fallbacks.inc()
                metrics["narrow_fallback"] = True
                continue
            if program.kept is not None:
                with _span("having", where="device",
                           groups_in=count) as sp:
                    n_kept = int(out["_kept"])
                    sp.set(groups_out=n_kept)
                kept_key = d.base_key + ("kept",)
                hints[kept_key] = max(n_kept, hints.get(kept_key, 0))
                if n_kept > program.kept:
                    continue
            return Fit(out, pin, program, count, hit, attempts)
    except BaseException:
        if pin is not None:
            runner._hbm_ledger.unpin_inflight(pin)
        raise


def run_sparse(runner, plan, metrics: dict, cut=True):
    """Sort-based sparse group-by dispatch with adaptive compact-table
    cap (kernels.sparse_groupby), under a `dispatch` span and a pipeline
    slot. Returns (partials dict, count, program): compact tables,
    SENTINEL-keyed past the present groups (a mesh's merged, `_per_chip`),
    and the `SparseProgram` that made them: where its `top` is set the
    partials are the TopN's [threshold] rows in rank order, where its
    `kept` is the groups the plan's HAVING lets through, in a bucket of
    `kept` rows (`cut` False: neither, whatever the query)."""
    from tpu_olap.executor.runner import _form_attr
    with _span("dispatch", sparse=True) as sp:
        with runner._pipeline_slot():
            out = _dispatch(runner, plan, metrics, cut)
        sp.set(jit_cache_hit=metrics.get("jit_cache_hit"),
               result_groups=metrics.get("result_groups"),
               num_shards=metrics.get("num_shards"),
               **_form_attr(metrics))
    return out


def _dispatch(runner, plan, metrics, cut):
    with runner._enqueue_lock(metrics):
        env, valid, seg_mask = runner._prepare(plan, metrics)
    win = runner._segment_window(plan, len(seg_mask))
    if win is not None:
        metrics["segments_window"] = win[1]
    mesh = runner.mesh
    n_shards = mesh.devices.size if mesh else 1
    d = _Dispatch(runner, plan, metrics, cut, env, valid, seg_mask,
                  {c: a.dtype for c, a in env["cols"].items()},
                  frozenset(env["nulls"]), win, sparse_key(plan, n_shards))
    if len(plan.key_words) > 1:
        runner._m_wide_key.inc()
    if 32 in sg.key_sort_bits(plan.sizes, plan.key_words):
        runner._m_narrow_key.inc()
    t0 = time.perf_counter()
    if mesh is not None and runner.mesh_program != "gspmd":
        out, fit, count = _per_chip(d)
    else:
        out, fit, count = _one_program(d)
    program = fit.program
    metrics["num_shards"] = n_shards
    runner._cap_hints[d.base_key] = count
    metrics["execute_ms"] = (time.perf_counter() - t0) * 1000
    metrics["jit_cache_hit"] = fit.hit
    metrics.update(_words(program, plan, d.stored, d.nullable))
    if program.boundary == "sorted":
        runner._m_boundary_sorted.inc()
    # how many attempts ran (1 once the template's hint is warm), the
    # compact table's final cap, and the groups present in it
    metrics["sparse"] = True
    metrics["sparse_attempts"] = fit.attempts
    metrics["sparse_cap"] = metrics["result_cap"] = program.cap
    metrics["present_groups"] = metrics["result_groups"] = count
    return out, count, program


def present_groups(out: dict, plan):
    """(mask of the present slots of `out`'s tables, their keys) by the
    sentinel: compact tables fill the tail with SENTINEL; exchange slot
    tables interleave empties. The keys: one int64 array, or of a wide
    key the tuple of its words' arrays (the further words are read where
    word 0 is present)."""
    keys = np.asarray(out["_keys"])
    pm = keys != sg.SENTINEL
    present = keys[pm].astype(np.int64)
    if len(plan.key_words) > 1:
        present = (present,) + tuple(
            np.asarray(out[n])[pm]
            for n in sg.key_names(len(plan.key_words))[1:])
    return pm, present


def jit_whole(runner, plan, program):
    """The jitted `program` of `plan` over all the rows: one chip's, its
    window's slice appended, or on a mesh GSPMD's (`_one_program`)."""
    kern = plan.make_sparse_kernel(program)
    if program.window is not None:
        kern = runner._window_kernel(kern, program.window)
    if runner.mesh is None:
        return jax.jit(kern)
    return jax.jit(kern, out_shardings=sh.replicated_spec(runner.mesh))


def _one_program(d: _Dispatch):
    """One program a cap over all the rows: one chip's, or on a mesh
    that spans processes GSPMD's. There remote chips' compact tables are
    not host-addressable, so neither the fan-out nor the broker merge
    can run: the WHOLE sparse program is handed to GSPMD with replicated
    outputs (global-budget capacity, like the gather contract). One chip
    alone counts first and knows a window."""
    runner, plan, valid = d.runner, d.plan, d.valid
    one_chip = runner.mesh is None
    _, local_limit, whole_space = _limits(runner.config, plan, 1)
    hint = runner._cap_hints.get(d.base_key)
    fit = attempt_loop(
        d, (d.win[1] if d.win else valid.shape[0]) * valid.shape[1],
        () if one_chip else ("gspmd",),
        lambda program: jit_whole(runner, plan, program),
        lambda out: int(out["_count"]),
        first_cap(runner.config, plan, hint), local_limit,
        # nothing says how many groups are present: count them first
        count_first=one_chip and hint is None and not whole_space)
    try:
        fit.out.pop("_narrow_ok", None)   # the loop read it; not a table
        with _span("host-transfer", cap=fit.program.cap):
            out = runner._fetch_tree(fit.out, d.metrics, fit.pin)
    finally:
        # a fetch has unpinned it; an error before one has not
        runner._hbm_ledger.unpin_inflight(fit.pin)
    return out, fit, fit.count


def _per_chip(d: _Dispatch):
    """Multi-chip sparse: the one-chip sort/compact kernel mapped over
    the mesh, ONE program a cap, + a merge (executor.sharding's account;
    docs/TPU_NOTES.md "sharded serving"). sparse_merge="exchange" lets
    the merged table hold D x sparse_group_budget present groups
    (capacity scales with chip count); "gather" keeps the legacy
    global-budget contract (every group must fit one chip's table)."""
    runner, plan = d.runner, d.plan
    mesh, hints = runner.mesh, runner._cap_hints
    n_shards = mesh.devices.size
    cap_limit, local_limit, _ = _limits(runner.config, plan, n_shards)
    local_key = d.base_key + ("local",)
    counts = []

    def probe(out):
        # the D-element sync that waits for the sorts
        counts[:] = [int(c) for c in jax.device_get(out["_count"])]
        return max(counts)

    fit = attempt_loop(
        d, d.valid.size // n_shards,   # a chip sorts its own share
        ("mesh",), lambda program: sh.mesh_sparse_kernel(plan, mesh, program),
        probe, first_cap(runner.config, plan,
                         hints.get(local_key, hints.get(d.base_key))),
        local_limit, noun="per-chip present groups", chips=range(n_shards))
    try:
        out, count = _merged(d, fit, counts, cap_limit)
    finally:
        # the fetch has unpinned it; an error before it has not
        runner._hbm_ledger.unpin_inflight(fit.pin)
    hints[local_key] = fit.count
    return out, fit, count


def _merged(d: _Dispatch, fit: Fit, counts: list, cap_limit: int):
    """The chips' compact tables of `fit` -> (the merged table on the
    host, its present groups). What leaves the chips is the present
    groups' size, not the cap's: tables are cut on the device to a
    power-of-two bucket (a program a bucket, no sort in it) before the
    one fetch, which unpins `fit.pin`."""
    runner, plan, metrics = d.runner, d.plan, d.metrics
    mesh = runner.mesh
    n_shards, cap = mesh.devices.size, fit.program.cap

    def program(key, build, what):
        """A second program of the dispatch, built once a key (a counted
        compile); call under the enqueue lock."""
        fn, hit = runner._program(key, build, what, metrics)
        fit.hit = fit.hit and hit
        return fn

    def head(tables, rows, merged):
        with runner._enqueue_lock(metrics):
            return program(
                ("sparse-head", n_shards, rows, merged),
                lambda: sh.mesh_head_kernel(mesh, rows, merged),
                "sparse-head")(tables)

    def over_budget(count):
        return UnsupportedAggregation(
            f"{count} present groups exceed sparse budget {cap_limit}")

    tables = {k: v for k, v in fit.out.items() if k != "_count"}
    rows = min(cap, max(64, next_pow2(fit.count)))
    rows_in = sum(counts)
    cap_global = min(cap_limit, max(64, next_pow2(rows_in)))
    on_device = runner.config.mesh_merge == "device" \
        and sg.merges_on_device(plan.agg_plans)
    if on_device:
        # every chip gathers the others' first `rows` slots and merges
        # them; the host waits for the merged count and fetches one
        # chip's copy of the table
        with _span("broker-merge", num_shards=n_shards,
                   where="device") as sp:
            with runner._enqueue_lock(metrics):
                tables = program(
                    d.base_key + ("mesh-merge", rows),
                    lambda: sh.mesh_merge_kernel(plan, mesh, rows),
                    "sparse-merge")(tables)
            count = int(tables.pop("_count"))
            sp.set(rows_in=rows_in, groups_out=count)
        if count > cap_limit:
            raise over_budget(count)
        n_from, n_rows = 1, max(64, next_pow2(count))
        if n_rows < n_shards * rows:
            tables = head(tables, n_rows, True)
        else:
            n_rows = n_shards * rows
    else:
        n_from, n_rows = n_shards, n_shards * rows
        if rows < cap:
            tables = head(tables, rows, False)
    with _span("sparse-shard-fetch", chips=n_from, rows=n_rows) as sp:
        tables = runner._fetch_tree(tables, metrics, fit.pin)
        fetched = sum(int(a.nbytes) for a in tables.values())
        sp.set(bytes=fetched)
    if on_device:
        out = dict(tables, _count=np.int32(count))
    else:
        # the broker merges the chips' present rows
        with _span("broker-merge", num_shards=n_shards,
                   where="broker") as sp:
            parts = [dict({k: v[:n] for k, v in t.items()},
                          _count=np.int32(n))
                     for t, n in zip(sh.chip_tables(tables, n_shards),
                                     counts)]
            out = sg.merge_sparse(parts, plan.agg_plans, cap_global)
            count = int(out["_count"])
            sp.set(rows_in=rows_in, groups_out=count)
            if count > cap_limit:
                raise over_budget(count)
    metrics["merge"] = "device" if on_device else "broker"
    metrics["sparse_fetch_bytes"] = fetched
    metrics["sparse_merge_rows_in"] = rows_in
    if n_shards > 1 and runner.config.sparse_merge == "exchange":
        metrics["sparse_merge"] = "exchange"
        metrics["result_cap_owner"] = cap_global
    return out, count
