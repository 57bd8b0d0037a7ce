"""Multi-chip execution: one chip's program mapped over the mesh.

The TPU-native replacement for the reference's direct-historical fan-out
(SURVEY.md §3.5 P2): columns are placed ONCE with
`jax.device_put(x, NamedSharding(mesh, P(AXIS)))` over an INTERLEAVED
segment→chip assignment (segment i → chip i mod D, the way a Druid
coordinator balances an interval's segments across historicals), and the
dense aggregate runs as the SINGLE-CHIP kernel under `jax.shard_map`:
every chip reduces its own rows with the program one chip would run
(the Pallas one-hot reduce included), and no collective is compiled in
(the sparse group-by's merge, below, is the one exception).

Which spelling of the dense aggregate runs is a fact of the mesh, fixed
when it is built (QueryRunner.mesh), and the record's `mesh_program`
names it:

- "per_chip", every mesh within one process: `shard_map(plan.kernel)` —
  each chip returns its unfinalized [K, ...] partial table from plain
  [0, K) keys, the tables come back laid end to end as [D·K, ...] (each
  chip's K-block lives in its own HBM — zero cross-chip traffic), and a
  host-side **broker** step merges the D tables with the exact algebra
  the segment cache and cube folds already share
  (kernels.groupby.merge_partials / partials_radix). One device fetch
  pulls every chip's shard concurrently, so stage-2 transfers overlap
  across chips.
- "gspmd", a mesh that spans processes (is_multihost), whose remote
  shards the host broker cannot see: the WHOLE program in global shapes
  is handed to GSPMD under `jax.jit(..., out_shardings=...)` — plain
  group keys, replicated outputs, compiler-inserted psum/all-gather.

Interleaved placement is what makes windowed dispatch prune PER-CHIP
working sets (docs/TPU_NOTES.md): a contiguous time range of logical
segments [lo, hi) lands on every chip as the LOCAL range
[lo//D, ceil(hi/D)), so inside the map each chip dynamic-slices axis 0
of its own [S/D, R] blocks exactly as one chip slices its window — each
chip reads only its ~(hi-lo)/D pruned segments, with no cross-chip data
movement and ONE compiled program per (template, local width). The
GSPMD spellings (the "gspmd" program, the per-segment cache partials)
reshape [S, R] → [D, S/D, R] and slice the local axis instead.

High-cardinality sparse group-by runs the same way: the one-chip
sort/compact kernel under `jax.shard_map` (mesh_sparse_kernel), ONE
program a cap for the whole mesh — a single-device jit a chip would
compile the sort once a chip, the persistent cache's key holding the
device assignment — whose [D·cap] compact tables stay on their chips.
They are merged where EngineConfig.mesh_merge says. "device" (the
default): a second program (mesh_merge_kernel) has every chip all-gather
the others' present rows — a power-of-two bucket of the largest count —
and merge them (kernels.sparse_groupby.merge_device), the engine's one
collective; the host waits for the merged count, cuts the replicated
table to a bucket of it (mesh_head_kernel) and fetches one copy.
"broker", and any plan with a sketch aggregate: mesh_head_kernel cuts
every chip's tables to that bucket, one fetch brings the D of them, and
kernels.sparse_groupby.merge_sparse merges the key-sorted tables in
numpy. Present-group capacity under sparse_merge="exchange" is D × the
per-chip budget — the merged table holds the union, so capacity scales
with chip count.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "chips"


def make_mesh(num_shards: int) -> Mesh:
    devs = jax.devices()
    if num_shards > len(devs):
        raise ValueError(
            f"num_shards={num_shards} exceeds {len(devs)} devices")
    return Mesh(np.array(devs[:num_shards]), (AXIS,))


def make_multihost_mesh(num_shards: int | None = None) -> Mesh:
    """Mesh over ALL processes' devices (call after
    jax.distributed.initialize on every host). Single-process callers
    get the same mesh make_mesh builds; multi-host callers get a 1-D
    chip axis spanning hosts — GSPMD's inserted collectives then ride
    ICI within a slice and DCN across slices with no code change."""
    devs = jax.devices()
    n = num_shards or len(devs)
    if n > len(devs):
        raise ValueError(f"num_shards={n} exceeds {len(devs)} devices")
    return Mesh(np.array(devs[:n]), (AXIS,))


def pad_segments(n_segments: int, num_shards: int) -> int:
    """Segments must split evenly across chips; padded blocks are fully
    invalid rows (valid mask False), so results are unaffected."""
    return -(-n_segments // num_shards) * num_shards


def placement(n_segments: int, num_shards: int):
    """(to_place, to_logical) permutations for the interleaved
    segment→chip assignment over a PADDED segment count.

    Logical segment i belongs to chip i mod D at local index i // D;
    the placed (device) order is chip-major, so chip c's contiguous
    NamedSharding block [c·S/D, (c+1)·S/D) holds exactly its
    interleaved segments. to_place[i] = placed position of logical i;
    to_logical[p] = logical id at placed position p."""
    per_chip = n_segments // num_shards
    logical = np.arange(n_segments, dtype=np.int64)
    to_place = (logical % num_shards) * per_chip + logical // num_shards
    to_logical = np.empty(n_segments, np.int64)
    to_logical[to_place] = logical
    return to_place.astype(np.int32), to_logical.astype(np.int32)


def chip_of(segment_id: int, num_shards: int) -> int:
    """Owning chip of a logical segment under interleaved placement."""
    return segment_id % num_shards


def is_multihost(mesh: Mesh) -> bool:
    """True when the mesh spans processes (DCN): remote shards are not
    addressable, so the host broker merge and per-chip fan-out cannot
    see them — those paths force the GSPMD spellings (replicated
    outputs, compiler-inserted collectives) instead."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def shard_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(AXIS))


def replicated_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_put(arr: np.ndarray, mesh: Mesh):
    """Host array (PLACEMENT order on the leading axis) -> device array
    sharded per chip.

    Uses make_array_from_callback, the multi-host-correct formulation:
    each process materializes only the shards addressable on ITS devices
    (on a single host this degenerates to a plain sharded device_put).
    With a multi-host mesh every host feeds its local slice of the
    placed segment axis — no host ever holds the whole table."""
    sharding = shard_spec(mesh)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx])


def replicate_put(arr, mesh: Mesh):
    return jax.device_put(arr, replicated_spec(mesh))


def mesh_sparse_kernel(plan, mesh: Mesh, program):
    """Jitted sparse group-by over the mesh: the one-chip program
    (`plan.make_sparse_kernel(program)`: key, sort, the [cap] tables read
    at the runs' boundaries, `program.boundary` said of a chip's share of
    the rows) `jax.shard_map`ped over the chip axis, as
    mesh_agg_kernel maps the dense one. Each chip compacts its own rows;
    out_specs=P(chips) lays the D tables end to end as [D·cap, ...] and
    the D true counts as `_count` [D], a chip each. No collective is in
    the program, and it compiles once a cap whatever the mesh's size."""
    local = plan.make_sparse_kernel(program)

    def per_chip(env, valid, seg_mask, consts):
        out = local(env, valid, seg_mask, consts)
        return dict(out, _count=out["_count"].reshape(1))

    seg, rep = P(AXIS), P()
    return jax.jit(jax.shard_map(per_chip, mesh=mesh,
                                 in_specs=(seg, seg, seg, rep),
                                 out_specs=seg, check_vma=False))


def mesh_head_kernel(mesh: Mesh, rows: int, merged: bool = False):
    """Jitted [D·cap, ...] -> [D·rows, ...]: every chip's first `rows`
    slots of each compact table. Slot i of a chip's table holds its i-th
    smallest present key, so the first `count` slots are all it has to
    say; what the broker fetches is then the present groups' size, not
    the cap's. `merged`: the tables are mesh_merge_kernel's, one
    replicated table and not a chip's each."""
    # (named `head_rows` / `merge_tables` since PR 38, which gave them
    # their stages: a program that differs from a cached one in metadata
    # alone is loaded from the persistent cache with the OLD op_names, as
    # JAX's key leaves metadata out; the module's name is in the key)
    def head_rows(tables):
        with jax.named_scope("pack"):
            return {name: t[:rows] for name, t in tables.items()}

    spec = P() if merged else P(AXIS)
    return jax.jit(jax.shard_map(head_rows, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))


def mesh_merge_kernel(plan, mesh: Mesh, rows: int):
    """Jitted [D·cap, ...] -> replicated [D·rows, ...]: the chips'
    compact tables merged on the device. Every chip all-gathers the
    others' first `rows` slots over ICI (a few megabytes) and runs the
    same merge (kernels.sparse_groupby.merge_device: two sorts of D·rows
    partial rows), so the merged table stands on every chip and the host
    fetches one copy of it: nothing of a sparse query is sorted or
    reduced on the host."""
    from tpu_olap.kernels.sparse_groupby import merge_device

    def merge_tables(tables):
        with jax.named_scope("merge"):
            whole = {name: jax.lax.all_gather(t[:rows], AXIS, tiled=True)
                     for name, t in tables.items()}
            return merge_device(whole, plan.agg_plans, mesh.devices.size)

    return jax.jit(jax.shard_map(merge_tables, mesh=mesh,
                                 in_specs=(P(AXIS),),
                                 out_specs=P(), check_vma=False))


def chip_tables(out: dict, num_shards: int) -> list:
    """{name: [D·n, ...]} fetched per-chip tables -> D dicts of [n, ...]
    views, in mesh order (no copy)."""
    split = {name: np.asarray(v).reshape(
                 (num_shards, -1) + np.shape(v)[1:])
             for name, v in out.items()}
    return [{name: v[d] for name, v in split.items()}
            for d in range(num_shards)]


def local_window(pruned_ids, num_shards: int, per_chip: int):
    """(lo_local, W_local) covering every pruned segment's LOCAL index
    on its chip, or None when windowing would not save >= 25% of the
    per-chip working set. Interleaved placement makes the local ranges
    near-identical across chips, so ONE (lo, W) serves all of them —
    the per-chip analog of QueryRunner._segment_window. `lo` is traced
    at dispatch, so a sliding interval of the same width re-uses the
    compiled program."""
    if not pruned_ids:
        return None
    lo = min(pruned_ids) // num_shards
    hi = max(pruned_ids) // num_shards + 1
    W = next_pow2(hi - lo)
    W = min(W, per_chip)
    if 4 * W >= 3 * per_chip:
        return None
    return min(lo, per_chip - W), W


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length() if n > 1 else 1


def _slice_local(a, D: int, per_chip: int, lo, W: int):
    """[D·per_chip, ...] -> [D·W, ...]: reshape the placed segment axis
    to (chip, local), dynamic-slice the LOCAL axis (unsharded — GSPMD
    slices per chip with no communication), flatten back."""
    a3 = a.reshape((D, per_chip) + a.shape[1:])
    w = jax.lax.dynamic_slice_in_dim(a3, lo, W, axis=1)
    return w.reshape((D * W,) + a.shape[1:])


def _window_env(env, valid, seg_mask, D, per_chip, lo, W):
    sl = functools.partial(_slice_local, D=D, per_chip=per_chip,
                           lo=lo, W=W)
    with jax.named_scope("window"):
        wenv = {"cols": {c: sl(a) for c, a in env["cols"].items()},
                "nulls": {c: sl(a) for c, a in env["nulls"].items()}}
        return wenv, sl(valid), sl(seg_mask)


def chip_extended_key(key, mask, D: int, blocks: int, K: int):
    """Group key extended by the owning chip (placement order: row
    block b belongs to chip b // blocks), so a GSPMD-partitioned
    [D·K] partial table shards per chip: the fused batch legs'
    (executor/batch.py) spelling of the layout broker_merge folds.
    The single-query kernel (mesh_agg_kernel) gets the same layout
    from shard_map's out_specs with plain keys."""
    import jax.numpy as jnp

    with jax.named_scope("key"):
        r = mask.shape[0] // (D * blocks)
        chip = jnp.repeat(
            jnp.arange(D * blocks, dtype=jnp.int32) // jnp.int32(blocks), r)
        return chip * jnp.int32(K) + key.astype(jnp.int32)


def mesh_agg_kernel(plan, mesh: Mesh, per_chip: int, program: str,
                    win=None):
    """Jitted dense-aggregation program over the mesh.

    program "per_chip": `plan.kernel` ITSELF — the function one
    chip jits: the Pallas one-hot reduce when the plan is eligible, the
    generic key + group_reduce otherwise — `jax.shard_map`ped over the
    chip axis. Each chip sees its own [S/D, block_rows] blocks (with a
    per-chip window, the single-chip dynamic slice of axis 0), keys are
    the plain [0, K) keys, and out_specs=P(chips) lays the D unfinalized
    [K, ...] partial tables end to end as [D·K, ...], a chip each — the
    layout broker_merge folds. No collective is in the program.

    program "gspmd": plain keys over the GLOBAL shapes handed to
    GSPMD -> replicated [K] outputs, compiler-inserted cross-chip
    merges. Always the plan's generic key_fn (GSPMD cannot partition a
    Mosaic call).

    Signature matches the single-device jit paths:
    fn(env, valid, seg_mask, consts[, lo_local]) with `lo_local` traced
    when a per-chip window is active."""
    if program == "per_chip":
        from tpu_olap.executor.runner import QueryRunner

        local = plan.kernel if win is None \
            else QueryRunner._window_kernel(plan.kernel, win[1])
        seg, rep = P(AXIS), P()
        in_specs = (seg, seg, seg, rep) + ((rep,) if win is not None
                                           else ())
        # check_vma off: pallas_call outputs carry no varying-axis type
        return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                     out_specs=seg, check_vma=False))
    if program != "gspmd":
        raise ValueError(f"unknown mesh program {program!r}")

    from tpu_olap.kernels.groupby import group_reduce

    D = mesh.devices.size
    K = plan.total_groups
    W = win[1] if win is not None else per_chip

    def mesh_agg(env, valid, seg_mask, consts, lo=None):
        if lo is not None:
            env, valid, seg_mask = _window_env(env, valid, seg_mask,
                                               D, per_chip, lo, W)
        fenv, mask, key = plan.key_fn(env, valid, seg_mask, consts)
        return group_reduce(key, mask, fenv, plan.agg_plans, K, consts)

    return jax.jit(mesh_agg, out_shardings=replicated_spec(mesh))


def mesh_mask_kernel(plan, mesh: Mesh):
    """Jitted row-mask program (scan/select/search): the plan's own
    kernel handed whole to GSPMD, outputs sharded per chip — the host
    fetch pulls each chip's rows concurrently, then inverse-permutes
    the placed segment axis back to logical order (runner side). On a
    multi-host mesh the mask replicates instead (every host must
    assemble the full row set)."""
    out = replicated_spec(mesh) if is_multihost(mesh) \
        else shard_spec(mesh)
    return jax.jit(plan.kernel, out_shardings=out)


def mesh_seg_partials_kernel(plan, mesh: Mesh, per_chip: int, W: int,
                             K: int):
    """Per-(chip, segment) partials in one mesh program: local-window
    slice, then the group key extends by the PLACED window position, so
    the [D·W·K] table comes back sharded per chip and splits into one
    mergeable partials dict per computed segment — the tier-1 cache
    shard entries the broker merge folds (docs/CACHING.md)."""
    import jax.numpy as jnp

    from tpu_olap.kernels.groupby import group_reduce

    D = mesh.devices.size

    def mesh_seg_partials(env, valid, seg_mask, consts, lo):
        env, valid, seg_mask = _window_env(env, valid, seg_mask,
                                           D, per_chip, lo, W)
        fenv, mask, key = plan.key_fn(env, valid, seg_mask, consts)
        with jax.named_scope("key"):
            r = mask.shape[0] // (D * W)
            pos = jnp.repeat(jnp.arange(D * W, dtype=jnp.int32), r)
            key2 = pos * jnp.int32(K) + key.astype(jnp.int32)
        return group_reduce(key2, mask, fenv, plan.agg_plans, D * W * K,
                            consts)

    return jax.jit(mesh_seg_partials, out_shardings=shard_spec(mesh))


def broker_merge(out: dict, agg_plans, num_shards: int) -> dict:
    """Host-side broker step: {name: [D·K, ...]} per-chip unfinalized
    partial tables -> one merged [K, ...] partials dict, folded with
    the exact merge algebra the segment cache and cube serves share
    (kernels.groupby.merge_partials: sums add, min/max fold, HLL
    registers max-merge, theta tables re-merge losslessly)."""
    from tpu_olap.kernels.groupby import merge_partials

    return functools.reduce(
        lambda a, b: merge_partials(a, b, agg_plans),
        chip_tables(out, num_shards))
