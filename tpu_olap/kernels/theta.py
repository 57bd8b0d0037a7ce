"""Theta (KMV) sketch count-distinct per group, sort-based, static shapes.

The datasketches-extension analog (SURVEY.md §3.3 Theta-sketch aggregator),
re-designed for XLA: per group keep the k smallest *distinct* 32-bit hash
values. Update is a lexsort + within-group rank + scatter (no dynamic
shapes); merge concatenates two [K, k] tables and re-selects k minimums —
both jittable, so merge also rides the ICI collective path.

State: float64 table [K, k] of hash values mapped to [0,1) (1.0 = empty
slot), plus implicit count = #slots < 1.0. Estimate: if the table is not
full, the count is exact; else (k-1)/theta with theta = k-th smallest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpu_olap.kernels.hashing import has_x64, to_unit_float

EMPTY = 1.0  # sentinel: empty slot (hashes are in [0, 1))


def theta_update(h, valid, key, num_groups, k):
    """h: [N] int32 hashes; -> [K, k] sorted unit-hash table."""
    u = to_unit_float(h, jnp)
    u = jnp.where(valid, u, EMPTY)
    g = jnp.where(valid, key.astype(jnp.int32), num_groups)  # invalid -> end
    order = jnp.lexsort((u, g))
    gs, us = g[order], u[order]
    first = jnp.ones(gs.shape, bool)
    if gs.shape[0] > 1:
        dup = (gs[1:] == gs[:-1]) & (us[1:] == us[:-1])
        first = jnp.concatenate([first[:1], ~dup])
    kept = first & (gs < num_groups) & (us < EMPTY)
    # rank of each kept row within its group
    prefix = jnp.cumsum(kept.astype(jnp.int32)) - kept.astype(jnp.int32)
    start = _seg_min(jnp.where(kept, prefix, np.int32(2**31 - 1)), gs,
                     num_groups + 1)
    rank = prefix - start[gs]
    ok = kept & (rank < k)
    idt = jnp.int64 if has_x64(jnp) else jnp.int32
    flat = jnp.where(ok, gs.astype(idt) * idt(k) + rank.astype(idt), 0)
    vals = jnp.where(ok, us, EMPTY)
    table = _scatter_min(vals, flat, num_groups * k)
    return table.reshape(num_groups, k)


def theta_merge(a, b, xp):
    """[K, k] + [K, k] -> [K, k]: keep k smallest distinct of the union."""
    k = a.shape[-1]
    both = xp.concatenate([a, b], axis=-1)
    both = xp.sort(both, axis=-1)
    # dedupe equal neighbors (same hash from both sides)
    dup = xp.concatenate(
        [xp.zeros(both.shape[:-1] + (1,), bool), both[..., 1:] == both[..., :-1]],
        axis=-1)
    both = xp.where(dup, EMPTY, both)
    both = xp.sort(both, axis=-1)
    return both[..., :k]


def theta_estimate(table, xp=np, float_dtype=np.float64):
    """[K, k] sorted unit-hash table -> [K] float estimates. Host (xp=np)
    or on-device finalize for the packed-result path (xp=jnp)."""
    ft = np.dtype(float_dtype).type
    t = xp.asarray(table).astype(float_dtype)
    k = t.shape[-1]
    count = (t < EMPTY).sum(axis=-1)
    full = count >= k
    theta = t[..., -1]
    est_full = ft(k - 1) / xp.maximum(theta, ft(1e-30))
    return xp.where(full, est_full, count.astype(float_dtype))


def _seg_min(v, key, n):
    return jax.ops.segment_min(v.astype(jnp.int32), key, num_segments=n)


def _scatter_min(v, flat, n):
    return jnp.minimum(
        jax.ops.segment_min(v, flat, num_segments=n), EMPTY)
