"""HyperLogLog per-group registers on device.

The TPU-native analog of Druid's HyperLogLogCollector (SURVEY.md §3.7):
per-group register arrays updated with scatter-max, merged with elementwise
max (which is exactly the cross-chip allreduce op), finalized host-side or
in a post-aggregation. log2m=11 (2048 registers) matches Druid's default;
estimates use the classic HLL formula with linear-counting small-range
correction, so estimates agree with Druid to within normal HLL tolerance
(~1.6% stddev) — the parity harness applies per-class tolerances
(SURVEY.md §8.4 #2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from tpu_olap.kernels.hashing import has_x64

LOG2M = 11
NUM_REGISTERS = 1 << LOG2M  # 2048
_ALPHA = 0.7213 / (1 + 1.079 / NUM_REGISTERS)


def hll_update(h, valid, key, num_groups):
    """h: [N] int32 hashes; valid: [N] bool; key: [N] int32 group ids.

    Returns [num_groups, NUM_REGISTERS] int32 rho registers.
    """
    u = h.astype(jnp.uint32)
    reg = (u & jnp.uint32(NUM_REGISTERS - 1)).astype(jnp.int32)
    w = (u >> LOG2M).astype(jnp.uint32)
    # rho = leading-zero count of the remaining (32-log2m) bits + 1
    shifted = (w << LOG2M).astype(jnp.uint32)
    rho = jnp.where(w == 0, (32 - LOG2M) + 1,
                    jax.lax.clz(shifted.astype(jnp.int32)) + 1
                    ).astype(jnp.int32)
    rho = jnp.where(valid, rho, 0)
    # index space is groups × 2048: compute in the widest int available so
    # group counts inside the dense budget can't overflow the flat index
    # (callers guard the x64-off case — see lowering's sketch radix check)
    idx_dtype = jnp.int64 if has_x64(jnp) else jnp.int32
    flat = key.astype(idx_dtype) * idx_dtype(NUM_REGISTERS) \
        + reg.astype(idx_dtype)
    flat = jnp.where(valid, flat, 0)
    regs = jax.ops.segment_max(rho, flat,
                               num_segments=num_groups * NUM_REGISTERS)
    regs = jnp.maximum(regs, 0)  # empty slots: segment_max yields -inf/min
    return regs.reshape(num_groups, NUM_REGISTERS)


def hll_merge(a, b, xp):
    return xp.maximum(a, b)


def hll_estimate(registers, xp=np, float_dtype=np.float64):
    """[K, m] registers -> [K] float estimates. Runs host-side (xp=np) or
    on device inside the packed-result program (xp=jnp) — finalizing on
    device keeps the per-query host fetch to one small buffer."""
    ft = np.dtype(float_dtype).type
    regs = xp.asarray(registers).astype(float_dtype)
    # clamp to the valid register range: padding/absent-group slots can
    # carry negative sentinels (exchange-merge buffers), and 2^-(-x)
    # overflows float for large x — those slots are masked downstream,
    # but the warning (and inf) must not be produced at all
    regs = xp.clip(regs, 0.0, 64.0)
    m = NUM_REGISTERS
    inv = xp.power(ft(2.0), -regs).sum(axis=-1)
    est = ft(_ALPHA * m * m) / inv
    zeros = (regs == 0).sum(axis=-1)
    small = est <= 2.5 * m
    lc = m * xp.log(xp.where(zeros > 0,
                             m / xp.maximum(zeros, 1).astype(float_dtype),
                             ft(1.0)))
    est = xp.where(small & (zeros > 0), lc, est)
    return est
