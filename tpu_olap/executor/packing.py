"""Device-side finalize + compact + pack: ONE host fetch per aggregate query.

Why this exists: the dominant per-query cost on real hardware is not the
scan/reduce (segment_sum over 300k rows is ~0.1 ms on a v5e) but
device->host result movement — each fresh buffer fetch pays a fixed
round-trip (~tens of ms through the runtime) plus bandwidth on the dense
group table (q4.3's year x city x brand table is ~2.3M groups x 8B per
aggregator). The reference has the same shape of problem (Druid broker
JSON -> JVM row iterator is its per-row hot loop, SURVEY.md §4.2); its
answer is streaming. The TPU-native answer is to finish the query ON
DEVICE and ship back only the answer:

  1. finalize sketches on device (HLL registers -> estimate, theta table
     -> estimate), so [K, 2048] register planes never cross the link;
  2. compact to the non-empty groups (BI group-bys are sparse: the dense
     mixed-radix table is mostly zeros) with a static-size
     `nonzero(size=cap)` so the program stays shape-stable and cacheable;
  3. bitcast every per-group array to int32 words and concatenate into a
     single 1-D buffer -> exactly one transfer, one round-trip.

If more than `cap` groups are non-empty (count is the buffer's header
word), the runner transparently re-runs the unpacked program — correct,
just slower; `result_group_cap` bounds the common case, not the maximum.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from tpu_olap.kernels import hll as hll_mod
from tpu_olap.kernels import theta as theta_mod

_WORD = np.dtype(np.int32)  # buffer word: everything bitcasts to int32


@dataclass(frozen=True)
class PackLayout:
    """Static buffer layout: [count:int32][idx:int32[cap]] then one
    [cap]-slot slab per field, each bitcast to int32 words.

    `f64_as_pair`: a float64 slab travels as [hi:f32[cap]][lo:f32[cap]]
    (value = hi + lo) instead of bitcast words — same width. The TPU
    emulates f64 as exactly such an f32 pair and XLA's x64 rewriter
    refuses every f64<->integer bitcast-convert there (UNIMPLEMENTED at
    compile time, found compiling a min/max plan for a described v5e),
    so the split carries all the device holds; elsewhere the bitcast is
    exact and stays."""
    cap: int
    total: int
    fields: tuple  # ((name, np.dtype), ...) in buffer order
    f64_as_pair: bool = False


def make_layout(plan, config, cap: int | None = None,
                backend: str | None = None) -> PackLayout:
    cap = min(cap if cap is not None else config.result_group_cap,
              plan.total_groups)
    fdt = np.dtype(np.float64 if config.enable_x64 else np.float32)
    fields = [("_rows", np.dtype(np.int32))]
    for p in plan.agg_plans:
        if p.kind in ("count", "sum"):
            fields.append((p.name, np.dtype(p.acc_dtype)))
        else:  # min | max | hll | theta -> finalized float
            fields.append((p.name, fdt))
    return PackLayout(cap, plan.total_groups, tuple(fields),
                      f64_as_pair=backend == "tpu")


def device_finalize(out: dict, agg_plans, layout: PackLayout, xp) -> dict:
    """Partial-aggregate dict -> final per-group values (device analog of
    results.finalize_aggs; HLL rounding stays host-side since it is
    per-spec)."""
    fdt = [dt for _, dt in layout.fields if dt.kind == "f"]
    fdt = fdt[0] if fdt else np.dtype(np.float64)
    res = {"_rows": out["_rows"].astype(xp.int32)}
    for p in agg_plans:
        v = out[p.name]
        if p.kind in ("count", "sum"):
            res[p.name] = v
        elif p.kind in ("min", "max"):
            nn = out[f"_nn_{p.name}"]
            res[p.name] = xp.where(nn > 0, v.astype(fdt), xp.asarray(
                np.nan, fdt))
        elif p.kind == "hll":
            res[p.name] = hll_mod.hll_estimate(v, xp, fdt)
        elif p.kind == "theta":
            res[p.name] = theta_mod.theta_estimate(v, xp, fdt)
        else:
            raise AssertionError(p.kind)
    return res


def build_packer(inner, plan, layout: PackLayout):
    """Wrap a partials kernel (single-chip or sharded+merged) so the jitted
    program returns the single packed int32 buffer."""
    import jax
    import jax.numpy as jnp

    agg_plans = plan.agg_plans

    def packed_agg(env, valid, seg_mask, consts):
        out = inner(env, valid, seg_mask, consts)
        with jax.named_scope("pack"):
            fin = device_finalize(out, agg_plans, layout, jnp)
            present = fin["_rows"] > 0
            count = present.sum(dtype=jnp.int32)
            idx = jnp.nonzero(present, size=layout.cap, fill_value=0)[0] \
                .astype(jnp.int32)
            parts = [count.reshape(1), idx]
            for name, dt in layout.fields:
                parts.append(_as_words(fin[name][idx].astype(dt),
                                       layout.f64_as_pair))
            return jnp.concatenate(parts)

    return packed_agg


def _as_words(x, f64_as_pair: bool):
    import jax
    import jax.numpy as jnp

    if x.dtype == jnp.int32:
        return x.reshape(-1)
    if f64_as_pair and x.dtype == jnp.float64:
        hi = x.astype(jnp.float32)
        # nan/inf ride in hi alone (inf - inf would turn them to nan)
        lo = jnp.where(jnp.isfinite(hi), x - hi.astype(jnp.float64),
                       0.0).astype(jnp.float32)
        return jax.lax.bitcast_convert_type(
            jnp.concatenate([hi, lo]), jnp.int32)
    return jax.lax.bitcast_convert_type(x, jnp.int32).reshape(-1)


def unpack(buf, layout: PackLayout):
    """Packed buffer (host numpy int32[...]) -> (count, idx[n], {name:
    array[n]}) with n = min(count, cap). count > cap means overflow: the
    caller must re-run unpacked."""
    words = np.asarray(buf)
    count = int(words[0])
    cap = layout.cap
    n = min(count, cap)
    idx = np.asarray(words[1:1 + cap][:n], np.int64)
    pos = 1 + cap
    arrays = {}
    for name, dt in layout.fields:
        w = dt.itemsize // _WORD.itemsize
        slab = words[pos:pos + cap * w]
        pos += cap * w
        slab = np.ascontiguousarray(slab)
        if layout.f64_as_pair and dt == np.float64:
            hi, lo = slab.view(np.float32).reshape(2, cap)[:, :n]
            arrays[name] = hi.astype(np.float64) + lo
        else:
            arrays[name] = slab.view(dt)[:n]
    return count, idx, arrays


def densify(idx, compact: dict, layout: PackLayout, agg_plans) -> dict:
    """Compacted results -> dense [total] arrays (what the host assembly
    paths consume). Empty groups: 0 for counts/sums/sketch estimates, NaN
    for min/max (rendered as SQL null)."""
    kinds = {p.name: p.kind for p in agg_plans}
    out = {}
    for name, dt in layout.fields:
        fill = np.nan if kinds.get(name) in ("min", "max") else 0
        a = np.full(layout.total, fill, dt)
        a[idx] = compact[name]
        out[name] = a
    return out
