"""Cost-based dispatch: the DruidQueryCostModel analog (SURVEY.md §3.2).

The reference chooses between two physical strategies for every rewritten
query: send one query to the Druid *broker* (Druid fans out internally and
merges) or fan out per-historical queries with Spark running the final
merge ("direct historicals"), driven by estimated result cardinality,
scan/transport/merge costs and knobs like `histMergeFactor` /
`queryOutputSizeEstimate`.

The TPU translation keeps the same decision shape with the same inputs:

- "**broker**"  -> hand the WHOLE jitted program to XLA's GSPMD
  partitioner over the mesh: plain group keys, replicated outputs,
  compiler-inserted psum/all-gather (the fan-out/merge is opaque, like
  Druid's broker). The only strategy on a multi-host (DCN) mesh, where
  remote shards are not host-addressable.
- "**historicals**" -> the single-chip kernel (the Pallas one-hot
  reduce included) under `jax.shard_map` over the chip axis: every
  chip reduces its OWN rows with plain group keys, each chip's explicit
  partial dense group table stays SHARDED in its own HBM (no
  collective in the program), one fetch pulls every chip's shard
  concurrently, and the host BROKER merges the D unfinalized tables
  with the segment-cache algebra (the analog of per-historical
  partial aggregates + Spark's final merge-aggregate, SURVEY.md §3.5
  P2; executor/sharding.py).

Explicit partials pay the [D·K] host merge instead of a device
collective, so they win while the group table is small relative to the
scan; a huge dense table (K within the dense budget but millions of
groups x several aggregators) makes the fixed-size merge dominate,
where the compiler's freedom to schedule (reduce-scatter, fusion into
the scatter) is worth more. Both strategies are semantically identical
— this model only picks the faster one, and
`EngineConfig.cost_model_enabled=False` pins "historicals" (the
reference's default fan-out path).

Constants are per-chip throughput guesses, deliberately coarse — the
decision only needs the crossover magnitude, and every term is exposed in
the explain payload so a misprediction is visible (the reference logs its
cost decisions the same way, SURVEY.md §6 observability).
"""

from __future__ import annotations

from dataclasses import dataclass
from math import ceil, log2

# coarse per-chip fallback constants (v5e-class guesses) — superseded by
# fitted per-backend values from cost_calibration.json when present
# (tools/calibrate_cost.py measures and writes them)
SCAN_NS_PER_ROW_COL = 0.05     # fused filter+reduce, HBM-bound
MERGE_NS_PER_BYTE = 0.05       # ICI allreduce per byte per hop (~20 GB/s)
COLLECTIVE_LAT_US = 25.0       # per-hop collective launch latency
GSPMD_OVERHEAD = 1.35          # generic partitioner vs hand-written merge

_FALLBACKS = {
    "scan_ns_per_row_col": SCAN_NS_PER_ROW_COL,
    "merge_ns_per_byte": MERGE_NS_PER_BYTE,
    "collective_lat_us": COLLECTIVE_LAT_US,
    "gspmd_overhead": GSPMD_OVERHEAD,
}
_calibration_cache: dict | None = None


def _calibration() -> dict:
    """Fitted constants for the current backend, {} when never fitted."""
    global _calibration_cache
    if _calibration_cache is None:
        import json
        import os
        path = os.path.join(os.path.dirname(__file__),
                            "cost_calibration.json")
        data = {}
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
        _calibration_cache = data
    import jax
    return _calibration_cache.get(jax.default_backend(), {})


def constants(config) -> dict:
    """Resolve the four model constants: explicit config pin > fitted
    calibration for this backend > coarse fallback."""
    cal = _calibration()
    out = {}
    for name, fb in _FALLBACKS.items():
        pinned = getattr(config, "cost_" + name, None)
        out[name] = pinned if pinned is not None else cal.get(name, fb)
    return out


@dataclass(frozen=True)
class CostDecision:
    strategy: str            # "historicals" (sharded partials + host
    #                           broker merge) | "broker" (GSPMD)
    shards: int
    rows_scanned: int
    groups: int
    table_bytes: int         # merged group-table size (all aggregators)
    scan_us: float           # per-chip scan estimate
    merge_us: float          # explicit-partials merge estimate
    reason: str

    def to_json(self) -> dict:
        return {
            "strategy": self.strategy, "shards": self.shards,
            "rowsScanned": self.rows_scanned, "groups": self.groups,
            "tableBytes": self.table_bytes,
            "scanUs": round(self.scan_us, 1),
            "mergeUs": round(self.merge_us, 1),
            "reason": self.reason,
        }


def estimate_groups(plan) -> int:
    """Expected non-empty groups: the dense id space capped by the rows
    that can populate it (the reference estimates result cardinality from
    segment-metadata per-column cardinalities the same way)."""
    rows = sum(plan.table.segments[i].meta.n_valid for i in plan.pruned_ids)
    return max(1, min(plan.total_groups, rows))


def table_width_bytes(plan) -> int:
    """Bytes per group across all partial-aggregate state (what the
    allreduce actually moves): accumulators + per-plan null counters +
    sketch state."""
    from tpu_olap.kernels.hll import NUM_REGISTERS

    width = 4  # _rows int32
    for p in plan.agg_plans:
        if p.kind == "hll":
            width += 4 * NUM_REGISTERS
        elif p.kind == "theta":
            width += 8 * p.theta_k
        else:
            import numpy as np
            width += np.dtype(p.acc_dtype).itemsize
            if p.kind in ("sum", "min", "max"):
                width += 4  # _nn_<name>
    return width


def decide(plan, config, shards: int) -> CostDecision:
    """Pick the dispatch strategy for an aggregate plan on a mesh."""
    rows = sum(plan.table.segments[i].meta.n_valid for i in plan.pruned_ids)
    groups = plan.total_groups
    n_cols = max(1, len(plan.columns))
    width = table_width_bytes(plan)
    table_bytes = groups * width
    c = constants(config)

    scan_us = (rows * n_cols * c["scan_ns_per_row_col"] / 1000.0
               / max(1, shards))
    hops = max(1, ceil(log2(max(2, shards))))
    merge_us = hops * (c["collective_lat_us"]
                       + table_bytes * c["merge_ns_per_byte"] / 1000.0
                       * config.shard_merge_factor)

    if shards <= 1:
        return CostDecision("historicals", 1, rows, groups, table_bytes,
                            scan_us, 0.0, "single device")
    if config.force_strategy is not None:
        return CostDecision(config.force_strategy, shards, rows, groups,
                            table_bytes, scan_us, merge_us,
                            "forced by config")
    if not config.cost_model_enabled:
        return CostDecision("historicals", shards, rows, groups,
                            table_bytes, scan_us, merge_us,
                            "cost model disabled")
    # broker (GSPMD) wins when the explicit merge dwarfs its own scan —
    # the compiler can overlap/restructure what the fixed psum cannot
    if merge_us > c["gspmd_overhead"] * (scan_us
                                         + c["collective_lat_us"] * hops):
        return CostDecision("broker", shards, rows, groups, table_bytes,
                            scan_us, merge_us,
                            "merge dominates scan; defer to partitioner")
    return CostDecision("historicals", shards, rows, groups, table_bytes,
                        scan_us, merge_us, "explicit partials cheaper")
