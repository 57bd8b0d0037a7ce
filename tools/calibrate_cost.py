"""Fit the DruidQueryCostModel-analog constants from measurements
(VERDICT round-2 task #6; SURVEY.md §3.2 DruidQueryCostModel).

Runs a grid of (rows, group-cardinality) GROUP BY queries through the
engine on an 8-device mesh, timing BOTH dispatch strategies via the
force_strategy override:

- "historicals" (sharded per-chip partials + host broker merge), whose model is
      t = scan_us + merge_us
        = rows*cols*SCAN/1e3/D  +  hops*(LAT + bytes*MERGE/1e3)
  fitted by least squares over the grid (SCAN from the rows axis at tiny
  K, LAT+MERGE from the table-bytes axis at fixed rows);
- "broker" (one program under GSPMD), modeled as
      t = OVERHEAD * (scan_us + LAT*hops)
  fitted as the median ratio over the grid.

Writes tpu_olap/planner/cost_calibration.json keyed by jax backend
("cpu" when run under the virtual mesh, "tpu" on hardware) — decide()
prefers these over the coarse built-ins. Run:

    python tools/calibrate_cost.py            # default backend
    CAL_FORCE_CPU=1 python tools/calibrate_cost.py   # 8-dev CPU mesh

On a single-chip backend only the scan
slope is measurable — there is no ICI to fit merge/latency against — so
the script writes just `scan_ns_per_row_col` (+ a single-device dispatch
floor) and `constants()` falls back per-key for the rest. Set
CAL_REQUIRE_TPU=1 to exit(3) instead of writing when jax resolves to CPU
(so a machine with no chip cannot bank a CPU fit as "tpu").
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from tpu_olap.utils.platform import (ensure_host_device_count,  # noqa: E402
                                     env_flag, force_cpu_platform)

SHARDS = 8
ITERS = 7


def _make_engine(force_strategy):
    from tpu_olap import Engine
    from tpu_olap.executor import EngineConfig
    return Engine(EngineConfig(num_shards=SHARDS,
                               force_strategy=force_strategy,
                               use_pallas="never"))


def _register(eng, rows, k):
    import pandas as pd
    rng = np.random.default_rng(7)
    df = pd.DataFrame({
        "ts": pd.to_datetime("2024-01-01")
        + pd.to_timedelta(np.arange(rows) % 86400, unit="s"),
        # numeric dim spanning exactly k dense ids (range [0, k))
        "g": np.concatenate([np.arange(k), rng.integers(0, k, rows - k)])
        .astype(np.int64),
        "v": rng.integers(0, 1000, rows).astype(np.int64),
    })
    eng.register_table("t", df, time_column="ts", block_rows=1 << 13)


SQL = "SELECT g, sum(v) AS s FROM t GROUP BY g"


def _write(backend, fitted, cost_mod):
    path = os.path.join(REPO, "tpu_olap", "planner",
                        "cost_calibration.json")
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data[backend] = fitted
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
    cost_mod._calibration_cache = None
    print(json.dumps({"backend": backend, **fitted}))


# v5e ICI figures (public: jax-ml.github.io/scaling-book hardware
# tables): ~45 GB/s per link per direction, us-scale collective launch.
# One chip cannot measure these, but the multi-chip decision
# terms must not run on generic fallbacks (VERDICT r4 missing #5): the
# MODEL FORM  t = hops*(lat + bytes*merge)  is validated by the 8-
# virtual-device CPU fit (same harness, "cpu" entry), and the v5e
# magnitudes are pinned from the datasheet until real ICI is reachable.
ICI_MERGE_NS_PER_BYTE = 1.0 / 45.0   # 45 GB/s/link/direction
ICI_COLLECTIVE_LAT_US = 1.0
GSPMD_OVERHEAD_TPU = 1.35            # XLA partitioner vs explicit psum


def _calibrate_single_device(backend, cost_mod):
    """One chip: fit the scan slope (the constant the SF100 projection
    runs on) from the rows axis; the merge/collective terms are pinned
    from the v5e ICI datasheet (no second device to move bytes to) with
    the model shape validated on the 8-virtual-device CPU mesh."""
    rows_a, rows_b, k0 = 1 << 19, 1 << 21, 8
    ta = _time_point(rows_a, k0, None)
    tb = _time_point(rows_b, k0, None)
    n_cols = 2
    scan = max(0.001, (tb - ta) * 1000.0 / ((rows_b - rows_a) * n_cols))
    fitted = {
        "scan_ns_per_row_col": round(float(scan), 5),
        "dispatch_floor_us": round(float(max(0.0, ta - rows_a * n_cols
                                             * scan / 1000.0)), 1),
        "merge_ns_per_byte": round(ICI_MERGE_NS_PER_BYTE, 5),
        "collective_lat_us": ICI_COLLECTIVE_LAT_US,
        "gspmd_overhead": GSPMD_OVERHEAD_TPU,
        "fitted_shards": 1,
        "fitted_iters": ITERS,
        "note": ("scan+floor measured on chip; merge/lat pinned from "
                 "v5e ICI datasheet (45 GB/s/link, us-scale launch); "
                 "gspmd_overhead v5e-class prior; model form validated "
                 "by the 8-virtual-device CPU fit"),
    }
    _write(backend, fitted, cost_mod)


def _time_point(rows, k, strategy):
    eng = _make_engine(strategy)
    _register(eng, rows, k)
    eng.sql(SQL)
    eng.sql(SQL)  # second warm: re-sized packed buffer compiles
    ts = []
    for _ in range(ITERS):
        t0 = time.perf_counter()
        eng.sql(SQL)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.percentile(ts, 50))  # microseconds


def main():
    global SHARDS
    if env_flag("CAL_FORCE_CPU"):
        ensure_host_device_count(SHARDS)
        force_cpu_platform()
    import jax
    backend = jax.default_backend()
    if backend == "cpu" and env_flag("CAL_REQUIRE_TPU"):
        print("backend is cpu; CAL_REQUIRE_TPU set — not writing",
              file=sys.stderr)
        sys.exit(3)
    if backend == "cpu" and jax.device_count() < SHARDS:
        ensure_host_device_count(SHARDS)
    # clamp to the device count only on hardware (one chip => the
    # single-device fit). On CPU the virtual 8-device mesh is the point:
    # a clamp there would silently overwrite the banked 8-shard fit with
    # a degraded single-device one when run without CAL_FORCE_CPU.
    if backend != "cpu":
        SHARDS = min(SHARDS, jax.device_count())
    SHARDS = min(SHARDS, int(os.environ.get("CAL_SHARDS", SHARDS)))
    from tpu_olap.planner import cost as cost_mod
    if SHARDS < 2:
        return _calibrate_single_device(backend, cost_mod)
    hops = max(1, int(np.ceil(np.log2(SHARDS))))

    # --- scan slope: tiny K, two row counts; historicals ---------------
    rows_a, rows_b, k0 = 1 << 17, 1 << 19, 8
    ta = _time_point(rows_a, k0, "historicals")
    tb = _time_point(rows_b, k0, "historicals")
    n_cols = 2  # g, v
    scan = max(0.001, (tb - ta) * 1000.0 * SHARDS
               / ((rows_b - rows_a) * n_cols))  # ns per row*col

    # --- merge slope: fixed rows, growing K; historicals ---------------
    rows_m = 1 << 17
    ks = [1 << 10, 1 << 14, 1 << 17]
    widths = 4 + 8 + 4  # _rows + int64 sum + _nn counter
    tms = [_time_point(rows_m, k, "historicals") for k in ks]
    xs = np.array([k * widths for k in ks], float)  # table bytes
    ys = np.array(tms, float)
    slope, intercept = np.polyfit(xs, ys, 1)
    merge = max(0.0001, slope * 1000.0 / hops)  # ns/byte/hop
    lat = max(1.0, (intercept - ta) / hops)     # us/hop over the scan base

    # --- broker overhead ratio -----------------------------------------
    ratios = []
    for rows, k in [(rows_a, k0), (rows_m, ks[1]), (rows_m, ks[2])]:
        tb_ = _time_point(rows, k, "broker")
        model_base = (rows * n_cols * scan / 1000.0 / SHARDS) + lat * hops
        ratios.append(tb_ / max(model_base, 1.0))
    overhead = float(np.median(ratios))

    fitted = {
        "scan_ns_per_row_col": round(float(scan), 5),
        "merge_ns_per_byte": round(float(merge), 5),
        "collective_lat_us": round(float(lat), 2),
        "gspmd_overhead": round(overhead, 3),
        "fitted_shards": SHARDS,
        "fitted_iters": ITERS,
    }
    _write(backend, fitted, cost_mod)


if __name__ == "__main__":
    main()
