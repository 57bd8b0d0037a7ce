"""Bench regression gate: compare two BENCH_*.json artifacts.

The bench trajectory was unbanked — every PR prints one JSON line, but
nothing diffs consecutive runs, so a 20% p50 regression on one query
rides in silently as long as the worst-case metric holds. This tool is
the gate CI (and future PRs) call:

    python tools/bench_compare.py BASELINE.json NEW.json
    python tools/bench_compare.py BENCH_r04.json BENCH_r06.json \
        --threshold 0.10
    python tools/bench_compare.py BENCH_CACHE_old.json BENCH_CACHE.json

It compares `detail.per_query_p50_ms` query by query, prints a delta
table, and exits non-zero when any query's p50 regressed beyond the
threshold (default 15%). When BOTH artifacts carry the cache bench's
`detail.cache` block (BENCH_CACHE.json), the table grows a cache-hit-
rate column and the gate ALSO checks the warm-path p50
(`per_query_warm_p50_ms`) against the same threshold — a cache that
stops hitting shows up as a warm regression even when the cold path
held. Queries present in only one artifact are reported but never gate
(a new query is not a regression; a removed one is visible in the
table). Sub-millisecond baselines are compared with a small absolute
floor so timer jitter on trivially fast queries cannot trip the gate.

Exit codes: 0 ok, 1 regression(s), 2 usage/artifact error.
"""

from __future__ import annotations

import argparse
import json
import sys

# relative regressions below this many ms of absolute growth never gate:
# at sub-ms scale the perf_counter jitter between two runs exceeds any
# honest percentage threshold
ABS_FLOOR_MS = 1.0


def _fail(msg: str):
    print(f"bench_compare: {msg}", file=sys.stderr)
    raise SystemExit(2)


def load_artifact(path: str) -> dict:
    """{"p50": {q: ms}, "warm": {q: ms}|None, "hit_rate": {q: f}|None}
    for latency artifacts, or {"kind": "concurrency", ...} for
    BENCH_CONCURRENCY.json-shaped throughput artifacts."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        _fail(f"cannot read {path}: {e}")
    if not isinstance(doc, dict):
        _fail(f"{path}: top-level JSON is {type(doc).__name__}, "
              "not an object (truncated/corrupt artifact?)")
    if isinstance(doc.get("parsed"), dict) and "detail" not in doc:
        doc = doc["parsed"]  # driver-banked wrapper (BENCH_rNN.json)
    if "throughput_qps" in doc and isinstance(doc.get("per_class"),
                                              dict):
        # concurrency artifact (tools/bench_concurrency.py): gate on
        # throughput + per-class p99 + per-stage queue wait (when both
        # artifacts carry the stage-scheduler occupancy block)
        stages = doc.get("stages")
        return {"kind": "concurrency",
                "qps": float(doc["throughput_qps"]),
                "p99": {str(c): float(v["p99_ms"])
                        for c, v in doc["per_class"].items()
                        if isinstance(v, dict) and "p99_ms" in v},
                "stages": {str(s): {
                    "wait_mean": float(v["queue_wait_ms_mean"]),
                    "busy_frac": float(v["busy_frac"])}
                    for s, v in stages.items()
                    if isinstance(v, dict)
                    and "queue_wait_ms_mean" in v}
                if isinstance(stages, dict) else None}
    if doc.get("mode") == "multichip" and \
            isinstance(doc.get("per_query"), dict):
        # sharded-serving artifact (bench.py --mesh N): gate mesh p50
        # + scaling efficiency + parity per query
        pq = doc["per_query"]
        return {"kind": "multichip",
                "n_devices": int(doc.get("n_devices", 0) or 0),
                "parity_ok": bool(doc.get("parity_ok")),
                "p50": {str(q): float(v["p50_mesh_ms"])
                        for q, v in pq.items()
                        if isinstance(v, dict) and "p50_mesh_ms" in v},
                "speedup": {str(q): float(v.get("speedup", 0.0))
                            for q, v in pq.items()
                            if isinstance(v, dict)}}
    detail = doc.get("detail") or {}
    per_query = detail.get("per_query_p50_ms")
    if not isinstance(per_query, dict) or not per_query:
        _fail(f"{path} has no detail.per_query_p50_ms and no "
              "throughput_qps (not a bench artifact?)")

    def _floats(d):
        try:
            return {str(q): float(v) for q, v in d.items()}
        except (TypeError, ValueError) as e:
            _fail(f"{path}: non-numeric p50 entry: {e}")

    out = {"kind": "latency", "p50": _floats(per_query), "warm": None,
           "hit_rate": None, "hbm_hwm": None}
    hbm = detail.get("hbm")
    if isinstance(hbm, dict) and \
            hbm.get("high_watermark_bytes") is not None:
        # telemetry-plane census (ISSUE 17): artifacts banked before
        # the sampler existed have no watermark — skipped, never gated
        out["hbm_hwm"] = float(hbm["high_watermark_bytes"])
    cache = detail.get("cache")
    if isinstance(cache, dict):
        warm = cache.get("per_query_warm_p50_ms")
        if isinstance(warm, dict) and warm:
            out["warm"] = _floats(warm)
        hr = cache.get("per_query_hit_rate")
        if isinstance(hr, dict) and hr:
            out["hit_rate"] = _floats(hr)
    return out


def compare(base: dict, new: dict, threshold: float):
    """Rows (query, base_ms, new_ms, delta_frac, regressed) for queries
    in both artifacts, plus the only-in-one leftovers."""
    rows = []
    for q in sorted(set(base) & set(new)):
        b, n = base[q], new[q]
        delta = (n - b) / b if b > 0 else (0.0 if n <= 0 else float("inf"))
        regressed = delta > threshold and (n - b) > ABS_FLOOR_MS
        rows.append((q, b, n, delta, regressed))
    only_base = sorted(set(base) - set(new))
    only_new = sorted(set(new) - set(base))
    return rows, only_base, only_new


def compare_concurrency(base: dict, new: dict, threshold: float) -> int:
    """Throughput-regression gate for BENCH_CONCURRENCY.json artifacts:
    exit 1 when throughput_qps dropped more than the threshold, any
    class's p99 grew beyond it, or any stage pool's mean queue wait
    grew beyond it (each with the absolute jitter floor)."""
    regressions = []
    bq, nq = base["qps"], new["qps"]
    dq = (nq - bq) / bq if bq > 0 else 0.0
    print(f"{'metric':<16}  {'base':>10}  {'new':>10}  {'delta':>8}  "
          "gate")
    flag = "ok"
    if dq < -threshold:
        regressions.append("throughput_qps")
        flag = "REGRESSED(qps)"
    print(f"{'throughput_qps':<16}  {bq:>10.1f}  {nq:>10.1f}  "
          f"{dq:>+7.1%}  {flag}")
    for cls in sorted(set(base["p99"]) & set(new["p99"])):
        b, n = base["p99"][cls], new["p99"][cls]
        d = (n - b) / b if b > 0 else 0.0
        reg = d > threshold and (n - b) > ABS_FLOOR_MS
        if reg:
            regressions.append(f"{cls}.p99")
        print(f"{cls + '.p99_ms':<16}  {b:>10.1f}  {n:>10.1f}  "
              f"{d:>+7.1%}  {'REGRESSED(p99)' if reg else 'ok'}")
    # per-stage queue-wait gate: a stage pool the load newly convoys
    # on is a regression even while total qps holds (the burst just
    # moved). Baselines banked before the stage scheduler existed have
    # no block — skipped, never gated. busy_frac is informational.
    if base.get("stages") and new.get("stages"):
        for s in sorted(set(base["stages"]) & set(new["stages"])):
            b = base["stages"][s]["wait_mean"]
            n = new["stages"][s]["wait_mean"]
            d = (n - b) / b if b > 0 else 0.0
            reg = d > threshold and (n - b) > ABS_FLOOR_MS
            if reg:
                regressions.append(f"{s}.queue_wait")
            print(f"{s + '.wait_ms':<16}  {b:>10.3f}  {n:>10.3f}  "
                  f"{d:>+7.1%}  "
                  f"{'REGRESSED(queue_wait)' if reg else 'ok'}"
                  f"  [busy {base['stages'][s]['busy_frac']:.3f}"
                  f" -> {new['stages'][s]['busy_frac']:.3f}]")
    if regressions:
        print(f"\nbench_compare: {len(regressions)} concurrency "
              f"metric(s) regressed past {threshold:.0%}: "
              f"{', '.join(regressions)}", file=sys.stderr)
        return 1
    print(f"\nbench_compare: ok (throughput + per-class p99 + stage "
          f"queue waits within {threshold:.0%})")
    return 0


def compare_multichip(base: dict, new: dict, threshold: float) -> int:
    """Sharded-serving gate for MULTICHIP_*.json artifacts (bench.py
    --mesh N): exit 1 when the candidate lost result parity vs the
    single-device path, any query's MESH p50 regressed past the
    threshold, or its mesh-vs-1-device speedup collapsed by more than
    the threshold. Prints the per-query scaling-efficiency column
    (speedup / n_devices) so ICI-merge or placement regressions are
    visible even while absolute p50s stay under the gate."""
    regressions = []
    if not new["parity_ok"]:
        regressions.append("parity")
    nd = max(1, new["n_devices"])
    rows, _, _ = compare(base["p50"], new["p50"], threshold)
    w = max([len(q) for q, *_ in rows] or [5])
    print(f"{'query':<{w}}  {'base ms':>10}  {'new ms':>10}  "
          f"{'delta':>8}  {'speedup':>8}  {'scale-eff':>9}  gate")
    for q, b, n, delta, regressed in rows:
        sp_b = base["speedup"].get(q, 0.0)
        sp_n = new["speedup"].get(q, 0.0)
        why = []
        if regressed:
            why.append("p50")
        if sp_b > 0 and (sp_n - sp_b) / sp_b < -threshold:
            why.append("speedup")
        if why:
            regressions.append(f"{q}({','.join(why)})")
        print(f"{q:<{w}}  {b:>10.3f}  {n:>10.3f}  {delta:>+7.1%}  "
              f"{sp_n:>7.2f}x  {sp_n / nd:>8.1%}  "
              f"{'REGRESSED(' + ','.join(why) + ')' if why else 'ok'}")
    if regressions:
        print(f"\nbench_compare: multichip regressed past "
              f"{threshold:.0%}: {', '.join(regressions)}",
              file=sys.stderr)
        return 1
    print(f"\nbench_compare: ok (mesh p50 + scaling within "
          f"{threshold:.0%}, parity held)")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Compare per-query SSB p50s of two bench artifacts "
                    "(cold always; warm-path + hit rate when both are "
                    "cache-bench artifacts); exit 1 when any query "
                    "regressed beyond the threshold.")
    p.add_argument("baseline", help="older BENCH_*.json")
    p.add_argument("candidate", help="newer BENCH_*.json")
    p.add_argument(
        "--threshold", type=float, default=0.15, metavar="FRAC",
        help="max tolerated relative p50 growth per query "
             "(default 0.15 = 15%%)")
    args = p.parse_args(argv)
    if not (0.0 <= args.threshold < 100.0):
        p.error(f"--threshold {args.threshold}: must be a fraction >= 0")

    base_art = load_artifact(args.baseline)
    new_art = load_artifact(args.candidate)
    if base_art["kind"] != new_art["kind"]:
        _fail(f"artifact kinds differ: {args.baseline} is "
              f"{base_art['kind']}, {args.candidate} is "
              f"{new_art['kind']}")
    if base_art["kind"] == "concurrency":
        return compare_concurrency(base_art, new_art, args.threshold)
    if base_art["kind"] == "multichip":
        return compare_multichip(base_art, new_art, args.threshold)
    base, new = base_art["p50"], new_art["p50"]
    rows, only_base, only_new = compare(base, new, args.threshold)
    if not rows:
        print("bench_compare: no queries in common — nothing to gate",
              file=sys.stderr)
        return 2

    have_cache = base_art["warm"] is not None \
        and new_art["warm"] is not None
    hit_rates = new_art["hit_rate"] or {}

    w = max(len(q) for q, *_ in rows)
    hdr = (f"{'query':<{w}}  {'base ms':>10}  {'new ms':>10}  "
           f"{'delta':>8}")
    if have_cache:
        hdr += f"  {'warm ms':>9}  {'wdelta':>8}  {'hit%':>6}"
    print(hdr + "  gate")
    regressions = []
    warm_rows = {}
    if have_cache:
        wr, _, _ = compare(base_art["warm"], new_art["warm"],
                           args.threshold)
        warm_rows = {q: (b, n, d, r) for q, b, n, d, r in wr}
    for q, b, n, delta, regressed in rows:
        why = []
        if regressed:
            why.append("p50")
        line = f"{q:<{w}}  {b:>10.3f}  {n:>10.3f}  {delta:>+7.1%}"
        if have_cache:
            wrow = warm_rows.get(q)
            if wrow is not None:
                wb, wn, wd, wreg = wrow
                if wreg:
                    why.append("warm")
                hr = hit_rates.get(q)
                line += (f"  {wn:>9.3f}  {wd:>+7.1%}  "
                         f"{hr * 100 if hr is not None else 0:>5.0f}%")
            else:
                line += f"  {'-':>9}  {'':>8}  {'':>6}"
        flag = "REGRESSED(" + ",".join(why) + ")" if why else "ok"
        print(line + f"  {flag}")
        if why:
            regressions.append(q)
    for q in only_base:
        print(f"{q:<{w}}  {base[q]:>10.3f}  {'-':>10}  {'':>8}  "
              "only in baseline")
    for q in only_new:
        print(f"{q:<{w}}  {'-':>10}  {new[q]:>10.3f}  {'':>8}  "
              "only in candidate")

    # HBM high-watermark gate (ISSUE 17): peak device-memory growth
    # past the threshold is a regression even when steady-state bytes
    # and p50s hold — a transient spike is tomorrow's OOM. Gated only
    # when BOTH artifacts carry the watermark (older artifacts skip).
    have_hwm = base_art.get("hbm_hwm") is not None \
        and new_art.get("hbm_hwm") is not None
    if have_hwm:
        bh, nh = base_art["hbm_hwm"], new_art["hbm_hwm"]
        dh = (nh - bh) / bh if bh > 0 else 0.0
        hwm_reg = dh > args.threshold
        print(f"{'hbm_hwm_bytes':<{w}}  {bh:>10.0f}  {nh:>10.0f}  "
              f"{dh:>+7.1%}" + ("  " * (3 if have_cache else 0))
              + f"  {'REGRESSED(hbm_hwm)' if hwm_reg else 'ok'}")
        if hwm_reg:
            regressions.append("hbm_hwm")

    if regressions:
        print(f"\nbench_compare: {len(regressions)} metric"
              f"{'' if len(regressions) == 1 else 's'} regressed "
              f"past {args.threshold:.0%}: {', '.join(regressions)}",
              file=sys.stderr)
        return 1
    print(f"\nbench_compare: ok ({len(rows)} queries within "
          f"{args.threshold:.0%}"
          + (", warm path + hit rate checked" if have_cache else "")
          + (", hbm high-watermark checked" if have_hwm else "")
          + ")")
    return 0


if __name__ == "__main__":
    sys.exit(main())
