"""One run of one cell: gate, set-up, window, check, the result line.

Importing this module touches neither JAX nor the program: the load
generator process and the data workers are spawned from it first, and they
re-import it.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import multiprocessing
import os
import shutil
import tempfile
import threading
import time
import types

from perfbench.lib import client as client_mod
from perfbench.lib import stats, traffic as traffic_mod, verify, xplane
from perfbench.lib.peaks import peaks_for

EXIT_NO_DEVICE, EXIT_REHEARSAL = 3, 10
MAX_WARMUP_TRIES = 6       # per template, until a run compiles nothing
SETTLE_ROUNDS = 2          # whole rounds after that, before the window...
SETTLE_ROUND_MAX_S = 5.0   # ...where a round takes no longer than this


def say(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)


# ------------------------------------------------------------- the manifest

def load_cell(root: str, workload: str) -> dict:
    """The cell's entry, its configuration and traffic files, and the
    metrics that apply to it, from BENCHMARK.json."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf_entry = next(c for c in manifest["configs"]
                      if c["name"] == cell["config"])
    with open(os.path.join(root, conf_entry["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, manifest["paths"][0])
    traffic = traffic_mod.load(traffic_mod.path_of(bench_dir, cell["traffic"]))

    def applies(metric):
        return "workloads" not in metric or workload in metric["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "bench_dir": bench_dir,
            "end_to_end": [m for m in manifest["end_to_end"] if applies(m)],
            "per_layer": [m for m in manifest["per_layer"] if applies(m)]}


def load_reader(bench_dir: str, name: str):
    """A metric's reader: perfbench/metrics/<name>.py, found by the name."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metrics(bench_dir: str, metrics: list, ctx) -> dict:
    out = {}
    for m in metrics:
        reader = load_reader(bench_dir, m["name"])
        if reader.UNIT != m["unit"]:
            raise SystemExit(f"metric {m['name']}: reader's unit "
                             f"{reader.UNIT!r} != manifest's {m['unit']!r}")
        value = reader.read(ctx)
        if value is not None and math.isfinite(value):
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# ------------------------------------------------------------------ the run

class Client:
    """The load generator process and its pipe."""

    def __init__(self):
        ctx = multiprocessing.get_context("spawn")
        self.pipe, child = ctx.Pipe()
        self.proc = ctx.Process(target=client_mod.serve, args=(child,),
                                daemon=True)
        self.proc.start()
        child.close()

    def call(self, *cmd):
        self.pipe.send(cmd)
        status, value = self.pipe.recv()
        if status != "ok":
            raise RuntimeError(f"load generator: {value}")
        return value

    def stop(self):
        try:
            if self.proc.is_alive():
                self.call("stop")
        except (OSError, EOFError, RuntimeError):
            pass
        self.proc.join(timeout=10)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(timeout=10)


def start_capture(trace_dir: str):
    """Start a profiler capture over the window; returns the function that
    stops it. The program's own hook (`obs/profile.capture_device_profile`)
    starts the profiler with its Python tracer on: over a million host
    events in five seconds, and a host path several times slower than the
    one being measured. So the benchmark starts the profiler itself with
    that tracer off and raises the program's flag, which is all the hook
    does besides: `annotate_dispatch` then writes the query id around every
    device call."""
    import jax
    from tpu_olap.obs import profile as profile_mod

    if not hasattr(profile_mod, "_capture_active"):
        raise SystemExit("tpu_olap.obs.profile has no _capture_active flag: "
                         "the trace would carry no query annotations")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with profile_mod._capture_lock:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        profile_mod._capture_active = True

    def stop():
        profile_mod._capture_active = False
        jax.profiler.stop_trace()

    return stop


def record_compiled(rec: dict) -> bool:
    return bool(rec.get("compile_ms") or rec.get("recompiles")
                or rec.get("jit_cache_hit") is False)


def record_on_device(rec: dict | None, num_shards: int) -> str | None:
    """None when the record says the device served the query as planned,
    else why not."""
    if rec is None:
        return "no history record"
    if rec.get("query_type") == "fallback" or "fallback_reason" in rec:
        return f"served by the pandas fallback: {rec.get('fallback_reason')}"
    if rec.get("failed"):
        return f"failed: {rec.get('failed')}"
    if (rec.get("num_shards") or 1) != num_shards:
        return f"num_shards={rec.get('num_shards')} != {num_shards}"
    return None


def cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def warm_up(client, engine, host, port, names: list, templates: dict):
    """Each of the cell's templates until a run compiles nothing (the packed
    cap re-size makes that two or three runs for some), then settling
    rounds. Every answer is kept for the comparison.
    -> ({template: [sample]}, {template: compiling runs}, round seconds)"""
    warm: dict = {t: [] for t in names}
    compiles = {t: 0 for t in names}
    for t in names:
        for _try in range(MAX_WARMUP_TRIES):
            s = client.call("requests", host, port, [(t, templates[t])])[0]
            warm[t].append(s)
            rec = history_by_id(engine).get(s["qid"])
            if s["status"] != 200 or rec is None or not record_compiled(rec):
                break
            compiles[t] += 1
    # settling rounds, for the host's sake (allocator, caches, threads): a
    # mix whose round takes seconds is device-bound and has run every
    # program twice already
    round_s = sum(warm[t][-1]["ms"] for t in names) / 1000.0
    if round_s <= SETTLE_ROUND_MAX_S:
        settle = [(t, templates[t]) for _ in range(SETTLE_ROUNDS)
                  for t in names]
        for s in client.call("requests", host, port, settle):
            warm[s["template"]].append(s)
    return warm, compiles, round_s


def check_answers(warm: dict, samples: list, records: dict, expected: dict,
                  num_shards: int):
    """The comparison: every distinct warm-up answer against the reference,
    every window answer against the verified answer of its template, every
    record against the device. -> (verified digests, {template: why wrong},
    reference rows compared, failed, {why: count})"""
    verified, wrong, compared_rows = {}, {}, 0
    for t, answers in warm.items():
        seen = set()
        for s in answers:
            if s["status"] != 200:
                wrong[t] = [f"HTTP {s['status']}: {s['body'][:200]!r}"]
            elif s["digest"] not in seen:
                seen.add(s["digest"])
                compared_rows += len(expected[t]["rows"])
                why = verify.answer_mismatches(json.loads(s["body"]),
                                               expected[t])
                if why:
                    wrong[t] = why
        why_dev = record_on_device(records.get(answers[-1]["qid"]),
                                   num_shards)
        if why_dev:
            wrong.setdefault(t, []).append(why_dev)
        verified[t] = answers[-1]["digest"]
    failed, why_failed = 0, {}
    for s in samples:
        if s["status"] != 200:
            why = f"HTTP {s['status']}"
        elif s["digest"] != verified[s["template"]]:
            why = "answer differs from the verified one"
        else:
            why = record_on_device(records.get(s["qid"]), num_shards)
        if why:
            failed += 1
            key = f"{s['template']}: {why}"
            why_failed[key] = why_failed.get(key, 0) + 1
    return verified, wrong, compared_rows, failed, why_failed


def history_by_id(engine) -> dict:
    return {r.get("query_id"): dict(r) for r in list(engine.runner.history)}


def make_work_dir() -> str:
    """Where one run keeps what it writes outside its checkout: under
    TMPDIR, removed at the end. libtpu's logs go there too (otherwise under
    the fixed /tmp/tpu_logs), so this runs before JAX is imported."""
    work_dir = tempfile.mkdtemp(prefix="perfbench_")
    os.makedirs(os.path.join(work_dir, "tpu_logs"))
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(work_dir, "tpu_logs"))
    return work_dir


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             rows: int | None = None, control: str | None = None,
             trace_out: str | None = None,
             work_dir: str | None = None) -> dict:
    """Everything after the gate. Returns the result line's keys, with
    `memory_peak_bytes` (and `busy_s`, `window_s`) beside them for the
    caller to put under `device`."""
    t_start = time.perf_counter()
    config, traffic = spec["config"], spec["traffic"]
    chips = int(config["chips"])
    rows = rows or int(config["rows"])
    dataset = importlib.import_module(
        f"perfbench.datasets.{config['dataset']}")
    templates = dataset.templates()
    plan = traffic_mod.plan(traffic, sorted(templates), seed, seconds)

    # the system under test; a checkout without it fails here, before
    # anything is started
    from tpu_olap import Engine
    from tpu_olap.api.server import QueryServer
    from tpu_olap.executor import EngineConfig
    from tpu_olap.utils.platform import configure_compile_cache

    work_dir = work_dir or make_work_dir()
    client = engine = server = None
    try:
        # --- the JAX-free children first: the load generator, the workers
        client = Client()
        workers = max(1, min(12, (os.cpu_count() or 2) - 1))
        gen: dict = {}

        def generate():
            try:
                gen["out"] = dataset.generate(
                    rows, seed, os.path.join(work_dir, "data"), workers)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                gen["error"] = e

        gen_thread = threading.Thread(target=generate)
        t0 = time.perf_counter()
        gen_thread.start()

        # --- the program, while the workers write
        engine_fields = dict(config["engine_config"])
        if control is not None:
            engine_fields.update(config["controls"][control])
            say(f"CONTROL {control}: engine fields "
                f"{config['controls'][control]}")
        engine = Engine(EngineConfig(**engine_fields))
        cache_dir = configure_compile_cache()
        n_cache0 = cache_entries(cache_dir)
        t_engine = time.perf_counter() - t0
        gen_thread.join()
        if "error" in gen:
            raise gen["error"]
        data = gen["out"]
        t_generate = time.perf_counter() - t0
        reference = data["reference"]
        say(f"setup: {rows:,} rows in {len(data['paths'])} parquet files, "
            f"generate_s={t_generate:.2f} ({workers} workers; engine import "
            f"and construction {t_engine:.2f}s inside it), reference_s="
            f"{data['reference_s']:.2f} (not counted in setup_s)")

        t0 = time.perf_counter()
        dataset.register(engine, data["paths"], rows, seed)
        t_ingest = time.perf_counter() - t0
        table = engine.catalog.get(dataset.TABLE).segments
        say(f"setup: ingest_s={t_ingest:.2f} segments={len(table.segments)} "
            f"rows={table.num_rows}")
        shutil.rmtree(os.path.join(work_dir, "data"), ignore_errors=True)

        server = QueryServer(engine, port=0).start()
        host, port = server.host, server.port

        # --- warm-up: this cell's templates only
        t0 = time.perf_counter()
        names = sorted(traffic_mod.weights(traffic, sorted(templates)))
        warm, compiles, round_s = warm_up(client, engine, host, port, names,
                                          templates)
        t_warm = time.perf_counter() - t0
        setup_s = time.perf_counter() - t_start - data["reference_s"]
        say(f"setup: warmup_s={t_warm:.2f} (a round takes {round_s:.2f}s) "
            f"compiling runs per template={compiles} compile cache "
            f"{cache_dir} entries {n_cache0} -> {cache_entries(cache_dir)}")
        say(f"setup: setup_s={setup_s:.3f}")

        # --- the window
        trace_dir = os.path.join(work_dir, "trace")
        capture = start_capture(trace_dir) if trace else None
        report = client.call("window", host, port, plan,
                             {t: templates[t] for t in names})
        if capture is not None:
            capture()
        samples = report["samples"]
        records = history_by_id(engine)
        qids = {s["qid"] for s in samples}
        traces = {t.query_id: t.to_json()
                  for t in engine.tracer.recent_traces(
                      engine.tracer.ring_limit)
                  if t.query_id in qids}
        import jax
        mem_peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                        for d in jax.devices()[:chips]), default=0)
        hbm = engine.runner.device_snapshot()
        server.stop()
        server = None

        # --- the check: every warm-up answer against the reference, every
        # window answer against the verified answer of its template
        expected = dataset.answers(reference)
        num_shards = int(engine_fields.get("num_shards") or 1)
        verified, wrong_templates, compared_rows, failed, why_failed = \
            check_answers(warm, samples, records, expected, num_shards)
        clients_ok = report["clients_reported"] == plan["clients"]
        totals = dataset.totals(reference)
        say(f"check: reference totals rows={totals['rows']} "
            f"sum_lo_revenue={totals['sum_lo_revenue']}")
        say(f"check: templates whose warm-up answers differ from the "
            f"reference = {len(wrong_templates)} of {len(names)} (limit 0; "
            f"{compared_rows} reference rows compared by equality)")
        for t, why in wrong_templates.items():
            say(f"check:   {t}: {why[:3]}")
        say(f"check: window answers not equal to the verified answer, not "
            f"200, or not served by the device as planned = {failed} of "
            f"{len(samples)} (limit 0)")
        for why, n in list(why_failed.items())[:10]:
            say(f"check:   {n} x {why}")
        if not clients_ok:
            say(f"check: only {report['clients_reported']} of "
                f"{plan['clients']} clients reported")
        correct = (not wrong_templates and failed == 0 and clients_ok
                   and len(samples) > 0)

        # --- what the window looked like
        meds = stats.template_medians(samples) if samples else {}
        for t in names:
            rec = records.get(warm[t][-1]["qid"]) or {}
            cost = rec.get("cost") or {}
            n_t = sum(1 for s in samples if s["template"] == t)
            say(f"template {t}: p50_ms={meds.get(t, float('nan')):.3f} "
                f"n={n_t} path={rec.get('path')} packed={rec.get('packed')} "
                f"num_shards={rec.get('num_shards')} merge={rec.get('merge')}"
                f" strategy={cost.get('strategy')} "
                f"groups={rec.get('result_groups')} "
                f"rows_scanned={rec.get('rows_scanned')} "
                f"digest={verified[t][:12]}")
        n_gaps = max(1, len(samples) - plan["clients"])
        late = [s["late_ms"] for s in samples if "late_ms" in s]
        say(f"window: attempted={len(samples)} failed={failed} elapsed_s="
            f"{report['elapsed_s']:.3f} client gap between answer and next "
            f"request {1000 * report['client_gap_s'] / n_gaps:.3f} ms mean"
            + (f", generator late by {stats.median(late):.3f} ms p50 "
               f"{max(late):.3f} ms max" if late else ""))
        say(f"device: memory_peak_bytes={mem_peak} hbm_ledger="
            f"{[int(r.get('hbm_bytes', 0)) for r in hbm]} "
            f"resident_bytes={[int(r.get('resident_bytes', 0)) for r in hbm]}")

        reduced = None
        if trace:
            t0 = time.perf_counter()
            path = xplane.find_xplane(trace_dir)
            reduced = xplane.reduce_file(
                path, {s["qid"]: s["template"] for s in samples})
            say(f"trace: {os.path.getsize(path)} bytes reduced in "
                f"{time.perf_counter() - t0:.1f}s; window_s="
                f"{reduced['window_s']:.3f} busy_s={reduced['busy_s']:.3f} "
                f"by device {reduced['busy_s_by_device']} annotated queries="
                f"{len(reduced['queries'])}")
            if trace_out:
                os.makedirs(trace_out, exist_ok=True)
                shutil.copy(path, trace_out)

        ctx = types.SimpleNamespace(
            samples=samples, records=records, traces=traces, trace=reduced,
            elapsed_s=report["elapsed_s"], failed=failed, setup_s=setup_s,
            templates=names, reference=reference, dataset=dataset,
            config=config, traffic=traffic, chips=chips, peaks=spec["peaks"])
        metrics = read_metrics(spec["bench_dir"],
                               spec["per_layer" if trace else "end_to_end"],
                               ctx)
        for name, m in metrics.items():
            say(f"metric {name} = {m['value']} {m['unit']}")
        result = {"correct": bool(correct), "attempted": len(samples),
                  "failed": failed, "metrics": metrics,
                  "memory_peak_bytes": int(mem_peak)}
        if reduced is not None:
            result["busy_s"] = reduced["busy_s"]
            result["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": xplane.top(reduced["op_s"]),
                "idle_gaps": xplane.top(reduced["idle_gap_s"])}
        return result
    finally:
        if server is not None:
            server.stop()
        elif engine is not None:
            engine.close()
        if client is not None:
            client.stop()
        shutil.rmtree(work_dir, ignore_errors=True)


def main(argv: list, root: str) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="CPU rehearsal: runs everything, never reports "
                         "correct, exits non-zero")
    ap.add_argument("--rehearse-rows", type=int, default=None,
                    help="another row count than the configuration's: a "
                         "rehearsal too, on any device")
    ap.add_argument("--control", default=None,
                    help="run one of the configuration's `controls` (a "
                         "lower precision) in the program's place")
    ap.add_argument("--trace-out", default=None,
                    help="copy the traced run's .xplane.pb here")
    args = ap.parse_args(argv)
    spec = load_cell(root, args.workload)
    chips = int(spec["config"]["chips"])
    if chips != int(spec["cell"]["chips"]):
        raise SystemExit("the cell's chips differ from its configuration's")
    rehearsal = args.allow_cpu or args.rehearse_rows is not None
    work_dir = make_work_dir()

    # the gate; the spawned children never come here
    import jax
    devices = jax.devices()
    dev = devices[0]
    refused = None
    if dev.platform != "tpu" and not args.allow_cpu:
        refused = (f"no accelerator: platform is {dev.platform!r}, not 'tpu' "
                   "(--allow-cpu is the rehearsal)")
    elif len(devices) != chips:
        refused = (f"the cell asks for {chips} chips, JAX reports "
                   f"{len(devices)}")
    if refused:
        say(refused)
        shutil.rmtree(work_dir, ignore_errors=True)
        return EXIT_NO_DEVICE
    spec["peaks"] = peaks_for(dev.device_kind) if dev.platform == "tpu" \
        else {"hbm_bytes_per_s": float("nan")}
    say(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; cell "
        f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace}" + (" REHEARSAL" if rehearsal else ""))

    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      rows=args.rehearse_rows, control=args.control,
                      trace_out=args.trace_out, work_dir=work_dir)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips,
              "memory_peak_bytes": result.pop("memory_peak_bytes")}
    for k in ("busy_s", "window_s"):
        if k in result:
            device[k] = result.pop(k)
    if rehearsal:
        say("rehearsal: would have reported correct="
            f"{result['correct']}; a rehearsal never does")
        result["correct"] = False
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": result["metrics"],
            "device": device}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    print(json.dumps(line), flush=True)
    return EXIT_REHEARSAL if rehearsal else 0
