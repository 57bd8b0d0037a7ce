"""Device-side profiling: Chrome-trace export + on-demand XLA capture.

Two complementary views of where a query's time goes:

1. **Span-tree timelines** (`chrome_trace`): the host-side span trees
   obs.trace already records, exported in the Chrome Trace Event format
   (the JSON flavor Perfetto / chrome://tracing load natively). Spans
   become complete (`"ph": "X"`) events positioned by the trace root's
   wall-clock `started_at` plus each span's monotonic `start_ms` offset,
   so concurrent queries — and the legs of a fused shared-scan batch —
   lay out side by side on one timeline. Served by `GET /debug/profile`
   and banked by `bench.py --trace-out`.

2. **XLA op-level capture** (`start_capture`, and around it
   `capture_device_profile`): an on-demand `jax.profiler` trace window
   (`POST /debug/profile?ms=N`), started with the profiler's Python
   tracer OFF — that tracer records every Python call of the serving
   threads (over a million host events in five seconds) and makes the
   host path it is meant to observe several times slower. While a
   capture is live, QueryRunner._dispatch wraps each device call in
   `jax.profiler.TraceAnnotation(query_id)` so the XLA ops in the
   profile nest under the query that dispatched them, and every span
   of obs.trace enters an annotation of its own name (`annotate_span`),
   so the profile shows the host stages beside the ops they starve.
   Both cost one module-flag probe when no capture is active, and the
   whole feature degrades gracefully (a structured "unavailable"
   result, not an exception) where `jax.profiler` cannot run.

No new dependencies: the Chrome trace format is plain JSON, and the
jax.profiler import is deferred + guarded.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

# ----------------------------------------------------- chrome-trace export

# one pid for the whole engine process; tids are assigned per trace so
# concurrent queries stack as separate rows under one process group
_PID = os.getpid()


def _span_events(trace, tid: int) -> list:
    """One trace -> complete events. Every span of a trace shares the
    trace's tid (a query is one logical timeline): batch legs therefore
    land on the same row as their shared-scan parent, nested by ts/dur
    containment — exactly how Perfetto renders sub-slices."""
    base_us = trace.started_at * 1e6
    events = []
    for depth, s in trace.walk():
        if s.start_ms is None or s.duration_ms is None:
            continue  # never entered / still open: not placeable
        args = dict(s.attrs)
        if depth == 0:
            args.setdefault("query_id", trace.query_id)
        events.append({
            "name": s.name,
            "ph": "X",
            "cat": "query",
            "ts": base_us + s.start_ms * 1000.0,
            "dur": max(0.0, s.duration_ms * 1000.0),
            "pid": _PID,
            "tid": tid,
            **({"args": args} if args else {}),
        })
    return events


def chrome_trace(traces) -> dict:
    """Export completed Trace objects (obs.trace.Tracer rings) as a
    Chrome Trace Event JSON object: {"traceEvents": [...]} with `ts` /
    `dur` in microseconds — loads directly in Perfetto. Traces get one
    tid each, named by query_id via thread_name metadata events."""
    events = []
    for i, t in enumerate(traces):
        tid = i + 1
        events.append({
            "name": "thread_name", "ph": "M", "pid": _PID, "tid": tid,
            "args": {"name": f"query {getattr(t, 'query_id', tid)}"},
        })
        events.extend(_span_events(t, tid))
    return {
        "traceEvents": [{
            "name": "process_name", "ph": "M", "pid": _PID, "tid": 0,
            "args": {"name": "tpu_olap"},
        }] + events,
        "displayTimeUnit": "ms",
    }


# ------------------------------------------------- on-demand XLA capture

# serialize captures: jax.profiler supports one trace at a time, and the
# flag below is what makes per-dispatch annotation free when idle
_capture_lock = threading.Lock()
_capture_active = False

# bounds for POST /debug/profile?ms=N — a capture blocks one handler
# thread and profiler buffers grow with the window
CAPTURE_MS_DEFAULT = 1000
CAPTURE_MS_MAX = 60_000


def capture_active() -> bool:
    return _capture_active


# one shared no-op context: nullcontext is stateless/re-enterable, so
# every non-captured dispatch reuses this instance allocation-free
_NULL_CM = contextlib.nullcontext()


def annotate_dispatch(query_id: str | None):
    """Context manager wrapping one device dispatch. While an on-demand
    capture is live, it is jax.profiler.TraceAnnotation(query_id), so
    the XLA ops of this dispatch nest under their query in the captured
    profile; otherwise (the perpetual common case) it is a no-op that
    cost one module-flag probe."""
    if not _capture_active or query_id is None:
        return _NULL_CM
    try:
        import jax
        return jax.profiler.TraceAnnotation(str(query_id))
    except Exception:  # noqa: BLE001 — annotation is best-effort
        return _NULL_CM


def annotate_span(name: str, query_id: str | None):
    """The annotation a span of obs.trace enters while a capture is
    live: the span's name, with the query id as its argument — never a
    query id as the NAME, which is what marks a device call."""
    try:
        import jax
        if query_id is None:
            return jax.profiler.TraceAnnotation(name)
        return jax.profiler.TraceAnnotation(name, query_id=query_id)
    except Exception:  # noqa: BLE001 — annotation is best-effort
        return _NULL_CM


def start_capture(trace_dir: str):
    """Start a jax.profiler capture into `trace_dir` and return the
    function that stops it — the one way this process starts the
    profiler. The Python tracer is off (see the module docstring); the
    capture holds `_capture_lock` from start to stop (jax.profiler runs
    one trace at a time) and `_capture_active` is up in between. Raises
    RuntimeError while another capture is live, and whatever
    jax.profiler raises where it cannot start."""
    global _capture_active
    import jax
    if not _capture_lock.acquire(blocking=False):
        raise RuntimeError("capture already in progress")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    except BaseException:
        _capture_lock.release()
        raise
    _capture_active = True

    def stop():
        global _capture_active
        _capture_active = False
        try:
            jax.profiler.stop_trace()
        finally:
            _capture_lock.release()

    return stop


def capture_device_profile(ms: float, trace_dir: str | None = None) -> dict:
    """Run a capture for `ms` milliseconds (`start_capture`, a sleep,
    stop) and return a structured result:

        {"ok": true, "trace_dir": ..., "ms": N}            on success
        {"ok": false, "reason": ...}                       degraded

    The capture is synchronous (the caller's thread sleeps out the
    window) but the engine keeps serving — dispatches and spans that
    land inside the window are annotated. Exactly one capture runs at a
    time; a second request while one is live degrades with "capture
    already in progress" instead of corrupting the profiler's global
    state."""
    ms = max(1.0, min(float(ms), float(CAPTURE_MS_MAX)))
    if _capture_lock.locked():  # before a directory is made for nothing
        return {"ok": False, "reason": "capture already in progress"}
    if trace_dir is None:
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix="tpu_olap_profile_")
    try:
        stop = start_capture(trace_dir)
    except RuntimeError as e:  # a capture is live
        return {"ok": False, "reason": str(e)}
    except Exception as e:  # noqa: BLE001 — jax absent / backend refused
        return {"ok": False,
                "reason": f"jax.profiler.start_trace failed: {e}"}
    try:
        time.sleep(ms / 1000.0)
    finally:
        try:
            stop()
        except Exception as e:  # noqa: BLE001 — partial capture
            return {"ok": False, "trace_dir": trace_dir,
                    "reason": f"jax.profiler.stop_trace failed: {e}"}
    return {"ok": True, "trace_dir": trace_dir, "ms": ms}
