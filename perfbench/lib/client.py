"""The load generator: a process of its own, spawned before the parent
touches JAX, that imports nothing but the standard library.

It holds the client's clock. A request's latency runs from the write of the
request to the last byte of the answer (closed loop), or from the time the
request was due (open loop, so a stall's cost to later requests counts).
Bodies are hashed here, so the parent gets digests and not megabytes.

Protocol over a multiprocessing pipe, one reply per command:
    ("requests", host, port, [(template, sql), ...]) -> [sample + "body"]
    ("window", host, port, plan, {template: sql})     -> window report
    ("stop",)                                          -> process exits
"""

from __future__ import annotations

import hashlib
import http.client
import json
import queue
import threading
import time

TIMEOUT_S = 600


def _post(conn, sql: str):
    """-> (status, body, X-Query-Id, seconds from write to last byte)."""
    payload = json.dumps({"query": sql})
    t0 = time.perf_counter()
    conn.request("POST", "/sql", payload,
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    body = resp.read()
    return (resp.status, body, resp.getheader("X-Query-Id"),
            time.perf_counter() - t0)


def _sample(template, status, body, qid, seconds, **more) -> dict:
    return {"template": template, "status": status, "qid": qid,
            "ms": seconds * 1000.0, "bytes": len(body),
            "digest": hashlib.sha256(body).hexdigest(), **more}


def run_requests(host, port, requests) -> list:
    conn = http.client.HTTPConnection(host, port, timeout=TIMEOUT_S)
    out = []
    try:
        for template, sql in requests:
            status, body, qid, s = _post(conn, sql)
            sample = _sample(template, status, body, qid, s)
            sample["body"] = body
            out.append(sample)
    finally:
        conn.close()
    return out


def _closed_client(host, port, seq, sqls, think_s, start, seconds, idx, out,
                   round_len=0):
    conn = http.client.HTTPConnection(host, port, timeout=TIMEOUT_S)
    samples, gap_s, last_end = [], 0.0, None
    try:
        conn.connect()
        for i, template in enumerate(seq):
            now = time.perf_counter()
            # past the deadline: stop, or with whole rounds (round_len)
            # finish the round that is under way first
            if now - start >= seconds and (
                    not round_len or i % round_len == 0):
                break
            if last_end is not None:
                # answer's last byte to the next request's write, less the
                # pause the traffic file asks for: the client's own cost
                gap_s += now - last_end - think_s
            status, body, qid, s = _post(conn, sqls[template])
            last_end = now + s
            samples.append(_sample(template, status, body, qid, s,
                                   t0=now - start, client=idx))
            if think_s:
                time.sleep(think_s)
    finally:
        conn.close()
    out[idx] = (samples, gap_s)


def _open_worker(host, port, jobs, sqls, start, idx, out):
    conn = http.client.HTTPConnection(host, port, timeout=TIMEOUT_S)
    samples = []
    try:
        conn.connect()
        while True:
            job = jobs.get()
            if job is None:
                break
            due, template = job
            sent = time.perf_counter() - start
            status, body, qid, _s = _post(conn, sqls[template])
            end = time.perf_counter() - start
            samples.append(_sample(template, status, body, qid, end - due,
                                   t0=due, late_ms=(sent - due) * 1000.0,
                                   client=idx))
    finally:
        conn.close()
    out[idx] = (samples, 0.0)


def run_window(host, port, plan, sqls) -> dict:
    n = plan["clients"]
    out: dict = {}
    start = time.perf_counter()
    if plan["loop"] == "closed":
        threads = [threading.Thread(
            target=_closed_client,
            args=(host, port, plan["sequences"][i], sqls, plan["think_s"],
                  start, plan["seconds"], i, out, plan.get("round_len", 0)))
            for i in range(n)]
        for t in threads:
            t.start()
    else:
        jobs: queue.Queue = queue.Queue()
        threads = [threading.Thread(
            target=_open_worker, args=(host, port, jobs, sqls, start, i, out))
            for i in range(n)]
        for t in threads:
            t.start()
        for due, template in plan["due"]:
            wait = due - (time.perf_counter() - start)
            if wait > 0:
                time.sleep(wait)
            jobs.put((due, template))
        for _ in threads:
            jobs.put(None)
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - start
    samples = sorted((s for i in out for s in out[i][0]),
                     key=lambda s: s["t0"])
    return {"samples": samples, "elapsed_s": elapsed,
            "client_gap_s": sum(out[i][1] for i in out),
            "clients_reported": len(out)}


def serve(pipe) -> None:
    """The process's main loop."""
    while True:
        cmd = pipe.recv()
        try:
            if cmd[0] == "stop":
                pipe.send(("ok", None))
                return
            if cmd[0] == "requests":
                pipe.send(("ok", run_requests(*cmd[1:])))
            elif cmd[0] == "window":
                pipe.send(("ok", run_window(*cmd[1:])))
            else:
                pipe.send(("error", f"unknown command {cmd[0]!r}"))
        except Exception as e:  # noqa: BLE001 - report to the parent, go on
            import traceback
            pipe.send(("error", f"{e!r}\n{traceback.format_exc()}"))
