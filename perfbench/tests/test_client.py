"""The load generator against a stub server: closed and open loops."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from perfbench.lib import client, traffic


class _Stub(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    delay_s = 0.002
    n = 0

    def log_message(self, *a):
        pass

    def do_POST(self):
        q = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        time.sleep(self.delay_s)
        type(self).n += 1
        body = json.dumps({"columns": ["x"],
                           "rows": [{"x": q["query"]}]}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.send_header("X-Query-Id", f"id-{type(self).n}")
        self.end_headers()
        self.wfile.write(body)


@pytest.fixture()
def stub():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield httpd.server_address
    httpd.shutdown()
    httpd.server_close()
    t.join(timeout=5)


SQLS = {"a": "select a", "b": "select b", "c": "select c"}


def test_closed_loop_does_whole_rounds_and_hashes_answers(stub):
    t = {"loop": "closed", "clients": 2, "think_ms": 0, "templates": "all",
         "order": "round_permutation", "stop": "round_end"}
    plan = traffic.plan(t, sorted(SQLS), 9, 0.3)
    rep = client.run_window(*stub, plan, SQLS)
    assert rep["clients_reported"] == 2 and rep["elapsed_s"] >= 0.3
    for c in (0, 1):
        mine = [s for s in rep["samples"] if s["client"] == c]
        assert len(mine) % 3 == 0 and len(mine) >= 3
        assert [s["template"] for s in mine] == \
            plan["sequences"][c][:len(mine)]
    by_t = {s["template"]: s["digest"] for s in rep["samples"]}
    assert len(set(by_t.values())) == 3       # one digest per template
    assert all(s["status"] == 200 and s["ms"] >= 2.0 and s["qid"]
               for s in rep["samples"])


def test_open_loop_sends_every_due_request_and_reports_lateness(stub):
    t = {"loop": "open", "clients": 3, "templates": {"a": 1, "b": 1},
         "order": "weighted_draw", "rate_per_s": 100.0, "arrivals": "uniform"}
    plan = traffic.plan(t, sorted(SQLS), 4, 0.5)
    rep = client.run_window(*stub, plan, SQLS)
    assert len(rep["samples"]) == len(plan["due"]) == 49
    assert all("late_ms" in s and s["late_ms"] > -1.0 for s in rep["samples"])
    # latency counts from when the request was due, not from when it left
    assert all(s["ms"] >= 2.0 for s in rep["samples"])
