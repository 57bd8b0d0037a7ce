"""The one general traffic generator: a traffic file's parameters and a seed
in, the requests of a window out. A new traffic mix is a new data file.

A traffic file (perfbench/traffic/<name>.json) holds:

    loop        "closed": each client sends its next request when the answer
                to the last has arrived. "open": requests are due at times
                fixed beforehand, whatever the system does.
    clients     closed: how many such clients. open: how many connections
                carry the requests (a request that finds all busy waits,
                and its latency counts from when it was due).
    think_ms    closed: pause between an answer and the next request.
    templates   "all", or {template: weight} with whole-number weights.
    order       "round_permutation": requests come in rounds; a round holds
                each template `weight` times, in an order drawn from the
                seed, so every seed sends the same work in another order.
                "weighted_draw": each request drawn independently by weight.
    stop        closed: "deadline" (default): no request starts after
                --seconds. "round_end": a client goes on to the end of the
                round it is in when --seconds have passed, so every run does
                whole rounds, the same work from every seed; the rate is
                taken over the time that took. For a mix whose round is
                long against the window.
    rate_per_s  open: offered rate, fixed in the file, never searched for.
    arrivals    open: "poisson" or "uniform" gaps.
    burst       open, optional: {"on_s", "off_s", "factor"}: the rate is
                multiplied by `factor` during the first `on_s` of every
                `on_s + off_s` seconds.
"""

from __future__ import annotations

import json
import os
import random

MAX_CLOSED_RATE_PER_S = 2000  # more than any one connection completes


def load(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    t.setdefault("think_ms", 0)
    t.setdefault("order", "round_permutation")
    if t["loop"] not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be closed or open")
    if t["loop"] == "open" and not t.get("rate_per_s"):
        raise ValueError(f"{path}: an open loop needs rate_per_s")
    return t


def path_of(root: str, name: str) -> str:
    p = os.path.join(root, "traffic", name + ".json")
    if not os.path.exists(p):
        raise SystemExit(f"no traffic file {p}")
    return p


def weights(traffic: dict, all_templates: list) -> dict:
    t = traffic["templates"]
    if t == "all":
        return {name: 1 for name in all_templates}
    unknown = [n for n in t if n not in all_templates]
    if unknown:
        raise ValueError(f"traffic names unknown templates {unknown}")
    return {n: int(w) for n, w in t.items() if int(w) > 0}


def _sequence(rng: random.Random, w: dict, order: str, n: int) -> list:
    """n template names by the file's ordering rule."""
    names = sorted(w)
    out: list = []
    if order == "round_permutation":
        one_round = [t for t in names for _ in range(w[t])]
        while len(out) < n:
            r = list(one_round)
            rng.shuffle(r)
            out.extend(r)
        return out[:n]
    if order == "weighted_draw":
        return rng.choices(names, [w[t] for t in names], k=n)
    raise ValueError(f"unknown order {order!r}")


def plan(traffic: dict, all_templates: list, seed: int,
         seconds: float) -> dict:
    """What the load generator process runs: for a closed loop one sequence
    of template names per client, longer than the window can use; for an
    open loop the list of (due second, template)."""
    w = weights(traffic, all_templates)
    clients = int(traffic["clients"])
    if traffic["loop"] == "closed":
        round_len = sum(w.values())
        n = int(seconds * MAX_CLOSED_RATE_PER_S) + 2 * round_len
        whole = traffic.get("stop", "deadline") == "round_end"
        if whole and traffic["order"] != "round_permutation":
            raise ValueError("stop=round_end needs order=round_permutation")
        return {"loop": "closed", "clients": clients,
                "round_len": round_len if whole else 0,
                "think_s": traffic["think_ms"] / 1000.0, "seconds": seconds,
                "sequences": [
                    _sequence(random.Random(seed * 1_000_003 + c), w,
                              traffic["order"], n) for c in range(clients)]}
    rng = random.Random(seed * 1_000_003 + 999_983)
    rate = float(traffic["rate_per_s"])
    burst = traffic.get("burst")
    due, t = [], 0.0
    while True:
        r = rate
        if burst:
            period = burst["on_s"] + burst["off_s"]
            r = rate * (burst["factor"] if t % period < burst["on_s"] else 1)
        t += rng.expovariate(r) if traffic.get("arrivals", "poisson") \
            == "poisson" else 1.0 / r
        if t >= seconds:
            break
        due.append(t)
    names = _sequence(rng, w, traffic["order"], len(due))
    return {"loop": "open", "clients": clients, "seconds": seconds,
            "due": list(zip(due, names))}
