"""Device time under the names the program gave it.

The program wraps every stage of its device programs in a `jax.named_scope`
of one flat vocabulary (`tpu_olap/kernels/groupby.py::STAGES`). A scope lands
in an op's `op_name` path, which a capture keeps as the stat `tf_op` of the
op's event metadata; the innermost vocabulary name among the path's scopes
(its last part is the primitive's own name) is the op's stage. Ops that the
compiler makes itself carry no `op_name` at all: XLA:TPU's reduce-window
rewriter (a `cumsum` becomes a tree of reduce-windows with no metadata),
layout copies, asynchronous slices. Such an op takes the stage of the ops
that consume its result, read from the program's optimised HLO, which the
capture keeps too (`lib/xspace.py::read_hlo`): what the compiler inserts, it
inserts for the consumer. What neither names stays unnamed, and
`stage_named_share` says how much that is.

`reduce(ctx)` joins, once a run, every "XLA Ops" event of the traced window
with the query whose annotation began last before it (the rule and the
queries of `lib/xplane.py`), its program and its stage, by the event's self
time (`xplane._self_times`), the mean over the chips where there are several,
and prints the table: template, stage, device ms a query, share. A program
without the vocabulary, or a run without a capture, gives None.
"""

from __future__ import annotations

import bisect
import glob
import os
import tempfile
import time

from perfbench.lib import xplane, xspace

LAYOUT_CATEGORY = "data formatting"
UNNAMED = "(unnamed)"
OWN, INHERITED = "own", "inherited"
_BUSY_TOLERANCE = 1e-3   # ProfileData rounds an event's start to the ns


def _say(msg: str) -> None:
    print(f"[perfbench] {msg}", flush=True)


def vocabulary():
    """The program's stage names, or None where it has none to give."""
    try:
        from tpu_olap.kernels import groupby
    except ImportError:
        return None
    return getattr(groupby, "STAGES", None)


def stage_of(op_name: str, stages) -> str | None:
    """The innermost name of `stages` among the scopes of a JAX op_name
    path (`jit(fn)/sort/filter/and` -> `filter`). The path's last part is
    the primitive's name, which may spell like a stage (`sort`, `gather`)."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in stages:
            return part
    return None


def _most_common(names: list):
    return max(names, key=lambda n: (names.count(n), -names.index(n))) \
        if names else None


def _root_stage(comps: dict, called: list, stages):
    """The stage of a fusion that has no op_name of its own: its body's
    root's, or what most of a tuple root's operands have. Not the body's
    other instructions': a constant or a convert that the compiler shares
    keeps the name of whoever made it first."""
    for cid in called:
        body = comps.get(cid)
        root = body and body["instructions"].get(body["root"])
        if not root:
            continue
        stage = stage_of(root["op_name"], stages)
        if stage is None and root["opcode"] == "tuple":
            stage = _most_common([
                s for o in root["operands"]
                if o in body["instructions"] and (s := stage_of(
                    body["instructions"][o]["op_name"], stages))])
        if stage is not None:
            return stage
    return None


def program_stages(hlo: dict, stages) -> dict:
    """{instruction name: (stage, OWN | INHERITED)} of one program (what
    `xspace.read_hlo` returns). OWN: the instruction's op_name holds a
    scope, or it is a fusion whose body's root's does. INHERITED, by the
    first of these that finds one, until none does: the stage most of its
    users have (the compiler's reduce-windows, copies and slices are made
    for who reads them), else the one most of its operands have (the last
    op of a rewritten chain whose readers are the program's outputs), else,
    inside a loop's or a call's body, the calling instruction's."""
    comps = hlo["computations"]
    found, users, caller = {}, {}, {}
    for comp in comps.values():
        for iid, ins in comp["instructions"].items():
            stage = stage_of(ins["op_name"], stages)
            if stage is None and ins["opcode"] == "fusion":
                stage = _root_stage(comps, ins["called"], stages)
            if stage is not None:
                found[iid] = (stage, OWN)
            for op in ins["operands"]:
                users.setdefault(op, []).append(iid)
            if ins["opcode"] != "fusion":
                for cid in ins["called"]:
                    caller[cid] = iid
    def inherit(pick: str) -> bool:
        """One pass over the instructions that have no stage yet; users
        before their operands, so that a chain resolves in one pass."""
        moved = False
        for cid, comp in comps.items():
            for iid, ins in reversed(comp["instructions"].items()):
                if iid in found:
                    continue
                if pick == "users":
                    near = users.get(iid, [])
                elif pick == "operands":
                    near = ins["operands"]
                else:
                    near = [caller[cid]] if cid in caller else []
                stage = _most_common([found[n][0] for n in near
                                      if n in found])
                if stage is not None:
                    found[iid] = (stage, INHERITED)
                    moved = True
        return moved

    # whatever a pass finds, the users' rule is asked again first
    while any(inherit(pick) for pick in ("users", "operands", "caller")):
        pass
    return {ins["name"]: found[iid]
            for comp in comps.values()
            for iid, ins in comp["instructions"].items() if iid in found}


def find_capture() -> str | None:
    """The run's `.xplane.pb`. The harness keeps no path to it on `ctx`: its
    work directory is the parent of the `TPU_LOG_DIR` that `make_work_dir`
    sets, else the newest `perfbench_*/trace` under the temporary
    directory. (A run removes its work directory when it ends; a capture
    another run left behind does not hold this run's first annotation, and
    `reduce_space` then reports nothing.)"""
    dirs = []
    log_dir = os.environ.get("TPU_LOG_DIR")
    if log_dir:
        dirs.append(os.path.join(os.path.dirname(log_dir.rstrip(os.sep)),
                                 "trace"))
    dirs.extend(sorted(glob.glob(os.path.join(tempfile.gettempdir(),
                                              "perfbench_*", "trace")),
                       key=os.path.getmtime, reverse=True))
    for d in dirs:
        try:
            return xplane.find_xplane(d)
        except FileNotFoundError:
            continue
    return None


def reduce_space(space: dict, queries: list, busy_s_by_device: dict,
                 stages) -> dict | None:
    """The join proper, on what `xspace.read` returns. `queries` are
    `xplane.reduce_planes`'s (`qid`, `template`, `start_s` from the first
    annotation, `whole`); the capture's host events named by the first
    query's id give that annotation's place on the capture's own axis."""
    if not space["devices"] or not queries:
        return None
    first_qid = queries[0]["qid"]
    origin = min((s for name, s, _e in space["host"] if name == first_qid),
                 default=None)
    if origin is None:
        _say(f"stages: the capture holds no annotation {first_qid!r}")
        return None
    origin -= queries[0]["start_s"]
    starts = [q["start_s"] for q in queries]
    n_dev = len(space["devices"])
    resolved: dict = {}

    def stage(op):
        own = stage_of(op.tf_op.rpartition(":")[0], stages)
        if own is not None:
            return own, OWN
        if op.program_id not in resolved:
            span = space["programs"].get(op.program_id)
            resolved[op.program_id] = program_stages(
                xspace.read_hlo(space["buf"], span), stages) if span else {}
        return resolved[op.program_id].get(op.instruction, (None, None))

    per_query = [{"qid": q["qid"], "template": q["template"],
                  "whole": q["whole"], "device_s": 0.0, "stage_s": {},
                  "layout_copy_s": 0.0} for q in queries]
    rows, how_s, busy = {}, {OWN: 0.0, INHERITED: 0.0, None: 0.0}, {}
    for dev, plane in sorted(space["devices"].items()):
        ops = [op for op in plane["ops"] if op.end_s - origin > 0]
        events = [(i, max(op.start_s - origin, 0.0), op.end_s - origin)
                  for i, op in enumerate(ops)]
        busy[dev] = sum(e - s for s, e in
                        xplane._union([(s, e) for _i, s, e in events]))
        for i, start, self_s in xplane._self_times(events):
            op = ops[i]
            name, how = stage(op)
            how_s[how] += self_s / n_dev
            k = bisect.bisect_right(starts, start) - 1
            if k < 0:
                continue
            q = per_query[k]
            q["device_s"] += self_s / n_dev
            key = name or UNNAMED
            q["stage_s"][key] = q["stage_s"].get(key, 0.0) + self_s / n_dev
            if op.category == LAYOUT_CATEGORY:
                q["layout_copy_s"] += self_s / n_dev
            if q["whole"]:
                row = rows.setdefault(
                    (q["template"], key, op.program_id, op.name),
                    {"s": 0.0, "tf_op": op.tf_op, "source": op.source,
                     "category": op.category, "how": how})
                row["s"] += self_s / n_dev
    for dev, mine in busy.items():
        theirs = busy_s_by_device.get(dev)
        if theirs is None or abs(mine - theirs) > _BUSY_TOLERANCE * theirs:
            _say(f"stages: device {dev} is busy {mine} s by the capture's "
                 f"wire format and {theirs} s by lib/xplane.py: the events "
                 "are not on the queries' axis; no stage is reported")
            return None
    return {"queries": per_query, "rows": rows, "how_s": how_s,
            "busy_s_by_device": busy, "stages": tuple(stages)}


def _print_table(out: dict, trace_queries: list) -> None:
    whole = [q for q in out["queries"] if q["whole"]]
    by_template: dict = {}
    for q in whole:
        t = by_template.setdefault(q["template"], {"n": 0, "stage_s": {}})
        t["n"] += 1
        for name, s in q["stage_s"].items():
            t["stage_s"][name] = t["stage_s"].get(name, 0.0) + s
    by_xplane: dict = {}
    for q in trace_queries:
        if q["whole"]:
            by_xplane[q["template"]] = by_xplane.get(q["template"], 0.0) \
                + q["device_s"]
    _say("stages: template | stage | device ms a query | share of the "
         "template's device time")
    for template in sorted(by_template):
        t = by_template[template]
        total = sum(t["stage_s"].values())
        cells = [f"{name} {1000 * s / t['n']:.3f} "
                 f"({100 * s / total if total else 0:.1f}%)"
                 for name, s in sorted(t["stage_s"].items(),
                                       key=lambda kv: -kv[1])]
        _say(f"stages: {template} n={t['n']} | " + " | ".join(cells)
             + f" | every stage {1000 * total / t['n']:.3f}, lib/xplane.py "
             f"{1000 * by_xplane.get(template, 0.0) / t['n']:.3f}")
    n_of = {t: v["n"] for t, v in by_template.items()}
    _say("stages: the ten largest (template, stage, op): device ms a query "
         "| how the stage is known | tf_op's tail | hlo_category | source")
    for (template, name, program, op), row in sorted(
            out["rows"].items(), key=lambda kv: -kv[1]["s"])[:10]:
        tail = "/".join(row["tf_op"].rstrip(":").split("/")[-3:])
        _say(f"stages:   {template} | {name} | {op} (program {program}) "
             f"{1000 * row['s'] / n_of[template]:.3f} | {row['how']} | "
             f"{tail or '-'} | {row['category'] or '-'} | "
             f"{row['source'] or '-'}")
    how = out["how_s"]
    total = sum(how.values())
    if total > 0:
        own, inherited, none = (100 * how[k] / total
                                for k in (OWN, INHERITED, None))
        _say(f"stages: of {total:.3f} s of device self time (mean of "
             f"{len(out['busy_s_by_device'])} chips) {own:.2f}% carries a "
             f"stage in its own op_name, {inherited:.2f}% takes its "
             f"consumers', {none:.2f}% has none")
    unnamed = sorted(((k, r) for k, r in out["rows"].items()
                      if k[1] == UNNAMED), key=lambda kv: -kv[1]["s"])[:5]
    for (template, _n, _program, op), row in unnamed:
        _say(f"stages:   unnamed: {template} {op} {row['s']:.4f} s | "
             f"tf_op {row['tf_op'] or '-'} | {row['category'] or '-'}")


def reduce(ctx) -> dict | None:
    """The run's reduction, made once and kept on `ctx`."""
    if hasattr(ctx, "_stages"):
        return ctx._stages
    ctx._stages = None
    stages = vocabulary()
    if ctx.trace is None or not ctx.trace["queries"]:
        return None
    if stages is None:
        _say("stages: the program has no stage vocabulary "
             "(tpu_olap.kernels.groupby.STAGES): nothing to read")
        return None
    path = find_capture()
    if path is None:
        _say("stages: no capture found beside TPU_LOG_DIR or under "
             f"{tempfile.gettempdir()}")
        return None
    t0 = time.perf_counter()
    space = xspace.read(path, host_names=(ctx.trace["queries"][0]["qid"],))
    out = reduce_space(space, ctx.trace["queries"],
                       ctx.trace["busy_s_by_device"], stages)
    if out is not None:
        _print_table(out, ctx.trace["queries"])
        out["seconds"] = time.perf_counter() - t0
        _say(f"stages: {os.path.getsize(path)} bytes read a second time and "
             f"joined in {out['seconds']:.2f} s")
    ctx._stages = out
    return out


# ------------------------------------------------- what the metrics read

def sparse_ms_per_query(ctx, stage: str):
    """Device self time under `stage` per query of the traced window's
    whole queries whose record says `reduce_path: sparse`."""
    out = reduce(ctx)
    if out is None or stage not in out["stages"]:
        return None
    took = [q["stage_s"].get(stage, 0.0) for q in out["queries"]
            if q["whole"] and (ctx.records.get(q["qid"]) or {})
            .get("reduce_path") == "sparse"]
    return 1000.0 * sum(took) / len(took) if took else None
