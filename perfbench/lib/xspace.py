"""The profiler's `.xplane.pb`, read from its wire format.

`jax.profiler.ProfileData` (what `lib/xplane.py` reads) gives an event's name,
time and own stats. What the program's `jax.named_scope`s leave in a capture
is elsewhere: in the stats of each "XLA Ops" event's METADATA (`tf_op`: JAX's
op_name path, `program_id`, `hlo_category`, `source`), which `ProfileData`
does not show, and in the optimised HLO of every program that ran, which the
capture keeps as an `HloProto` a program in the plane `/host:metadata`.
Neither TensorFlow's nor xprof's generated protos may be assumed where the
benchmark runs, so this is a reader of the protobuf wire format for the
messages it needs and no more:

    XSpace.planes; XPlane name, lines, event_metadata, stat_metadata;
    XLine name, timestamp_ns, events; XEvent metadata_id, offset_ps,
    duration_ps; XEventMetadata id, name, stats; XStat with its ref_value
    into stat_metadata; and of an HloProto the computations' instructions:
    name, opcode, metadata.op_name, id, operand ids, called computations.

It decodes the `/device:TPU:<n>` planes whole, of `/host:metadata` only
where each program's HloProto lies (`read_hlo` decodes one, when asked), of
the other `/host:` planes only the events whose
name the caller asks for (a query id: the annotation that places the device's
events on the axis of `lib/xplane.py`'s queries), and skips every other byte
by its length prefix.
"""

from __future__ import annotations

import re
import struct
from collections import namedtuple

from perfbench.lib.xplane import DEVICE_PLANE, OPS_LINE, short_name

MODULES_LINE = "XLA Modules"
HLO_PLANE = "/host:metadata"
_PROGRAM = re.compile(r"\((\d+)\)$")

# One "XLA Ops" event. `name` is `xplane.short_name`'s; `instruction` the HLO
# instruction's own name (`fusion.2`); `tf_op`, `category`, `source` the
# metadata's stats, "" where the compiler left none.
Op = namedtuple("Op", "program_id name instruction tf_op category source "
                      "start_s end_s")


def _varint(buf, i):
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, i
        shift += 7


def fields(buf, i, end):
    """(field number, wire type, value) of one message: an int for a varint,
    (start, end) for a length-delimited field, the raw bytes for a fixed."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """A map<int64, Message> entry -> (key, value's span)."""
    key, value = 0, None
    for f, _w, v in fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(buf, span, stat_names):
    """One XStat -> (its name, its value); a ref_value is resolved into the
    plane's stat_metadata, where a string stat's text is kept once."""
    name, value = None, None
    for f, w, v in fields(buf, *span):
        if f == 1:
            name = stat_names.get(v)
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = _text(buf, v)
        elif f == 6:
            value = v          # bytes: left as a span into the file
        elif f == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane_tables(buf, span):
    """-> (name, [line spans], {id: metadata span}, {stat id: stat name})"""
    name, lines, metas, stat_names = "", [], {}, {}
    for f, _w, v in fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            key, value = _map_entry(buf, v)
            metas[key] = value
        elif f == 5:
            key, value = _map_entry(buf, v)
            for f2, _w2, v2 in fields(buf, *value):
                if f2 == 2:
                    stat_names[key] = _text(buf, v2)
    return name, lines, metas, stat_names


def _event_metadata(buf, span, stat_names):
    """-> (name, {stat name: value})"""
    name, stats = "", {}
    for f, _w, v in fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 5:
            key, value = _stat(buf, v, stat_names)
            if key is not None:
                stats[key] = value
    return name, stats


def _line(buf, span):
    """-> (name, timestamp_ns, [event spans])"""
    name, t0, events = "", 0, []
    for f, _w, v in fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            t0 = v
        elif f == 4:
            events.append(v)
    return name, t0, events


def _event(buf, span):
    """-> (metadata_id, offset_ps, duration_ps). The one message a capture
    holds a hundred thousand of: its varints are read in place."""
    i, end = span
    meta = offset = duration = 0
    while i < end:
        key = buf[i]
        i += 1
        if key & 7 == 0 and key < 0x80:
            value = shift = 0
            while True:
                byte = buf[i]
                i += 1
                value |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            if key == 8:
                meta = value
            elif key == 16:
                offset = value
            elif key == 24:
                duration = value
        elif key & 7 == 2 and key < 0x80:
            n, i = _varint(buf, i)
            i += n
        else:   # a fixed-width or a high-numbered field: the general reader
            for f, _w, v in fields(buf, i - 1, end):
                if f == 1:
                    meta = v
                elif f == 2:
                    offset = v
                elif f == 3:
                    duration = v
            break
    return meta, offset, duration


def _times(t0_ns, offset_ps, duration_ps):
    start = t0_ns * 1e-9 + offset_ps * 1e-12
    return start, start + duration_ps * 1e-12


def _device_plane(buf, lines, metas, stat_names):
    ops, modules, known = [], [], {}
    for span in lines:
        name, t0, events = _line(buf, span)
        if name not in (OPS_LINE, MODULES_LINE):
            continue
        for ev in events:
            meta, offset, duration = _event(buf, ev)
            if meta not in known:
                known[meta] = _event_metadata(buf, metas[meta], stat_names) \
                    if meta in metas else ("", {})
            ev_name, stats = known[meta]
            start, end = _times(t0, offset, duration)
            if name == MODULES_LINE:
                m = _PROGRAM.search(ev_name)
                modules.append((int(m.group(1)) if m else None, ev_name,
                                start, end))
                continue
            ops.append(Op(
                stats.get("program_id"), short_name(ev_name),
                ev_name.partition(" = ")[0].lstrip("%"),
                stats.get("tf_op") or "", stats.get("hlo_category") or "",
                stats.get("source") or "", start, end))
    return {"ops": ops, "modules": modules}


def _host_events(buf, lines, metas, wanted):
    ids = {}
    for key, span in metas.items():
        for f, _w, v in fields(buf, *span):
            if f == 2:
                name = _text(buf, v)
                if name in wanted:
                    ids[key] = name
                break
    out = []
    if not ids:
        return out
    for span in lines:
        _name, t0, events = _line(buf, span)
        for ev in events:
            meta, offset, duration = _event(buf, ev)
            if meta in ids:
                out.append((ids[meta],) + _times(t0, offset, duration))
    return out


def _stand_in(buf, lines, metas, stat_names):
    """A capture of the CPU backend has no device plane: there the host
    events that carry an `hlo_op` stat stand in as device 0, as in
    `lib/xplane.py`, so that the join can be rehearsed and tested without a
    chip. Such an event has no `tf_op`; its stage comes from the program's
    HLO alone. Its numbers are never a device's."""
    ops, names = [], {}
    for span in lines:
        _name, t0, events = _line(buf, span)
        for ev in events:
            stats = dict(_stat(buf, v, stat_names)
                         for f, _w, v in fields(buf, *ev) if f == 4)
            meta, offset, duration = _event(buf, ev)
            if not duration or "hlo_op" not in stats:
                continue
            if meta not in names:
                names[meta] = _event_metadata(buf, metas[meta],
                                              stat_names)[0]
            start, end = _times(t0, offset, duration)
            ops.append(Op(stats.get("program_id"), names[meta],
                          stats["hlo_op"], "", "", "", start, end))
    return ops


def read(path: str, host_names=()) -> dict:
    """{"devices": {index: {"ops": [Op], "modules": [(program_id, name,
    start_s, end_s)]}}, "host": [(name, start_s, end_s)] of the host events
    named in `host_names`, "programs": {program_id: span of its HloProto}},
    and "buf", the file, which the spans point into. Times are seconds on
    the profiler's own axis, the one `ProfileData` gives."""
    with open(path, "rb") as f:
        buf = f.read()
    wanted = frozenset(host_names)
    devices, host_planes, hlo = {}, [], None
    for f, _w, span in fields(buf, 0, len(buf)):
        if f != 1:
            continue
        # a plane's name comes after its id: read it before anything else
        name = next((_text(buf, v) for f2, _w2, v in fields(buf, *span)
                     if f2 == 2), "")
        m = DEVICE_PLANE.match(name)
        if m:
            _n, lines, metas, stat_names = _plane_tables(buf, span)
            devices[int(m.group(1))] = _device_plane(buf, lines, metas,
                                                     stat_names)
        elif name == HLO_PLANE:
            hlo = span
        elif name.startswith("/host:"):
            host_planes.append(span)
    host, stand_in = [], []
    for span in host_planes if wanted or not devices else ():
        _n, lines, metas, stat_names = _plane_tables(buf, span)
        host.extend(_host_events(buf, lines, metas, wanted))
        if not devices:
            stand_in.extend(_stand_in(buf, lines, metas, stat_names))
    if stand_in:
        devices[0] = {"ops": stand_in, "modules": []}
    programs = {}
    if hlo is not None:
        _n, _lines, metas, stat_names = _plane_tables(buf, hlo)
        for span in metas.values():
            name, stats = _event_metadata(buf, span, stat_names)
            m = _PROGRAM.search(name)
            proto = next((v for v in stats.values()
                          if isinstance(v, tuple)), None)
            if m and proto is not None:
                programs[int(m.group(1))] = proto
    return {"devices": devices, "host": host, "programs": programs,
            "buf": buf}


# ------------------------------------------------------------ an HloProto

def _packed_ints(buf, span):
    out, i = [], span[0]
    while i < span[1]:
        v, i = _varint(buf, i)
        out.append(v)
    return out


def _instruction(buf, span):
    """One HloInstructionProto -> (id, name, opcode, op_name, source,
    operand ids, called computation ids)."""
    iid, name, opcode, op_name, source = 0, "", "", "", ""
    operands, called = [], []
    for f, w, v in fields(buf, *span):
        if f == 1:
            name = _text(buf, v)
        elif f == 2:
            opcode = _text(buf, v)
        elif f == 7:
            src_file, src_line = "", 0
            for f2, _w2, v2 in fields(buf, *v):
                if f2 == 2:
                    op_name = _text(buf, v2)
                elif f2 == 3:
                    src_file = _text(buf, v2)
                elif f2 == 4:
                    src_line = v2
            if src_file:
                source = f"{src_file}:{src_line}"
        elif f == 35:
            iid = v
        elif f == 36:
            operands.extend(_packed_ints(buf, v) if w == 2 else [v])
        elif f == 38:
            called.extend(_packed_ints(buf, v) if w == 2 else [v])
    return iid, name, opcode, op_name, source, operands, called


def read_hlo(buf, span) -> dict:
    """An HloProto -> {"name", "entry": its entry computation's id,
    "computations": {id: {"name", "root": an instruction id,
    "instructions": {id: {"name", "opcode", "op_name", "source",
    "operands": [ids], "called": [computation ids]}}}}}. Instruction ids are
    the module's own: unique across its computations."""
    module = next((v for f, w, v in fields(buf, *span) if f == 1 and w == 2),
                  (0, 0))
    out = {"name": "", "entry": None, "computations": {}}
    for f, w, v in fields(buf, *module):
        if f == 1 and w == 2:
            out["name"] = _text(buf, v)
        elif f == 6:
            out["entry"] = v
        elif f == 3:
            comp = {"name": "", "root": None, "instructions": {}}
            cid = 0
            for f2, _w2, v2 in fields(buf, *v):
                if f2 == 1:
                    comp["name"] = _text(buf, v2)
                elif f2 == 5:
                    cid = v2
                elif f2 == 6:
                    comp["root"] = v2
                elif f2 == 2:
                    iid, name, opcode, op_name, source, operands, called = \
                        _instruction(buf, v2)
                    comp["instructions"][iid] = {
                        "name": name, "opcode": opcode, "op_name": op_name,
                        "source": source, "operands": operands,
                        "called": called}
            out["computations"][cid] = comp
    return out
