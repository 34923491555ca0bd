"""The raw `XSpace` file behind `jax.profiler.ProfileData`.

`ProfileData` gives an event's name (the HLO text) and its timing; the names
the program puts on its work are on each event's *metadata* record, which it
does not expose: `tf_op` (the JAX name stack, `jit(step)/.../optimizer/mul`:
where a `jax.named_scope` lands), `hlo_category`, `display_name`.  This is a
decoder of the protobuf wire format for the few messages that carry them
(tensorflow/tsl/profiler/protobuf/xplane.proto), with nothing imported to
read them (TensorFlow's reader takes 10 s to load and must not be loaded
beside JAX on the chip):

    XSpace  1 planes
    XPlane  2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
    XLine   2 name, 3 timestamp_ns, 4 events
    XEvent  1 metadata_id, 2 offset_ps, 3 duration_ps
    XEventMetadata  1 id, 2 name, 4 display_name, 5 stats
    XStatMetadata   1 id, 2 name
    XStat   1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str, 6 bytes, 7 ref

Starts and durations come out in nanoseconds, rounded down as `ProfileData`
rounds them, so the two agree event for event (`tests/test_xplane_raw.py`).
"""
from __future__ import annotations

import re

from . import trace

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields (a stat's
    double) are passed over."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError("wire type %d in an XSpace" % wire)
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _named(buf):
    """(id, name) of an XStatMetadata."""
    ident, name = 0, ""
    for f, v in _fields(buf):
        if f == 1:
            ident = v
        elif f == 2:
            name = _text(v)
    return ident, name


def _event_metadata(buf, stat_names):
    meta = {"name": "", "display_name": ""}
    for f, v in _fields(buf):
        if f == 2:
            meta["name"] = _text(v)
        elif f == 4:
            meta["display_name"] = _text(v)
        elif f == 5:
            key, value = None, None
            for sf, sv in _fields(v):
                if sf == 1:
                    key = stat_names.get(sv)
                elif sf in (5, 6):
                    value = _text(sv)
                elif sf == 7:
                    value = stat_names.get(sv, "")
                elif sv is not None:
                    value = sv
            if key is not None:
                meta[key] = value
    return meta


def _line(buf, metadata, keep):
    name, t0_ns, events = "", 0, []
    raw = []
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            t0_ns = v
        elif f == 4:
            raw.append(v)
    for ev in raw:
        # the Python tracer writes an event for every call of every
        # function: one whose metadata is not wanted is left after its
        # first field
        if keep is not None and ev[0] == 0x08 \
                and _varint(ev, 1)[0] not in keep:
            continue
        mid = off_ps = dur_ps = 0
        for f, v in _fields(ev):
            if f == 1:
                mid = v
            elif f == 2:
                off_ps = v
            elif f == 3:
                dur_ps = v
        start_ps = t0_ns * 1000 + off_ps
        events.append((start_ps // 1000, dur_ps // 1000,
                       metadata.get(mid, {"name": ""})))
    return name, events


def planes(path, host_prefixes=None):
    """[{"name": plane, "lines": {line name: [(start_ns, dur_ns, meta)]}}],
    ``meta`` the event's metadata record as a dict: `name`, `display_name`
    and every stat it carries (`tf_op`, `hlo_category`, ...).  Lines of one
    name within a plane (one per host thread of that name) are joined.
    With ``host_prefixes``, a host plane keeps only the events whose name
    starts with one of them."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, lines, metas, stats = "", [], [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = _text(v)
            elif pf == 3:
                lines.append(v)
            elif pf == 4:
                metas.append(v)
            elif pf == 5:
                ident, stat = _named(_map_entry(v)[1])
                stats[ident] = stat
        metadata = {}
        for entry in metas:
            key, value = _map_entry(entry)
            metadata[key] = _event_metadata(value, stats)
        keep = None
        if host_prefixes is not None and name.startswith("/host:"):
            keep = {k for k, m in metadata.items()
                    if m["name"].startswith(tuple(host_prefixes))}
        by_line = {}
        for buf in lines:
            line, events = _line(buf, metadata, keep)
            by_line.setdefault(line, []).extend(events)
        out.append({"name": name, "lines": by_line})
    return out


#: the spans the program writes into the profiler's trace
PROGRAM_SPANS = ("sched.", "train_step")


def load(path):
    """What the readers read, of the first device that ran anything:

    {"ops":     [(start_ns, dur_ns, meta)]        the "XLA Ops" line
     "modules": [(start_ns, dur_ns, name)]        the "XLA Modules" line
     "spans":   [(start_ns, dur_ns, name, line)]  the program's own spans
                on the host planes, ``line`` the thread that wrote them}

    ``None`` where no device plane holds an operation (a CPU trace)."""
    device, spans = None, []
    for plane in planes(path, host_prefixes=PROGRAM_SPANS):
        if DEVICE_PLANE.match(plane["name"]):
            if device is None and plane["lines"].get(OPS_LINE):
                device = plane
        elif plane["name"].startswith("/host:"):
            for line, events in plane["lines"].items():
                # attributes given at the span's start ride in its name
                # (`sched.iteration#rows=3#`) on some runtimes
                spans += [(s, d, m["name"].split("#", 1)[0], line)
                          for s, d, m in events]
    if device is None:
        return None
    return {"ops": sorted(device["lines"][OPS_LINE], key=lambda e: e[0]),
            "modules": sorted(
                (s, d, m["name"])
                for s, d, m in device["lines"].get(MODULES_LINE, ())),
            "spans": sorted(spans)}


def of_run(run):
    """`load()` of a traced run's file, decoded once for all its readers;
    ``None`` where the run was not traced or no device ran."""
    if not hasattr(run, "_raw"):
        path = getattr(run, "_trace_path", None)
        path = trace.find_xplane(path) if path else None
        run._raw = load(path) if path else None
    return run._raw


def named(raw):
    """Whether the traced program writes its names into the trace at all:
    one of `PROGRAM_SPANS` on a host plane, or a serving program jitted
    under its own name.  Where it does, a reader that misses the name it
    looks for raises (a rename must not silence a metric); where it does
    not (a program from before the names), the reader has nothing to
    read."""
    return bool(raw["spans"]) or any(
        name.startswith("jit_serve_") for _, _, name in raw["modules"])


def programs_of(raw, programs):
    """The module events of the jitted functions whose names start with
    one of ``programs`` (`serve_decode_` finds `jit_serve_decode_b32(...)`)."""
    heads = tuple("jit_" + p for p in programs)
    return [m for m in raw["modules"] if m[2].startswith(heads)]


_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()*([^()]*)\)*$")


def scopes_of(tf_op):
    """The name stack of an operation as a list of scopes, with the
    wrappers that transformations put around one taken off:
    `jit(step)/transpose(jvp(pred))/pallas_call:` reads
    [`step`, `pred`, `pallas_call`]."""
    out = []
    for part in (tf_op or "").rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        out.append(m.group(1) if m else part)
    return out
