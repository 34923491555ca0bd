"""From a profiler trace (`.xplane.pb`) to numbers.

`jax.profiler.ProfileData.from_file` reads the file with nothing but JAX:
planes (one per device, and the host's), their lines, and events with a start
and a duration in nanoseconds.  A device plane's "XLA Ops" line holds one
event per executed operation, and on this runtime its name is the whole HLO
instruction as text (`%jvp__.147 = (f32[1,8192]...) custom-call(bf16[8192,
1024]... %x, ...), custom_call_target="tpu_custom_call", ...`).  No stat
carries a Pallas kernel's function name, so a kernel is found by what its
instruction says: a `tpu_custom_call` over operands of the kernel's shapes.

Everything a metric reads from a trace goes through `reduce()`, checked
against the small recorded trace in `tests/data/`.
"""
from __future__ import annotations

import glob
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_TENSOR = re.compile(r"[a-z]+\d+\[\d+(?:,\d+)+\]")


def trace_dir(root):
    """A fixed directory inside the checkout (git-ignored), emptied before
    each traced run: a trace is tens of megabytes and only the last is
    read."""
    path = os.path.join(root, ".bench_out", "trace")
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)
    return path


def find_xplane(path):
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def load_events(xplane_path):
    """{"devices": {index: [(start_ns, dur_ns, name, text)]},
        "host": [(start_ns, dur_ns, name)]}

    ``name`` is the instruction's own name (`jvp__.147`), ``text`` the whole
    instruction as the event carries it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            events = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    text = ev.name
                    events.append((int(ev.start_ns), int(ev.duration_ns),
                                   text.split(" = ", 1)[0].lstrip("%"),
                                   text))
            devices[int(m.group(1))] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((int(ev.start_ns), int(ev.duration_ns),
                                     "%s/%s" % (line.name, ev.name)))
    return {"devices": devices, "host": host}


def busy_union(events):
    """Seconds covered by the union of the (start, duration) intervals, the
    merged busy intervals, and the gaps between them."""
    spans = sorted((s, s + d) for s, d, *_ in events if d > 0)
    merged = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    busy = sum(e - s for s, e in merged) / 1e9
    gaps = [(merged[i][1], merged[i + 1][0])
            for i in range(len(merged) - 1)
            if merged[i + 1][0] - merged[i][1] >= 1000]   # a microsecond
    return busy, merged, gaps


def kind_of(name, text=""):
    """An operation's kind: its HLO name without the trailing number (and
    the compiler's `.remat` mark), so that `fusion.12` and `fusion.97` add up.  Custom calls (the Pallas
    kernels) all carry names made from the trace's name stack (`jvp__`), so
    theirs adds the opcode and the first tensor operand's shape."""
    base = re.sub(r"(\.\d+|\.remat\d*)+$", "", name) or name
    rest = text.split(" = ", 1)[-1]
    op = _OPCODE.search(rest)
    if op is None or op.group(1) != "custom-call":
        return base
    shape = _TENSOR.search(rest, op.end())
    return "%s custom-call %s" % (base, shape.group(0) if shape else "")


def time_by_kind(events):
    out = {}
    for _, d, name, text in events:
        k = kind_of(name, text)
        out[k] = out.get(k, 0.0) + d / 1e9
    return out


def kernel_seconds(events, needles):
    """(seconds, calls) of the device operations whose instruction text
    holds every string of ``needles``."""
    total, calls = 0.0, 0
    for _, d, _name, text in events:
        if all(n in text for n in needles):
            total += d / 1e9
            calls += 1
    return total, calls


def _host_during(host, lo, hi):
    """The host event that covers most of the gap (lo, hi)."""
    best, best_cover = "nothing recorded on the host", 0
    for s, d, name in host:
        cover = min(hi, s + d) - max(lo, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(xplane_path, window_s=None, top=10):
    """The one reduction every trace metric reads.

    busy_s is averaged over the device planes that ran anything; window_s is
    the traced window as the host timed it (or, if not given, the span from
    the first to the last device operation)."""
    loaded = load_events(xplane_path)
    per_device = []
    all_events = []
    for idx in sorted(loaded["devices"]):
        events = loaded["devices"][idx]
        if not events:
            continue
        busy, merged, gaps = busy_union(events)
        per_device.append({"device": idx, "busy_s": busy, "merged": merged,
                           "gaps": gaps, "events": events})
        all_events += events
    if not per_device:
        return None
    first = per_device[0]
    span_s = (max(s + d for s, d, *_ in all_events)
              - min(s for s, *_ in all_events)) / 1e9
    by_kind = time_by_kind(first["events"])
    ops = sorted(by_kind.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:top]
    idle = [[_host_during(loaded["host"], lo, hi), (hi - lo) / 1e9]
            for lo, hi in longest]
    return {
        "busy_s": sum(p["busy_s"] for p in per_device) / len(per_device),
        "window_s": float(window_s) if window_s else span_s,
        "span_s": span_s,
        "n_devices": len(per_device),
        "n_ops": len(first["events"]),
        "events": first["events"],
        "breakdown": {"device_ops": [[k, v] for k, v in ops],
                      "idle_gaps": idle},
    }
