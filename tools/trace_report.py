#!/usr/bin/env python
"""Render a request-tracing span stream (mxnet_tpu.tracing records riding
the telemetry JSONL sink) into per-request waterfalls, a p99
ttft/e2e-attribution table, and a Chrome/Perfetto ``trace_event`` export.

    python tools/trace_report.py bench_results/telemetry_serve.jsonl
    python tools/trace_report.py stream.jsonl --trace 17
    python tools/trace_report.py stream.jsonl --chrome trace.json

The export opens in chrome://tracing or https://ui.perfetto.dev: one
"process" per trace (request), one "thread" per replica the request
touched, so a handed-off request shows its prefill-role and decode-role
timelines stacked under one request id.

Stdlib-only (like tools/telemetry_report.py): the tool must render
streams from machines that never import the framework.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# The rendered phase taxonomy — mxlint's span-phase-drift rule checks
# every phase name emitted by the framework against this tuple (and
# against docs/observability.md), the telemetry-unrendered pattern.
RENDERED_PHASES = (
    "request", "queue_wait", "prefill", "replay", "restore_wait",
    "handoff_wait", "decode", "prefill_chunk", "handoff_pack",
    "handoff_land", "megastep", "host_sweep", "spec_round", "iteration",
    "gateway_send")

# interval phases: at most one open per trace at a time; their per-trace
# totals are the serve.attr.* decomposition and must tile ~all of e2e
INTERVAL_PHASES = ("queue_wait", "prefill", "replay", "restore_wait",
                   "handoff_wait", "decode")
# phases that end at (or before) the first token: the ttft decomposition
TTFT_PHASES = ("queue_wait", "prefill", "replay", "restore_wait",
               "handoff_wait")
LEAF_PHASES = ("prefill_chunk", "handoff_pack", "handoff_land",
               "megastep", "host_sweep", "spec_round", "iteration",
               "gateway_send")

BAR_WIDTH = 36


def load(path):
    """(spans, recorder_dumps) from a JSONL stream, rotated siblings
    (`path.K` ... `path.1`, oldest first) included when present."""
    paths = []
    for k in range(16, 0, -1):
        p = "%s.%d" % (path, k)
        if os.path.exists(p):
            paths.append(p)
    paths.append(path)
    spans, recorders = [], []
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn tail line from a crashed run
                t = rec.get("type")
                if t == "span":
                    spans.append(rec)
                elif t == "flight_recorder":
                    recorders.append(rec)
    return spans, recorders


def by_trace(spans):
    """{trace id: [span, ...]} sorted by start time; the replica-scoped
    spans (megastep / host_sweep / spec_round / iteration) live under
    key 0."""
    traces = {}
    for s in spans:
        traces.setdefault(s.get("trace", 0), []).append(s)
    for lst in traces.values():
        lst.sort(key=lambda s: (s.get("t0", 0.0), s.get("sid", 0)))
    return traces


def _root(trace_spans):
    for s in trace_spans:
        if s.get("phase") == "request":
            return s
    return None


def _bar(t0, t1, lo, hi):
    span = max(hi - lo, 1e-9)
    a = int(round(BAR_WIDTH * (t0 - lo) / span))
    b = int(round(BAR_WIDTH * (t1 - lo) / span))
    a = min(max(a, 0), BAR_WIDTH)
    b = min(max(b, a + 1), BAR_WIDTH)
    return " " * a + "#" * (b - a) + " " * (BAR_WIDTH - b)


def waterfall(trace, trace_spans):
    """One request's timeline as indented bars on a shared time axis."""
    root = _root(trace_spans)
    lo = min(s["t0"] for s in trace_spans)
    hi = max(s["t1"] for s in trace_spans)
    lines = []
    head = "trace %s" % trace
    if root is not None:
        attrs = root.get("attrs") or {}
        head += "  %s  e2e %.1fms" % (
            "ok" if attrs.get("ok") else
            "FAIL(%s)" % attrs.get("error", "?"), root.get("ms", 0.0))
        if attrs.get("ttft_ms") is not None:
            head += "  ttft %.1fms" % attrs["ttft_ms"]
        if attrs.get("n_tokens") is not None:
            head += "  tokens %d" % attrs["n_tokens"]
    replicas = []
    for s in trace_spans:
        r = s.get("replica")
        if r and r not in replicas:
            replicas.append(r)
    if replicas:
        head += "  replicas: %s" % " -> ".join(str(r) for r in replicas)
    lines.append(head)
    for s in trace_spans:
        ph = s.get("phase", "?")
        if ph == "request":
            continue
        indent = "    " if ph in LEAF_PHASES else "  "
        lines.append("%s%-14s %-12s %9.2fms |%s|" % (
            indent, ph, s.get("replica") or "-", s.get("ms", 0.0),
            _bar(s["t0"], s["t1"], lo, hi)))
    return "\n".join(lines)


def _pct(vals, q):
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(len(vals) * q))]


def attribution(spans):
    """Fold every completed root span's per-phase totals into the
    p50/p99 attribution table data: {phase: {n, mean, p50, p99}} plus
    `ttft` and `e2e` rows and the attributed-fraction check."""
    cols = {}
    e2e, ttft = [], []
    n_ok = 0
    for s in spans:
        if s.get("phase") != "request":
            continue
        attrs = s.get("attrs") or {}
        if not attrs.get("ok"):
            continue
        n_ok += 1
        e2e.append(s.get("ms", 0.0))
        if attrs.get("ttft_ms") is not None:
            ttft.append(attrs["ttft_ms"])
        for ph in INTERVAL_PHASES:
            v = attrs.get("%s_ms" % ph)
            if v is not None:
                cols.setdefault(ph, []).append(v)
    out = {"n": n_ok}
    for name, vals in [("e2e", e2e), ("ttft", ttft)] + \
            [(ph, cols.get(ph, [])) for ph in INTERVAL_PHASES]:
        if not vals:
            continue
        out[name] = {"n": len(vals),
                     "mean": sum(vals) / len(vals),
                     "p50": _pct(vals, 0.5),
                     "p99": _pct(vals, 0.99)}
    if e2e and cols:
        attributed = sum(sum(v) for v in cols.values())
        out["attributed_frac"] = round(attributed / max(sum(e2e), 1e-9),
                                       4)
    return out


def format_attribution(att):
    lines = ["p99 attribution (%d completed requests):" % att.get("n", 0)]
    lines.append("  %-14s %6s %10s %10s %10s" % (
        "phase", "n", "mean_ms", "p50_ms", "p99_ms"))
    for name in ("e2e", "ttft") + INTERVAL_PHASES:
        row = att.get(name)
        if not row:
            continue
        tag = name if name not in TTFT_PHASES else name + " *"
        lines.append("  %-14s %6d %10.2f %10.2f %10.2f" % (
            tag, row["n"], row["mean"], row["p50"], row["p99"]))
    if "attributed_frac" in att:
        lines.append("  phases cover %.1f%% of e2e "
                     "(* = phases charged to ttft)"
                     % (100.0 * att["attributed_frac"]))
    return "\n".join(lines)


def chrome_trace(spans):
    """The span stream as Chrome/Perfetto ``trace_event`` JSON: complete
    ("ph": "X") events, one pid per trace, one tid per replica within
    it, timestamps rebased to the stream's earliest span (us)."""
    if not spans:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    base = min(s["t0"] for s in spans)
    events = []
    tids = {}   # (trace, replica) -> tid
    named = set()
    for s in spans:
        trace = int(s.get("trace", 0) or 0)
        replica = str(s.get("replica") or "-")
        key = (trace, replica)
        if key not in tids:
            tids[key] = len([k for k in tids if k[0] == trace]) + 1
        tid = tids[key]
        if trace not in named:
            named.add(trace)
            events.append({"name": "process_name", "ph": "M",
                           "pid": trace, "tid": 0,
                           "args": {"name": "request %d" % trace
                                    if trace else "replica-scope"}})
        if key not in named:
            named.add(key)
            events.append({"name": "thread_name", "ph": "M",
                           "pid": trace, "tid": tid,
                           "args": {"name": replica}})
        ev = {"name": s.get("phase", "?"), "cat": "span", "ph": "X",
              "ts": round(1e6 * (s["t0"] - base), 1),
              "dur": round(1e6 * max(s["t1"] - s["t0"], 0.0), 1),
              "pid": trace, "tid": tid,
              "args": {"sid": s.get("sid"), "parent": s.get("parent")}}
        attrs = s.get("attrs")
        if attrs:
            ev["args"].update(attrs)
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def format_recorders(recorders):
    lines = ["flight recorder dumps: %d" % len(recorders)]
    for r in recorders:
        lines.append("  %-12s %-18s tail=%d cap=%d" % (
            r.get("replica", "?"), r.get("reason", "?"),
            r.get("n", 0), r.get("ring_cap", 0)))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="telemetry JSONL stream with span "
                                 "records")
    ap.add_argument("--trace", type=int, default=None,
                    help="render only this trace id's waterfall")
    ap.add_argument("--limit", type=int, default=8,
                    help="waterfalls for at most the last N traces "
                         "(0 = all)")
    ap.add_argument("--chrome", metavar="OUT",
                    help="write Chrome/Perfetto trace_event JSON to OUT")
    ap.add_argument("--json", action="store_true",
                    help="print the attribution table as JSON")
    args = ap.parse_args(argv)
    spans, recorders = load(args.path)
    if not spans:
        print("no span records in %s (tracing off, or no sink attached?)"
              % args.path, file=sys.stderr)
        return 1
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(chrome_trace(spans), f)
        print("wrote %d trace events to %s"
              % (len(chrome_trace(spans)["traceEvents"]), args.chrome),
              file=sys.stderr)  # status, not payload: --json owns stdout
    att = attribution(spans)
    if args.json:
        print(json.dumps(att, default=str))
        return 0
    traces = by_trace(spans)
    ids = [t for t in traces if t and (args.trace is None
                                       or t == args.trace)]
    ids.sort()
    if args.limit and args.trace is None:
        ids = ids[-args.limit:]
    for t in ids:
        print(waterfall(t, traces[t]))
        print()
    print(format_attribution(att))
    if recorders:
        print()
        print(format_recorders(recorders))
    return 0


if __name__ == "__main__":
    sys.exit(main())
