"""Distributed request tracing (ISSUE-18): span timelines, SLO
attribution, and the crash flight recorder.

Contracts under test:

1. `Tracer` phase machine: interval phases tile the trace (close-open
   transitions), leaf spans parent under the current interval, `finish`
   folds the per-phase totals into the root attrs AND the
   ``serve.attr.*`` histograms; the recorder ring is bounded and
   `dump()` snapshots it into one atomic record.
2. Engine integration: every completed request exports one connected
   span tree (no orphan parents) whose interval phases cover ~all of
   e2e, and no open roots leak after the drain.
3. Trace continuity: ONE trace id crosses the disaggregated
   prefill→decode handoff (spans on both replicas, `handoff_pack` /
   `handoff_land` leaves), survives journal migration off a crashed
   replica (the `replay` phase rides the original trace), and survives
   preemption-replay — with stream positions matching the span tree's
   root accounting (`n_tokens` / `published`).
4. Flight recorder roads: `engine_crash` chaos dumps the dying
   replica's ring (`scheduler_death`), `handoff_fail` chaos dumps the
   source's (`handoff_fail`) — both as well-formed single records.
5. Kill-switch: `MXNET_SERVE_TRACING=0` emits ZERO tracing records,
   never builds the tracer, keeps the retrace watchdog silent, and the
   tokens are bit-for-bit the traced leg's.
6. Satellite-3 regression: `serve.handoff_wait_ms` (stamped at pack
   START since this PR) agrees with the span-derived
   `serve.attr.handoff_wait_ms` within tolerance.
7. Telemetry JSONL sink rotation: `MXNET_TELEMETRY_MAX_MB` rotates
   shift-style on record boundaries keeping `MXNET_TELEMETRY_KEEP`
   files, every file valid JSONL, no records lost.
8. tools/trace_report.py renders waterfalls + the attribution table
   and writes valid Chrome ``trace_event`` JSON.
9. mxlint span-phase-drift: an unknown phase at a call site, an
   undocumented/unrendered PHASES entry, and the clean fixture.
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from mxnet_tpu import chaos, telemetry, tracing
from mxnet_tpu.analysis import run as lint_run
from mxnet_tpu.serving import (ReplicaRouter, ServingEngine,
                               TransformerKVModel, ServeError)
from mxnet_tpu.serving.engine import SCHED_PHASES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_REPORT = os.path.join(REPO, "tools", "trace_report.py")

V, S, L, H, E = 61, 32, 2, 2, 32


@pytest.fixture
def model_and_params():
    model = TransformerKVModel(V, S, num_layers=L, num_heads=H, num_embed=E)
    return model, model.init_params(np.random.RandomState(7))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("MXNET_CHAOS", "MXNET_SERVE_TRACING", "MXNET_SERVE_DISAGG",
                "MXNET_SERVE_PREFILL_REPLICAS", "MXNET_TELEMETRY_MAX_MB",
                "MXNET_TELEMETRY_KEEP", "MXNET_TRACE_RING"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("MXNET_CHAOS_SEED", "0")
    telemetry.reset()
    tracing.reset()
    chaos.reset()
    yield
    telemetry.reset()
    tracing.reset()
    chaos.reset()


def _sink():
    return telemetry.add_sink(telemetry.MemorySink())


def _engine(model, params, name=None, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("sampling", False)
    eng = ServingEngine(model, params, **kw)
    if name is not None:
        eng.name = name
        eng._gauge = "serve.%s." % name
    return eng


def _fleet(model, params, n, **kw):
    return [_engine(model, params, name="replica%d" % i, **kw)
            for i in range(n)]


def _run_router(router, submits, timeout=300):
    router.start()
    try:
        reqs = [router.submit(p, **kw) for p, kw in submits]
        for r in reqs:
            try:
                r.result(timeout=timeout)
            except ServeError:
                pass
    finally:
        router.stop()
    return reqs


def _spans(sink):
    """{trace: [span, ...]} from a MemorySink, request traces only."""
    by_trace = tracing.spans(sink.records)
    by_trace.pop(0, None)   # replica-scoped megastep/sweep spans
    return by_trace


def _assert_connected(trace_spans):
    """No orphans: every non-root parent sid resolves inside the trace."""
    sids = {s["sid"] for s in trace_spans}
    for s in trace_spans:
        if s.get("parent") in (0, None):
            continue
        assert s["parent"] in sids, \
            "orphan span %s (parent %s unresolved)" % (s, sorted(sids))


def _root_of(trace_spans):
    roots = [s for s in trace_spans if s["phase"] == "request"]
    assert len(roots) == 1, "want exactly one root, got %d" % len(roots)
    return roots[0]


def _attributed_frac(root):
    attrs = root.get("attrs") or {}
    attributed = sum(v for k, v in attrs.items()
                     if k.endswith("_ms") and
                     k not in ("ttft_ms", "e2e_ms") and
                     isinstance(v, (int, float)))
    return attributed / max(root["ms"], 1e-9)


# ---------------------------------------------------------------------------
# 1. the phase machine + flight-recorder ring (unit)
# ---------------------------------------------------------------------------

def test_phase_transitions_tile_and_attribute():
    sink = _sink()
    t0 = time.perf_counter()
    tracing.open_trace(7, "r0", t=t0)
    tracing.phase(7, "queue_wait", "r0", t=t0)
    tracing.phase(7, "prefill", "r0", t=t0 + 0.010)
    tracing.add_span(7, "prefill_chunk", "r0", t0 + 0.011, t0 + 0.014,
                     tokens=8)
    tracing.phase(7, "decode", "r0", t=t0 + 0.030)
    rec = tracing.finish(7, ttft_ms=30.0, e2e_ms=90.0, n_tokens=4)

    spans = [r for r in sink.records if r.get("type") == "span"]
    phases = [s["phase"] for s in spans]
    # intervals close in transition order; the leaf lands mid-prefill
    assert phases == ["queue_wait", "prefill_chunk", "prefill",
                      "decode", "request"]
    _assert_connected(spans)
    root = _root_of(spans)
    assert rec == root
    by_phase = {s["phase"]: s for s in spans}
    # the leaf parents under the open prefill interval, intervals under
    # the root
    assert by_phase["prefill_chunk"]["parent"] == by_phase["prefill"]["sid"]
    assert by_phase["queue_wait"]["parent"] == root["sid"]
    # per-phase totals on the root, ~10ms queue / 20ms prefill
    attrs = root["attrs"]
    assert attrs["ok"] is True
    assert attrs["queue_wait_ms"] == pytest.approx(10.0, abs=0.5)
    assert attrs["prefill_ms"] == pytest.approx(20.0, abs=0.5)
    assert attrs["n_tokens"] == 4
    # the SLO attribution histograms got the same numbers
    reg = telemetry.registry()
    assert reg._hists["serve.attr.queue_wait_ms"][0] == \
        pytest.approx(10.0, abs=0.5)
    assert reg._hists["serve.attr.e2e_ms"] == [90.0]
    assert reg._hists["serve.attr.ttft_ms"] == [30.0]
    assert "serve.attr.unattributed_ms" in reg._hists
    assert tracing.tracer().open_traces() == []


def test_failed_trace_exports_but_skips_attribution():
    sink = _sink()
    tracing.phase(3, "queue_wait", "r0")
    tracing.finish(3, error="ServeTimeout", e2e_ms=5.0)
    root = _root_of([r for r in sink.records if r.get("type") == "span"])
    assert root["attrs"]["ok"] is False
    assert root["attrs"]["error"] == "ServeTimeout"
    assert "serve.attr.e2e_ms" not in telemetry.registry()._hists


def test_ring_bounded_and_dump_atomic(monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_RING", "8")
    sink = _sink()
    for i in range(40):
        tracing.note("r0", {"kind": "tick", "i": i})
    assert len(tracing.snapshot("r0")) == 8
    assert tracing.snapshot("r0")[-1]["i"] == 39   # newest survive
    rec = tracing.dump("r0", "quarantine", request=17)
    assert rec["type"] == "flight_recorder"
    assert rec["replica"] == "r0" and rec["reason"] == "quarantine"
    assert rec["n"] == len(rec["tail"]) == 8
    assert rec["ring_cap"] == 8 and rec["request"] == 17
    # ONE sink record, not one per tail entry
    dumps = [r for r in sink.records
             if r.get("type") == "flight_recorder"]
    assert dumps == [rec]


def test_event_tap_mirrors_replica_events():
    tracing.tracer()   # arm the tap
    telemetry.record_event("serve_probe", replica="r9", detail=1)
    ring = tracing.snapshot("r9")
    assert ring and ring[-1]["kind"] == "serve_probe"
    assert ring[-1]["type"] == "event"


# ---------------------------------------------------------------------------
# 2. engine integration: connected trees, full attribution, no leaks
# ---------------------------------------------------------------------------

def test_engine_span_tree_connected_and_tiled(model_and_params):
    model, params = model_and_params
    sink = _sink()
    eng = _engine(model, params)
    eng.warmup()
    published = []
    reqs = [eng.submit([3, 4, 5], max_new_tokens=6,
                       on_token=lambda t: published.append(t)),
            eng.submit([7, 8], max_new_tokens=6),
            eng.submit([9] * 6, max_new_tokens=6)]
    eng.run_until_idle(timeout=300)
    eng.stop()
    by_trace = _spans(sink)
    assert sorted(by_trace) == sorted(r.id for r in reqs)
    for r in reqs:
        spans = by_trace[r.id]
        _assert_connected(spans)
        root = _root_of(spans)
        assert root["attrs"]["ok"] is True
        assert root["attrs"]["n_tokens"] == len(r.tokens)
        phases = {s["phase"] for s in spans}
        assert {"queue_wait", "prefill", "decode"} <= phases
        # interval phases tile submit -> done
        assert _attributed_frac(root) > 0.8
    # stream positions match the span accounting on the streamed request
    root0 = _root_of(by_trace[reqs[0].id])
    assert root0["attrs"]["published"] == len(published) \
        == len(reqs[0].tokens)
    assert tracing.tracer().open_traces() == []


def test_preemption_replay_keeps_trace(model_and_params):
    """Pool pressure preempts the loser; its requeue + re-prefill ride
    the ORIGINAL trace id with a `replay` phase, one root, connected."""
    model, params = model_and_params
    rng = np.random.RandomState(13)
    sink = _sink()
    eng = _engine(model, params, max_batch=2, n_blocks=4,
                  max_new_tokens=12)
    ra = eng.submit(list(rng.randint(0, V, size=7)), max_new_tokens=12)
    rb = eng.submit(list(rng.randint(0, V, size=7)), max_new_tokens=12)
    eng.run_until_idle(timeout=300)
    eng.stop()
    ra.result(1), rb.result(1)
    assert eng.stats["preemptions"] >= 1
    by_trace = _spans(sink)
    assert sorted(by_trace) == sorted([ra.id, rb.id])
    replayed = set()
    for rid, spans in by_trace.items():
        _assert_connected(spans)
        root = _root_of(spans)
        assert root["attrs"]["ok"] is True
        replayed.update(s["phase"] for s in spans)
    assert "replay" in replayed   # the preempted victim re-prefilled
    assert tracing.tracer().open_traces() == []


# ---------------------------------------------------------------------------
# 3. continuity across the disaggregated handoff + migration
# ---------------------------------------------------------------------------

def test_handoff_single_trace_crosses_replicas(model_and_params):
    model, params = model_and_params
    sink = _sink()
    engines = _fleet(model, params, 2)
    router = ReplicaRouter(engines, respawn=False, disagg=True,
                           prefill_replicas=1)
    router.warmup()
    prompts = [[3, 4, 5], [7, 8], [9] * 6]
    reqs = _run_router(router, [(p, {"max_new_tokens": 6})
                                for p in prompts])
    assert all(r.done and r.error is None for r in reqs)
    assert engines[0].stats["handoffs"] == len(prompts)
    by_trace = _spans(sink)
    assert sorted(by_trace) == sorted(r.id for r in reqs)
    for r in reqs:
        spans = by_trace[r.id]
        _assert_connected(spans)
        root = _root_of(spans)
        assert root["attrs"]["ok"] is True
        assert root["attrs"]["n_tokens"] == len(r.tokens)
        phases = {s["phase"] for s in spans}
        # prefill on the source, the handoff leaves, decode on the target
        assert {"prefill", "handoff_wait", "handoff_pack",
                "handoff_land", "decode"} <= phases
        # ONE trace id spans BOTH roles
        assert {s["replica"] for s in spans} == {"replica0", "replica1"}
        assert _attributed_frac(root) > 0.8
    assert tracing.tracer().open_traces() == []


def test_migration_keeps_original_trace_id(model_and_params, monkeypatch):
    """engine_crash mid-traffic: journal migration replays the in-flight
    requests on the survivor under their ORIGINAL trace ids — one root
    each, `replay` spans present, no orphans."""
    model, params = model_and_params
    sink = _sink()
    engines = _fleet(model, params, 2)
    router = ReplicaRouter(engines, respawn=False)
    router.warmup()
    monkeypatch.setenv("MXNET_CHAOS", "engine_crash:2:replica0")
    chaos.reset()
    prompts = [[3 + i, 4, 5] for i in range(8)]
    reqs = _run_router(router, [(p, {"max_new_tokens": 6,
                                     "deadline_ms": 60000})
                                for p in prompts])
    assert any(e._dead is not None for e in engines)
    assert all(r.done and r.error is None for r in reqs)
    assert telemetry.registry().counter("serve.replays").value >= 1
    by_trace = _spans(sink)
    phases_seen = set()
    for r in reqs:
        spans = by_trace[r.id]
        _assert_connected(spans)
        root = _root_of(spans)
        assert root["attrs"]["ok"] is True
        phases_seen.update(s["phase"] for s in spans)
    assert "replay" in phases_seen
    assert tracing.tracer().open_traces() == []


# ---------------------------------------------------------------------------
# 4. flight-recorder roads
# ---------------------------------------------------------------------------

def test_flight_recorder_dumps_on_engine_crash(model_and_params,
                                               monkeypatch):
    model, params = model_and_params
    sink = _sink()
    engines = _fleet(model, params, 2)
    router = ReplicaRouter(engines, respawn=False)
    router.warmup()
    monkeypatch.setenv("MXNET_CHAOS", "engine_crash:2:replica0")
    chaos.reset()
    _run_router(router, [([3 + i, 4, 5], {"max_new_tokens": 6,
                                          "deadline_ms": 60000})
                         for i in range(8)])
    dead = [e.name for e in engines if e._dead is not None]
    assert dead
    dumps = [r for r in sink.records
             if r.get("type") == "flight_recorder"]
    crash = [d for d in dumps if d["reason"] == "scheduler_death"]
    assert crash, "no flight-recorder dump for the crashed scheduler"
    assert crash[0]["replica"] in dead
    assert crash[0]["n"] == len(crash[0]["tail"]) > 0
    # the tail holds the lead-up (spans/events), each itself well-formed
    assert all(e.get("type") in ("span", "event")
               for e in crash[0]["tail"])


def test_flight_recorder_dumps_on_handoff_fail(model_and_params,
                                               monkeypatch):
    model, params = model_and_params
    sink = _sink()
    engines = _fleet(model, params, 2)
    router = ReplicaRouter(engines, respawn=False, disagg=True,
                           prefill_replicas=1)
    router.warmup()
    monkeypatch.setenv("MXNET_CHAOS", "handoff_fail:1.0")
    chaos.reset()
    reqs = _run_router(router, [([3 + i, 4, 5], {"max_new_tokens": 6})
                                for i in range(4)])
    assert all(r.done and r.error is None for r in reqs)   # replay road
    dumps = [r for r in sink.records
             if r.get("type") == "flight_recorder"
             and r["reason"] == "handoff_fail"]
    assert len(dumps) == len(reqs)
    assert all(d["replica"] == "replica0" for d in dumps)


# ---------------------------------------------------------------------------
# 5. kill-switch parity
# ---------------------------------------------------------------------------

def test_kill_switch_bit_for_bit(model_and_params, monkeypatch):
    model, params = model_and_params
    prompts = [[3, 4, 5], [7, 8], [9] * 6]

    def leg():
        sink = _sink()
        eng = _engine(model, params)
        eng.warmup()
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_idle(timeout=300)
        eng.stop()
        toks = [r.result(1) for r in reqs]
        retraces = [e for e in telemetry.events("retrace")
                    if str(e.get("site", "")).startswith("serving.")]
        return toks, sink.records, retraces, eng._phase_s

    traced, traced_recs, traced_retraces, traced_s = leg()
    telemetry.reset()
    tracing.reset()
    monkeypatch.setenv("MXNET_SERVE_TRACING", "0")
    off, off_recs, off_retraces, off_s = leg()

    assert off == traced                      # bit-for-bit tokens
    assert traced_retraces == [] and off_retraces == []
    assert any(r.get("type") == "span" for r in traced_recs)
    assert not any(r.get("type") in ("span", "flight_recorder")
                   for r in off_recs)
    assert tracing._TRACER is None            # never even built
    assert set(traced_s) == set(SCHED_PHASES) and off_s is None
    assert not any(k.startswith("serve.attr.")
                   for k in telemetry.registry()._hists)


# ---------------------------------------------------------------------------
# 6. satellite-3: wait metrics measured from STAGE time agree with spans
# ---------------------------------------------------------------------------

def test_handoff_wait_metric_agrees_with_span(model_and_params):
    model, params = model_and_params
    _sink()
    engines = _fleet(model, params, 2)
    router = ReplicaRouter(engines, respawn=False, disagg=True,
                           prefill_replicas=1)
    router.warmup()
    reqs = _run_router(router, [([3 + i, 4, 5], {"max_new_tokens": 6})
                                for i in range(4)])
    assert all(r.done and r.error is None for r in reqs)
    hists = telemetry.registry()._hists
    metric = hists.get("serve.handoff_wait_ms")
    attr = hists.get("serve.attr.handoff_wait_ms")
    assert metric and attr and len(metric) == len(attr)
    m_mean = sum(metric) / len(metric)
    a_mean = sum(attr) / len(attr)
    # the metric now covers the whole stage->land window the span
    # measures; generous tolerance for scheduler-iteration jitter
    assert abs(m_mean - a_mean) <= max(0.5 * max(m_mean, a_mean), 30.0)


# ---------------------------------------------------------------------------
# 7. telemetry JSONL sink rotation
# ---------------------------------------------------------------------------

def test_jsonl_sink_rotates_and_keeps_k(tmp_path):
    path = str(tmp_path / "stream.jsonl")
    sink = telemetry.JsonlSink(path, max_mb=300 / (1024.0 * 1024.0),
                               keep=2)
    n = 40
    for i in range(n):
        sink.emit({"type": "span", "i": i, "pad": "x" * 40})
    sink.close()
    files = sorted(p.name for p in tmp_path.iterdir())
    # rotation fires after the write that crosses the threshold, so a
    # stream that ends exactly on a rotation may leave only .1/.2 — the
    # bare path is optional, the rotated siblings are not
    assert "stream.jsonl.1" in files
    assert not any(f.endswith(".3") for f in files)   # keep=2 pruned
    kept = []
    # read oldest -> newest (trace_report order): .2, .1, then bare
    for f in ["stream.jsonl.2", "stream.jsonl.1", "stream.jsonl"]:
        if f not in files:
            continue
        with open(tmp_path / f) as fh:   # every file valid JSONL,
            kept += [json.loads(line)["i"] for line in fh]  # line bounds
    # the newest records always survive; ids read back in emit order
    assert max(kept) == n - 1
    assert kept == sorted(kept)


def test_jsonl_sink_reads_env_knobs(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TELEMETRY_MAX_MB", "2")
    monkeypatch.setenv("MXNET_TELEMETRY_KEEP", "5")
    sink = telemetry.JsonlSink(str(tmp_path / "s.jsonl"))
    assert sink.max_bytes == 2 * 1024 * 1024
    assert sink.keep == 5
    assert telemetry.JsonlSink(str(tmp_path / "t.jsonl"),
                               max_mb=0).max_bytes == 0


# ---------------------------------------------------------------------------
# 8. trace_report: waterfall, attribution, Chrome export
# ---------------------------------------------------------------------------

def _synthetic_stream(path):
    recs = [
        {"type": "span", "trace": 1, "sid": 2, "parent": 1,
         "phase": "queue_wait", "replica": "replica0",
         "t0": 0.0, "t1": 0.01, "ms": 10.0},
        {"type": "span", "trace": 1, "sid": 4, "parent": 3,
         "phase": "prefill_chunk", "replica": "replica0",
         "t0": 0.011, "t1": 0.014, "ms": 3.0, "attrs": {"tokens": 8}},
        {"type": "span", "trace": 1, "sid": 3, "parent": 1,
         "phase": "prefill", "replica": "replica0",
         "t0": 0.01, "t1": 0.03, "ms": 20.0},
        {"type": "span", "trace": 1, "sid": 5, "parent": 1,
         "phase": "decode", "replica": "replica1",
         "t0": 0.03, "t1": 0.09, "ms": 60.0},
        {"type": "span", "trace": 1, "sid": 1, "parent": 0,
         "phase": "request", "replica": "replica0",
         "t0": 0.0, "t1": 0.09, "ms": 90.0,
         "attrs": {"ok": True, "ttft_ms": 30.0, "n_tokens": 6,
                   "queue_wait_ms": 10.0, "prefill_ms": 20.0,
                   "decode_ms": 60.0}},
        {"type": "span", "trace": 0, "sid": 6, "parent": 0,
         "phase": "megastep", "replica": "replica1",
         "t0": 0.04, "t1": 0.05, "ms": 10.0},
        {"type": "flight_recorder", "replica": "replica0",
         "reason": "quarantine", "time": 1.0, "n": 1, "ring_cap": 8,
         "tail": [{"type": "event", "kind": "serve_probe"}]},
        {"type": "step", "step": 1},   # non-span records are ignored
    ]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def test_trace_report_waterfall_and_chrome(tmp_path):
    stream = str(tmp_path / "t.jsonl")
    chrome = str(tmp_path / "chrome.json")
    _synthetic_stream(stream)
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, stream, "--chrome", chrome],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "trace 1" in out and "ttft 30.0ms" in out
    assert "replica0 -> replica1" in out
    for ph in ("queue_wait", "prefill", "decode", "prefill_chunk"):
        assert ph in out
    assert "p99 attribution (1 completed requests)" in out
    assert "flight recorder dumps: 1" in out
    data = json.load(open(chrome))
    assert isinstance(data["traceEvents"], list) and data["traceEvents"]
    complete = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert len(complete) == 6   # every span, trace-0 ones included
    for e in data["traceEvents"]:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
    # metadata names the request process and its per-replica threads
    meta = {(e["name"], e["args"]["name"])
            for e in data["traceEvents"] if e["ph"] == "M"}
    assert ("process_name", "request 1") in meta
    assert ("thread_name", "replica1") in meta


def test_trace_report_json_attribution(tmp_path):
    stream = str(tmp_path / "t.jsonl")
    _synthetic_stream(stream)
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, stream, "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    att = json.loads(proc.stdout)
    assert att["n"] == 1
    assert att["e2e"]["p99"] == 90.0
    assert att["decode"]["mean"] == 60.0
    assert att["attributed_frac"] == 1.0


def test_trace_report_empty_stream_is_typed(tmp_path):
    stream = tmp_path / "empty.jsonl"
    stream.write_text("")
    proc = subprocess.run(
        [sys.executable, TRACE_REPORT, str(stream)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "no span records" in proc.stderr


# ---------------------------------------------------------------------------
# 9. mxlint span-phase drift
# ---------------------------------------------------------------------------

_FIXTURE_TRACING = """
    PHASES = ("request", "queue_wait", "prefill", "replay", "decode")
"""
_FIXTURE_DOC = """
    Phases: `request`, `queue_wait`, `prefill`, `replay`, `decode`.
"""
_FIXTURE_REPORT = """
    RENDERED = ("request", "queue_wait", "prefill", "replay", "decode")
"""


def _lint(tmp_path, files, rules):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    targets = tuple(r for r in files if r.endswith(".py"))
    return lint_run(str(tmp_path), targets=targets, rules=rules)


_SPAN_RULES = ["span-phase-unknown", "span-phase-undocumented",
               "span-phase-unrendered"]


def test_span_phase_unknown_detected(tmp_path):
    res = _lint(tmp_path, {
        "mxnet_tpu/tracing.py": _FIXTURE_TRACING,
        "mxnet_tpu/serving/mod.py": """
            from mxnet_tpu import tracing

            def f(req, name):
                tracing.phase(req.id, "not_a_phase", name)
        """,
        "docs/observability.md": _FIXTURE_DOC,
        "tools/trace_report.py": _FIXTURE_REPORT,
    }, rules=_SPAN_RULES)
    assert [f.rule for f in res.findings] == ["span-phase-unknown"]
    assert "not_a_phase" in res.findings[0].message


def test_span_phase_undocumented_and_unrendered(tmp_path):
    res = _lint(tmp_path, {
        "mxnet_tpu/tracing.py": """
            PHASES = ("request", "queue_wait", "ghost_phase")
        """,
        "docs/observability.md": "Phases: `request`, `queue_wait`.",
        "tools/trace_report.py": """
            RENDERED = ("request", "queue_wait")
        """,
    }, rules=_SPAN_RULES)
    assert sorted(f.rule for f in res.findings) == \
        ["span-phase-undocumented", "span-phase-unrendered"]
    assert all("ghost_phase" in f.message for f in res.findings)


def test_span_phase_clean_including_ifexp(tmp_path):
    res = _lint(tmp_path, {
        "mxnet_tpu/tracing.py": _FIXTURE_TRACING,
        "mxnet_tpu/serving/mod.py": """
            from mxnet_tpu import tracing

            def f(req, name, resumed, t0, t1):
                tracing.phase(req.id,
                              "replay" if resumed else "prefill", name)
                tracing.add_span(req.id, "decode", name, t0, t1)
        """,
        "docs/observability.md": _FIXTURE_DOC,
        "tools/trace_report.py": _FIXTURE_REPORT,
    }, rules=_SPAN_RULES)
    assert res.findings == []


# ---------------------------------------------------------------------------
# 10. the span store holds a window, and records each iteration (ISSUE-25)
# ---------------------------------------------------------------------------

def test_window_bounds_are_half_open_on_the_close_time():
    for i, (t0, t1) in enumerate([(0.0, 1.0), (0.5, 2.0), (1.5, 3.0)]):
        tracing.add_span(0, "megastep", "r0", t0, t1, i=i)
    tracing.note("r0", {"kind": "tick", "t1": 1.5})   # events never count
    tracing.add_span(0, "megastep", "r1", 0.0, 2.0)
    got = tracing.window("r0", 1.0, 3.0)
    assert [s["attrs"]["i"] for s in got] == [0, 1]    # t1 in [1.0, 3.0)
    assert all(s["type"] == "span" for s in got)
    assert tracing.window("r0", 3.0, 9.0)[0]["attrs"]["i"] == 2
    assert tracing.window("r0", 3.1, 9.0) == []
    assert tracing.window("nobody", 0.0, 9.0) == []
    assert "iteration" in tracing.PHASES


def test_ring_defaults_to_a_window_and_dumps_its_newest_256():
    assert tracing.tracer()._ring_cap == 16384
    for i in range(16384 + 10):
        tracing.note("r0", {"kind": "tick", "i": i})
    ring = tracing.snapshot("r0")
    assert len(ring) == 16384 and ring[0]["i"] == 10
    rec = tracing.dump("r0", "quarantine")
    assert rec["n"] == len(rec["tail"]) == 256
    assert rec["tail"][-1]["i"] == 16384 + 9 and rec["ring_cap"] == 16384


def test_iteration_record_counts_what_the_iteration_did(model_and_params):
    """One `iteration` span per step() that launched, from the stamps
    step() takes, with the counts taken where the work happens."""
    model, params = model_and_params
    eng = _engine(model, params, name="it0", block_size=4, n_blocks=32,
                  decode_buckets=[2, 4], prefix=False)
    eng.warmup()
    t0 = time.perf_counter()
    reqs = [eng.submit(list(range(1, 10)), max_new_tokens=4),
            eng.submit(list(range(20, 26)), max_new_tokens=4)]
    eng.run_until_idle(timeout=120)
    t1 = time.perf_counter()
    assert all(r.error is None for r in reqs)
    its = [s for s in tracing.window("it0", t0, t1)
           if s["phase"] == "iteration"]
    assert len(its) == eng.stats["decode_steps"] >= 3
    for s in its:
        a = s["attrs"]
        assert set(a) == {"rows", "bucket", "chunks", "queued",
                          "blocks_live", "blocks_parked", "attn_kernel",
                          "ctx_blocks", "sampled_rows", "phase_ms"}
        assert set(a["phase_ms"]) == set(SCHED_PHASES)
        # greedy programs: no row samples
        assert a["sampled_rows"] == 0
        # the table entries the rows' attention walks: their live blocks
        assert a["rows"] <= a["ctx_blocks"]
        assert 1 <= a["rows"] <= a["bucket"] and a["bucket"] in (2, 4)
        # the CPU backend's decode programs hold no Pallas kernel
        assert a["attn_kernel"] == 0
        assert a["blocks_parked"] == 0 and a["queued"] == 0
        assert s["trace"] == 0 and s["t0"] < s["t1"]
    # both prompts went through their one chunk in the first iteration
    assert its[0]["attrs"]["chunks"] == 2
    assert sum(s["attrs"]["chunks"] for s in its) == 2
    # 9 and 6 prompt tokens in blocks of 4: 3 + 2 blocks, the first decode
    # write of the 9-token row lands in its third block, the 6-token row's
    # in its second
    assert its[0]["attrs"]["blocks_live"] == 5
    assert its[0]["attrs"]["ctx_blocks"] == 5
    assert its[0]["attrs"]["rows"] == 2
    assert sum(s["attrs"]["rows"] for s in its) == eng.stats["decode_rows"]
    assert eng.leaked_blocks() == 0
    # the counts are made anew each step(): one that launches nothing
    # leaves no record and carries no launch over from the last
    n = len(tracing.window("it0", t0, time.perf_counter() + 1))
    eng.step()
    assert not {"rows", "bucket", "attn_kernel"} & set(eng._iter)
    assert eng._iter["blocks_live"] == 0
    assert len(tracing.window("it0", t0, time.perf_counter() + 1)) == n


def test_iteration_blocks_live_excludes_parked_blocks(model_and_params):
    """The prefix cache parks a finished request's full blocks: they are
    held (the allocator's free count stays down) but no sequence lives in
    them, and `blocks_live` says so."""
    model, params = model_and_params
    eng = _engine(model, params, name="it1", block_size=4, n_blocks=32,
                  decode_buckets=[2], prefix=True)
    eng.warmup()
    first = eng.submit(list(range(1, 14)), max_new_tokens=3)
    eng.run_until_idle(timeout=120)
    assert first.error is None
    parked = eng._prefix.parked_count
    assert parked >= 3                       # 13 prompt tokens: 3 full blocks
    t0 = time.perf_counter()
    other = eng.submit(list(range(30, 36)), max_new_tokens=3)
    eng.run_until_idle(timeout=120)
    assert other.error is None
    its = [s for s in tracing.window("it1", t0, time.perf_counter())
           if s["phase"] == "iteration"]
    assert its
    for s in its:
        a = s["attrs"]
        assert a["blocks_parked"] >= parked
        assert a["blocks_live"] == 2         # 6 tokens + the decode writes
        assert eng._alloc.n_blocks - 1 - eng._alloc.free_blocks \
            >= a["blocks_live"] + parked - 1


def test_sched_phases_reach_the_profilers_trace(model_and_params, tmp_path):
    """The scheduler's phases are `TraceAnnotation`s: a profiler session
    around a few iterations finds `sched.iteration` and its nine leaf
    phases on the host's plane (read with the benchmark's raw reader)."""
    import jax

    sys.path.insert(0, REPO)
    from benchmark import xplane_raw

    model, params = model_and_params
    eng = _engine(model, params, name="it2", block_size=4, n_blocks=32,
                  decode_buckets=[2])
    eng.warmup()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        req = eng.submit(list(range(1, 10)), max_new_tokens=4)
        eng.run_until_idle(timeout=120)
    finally:
        jax.profiler.stop_trace()
    assert req.error is None
    path = xplane_raw.trace.find_xplane(str(tmp_path))
    names = {}
    for plane in xplane_raw.planes(path, host_prefixes=("sched.",)):
        for events in plane["lines"].values():
            for _, _, meta in events:
                name = meta["name"].split("#")[0]
                names[name] = names.get(name, 0) + 1
    assert set(names) == {"sched." + p for p in (
        "iteration", "sweep", "prefill", "admit", "grow", "pack", "launch",
        "fetch", "publish", "account")}
    assert names["sched.launch"] == names["sched.fetch"] \
        == names["sched.publish"] == eng.stats["decode_steps"]
    assert names["sched.iteration"] >= names["sched.launch"]


# ---------------------------------------------------------------------------
# 11. every phase's host time, in the store and on the profiler's clock
# ---------------------------------------------------------------------------

def _served_by_its_thread(eng, prompts, gap_s):
    """Submit ``prompts`` to a start()ed engine one at a time, each
    ``gap_s`` after the one before has been served, so that it waits for
    work between them and no two share an iteration however slow the host
    is; returns the window's `iteration` records."""
    eng.warmup()
    t0 = time.perf_counter()
    eng.start()
    try:
        for p in prompts:
            eng.submit(p, max_new_tokens=4).result(timeout=120)
            time.sleep(gap_s)
    finally:
        eng.stop()
    return [s for s in tracing.window(eng.name, t0, time.perf_counter())
            if s["phase"] == "iteration"]


def test_iteration_records_tile_the_schedulers_time(model_and_params):
    """Each record carries the host milliseconds of the ten phases since
    the record before: the records after the first cover the time between
    the first's close and the last's, waits for arrivals included, and
    count none of it twice."""
    model, params = model_and_params
    eng = _engine(model, params, name="ph0", block_size=4, n_blocks=32,
                  decode_buckets=[2, 4])
    its = _served_by_its_thread(eng, [list(range(1, 10)), [20, 21, 22],
                                      list(range(30, 36))], 0.2)
    assert len(its) >= 6
    assert all(set(s["attrs"]["phase_ms"]) == set(SCHED_PHASES)
               for s in its)
    span_ms = 1e3 * (its[-1]["t1"] - its[0]["t1"])
    total = sum(sum(s["attrs"]["phase_ms"].values()) for s in its[1:])
    assert 0.9 * span_ms <= total <= span_ms + 1.0
    # the engine waited between the arrivals (0.2 s after each request
    # was served, 0.4 s in all), and said so in the next
    # record; every launch was packed, launched, fetched and published
    waits = sum(s["attrs"]["phase_ms"]["wait"] for s in its[1:])
    assert waits >= 100.0
    for s in its:
        ms = s["attrs"]["phase_ms"]
        assert all(v >= 0 for v in ms.values())
        assert min(ms["pack"], ms["launch"], ms["fetch"], ms["publish"],
                   ms["account"]) > 0


def test_sched_wait_reaches_the_profilers_trace(model_and_params, tmp_path):
    """A start()ed engine with no work waits inside a `sched.wait` span,
    outside any `sched.iteration`."""
    import jax

    sys.path.insert(0, REPO)
    from benchmark import xplane_raw

    model, params = model_and_params
    eng = _engine(model, params, name="ph1", block_size=4, n_blocks=32,
                  decode_buckets=[2])
    eng.warmup()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.start()
        time.sleep(0.2)
        req = eng.submit(list(range(1, 10)), max_new_tokens=4)
        req.result(timeout=120)
        time.sleep(0.2)
    finally:
        eng.stop()
        jax.profiler.stop_trace()
    path = xplane_raw.trace.find_xplane(str(tmp_path))
    spans = {}
    for plane in xplane_raw.planes(path, host_prefixes=("sched.",)):
        for events in plane["lines"].values():
            for s, d, meta in events:
                spans.setdefault(meta["name"].split("#")[0], []).append(
                    (s, s + d))
    assert len(spans["sched.wait"]) >= 2
    assert "sched.account" in spans and "sched.launch" in spans
    for lo, hi in spans["sched.wait"]:
        assert not any(a < hi and lo < b for a, b in spans["sched.iteration"])


def test_iteration_span_carries_the_records_clock(model_and_params,
                                                  tmp_path):
    """`sched.iteration` carries the store's `t0` as its `t0_ns` stat: one
    subtraction maps the store's clock onto the trace's, and every
    launching iteration's record then lies within 1 ms of its span."""
    import jax

    model, params = model_and_params
    eng = _engine(model, params, name="ph2", block_size=4, n_blocks=32,
                  decode_buckets=[2])
    eng.warmup()
    t0 = time.perf_counter()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        req = eng.submit(list(range(1, 10)), max_new_tokens=5)
        eng.run_until_idle(timeout=120)
    finally:
        jax.profiler.stop_trace()
    assert req.error is None
    its = [s for s in tracing.window("ph2", t0, time.perf_counter())
           if s["phase"] == "iteration"]
    assert len(its) == eng.stats["decode_steps"] >= 4

    sys.path.insert(0, REPO)
    from benchmark import trace

    data = jax.profiler.ProfileData.from_file(
        trace.find_xplane(str(tmp_path)))
    events = {}        # t0_ns -> (start, end) in the trace's nanoseconds
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sched.iteration"):
                    stats = dict(ev.stats)
                    events[int(stats["t0_ns"])] = (
                        ev.start_ns, ev.start_ns + ev.duration_ns)
    assert len(events) >= len(its)
    # the clock's offset, from one span
    anchor, (start, _) = min(events.items())
    offset = start - anchor
    for s in its:
        lo, hi = events[int(s["t0"] * 1e9)]
        assert abs(s["t0"] * 1e9 + offset - lo) < 1e6
        assert abs(s["t1"] * 1e9 + offset - hi) < 1e6
