#!/usr/bin/env python
"""Render a telemetry JSONL stream (mxnet_tpu.telemetry JsonlSink /
MXNET_TELEMETRY_JSONL) into a per-step table and a run summary.

    python tools/telemetry_report.py /path/to/telemetry.jsonl [--steps N]

Per-step columns: step wall-clock, samples/sec gauge, jit-entry and
host-transfer deltas, comm bytes delta (kvstore + dist PS), io wait, and
retrace events.  The summary reports p50/p99 step ms, total retrace count
(with diagnoses), cumulative comm GB, and total dispatches — the numbers a
BENCH round needs to show the O(1)-dispatch contract held and nothing
recompiled mid-run.
"""
from __future__ import annotations

import argparse
import json
import sys


COMM_KEYS = ("kvstore.push_bytes", "kvstore.pull_bytes",
             "dist.bytes_sent", "dist.bytes_recv")

# fault-tolerance accounting (docs/fault_tolerance.md): event kinds and
# counters emitted by the recovery paths — RPC retries, skipped nonfinite
# steps, lr backoffs, server snapshot/rejoin, auto-checkpoint/resume
RECOVERY_EVENT_KINDS = ("rpc_retry", "nonfinite_grads", "lr_backoff",
                        "server_rejoin", "auto_checkpoint", "resume")
RECOVERY_COUNTERS = ("dist.rpc_retries", "dist.dup_push_applied",
                     "dist.dup_push_pending", "dist.dup_barrier",
                     "dist.server_snapshots", "dist.server_rehydrations",
                     "chaos.rpc_drops", "train.nonfinite_steps",
                     "train.auto_checkpoints", "train.resumes")

# serving accounting (docs/serving.md): counters/gauges/hists emitted by
# the continuous-batching engine (mxnet_tpu/serving)
SERVE_COUNTERS = ("serve.requests", "serve.completed", "serve.tokens",
                  "serve.prefills", "serve.decode_steps",
                  "serve.decode_padded", "serve.aot.compiles",
                  "serve.aot.hits", "serve.aot.frozen_compiles",
                  "serve.engine_failures", "serve.prefill_chunks",
                  "serve.greedy_requests", "serve.sampled_requests",
                  "serve.prefix_hits", "serve.prefix_bootstraps",
                  "serve.prefix_tokens", "serve.cow_copies",
                  "serve.prefix_evictions", "serve.state_resets")
# per-replica paged-cache gauges (serve.<name>.blocks_free/_frag plus the
# prefix-sharing set blocks_shared/_parked and prefix_hit_rate, and the
# per-sequence state's state_slots_live): the final value seen in the
# stream is the replica's end-of-run state
SERVE_BLOCK_GAUGE_SUFFIXES = (".blocks_free", ".blocks_frag",
                              ".blocks_shared", ".blocks_parked",
                              ".prefix_hit_rate", ".state_slots_live")

# serving resilience accounting (docs/serving.md "Failure semantics"):
# the SLO/failover counters + the failover/respawn event kinds
SERVE_RESILIENCE_COUNTERS = (
    "serve.shed", "serve.expired", "serve.cancelled", "serve.degraded",
    "serve.quarantined", "serve.cache_rebuilds", "serve.launch_errors",
    "serve.failovers", "serve.redispatched", "serve.respawns",
    "serve.chaos_flooded", "serve.block_waits", "serve.preempted",
    "serve.alloc_denied", "serve.blocks_rejected")
SERVE_RESILIENCE_EVENT_KINDS = (
    "serve_failover", "serve_respawn", "serve_respawn_failed",
    "serve_respawn_compiled", "serve_cache_rebuild", "serve_quarantine",
    "serve_preempt", "aot_frozen_compile")

# speculative decoding accounting (docs/serving.md "Speculative
# decoding"): serve.spec.* counters + the per-replica accept-rate gauge
# (serve.<name>.spec_accept_rate) and draft-degradation events
SERVE_SPEC_COUNTERS = (
    "serve.spec.proposed", "serve.spec.accepted", "serve.spec.rollbacks",
    "serve.verify_steps", "serve.chaos_draft_junk", "serve.draft_degraded")
SERVE_SPEC_GAUGE_SUFFIX = ".spec_accept_rate"

# serving durability accounting (docs/serving.md "Durability"): journal
# migration / exact replay, rolling-restart drain, and the anti-thrash
# preemption policy (stalls + storm trips)
SERVE_DURABILITY_COUNTERS = (
    "serve.migrated", "serve.replays", "serve.drained", "serve.stalled",
    "serve.thrash_trips")
SERVE_DURABILITY_EVENT_KINDS = (
    "serve_migrate", "serve_drain", "serve_drain_begin",
    "serve_thrash_trip")

# memory tiering accounting (docs/serving.md "Memory tiering &
# sessions"): host-tier spill/restore traffic, the per-replica
# host-pool occupancy gauge, the restore-wait histogram, and session
# continuity hits
SERVE_TIER_COUNTERS = (
    "serve.spilled", "serve.restored", "serve.spill_fails",
    "serve.restore_fails", "serve.session_hits")
SERVE_TIER_GAUGE_SUFFIX = ".host_blocks_used"
SERVE_TIER_EVENT_KINDS = ("serve_spill_failed", "serve_restore_failed")

# decode-loop accounting (docs/serving.md "Megastep decode &
# streaming"): fused megastep launches/tokens, rows retired in-graph
# mid-scan, and the per-replica exposed-host fraction gauge
# (serve.<name>.host_frac) the double-buffered sweep drives down
SERVE_DECODE_LOOP_COUNTERS = (
    "serve.megasteps", "serve.megastep_tokens", "serve.ingraph_retired")
SERVE_DECODE_LOOP_GAUGE_SUFFIX = ".host_frac"

# disaggregation accounting (docs/serving.md "Disaggregated
# prefill/decode"): prefill→decode handoff traffic (tickets out/in,
# bytes, fails, exact-replay fallbacks), the per-role replica gauge
# (serve.<name>.role: 1=prefill 2=decode), the router's per-role queue
# gauges, and the staging-to-landing wait histogram
SERVE_DISAGG_COUNTERS = (
    "serve.handoffs", "serve.handoffs_in", "serve.handoff_bytes",
    "serve.handoff_fails", "serve.replays_from_handoff")
SERVE_DISAGG_GAUGES = ("serve.prefill_depth", "serve.decode_depth")
SERVE_DISAGG_GAUGE_SUFFIX = ".role"
SERVE_DISAGG_EVENT_KINDS = ("serve_handoff", "serve_handoff_fail")

# quantization accounting (docs/serving.md "Quantization"): logit-gate
# trips + chaos scale corruptions (serve.<name>.quant.* per replica,
# process-wide serve.quant.*), and the live logit-error gauge the
# parity instrument exports
SERVE_QUANT_COUNTERS = ("serve.quant.trips", "serve.quant.scale_corrupts")
SERVE_QUANT_GAUGE = "serve.quant_logit_err"
SERVE_QUANT_EVENT_KINDS = ("serve_quant_trip", "serve_scale_corrupt")

# gateway & elasticity (docs/serving.md "Gateway & autoscaling"): the
# HTTP/SSE front door's accept/shed/cancel accounting + the streamed
# time-to-first-byte histogram, the autoscaler's fleet actions, and the
# session migration that makes scale-down invisible to conversations
SERVE_GATEWAY_COUNTERS = (
    "serve.gateway.requests", "serve.gateway.accepted",
    "serve.gateway.errors", "serve.gateway.conn_shed",
    "serve.gateway.disconnects", "serve.gateway.slow_consumer_cancels",
    "serve.scale_ups", "serve.scale_downs", "serve.sessions_migrated")
SERVE_GATEWAY_GAUGE = "serve.gateway.open_conns"
SERVE_GATEWAY_HIST = "serve.gateway.ttfb_ms"
SERVE_GATEWAY_EVENT_KINDS = ("serve_gateway_cancel", "serve_scale_up",
                             "serve_scale_down", "serve_sessions_migrated")

# mixture-of-experts accounting (docs/serving.md "Sharded replicas" +
# parallel/moe.py): per-expert dispatch counters, capacity-overflow drops
# (those tokens' FFN output is silently zero), and the serving engines'
# per-replica expert-load gauges (serve.<name>.expert_load.<e>)
MOE_DISPATCH_PREFIX = "moe.expert_dispatch."
MOE_DROP_COUNTER = "moe.overflow_dropped"
MOE_SERVE_GAUGE_MARK = ".expert_load."

# SLO attribution (docs/observability.md "Request tracing"): the tracing
# layer folds every retired request's span timeline into per-phase
# serve.attr.*_ms histograms — a ttft/e2e p99 regression names its phase
SERVE_ATTR_HISTS = (
    "serve.attr.queue_wait_ms", "serve.attr.prefill_ms",
    "serve.attr.replay_ms", "serve.attr.restore_wait_ms",
    "serve.attr.handoff_wait_ms", "serve.attr.decode_ms",
    "serve.attr.unattributed_ms", "serve.attr.e2e_ms",
    "serve.attr.ttft_ms")


def load(path):
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail line from a crashed run
            if rec.get("type") == "step":
                records.append(rec)
    return records


def _step_ms(rec):
    h = rec.get("hists", {}).get("step.ms")
    if h and h.get("count"):
        return h["mean"]
    return rec.get("wall_ms")


def _comm_delta(rec):
    d = rec.get("deltas", {})
    return sum(int(d.get(k, 0)) for k in COMM_KEYS)


def _merge_hists(records, name):
    """Pool a histogram's per-step summaries across the stream: count-
    weighted mean plus the worst per-step p99/max (the pools themselves
    are drained per report, so exact stream-wide percentiles are gone)."""
    rows = [r["hists"][name] for r in records
            if r.get("hists", {}).get(name, {}).get("count")]
    if not rows:
        return None
    n = sum(h["count"] for h in rows)
    return {"count": n,
            "mean": round(sum(h["mean"] * h["count"] for h in rows) / n, 2),
            "p99_max": round(max(h["p99"] for h in rows), 2),
            "max": round(max(h["max"] for h in rows), 2)}


def _fmt_bytes(n):
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if n >= div:
            return "%.2f %s" % (n / div, unit)
    return "%d B" % n


def step_rows(records, max_steps=None):
    """The per-step table as data: one dict per rendered row with the
    same columns — the machine-readable twin `--json` emits so gates
    read fields instead of scraping the rendered text."""
    rows = records if max_steps is None else records[-max_steps:]
    out = []
    for rec in rows:
        d = rec.get("deltas", {})
        io = rec.get("hists", {}).get("io.wait_ms", {})
        out.append({
            "step": rec.get("step"),
            "step_ms": _step_ms(rec),
            "samples_per_sec": rec.get("gauges", {}).get(
                "train.samples_per_sec"),
            "jit_entries": int(d.get("dispatch.jit_entries", 0)),
            "host_transfers": int(d.get("dispatch.host_transfers", 0)),
            "comm_bytes": _comm_delta(rec),
            "io_wait_ms": io.get("mean") if io.get("count") else None,
            "events": [e.get("kind", "?")
                       for e in rec.get("events", [])],
        })
    return out


def render(records, max_steps=None):
    lines = []
    lines.append("%6s %10s %12s %8s %8s %10s %9s %s" % (
        "step", "step_ms", "samples/s", "jit", "xfers", "comm", "io_ms",
        "events"))
    for row in step_rows(records, max_steps=max_steps):
        ms, sps, io = row["step_ms"], row["samples_per_sec"], \
            row["io_wait_ms"]
        lines.append("%6s %10s %12s %8d %8d %10s %9s %s" % (
            row["step"] if row["step"] is not None else "?",
            "%.1f" % ms if ms is not None else "-",
            "%.1f" % sps if sps is not None else "-",
            row["jit_entries"],
            row["host_transfers"],
            _fmt_bytes(row["comm_bytes"]),
            "%.1f" % io if io is not None else "-",
            ",".join(row["events"])))
    return "\n".join(lines)


def summarize(records):
    if not records:
        return {"steps": 0}
    step_ms = sorted(ms for ms in (_step_ms(r) for r in records)
                     if ms is not None)
    retraces = [e for r in records for e in r.get("events", [])
                if e.get("kind") == "retrace"]
    # per-record counters hold cumulative values of only the counters that
    # changed that step, so a counter's final total is its LAST appearance
    # anywhere in the stream
    final = {}
    for r in records:
        final.update(r.get("counters", {}))
    comm = sum(int(final.get(k, 0)) for k in COMM_KEYS)
    out = {
        "steps": len(records),
        "retrace_count": len(retraces),
        "retraces": [{"site": e.get("site"),
                      "diagnosis": e.get("diagnosis")} for e in retraces],
        "jit_entries_total": int(final.get("dispatch.jit_entries", 0)),
        "host_transfers_total": int(final.get("dispatch.host_transfers", 0)),
        "comm_gb": comm / 1e9,
    }
    if step_ms:
        n = len(step_ms)
        out.update({
            "step_ms_p50": step_ms[n // 2],
            "step_ms_p99": step_ms[min(n - 1, int(n * 0.99))],
            "step_ms_mean": sum(step_ms) / n,
        })
    recovery = {}
    for kind in RECOVERY_EVENT_KINDS:
        n = sum(1 for r in records for e in r.get("events", [])
                if e.get("kind") == kind)
        if n:
            recovery["%s_events" % kind] = n
    for key in RECOVERY_COUNTERS:
        v = int(final.get(key, 0))
        if v:
            recovery[key] = v
    if recovery:
        out["recovery"] = recovery
    serving = {k: int(final.get(k, 0)) for k in SERVE_COUNTERS
               if final.get(k)}
    if serving:
        # batch occupancy over the whole stream: real decode rows vs the
        # bucket slots launched (padding included)
        toks = serving.get("serve.tokens", 0) - \
            serving.get("serve.prefills", 0)
        padded = serving.get("serve.decode_padded", 0)
        if toks + padded:
            serving["batch_occupancy"] = round(
                toks / float(toks + padded), 4)
        serving["steady_state_recompiles"] = len(
            [e for e in retraces
             if str(e.get("site", "")).startswith("serving.")])
        # paged-cache gauges: last-seen per replica (serve.<name>.*)
        block_gauges = {}
        for r in records:
            for k, v in r.get("gauges", {}).items():
                if k.startswith("serve.") and \
                        k.endswith(SERVE_BLOCK_GAUGE_SUFFIXES):
                    block_gauges[k] = v
        serving.update(block_gauges)
        for name in ("serve.latency_ms", "serve.ttft_ms"):
            agg = _merge_hists(records, name)
            if agg:
                serving[name] = agg
        out["serving"] = serving
    speculation = {k: int(final.get(k, 0)) for k in SERVE_SPEC_COUNTERS
                   if final.get(k)}
    if speculation:
        prop = speculation.get("serve.spec.proposed", 0)
        if prop:
            speculation["accept_rate"] = round(
                speculation.get("serve.spec.accepted", 0) / float(prop), 4)
        for r in records:
            for k, v in r.get("gauges", {}).items():
                if k.startswith("serve.") and \
                        k.endswith(SERVE_SPEC_GAUGE_SUFFIX):
                    speculation[k] = v  # last-seen per replica
        n = sum(1 for r in records for e in r.get("events", [])
                if e.get("kind") == "serve_draft_degraded")
        if n:
            speculation["serve_draft_degraded_events"] = n
        out["speculation"] = speculation
    resilience = {k: int(final.get(k, 0))
                  for k in SERVE_RESILIENCE_COUNTERS if final.get(k)}
    for kind in SERVE_RESILIENCE_EVENT_KINDS:
        n = sum(1 for r in records for e in r.get("events", [])
                if e.get("kind") == kind)
        if n:
            resilience["%s_events" % kind] = n
    age = _merge_hists(records, "serve.queue_age_ms")
    if age:
        resilience["serve.queue_age_ms"] = age
    # live replica count: last-seen value of the router's submit-side
    # gauge — end-of-stream N below the configured fleet means a dead
    # replica was never respawned
    for r in records:
        v = r.get("gauges", {}).get("serve.replicas")
        if v is not None:
            resilience["serve.replicas"] = v
    if resilience:
        out["resilience"] = resilience
    durability = {k: int(final.get(k, 0))
                  for k in SERVE_DURABILITY_COUNTERS if final.get(k)}
    for kind in SERVE_DURABILITY_EVENT_KINDS:
        n = sum(1 for r in records for e in r.get("events", [])
                if e.get("kind") == kind)
        if n:
            durability["%s_events" % kind] = n
    # journal occupancy: last-seen depth of the router's request journal
    # — nonzero at end-of-stream means handles outlived their requests
    for r in records:
        v = r.get("gauges", {}).get("serve.journal_depth")
        if v is not None:
            durability["serve.journal_depth"] = v
    if durability:
        out["durability"] = durability
    tiering = {k: int(final.get(k, 0)) for k in SERVE_TIER_COUNTERS
               if final.get(k)}
    for r in records:
        for k, v in r.get("gauges", {}).items():
            if k.startswith("serve.") and \
                    k.endswith(SERVE_TIER_GAUGE_SUFFIX):
                tiering[k] = v  # last-seen per replica
    for kind in SERVE_TIER_EVENT_KINDS:
        n = sum(1 for r in records for e in r.get("events", [])
                if e.get("kind") == kind)
        if n:
            tiering["%s_events" % kind] = n
    wait = _merge_hists(records, "serve.restore_wait_ms")
    if wait:
        tiering["serve.restore_wait_ms"] = wait
    if tiering:
        out["tiering"] = tiering
    decode_loop = {k: int(final.get(k, 0))
                   for k in SERVE_DECODE_LOOP_COUNTERS if final.get(k)}
    for r in records:
        for k, v in r.get("gauges", {}).items():
            if k.startswith("serve.") and \
                    k.endswith(SERVE_DECODE_LOOP_GAUGE_SUFFIX):
                decode_loop[k] = v  # last-seen per replica
    if decode_loop:
        megs = decode_loop.get("serve.megasteps", 0)
        if megs:
            # tokens each fused launch actually emitted — m minus the
            # padding and the dead tail behind in-graph retirements
            decode_loop["tokens_per_megastep"] = round(
                decode_loop.get("serve.megastep_tokens", 0) / float(megs),
                2)
        out["decode_loop"] = decode_loop
    disagg = {k: int(final.get(k, 0)) for k in SERVE_DISAGG_COUNTERS
              if final.get(k)}
    for r in records:
        for k, v in r.get("gauges", {}).items():
            if k in SERVE_DISAGG_GAUGES or (
                    k.startswith("serve.") and
                    k.endswith(SERVE_DISAGG_GAUGE_SUFFIX)):
                disagg[k] = v  # last-seen (role flips only on respawn)
    for kind in SERVE_DISAGG_EVENT_KINDS:
        n = sum(1 for r in records for e in r.get("events", [])
                if e.get("kind") == kind)
        if n:
            disagg["%s_events" % kind] = n
    wait = _merge_hists(records, "serve.handoff_wait_ms")
    if wait:
        disagg["serve.handoff_wait_ms"] = wait
    if disagg:
        out["disaggregation"] = disagg
    moe = {k: int(v) for k, v in final.items()
           if k.startswith(MOE_DISPATCH_PREFIX) and v}
    if final.get(MOE_DROP_COUNTER):
        moe[MOE_DROP_COUNTER] = int(final[MOE_DROP_COUNTER])
    for r in records:
        for k, v in r.get("gauges", {}).items():
            if k.startswith("serve.") and MOE_SERVE_GAUGE_MARK in k:
                moe[k] = v  # last-seen per replica
    if moe:
        # load balance: max over experts / mean over experts of the
        # cumulative dispatch counters (1.0 = perfectly balanced)
        counts = [v for k, v in moe.items()
                  if k.startswith(MOE_DISPATCH_PREFIX)]
        if counts and sum(counts):
            moe["load_imbalance"] = round(
                max(counts) / (sum(counts) / float(len(counts))), 4)
        out["moe"] = moe
    attribution = {}
    for name in SERVE_ATTR_HISTS:
        agg = _merge_hists(records, name)
        if agg:
            attribution[name] = agg
    if attribution:
        e2e = attribution.get("serve.attr.e2e_ms")
        if e2e and e2e["count"]:
            # the structural invariant the nightly tracing gate asserts:
            # interval phases tile submit->done, so their totals cover
            # ~all of e2e (unattributed = finish-path remainder)
            total = sum(v["mean"] * v["count"]
                        for k, v in attribution.items()
                        if k not in ("serve.attr.e2e_ms",
                                     "serve.attr.ttft_ms"))
            attribution["attributed_frac"] = round(
                total / (e2e["mean"] * e2e["count"]), 4)
        out["attribution"] = attribution
    quantization = {k: int(final.get(k, 0)) for k in SERVE_QUANT_COUNTERS
                    if final.get(k)}
    for r in records:
        for k, v in r.get("gauges", {}).items():
            if k == SERVE_QUANT_GAUGE or (
                    k.startswith("serve.") and ".quant" in k
                    and k.endswith("_logit_err")):
                quantization[k] = v  # last-seen
    for kind in SERVE_QUANT_EVENT_KINDS:
        n = sum(1 for r in records for e in r.get("events", [])
                if e.get("kind") == kind)
        if n:
            quantization["%s_events" % kind] = n
    if quantization:
        out["quantization"] = quantization
    gateway = {k: int(final.get(k, 0)) for k in SERVE_GATEWAY_COUNTERS
               if final.get(k)}
    # live connection count: last-seen value of the gateway's accept
    # gauge — nonzero at end-of-stream means connections outlived stop()
    for r in records:
        v = r.get("gauges", {}).get(SERVE_GATEWAY_GAUGE)
        if v is not None:
            gateway[SERVE_GATEWAY_GAUGE] = v
    for kind in SERVE_GATEWAY_EVENT_KINDS:
        n = sum(1 for r in records for e in r.get("events", [])
                if e.get("kind") == kind)
        if n:
            gateway["%s_events" % kind] = n
    ttfb = _merge_hists(records, SERVE_GATEWAY_HIST)
    if ttfb:
        gateway[SERVE_GATEWAY_HIST] = ttfb
    if gateway:
        out["gateway"] = gateway
    healths = [r["health"] for r in records if "health" in r]
    if healths:
        out["last_health"] = healths[-1]
        out["nonfinite_steps"] = sum(
            1 for h in healths if h.get("nonfinite", 0))
    return out


def format_summary(summary):
    lines = ["", "summary:"]
    lines.append("  steps                %d" % summary.get("steps", 0))
    if "step_ms_p50" in summary:
        lines.append("  step ms p50/p99      %.1f / %.1f (mean %.1f)" % (
            summary["step_ms_p50"], summary["step_ms_p99"],
            summary["step_ms_mean"]))
    lines.append("  jit entries          %d" %
                 summary.get("jit_entries_total", 0))
    lines.append("  host transfers       %d" %
                 summary.get("host_transfers_total", 0))
    lines.append("  comm                 %.3f GB" % summary.get("comm_gb", 0))
    lines.append("  retraces             %d" %
                 summary.get("retrace_count", 0))
    for r in summary.get("retraces", []):
        lines.append("    %s: %s" % (r["site"], r["diagnosis"]))
    recovery = summary.get("recovery")
    if recovery:
        lines.append("  recovery:")
        for key in sorted(recovery):
            lines.append("    %-24s %d" % (key, recovery[key]))
    serving = summary.get("serving")
    if serving:
        lines.append("  serving:")
        for key in sorted(serving):
            v = serving[key]
            if isinstance(v, dict):
                lines.append("    %-24s n=%d mean=%.1f p99<=%.1f max=%.1f"
                             % (key, v["count"], v["mean"], v["p99_max"],
                                v["max"]))
            else:
                lines.append("    %-24s %s" % (key, v))
    speculation = summary.get("speculation")
    if speculation:
        lines.append("  speculation:")
        for key in sorted(speculation):
            lines.append("    %-24s %s" % (key, speculation[key]))
    resilience = summary.get("resilience")
    if resilience:
        lines.append("  resilience:")
        for key in sorted(resilience):
            v = resilience[key]
            if isinstance(v, dict):
                lines.append("    %-24s n=%d mean=%.1f p99<=%.1f max=%.1f"
                             % (key, v["count"], v["mean"], v["p99_max"],
                                v["max"]))
            else:
                lines.append("    %-24s %d" % (key, v))
    durability = summary.get("durability")
    if durability:
        lines.append("  durability:")
        for key in sorted(durability):
            lines.append("    %-24s %d" % (key, durability[key]))
    tiering = summary.get("tiering")
    if tiering:
        lines.append("  tiering:")
        for key in sorted(tiering):
            v = tiering[key]
            if isinstance(v, dict):
                lines.append("    %-24s n=%d mean=%.1f p99<=%.1f max=%.1f"
                             % (key, v["count"], v["mean"], v["p99_max"],
                                v["max"]))
            else:
                lines.append("    %-24s %s" % (key, v))
    decode_loop = summary.get("decode_loop")
    if decode_loop:
        lines.append("  decode loop:")
        for key in sorted(decode_loop):
            lines.append("    %-24s %s" % (key, decode_loop[key]))
    disagg = summary.get("disaggregation")
    if disagg:
        lines.append("  disaggregation:")
        for key in sorted(disagg):
            v = disagg[key]
            if isinstance(v, dict):
                lines.append("    %-24s n=%d mean=%.1f p99<=%.1f max=%.1f"
                             % (key, v["count"], v["mean"], v["p99_max"],
                                v["max"]))
            else:
                lines.append("    %-24s %s" % (key, v))
    moe = summary.get("moe")
    if moe:
        lines.append("  mixture-of-experts:")
        for key in sorted(moe):
            lines.append("    %-32s %s" % (key, moe[key]))
    attribution = summary.get("attribution")
    if attribution:
        lines.append("  attribution:")
        for key in sorted(attribution):
            v = attribution[key]
            if isinstance(v, dict):
                lines.append("    %-28s n=%d mean=%.1f p99<=%.1f max=%.1f"
                             % (key, v["count"], v["mean"], v["p99_max"],
                                v["max"]))
            else:
                lines.append("    %-28s %s" % (key, v))
    quantization = summary.get("quantization")
    if quantization:
        lines.append("  quantization:")
        for key in sorted(quantization):
            lines.append("    %-24s %s" % (key, quantization[key]))
    gateway = summary.get("gateway")
    if gateway:
        lines.append("  gateway & elasticity:")
        for key in sorted(gateway):
            v = gateway[key]
            if isinstance(v, dict):
                lines.append("    %-32s n=%d mean=%.1f p99<=%.1f max=%.1f"
                             % (key, v["count"], v["mean"], v["p99_max"],
                                v["max"]))
            else:
                lines.append("    %-32s %s" % (key, v))
    if "last_health" in summary:
        h = summary["last_health"]
        lines.append("  health (last step)   grad_norm=%.4g "
                     "update_ratio=%.4g nonfinite=%d"
                     % (h.get("grad_norm", 0), h.get("update_ratio", 0),
                        h.get("nonfinite", 0)))
        lines.append("  steps w/ nonfinite   %d" %
                     summary.get("nonfinite_steps", 0))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="telemetry JSONL stream")
    ap.add_argument("--steps", type=int, default=40,
                    help="show at most the last N per-step rows (0 = all)")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON object mirroring every rendered "
                         "section (summary + per-step table) instead of "
                         "text")
    args = ap.parse_args(argv)
    records = load(args.path)
    if not records:
        print("no step records in %s" % args.path, file=sys.stderr)
        return 1
    summary = summarize(records)
    if args.json:
        print(json.dumps(
            {"summary": summary,
             "steps": step_rows(records, max_steps=args.steps or None)},
            default=str))
        return 0
    print(render(records, max_steps=args.steps or None))
    print(format_summary(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
