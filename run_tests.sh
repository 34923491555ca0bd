#!/bin/bash
# Tier-1 test runner.  Tests run on a virtual 8-device CPU mesh forced by
# tests/conftest.py (JAX_PLATFORMS=cpu); nothing here touches a chip —
# `python chip_smoke.py` is the on-chip check.
#
# With no arguments this is the EXACT tier-1 invocation from ROADMAP.md —
# pipefail, the same pytest flags and timeout, and the DOTS_PASSED count
# parsed from the log — so local runs and the verify gate agree.  Any
# arguments replace the tier-1 selection and run untimed (tests/nightly.sh
# runs the full suite including slow tests this way).
set -o pipefail
T1="timeout -k 10 870"
if [ $# -eq 0 ]; then
    set -- tests/ -q -m 'not slow' --continue-on-collection-errors \
        -p no:cacheprovider -p no:xdist -p no:randomly
elif [ "$1" = "--lint" ]; then
    # static-analysis gate (docs/static_analysis.md): tools/mxlint.py
    # proves the graph-safety + concurrency invariants — trace safety,
    # donation discipline, lock discipline, registry drift, AOT-shape
    # hygiene.  Zero unsuppressed findings or the gate fails.  Runs on a
    # bare interpreter (no jax import), so it is the cheapest gate here.
    shift
    exec env PYTHONPATH= python "$(dirname "$0")/tools/mxlint.py" --json "$@"
elif [ "$1" = "--serve-smoke" ]; then
    # fast serving smoke: KV-cache decode parity, admit/retire scheduling,
    # the zero-retrace bucket contract, and the 2-replica CPU-mesh
    # dispatch (docs/serving.md) — the quick check that the continuous-
    # batching engine still serves correctly
    shift
    T1=""
    set -- tests/test_serving.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-paged-smoke" ]; then
    # fast paged-cache smoke: block allocator, parity with the full forward,
    # chunked prefill, seeded sampling, block-leak and preemption
    # coverage, and the paged zero-retrace gate (docs/serving.md
    # "Paged KV cache")
    shift
    T1=""
    set -- tests/test_serve_paged.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-prefix-smoke" ]; then
    # fast prefix-caching smoke: refcounted allocator invariants, the
    # radix prefix index, shared-prefix admission parity, copy-on-write
    # (incl. denied-CoW preemption), LRU eviction under pressure, and
    # the prefix zero-retrace gate (docs/serving.md "Prefix caching")
    shift
    T1=""
    set -- tests/test_serve_prefix.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-spec-smoke" ]; then
    # fast speculative-decoding smoke: verify-attention numerics, T=0/T>0
    # token parity for both drafters, the rewind-sharing regression,
    # draft_junk/block_exhaust chaos with speculation on, and the spec
    # zero-retrace gate (docs/serving.md "Speculative decoding")
    shift
    T1=""
    set -- tests/test_serve_spec.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-tier-smoke" ]; then
    # fast memory-tiering smoke: host-tier spill/restore bit-exactness,
    # the structured eviction hook, tier-aware lookup plans, session
    # reattach parity + suffix-only prefill, the MXNET_SERVE_TIER=0
    # kill-switch, cross-tier leak accounting, and the spill_fail/
    # restore_slow chaos legs (docs/serving.md "Memory tiering &
    # sessions")
    shift
    T1=""
    set -- tests/test_serve_tiers.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-quant-smoke" ]; then
    # fast quantization smoke: codec round-trip error bounds, quantized-
    # vs-bf16 serving parity (logit tolerance + greedy token-match at
    # T=0), the MXNET_SERVE_QUANT=0 kill-switch, prefix/CoW/spec/tier
    # composition with int8 KV scales, the scale_corrupt chaos clause,
    # the PS wire codec, and the quant zero-retrace gate
    # (docs/serving.md "Quantization")
    shift
    T1=""
    set -- tests/test_serve_quant.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-durability-smoke" ]; then
    # fast serving-durability smoke: journal exact-replay migration on
    # replica death, rolling-restart drain, anti-thrash preemption
    # (min-progress stall, oldest-request protection, storm -> degrade),
    # the mid-prefill victim regression, and the 3-clause chaos
    # composition run (docs/serving.md "Durability")
    shift
    T1=""
    set -- tests/test_serve_durability.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-megastep-smoke" ]; then
    # fast megastep smoke: m-step fused decode vs the sequential
    # single-step oracle (token parity across EOS/max_new/depth edges,
    # T=0 and T>0, spec on/off), in-graph retirement accounting, the
    # double-buffered sweep, token streaming (iterator + callback,
    # exactly-once across crash/migration), and the megastep
    # zero-retrace gate (docs/serving.md "Megastep decode & streaming")
    shift
    T1=""
    set -- tests/test_serve_megastep.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-disagg-smoke" ]; then
    # fast disaggregation smoke: prefill/decode role split with paged-KV
    # handoff — colocated-oracle parity (T=0 and seeded T>0), the
    # exact-replay fallback under handoff_fail / target death, session
    # affinity to the decode holder, the drain fence (rolling restart,
    # zero failed), the kill-switch, and the per-role zero-retrace gate
    # (docs/serving.md "Disaggregated prefill/decode")
    shift
    T1=""
    set -- tests/test_serve_disagg.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-sharded-smoke" ]; then
    # fast sub-mesh replica smoke: single-device-oracle token parity on a
    # multi-device CPU mesh (T=0 and seeded T>0), the
    # MXNET_SERVE_SHARDED=0 kill-switch, per-shard-count zero-retrace
    # gates, chaos with a sub-mesh replica in the fleet, and
    # expert-parallel MoE decode parity + load telemetry
    # (docs/serving.md "Sharded replicas")
    shift
    T1=""
    set -- tests/test_serve_sharded.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--serve-chaos-smoke" ]; then
    # fast serving-resilience smoke: deadlines/cancellation, overload
    # policies, quarantine + cache-rebuild scoping, router failover and
    # respawn, and the 2-replica chaos acceptance gate
    # (docs/serving.md "Failure semantics")
    shift
    T1=""
    set -- tests/test_serve_chaos.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--gateway-smoke" ]; then
    # fast gateway/autoscaler smoke: HTTP/SSE stream parity with the
    # engine oracle, the status-code taxonomy on the wire, the
    # backpressure failure matrix (disconnect frees blocks, slow
    # consumer cancels typed, conn_flood sheds), autoscaler hysteresis
    # on synthetic gauge streams, compile-free scale-up and zero-failed
    # scale-down, session survival across a holder drain, and the
    # MXNET_SERVE_GATEWAY=0 kill-switch (docs/serving.md "Gateway &
    # autoscaling")
    shift
    T1=""
    set -- tests/test_serve_gateway.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--trace-smoke" ]; then
    # fast request-tracing smoke: span-tree continuity across handoff /
    # migration / preemption-replay (one trace id end to end, no orphan
    # spans), SLO attribution folding (phases tile e2e), the flight
    # recorder dump on engine_crash/handoff_fail, JSONL sink rotation,
    # the MXNET_SERVE_TRACING=0 kill-switch parity, and the
    # span-phase-drift lint rule (docs/observability.md
    # "Request tracing")
    shift
    T1=""
    set -- tests/test_tracing.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
elif [ "$1" = "--chaos-smoke" ]; then
    # fast single-host fault-tolerance smoke: the chaos-driven recovery
    # tests (idempotent retries, snapshot/restart, nonfinite skip,
    # auto-resume) without the slow multi-process sweeps — the quick
    # check that the recovery layer still works (docs/fault_tolerance.md)
    shift
    T1=""
    set -- tests/test_fault_tolerance.py -q -m 'not slow' \
        -p no:cacheprovider "$@"
else
    T1=""
fi
# per-run log (a shared path would let concurrent runs clobber each
# other's DOTS_PASSED); kept on disk for post-mortem greps
LOG="$(mktemp /tmp/_t1.XXXXXX.log)"
$T1 env PYTHONPATH= JAX_PLATFORMS=cpu \
    python -m pytest "$@" 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)"
exit $rc
