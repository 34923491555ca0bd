#!/bin/bash
# Nightly-style gate (reference `tests/nightly/test_all.sh`): the full test
# suite — including the slow multi-process distributed oracles and the
# accuracy-gated training runs in tests/test_train.py, tests/test_dist.py
# and tests/test_examples.py — plus REAL-DATA convergence gates on
# generated idx-format digit images (`tools/make_mnist.py`; this
# environment has no egress for the real MNIST download) and a CPU-mesh
# bench smoke.
set -e
cd "$(dirname "$0")/.."

# -- static-analysis gate (docs/static_analysis.md) -----------------------
# First and cheapest: zero unsuppressed mxlint findings (trace safety,
# donation discipline, lock discipline, registry drift, AOT-shape
# hygiene) before any compute is spent on the suites below.
./run_tests.sh --lint

./run_tests.sh tests/ -q

# -- full multi-process chaos sweep (docs/fault_tolerance.md) -------------
# The tier-1 run above already includes the fast chaos smoke and the
# slow-marked recovery tests; MXNET_CHAOS_NIGHTLY=1 additionally enables
# the heavyweight parameter sweeps (higher drop rates, more rounds) that
# are skipped everywhere else.
MXNET_CHAOS_NIGHTLY=1 ./run_tests.sh tests/test_fault_tolerance.py -q

CPU_ENV="env PYTHONPATH=$(pwd) JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8"

# -- round-6 fused-CE gates ----------------------------------------------
# (1) interpret-mode single-pass CE parity: the REAL Pallas kernel bodies
# of the round-6 single-pass + row-scaled backward structures, executed
# through the Pallas interpreter against the jnp fallbacks (a kernel-body
# regression must not ride to the chip preflight to be caught)
./run_tests.sh tests/test_pallas_interpret.py -q -k fused_ce
# (2) sharded-CE steady state: a fixed-shape training loop with
# MXNET_CE_SHARD=1 must log ZERO trainer.step retrace events after
# warmup (the retrace watchdog is the witness), and the sharded/single-
# pass grad-parity suite must hold
./run_tests.sh tests/test_fused_ce.py -q \
    -k "zero_steady_state_retraces or sharded or single_pass"

# -- real-data convergence gates (test_all.sh:44-73 check_val pattern) ----
MNIST_DIR=$(mktemp -d)/mnist
$CPU_ENV python tools/make_mnist.py --out "$MNIST_DIR" --train 8000 --test 2000

check_val() {  # check_val <logfile> <threshold> <name>
    python - "$1" "$2" "$3" <<'PY'
import re, sys
log, thr, name = open(sys.argv[1]).read(), float(sys.argv[2]), sys.argv[3]
accs = [float(m) for m in re.findall(r"final validation accuracy: ([\d.]+)", log)]
assert accs, "%s: no accuracy line in log" % name
assert min(accs) >= thr, "%s: accuracy %s < gate %s" % (name, accs, thr)
print("%s gate passed: %s >= %s" % (name, accs, thr))
PY
}

# single-device lenet, gate 0.99 (test_all.sh:55-60)
$CPU_ENV python examples/train_mnist.py --network lenet \
    --data-dir "$MNIST_DIR" --num-epochs 10 2>&1 | tee /tmp/nightly_lenet.log
check_val /tmp/nightly_lenet.log 0.99 "mnist lenet"

# dist_sync 2-worker lenet via the launcher, gate 0.98 (test_all.sh:71-73).
# Each worker trains its data shard; the server sums the 2 workers' mean
# gradients, so per-worker lr 0.05 gives the single-device-0.1 dynamics.
$CPU_ENV python tools/launch.py -n 2 \
    python examples/train_mnist.py --network lenet --data-dir "$MNIST_DIR" \
    --num-epochs 10 --lr 0.05 --kv-store dist_sync 2>&1 | tee /tmp/nightly_dist.log
check_val /tmp/nightly_dist.log 0.98 "mnist lenet dist_sync"

# -- the chip's own programs refuse to run without it ----------------------
# `python bench.py` (the training benchmark) and `python chip_smoke.py` are
# measurements and proofs of the chip: on the CPU mesh both must exit
# non-zero and print no result (tests/test_bench_store.py,
# tests/test_chip_smoke.py hold the same in tier-1)
for prog in bench.py chip_smoke.py; do
    if out=$(env PYTHONPATH= JAX_PLATFORMS=cpu python "$prog" 2>/dev/null); then
        echo "$prog ran without a TPU"; exit 1
    fi
    [ -z "$out" ] || { echo "$prog printed a result without a TPU"; exit 1; }
done

# -- input-pipeline overlap gate (docs/data_pipeline.md) ------------------
# throttled-iterator synthetic: the device prefetcher must beat the
# synchronous loop when input time ~ compute time (ISSUE-5 acceptance is
# >= 1.5x on quiet hardware; gate at 1.3x for shared-CI noise); artifact
# lands in bench_results/overlap_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu python bench.py --overlap \
    | tee /tmp/nightly_overlap.log
python - <<'PY'
import json
rec = json.loads(open("/tmp/nightly_overlap.log").read().strip().splitlines()[-1])
assert rec["value"] and rec["value"] >= 1.3, \
    "overlap gate failed: speedup %s < 1.3" % rec["value"]
print("overlap gate passed: %sx" % rec["value"])
PY

# -- serving gate (docs/serving.md) ---------------------------------------
# short Poisson-traffic run of the continuous-batching engine on the CPU
# mesh, 2 replicas, under the retrace watchdog: every request must
# complete and steady state must compile NOTHING after warmup (the
# bucketed-AOT contract); artifact lands in bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    SERVE_REQUESTS=24 SERVE_RATE=12 SERVE_REPLICAS=2 SERVE_SEQ=64 \
    SERVE_NEW=8 SERVE_PROMPT_MAX=16 \
    python bench.py --serve | tee /tmp/nightly_serve.log
python - <<'PY'
import json
rec = json.loads(open("/tmp/nightly_serve.log").read().strip().splitlines()[-1])
assert rec["completed"] == rec["requests"], \
    "serve gate: %s/%s requests completed (errors: %s)" % (
        rec["completed"], rec["requests"], rec.get("errors"))
assert rec["steady_state_recompiles"] == 0, \
    "serve gate: %d steady-state recompiles" % rec["steady_state_recompiles"]
assert rec["steady_state_retrace_events"] == 0, \
    "serve gate: retrace watchdog fired %d times after warmup" \
    % rec["steady_state_retrace_events"]
print("serve gate passed: %s tok/s/chip, p99 %s ms, occupancy %s" % (
    rec["value"], rec["latency_ms"]["p99"], rec["batch_occupancy"]))
PY

# -- prefix-caching serve gate (docs/serving.md "Prefix caching") ---------
# single-owner vs prefix-sharing A/B at EQUAL HBM under the shared-
# system-prompt trace: the prefix cache must answer strictly faster
# (ttft p50), admit a strictly higher concurrent batch, reproduce the
# single-owner outputs token for token, leak no blocks, and compile
# nothing in steady state on either leg; artifact lands in
# bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    SERVE_REQUESTS=32 SERVE_SEQ=64 SERVE_NEW=12 SERVE_PROMPT_MAX=24 \
    SERVE_PREFIX_LEN=16 MXNET_SERVE_BLOCK_SIZE=16 \
    python bench.py --serve --prefix | tee /tmp/nightly_serve_prefix.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_prefix.log").read().strip().splitlines()[-1])
single, prefix = rec["single"], rec["prefix"]
for leg, r in (("single", single), ("prefix", prefix)):
    assert r["completed"] == r["requests"], \
        "prefix gate (%s): %s/%s completed (errors: %s)" % (
            leg, r["completed"], r["requests"], r.get("errors"))
    assert r["steady_state_recompiles"] == 0, \
        "prefix gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
    assert r["steady_state_retrace_events"] == 0, \
        "prefix gate (%s): watchdog fired %d times" % (
            leg, r["steady_state_retrace_events"])
    assert r["blocks"]["leaked"] == 0, \
        "prefix gate (%s): %d blocks leaked" % (leg, r["blocks"]["leaked"])
assert rec["token_parity"], \
    "prefix gate: outputs diverged between single-owner and prefix legs"
assert prefix["ttft_ms"]["p50"] < single["ttft_ms"]["p50"], \
    "prefix gate: ttft p50 %s not below single-owner %s" % (
        prefix["ttft_ms"]["p50"], single["ttft_ms"]["p50"])
assert prefix["max_concurrent"] > single["max_concurrent"], \
    "prefix gate: concurrency %s not above single-owner %s at equal HBM" \
    % (prefix["max_concurrent"], single["max_concurrent"])
print("prefix gate passed: ttft p50 %s->%s ms (%sx), concurrency %s->%s, "
      "hit_rate %s" % (single["ttft_ms"]["p50"], prefix["ttft_ms"]["p50"],
                       rec["value"], single["max_concurrent"],
                       prefix["max_concurrent"], rec["prefix_hit_rate"]))
PY

# -- memory-tiering serve gate (docs/serving.md "Memory tiering &
# sessions") --------------------------------------------------------------
# evict-and-recompute vs host-tier A/B at EQUAL HBM with a hot-prefix
# working set >= 4x the device block capacity: the tier leg must hit
# strictly more prefix tokens and answer strictly faster (ttft p50)
# with token-for-token parity (a restore is the same bytes), zero
# leaked blocks in EITHER tier, and zero steady-state recompiles on
# both legs (the restore program is part of the frozen warmup set);
# artifact lands in bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    python bench.py --serve --tier | tee /tmp/nightly_serve_tier.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_tier.log").read().strip().splitlines()[-1])
single, tier = rec["single"], rec["tier"]
for leg, r in (("single", single), ("tier", tier)):
    assert r["completed"] == r["requests"], \
        "tier gate (%s): %s/%s completed (errors: %s)" % (
            leg, r["completed"], r["requests"], r.get("errors"))
    assert r["steady_state_recompiles"] == 0, \
        "tier gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
    assert r["steady_state_retrace_events"] == 0, \
        "tier gate (%s): watchdog fired %d times" % (
            leg, r["steady_state_retrace_events"])
    assert r["blocks"]["leaked"] == 0, \
        "tier gate (%s): %d blocks leaked" % (leg, r["blocks"]["leaked"])
assert rec["working_set_tokens"] >= 4 * rec["device_capacity_tokens"], \
    "tier gate: working set %s < 4x device capacity %s" % (
        rec["working_set_tokens"], rec["device_capacity_tokens"])
assert rec["token_parity"], \
    "tier gate: outputs diverged between evict and tier legs"
assert rec["hit_rate"]["tier"] > rec["hit_rate"]["single"], \
    "tier gate: hit rate %s not above evict-and-recompute %s" % (
        rec["hit_rate"]["tier"], rec["hit_rate"]["single"])
assert rec["ttft_p50_ms"]["tier"] < rec["ttft_p50_ms"]["single"], \
    "tier gate: ttft p50 %s not below evict-and-recompute %s" % (
        rec["ttft_p50_ms"]["tier"], rec["ttft_p50_ms"]["single"])
assert rec["host_leaked"] == 0, \
    "tier gate: %d host-tier blocks leaked" % rec["host_leaked"]
print("tier gate passed: ttft p50 %s->%s ms (%sx), hit_rate %s->%s, "
      "spilled %s restored %s" % (
          rec["ttft_p50_ms"]["single"], rec["ttft_p50_ms"]["tier"],
          rec["value"], rec["hit_rate"]["single"], rec["hit_rate"]["tier"],
          rec["spilled"], rec["restored"]))
PY

# -- memory-tiering smoke: spill/restore/session/chaos unit coverage ------
./run_tests.sh --serve-tier-smoke

# -- speculative-decoding serve gate (docs/serving.md "Speculative
# decoding") --------------------------------------------------------------
# draft-verify vs one-token-per-step A/B at EQUAL HBM on the templated
# mixed-length trace: the spec leg must deliver >= 1.5x tok/s/chip with
# token-for-token output parity at temperature 0 (speculation is exact,
# not approximate), zero leaked blocks, and zero steady-state recompiles
# on either leg (the verify/draft shapes all join the frozen warmup
# set); artifact lands in bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    SERVE_REQUESTS=64 \
    python bench.py --serve --spec | tee /tmp/nightly_serve_spec.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_spec.log").read().strip().splitlines()[-1])
off, spec = rec["off"], rec["spec"]
for leg, r in (("off", off), ("spec", spec)):
    assert r["completed"] == r["requests"], \
        "spec gate (%s): %s/%s completed (errors: %s)" % (
            leg, r["completed"], r["requests"], r.get("errors"))
    assert r["steady_state_recompiles"] == 0, \
        "spec gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
    assert r["steady_state_retrace_events"] == 0, \
        "spec gate (%s): watchdog fired %d times" % (
            leg, r["steady_state_retrace_events"])
    assert r["blocks"]["leaked"] == 0, \
        "spec gate (%s): %d blocks leaked" % (leg, r["blocks"]["leaked"])
assert rec["token_parity"], \
    "spec gate: outputs diverged between spec and non-spec legs"
assert rec["value"] >= 1.5, \
    "spec gate: %sx tok/s/chip below the 1.5x acceptance floor " \
    "(accept_rate %s)" % (rec["value"], rec["accept_rate"])
print("spec gate passed: %sx tok/s (%s -> %s), accept_rate %s, "
      "drafter %s k=%s" % (rec["value"], rec["tok_s"]["off"],
                           rec["tok_s"]["spec"], rec["accept_rate"],
                           rec["drafter"], rec["k"]))
PY

# -- speculative-decoding chaos smoke: draft_junk + block_exhaust +
# prefix_evict with speculation ON must keep token parity (run_tests.sh
# --serve-spec-smoke runs the same clauses as unit tests)
./run_tests.sh --serve-spec-smoke -k "chaos or preemption"

# -- megastep-decode gate (docs/serving.md "Megastep decode &
# streaming") -------------------------------------------------------------
# one-token-per-launch vs m-step fused megastep A/B at small batch on
# the templated mixed trace: the megastep leg must deliver STRICTLY
# higher tok/s/chip (the whole point is removing the per-token host
# round-trip), drive the exposed-host fraction below 0.5 and below the
# single-step leg's, keep token-for-token greedy parity (the fused scan
# is exact, not approximate), and leak nothing / recompile nothing on
# either leg (every (bucket, m) megastep shape joins the frozen warmup
# set); artifact lands in bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    SERVE_REQUESTS=64 \
    python bench.py --serve --megastep | tee /tmp/nightly_serve_megastep.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_megastep.log").read().strip().splitlines()[-1])
off, mega = rec["off"], rec["megastep"]
for leg, r in (("off", off), ("megastep", mega)):
    assert r["completed"] == r["requests"], \
        "megastep gate (%s): %s/%s completed (errors: %s)" % (
            leg, r["completed"], r["requests"], r.get("errors"))
    assert r["steady_state_recompiles"] == 0, \
        "megastep gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
    assert r["steady_state_retrace_events"] == 0, \
        "megastep gate (%s): watchdog fired %d times" % (
            leg, r["steady_state_retrace_events"])
    assert r["blocks"]["leaked"] == 0, \
        "megastep gate (%s): %d blocks leaked" % (leg, r["blocks"]["leaked"])
assert rec["token_parity"], \
    "megastep gate: outputs diverged between megastep and single-step legs"
assert rec["value"] > 1.0, \
    "megastep gate: %sx tok/s/chip — megastep must be strictly faster " \
    "than one-token-per-launch at small batch" % rec["value"]
hf_off, hf_mega = rec["host_frac"]["off"], rec["host_frac"]["megastep"]
assert hf_mega is not None and hf_mega < 0.5, \
    "megastep gate: exposed host fraction %s not driven below 0.5" % hf_mega
assert hf_off is None or hf_mega < hf_off, \
    "megastep gate: host_frac did not shrink (off %s -> megastep %s)" % (
        hf_off, hf_mega)
assert rec["ingraph_retired"] > 0, \
    "megastep gate: no request ever retired in-graph mid-scan"
print("megastep gate passed: %sx tok/s (%s -> %s), m=%s, host_frac "
      "%s -> %s, ingraph_retired %s" % (
          rec["value"], rec["tok_s"]["off"], rec["tok_s"]["megastep"],
          rec["m"], hf_off, hf_mega, rec["ingraph_retired"]))
PY

# -- megastep chaos + streaming smoke: engine_crash mid-megastep and
# mid-stream must replay from the journal without re-streaming delivered
# tokens (run_tests.sh --serve-megastep-smoke runs the same clauses as
# unit tests)
./run_tests.sh --serve-megastep-smoke -k "chaos or crash or stream"

# -- serve-chaos gate (docs/serving.md "Failure semantics") ---------------
# the same Poisson run with one replica crashed mid-traffic, slow decode
# steps, and injected launch errors: every request must RESOLVE (tokens
# or a typed error — zero hung), the crash must fail over and respawn,
# and recovery must compile nothing (the respawned replica warms from
# the shared AOT cache); artifact lands in bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    SERVE_REQUESTS=24 SERVE_RATE=12 SERVE_REPLICAS=2 SERVE_SEQ=64 \
    SERVE_NEW=8 SERVE_PROMPT_MAX=16 SERVE_DEADLINE_MS=30000 \
    MXNET_CHAOS="engine_crash:6:replica0,decode_slow:0.1:10,launch_error:0.05,block_exhaust:0.1,prefix_evict:0.1,handoff_fail:0.05" \
    python bench.py --serve --chaos | tee /tmp/nightly_serve_chaos.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_chaos.log").read().strip().splitlines()[-1])
assert rec["hung"] == 0, "serve-chaos gate: %d hung requests" % rec["hung"]
assert rec["resolved"] == rec["requests"], \
    "serve-chaos gate: %s/%s requests resolved (errors: %s)" % (
        rec["resolved"], rec["requests"], rec.get("errors"))
assert rec["resilience"].get("failovers", 0) >= 1, \
    "serve-chaos gate: injected crash never failed over (%s)" % \
    rec["resilience"]
assert rec["steady_state_recompiles"] == 0, \
    "serve-chaos gate: %d recompiles after failover" \
    % rec["steady_state_recompiles"]
assert rec["steady_state_retrace_events"] == 0, \
    "serve-chaos gate: retrace watchdog fired %d times" \
    % rec["steady_state_retrace_events"]
print("serve-chaos gate passed: %s/%s resolved, resilience %s, "
      "deadline hit_rate %s" % (rec["resolved"], rec["requests"],
                                rec["resilience"],
                                rec["deadline"]["hit_rate"]))
PY

# -- quantized-serving gate (docs/serving.md "Quantization") --------------
# bf16 vs int8-weights+int8-KV A/B at EQUAL HBM on the mixed trace: the
# quant leg must admit >= 1.8x the concurrency OR deliver >= 1.3x
# tok/s/chip, the logit-error/token-match parity gate must pass against
# the bf16 oracle, MXNET_SERVE_QUANT=0 (the bf16 leg) runs the PR-13
# programs bit for bit, zero leaked blocks and zero steady-state
# recompiles on BOTH legs (quantized programs join the frozen warmup
# set); artifact lands in bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    SERVE_REQUESTS=32 \
    python bench.py --serve --quant | tee /tmp/nightly_serve_quant.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_quant.log").read().strip().splitlines()[-1])
for leg in ("bf16", "quant"):
    r = rec[leg]
    assert r["completed"] == r["requests"], \
        "quant gate (%s): %s/%s completed (errors: %s)" % (
            leg, r["completed"], r["requests"], r.get("errors"))
    assert r["steady_state_recompiles"] == 0, \
        "quant gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
    assert r["steady_state_retrace_events"] == 0, \
        "quant gate (%s): watchdog fired %d times" % (
            leg, r["steady_state_retrace_events"])
    assert r["blocks"]["leaked"] == 0, \
        "quant gate (%s): %d blocks leaked" % (leg, r["blocks"]["leaked"])
assert rec["concurrency_gain"] >= 1.8 or rec["tok_s_gain"] >= 1.3, \
    "quant gate: concurrency %sx and tok/s %sx both below the " \
    "1.8x/1.3x acceptance floor at equal HBM" % (
        rec["concurrency_gain"], rec["tok_s_gain"])
assert rec["parity_gate"]["passed"], \
    "quant gate: parity failed (%s vs gate %s)" % (
        rec["parity"], rec["parity_gate"])
print("quant gate passed: concurrency %sx (%s->%s), tok/s %sx, "
      "logit_err_rel %s, token_match %s" % (
          rec["concurrency_gain"], rec["bf16"]["max_concurrent"],
          rec["quant"]["max_concurrent"], rec["tok_s_gain"],
          rec["parity"]["logit_err_rel"],
          rec["parity"]["token_match_rate"]))
PY

# -- quantization smoke: codec/parity/kill-switch/chaos unit coverage -----
./run_tests.sh --serve-quant-smoke

# -- serve-durability gate (docs/serving.md "Durability") -----------------
# kill-one-of-two-replicas mid-Poisson with the request journal ON: 100%
# of requests — including the dead replica's ADMITTED in-flight ones,
# which migrate via exact journal replay — must complete OK with
# token-for-token parity vs an undisturbed oracle run (T=0: replay, not
# re-generation divergence), and a rolling restart (router.drain of each
# replica in turn, mid-traffic) must lose nothing; zero leaked blocks,
# zero steady-state compiles on every leg (respawned/drained replicas
# warm from the shared AOT cache); artifact lands in
# bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    SERVE_REQUESTS=24 \
    python bench.py --serve --durability | tee /tmp/nightly_serve_durab.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_durab.log").read().strip().splitlines()[-1])
for leg in ("oracle", "crash", "drain"):
    r = rec[leg]
    assert r["hung"] == 0, \
        "durability gate (%s): %d hung requests" % (leg, r["hung"])
    assert r["failed"] == 0, \
        "durability gate (%s): %d failed requests" % (leg, r["failed"])
    assert r["completed"] == rec["requests"], \
        "durability gate (%s): %s/%s completed" % (
            leg, r["completed"], rec["requests"])
    assert r["leaked"] == 0, \
        "durability gate (%s): %d blocks leaked" % (leg, r["leaked"])
    assert r["steady_state_recompiles"] == 0, \
        "durability gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
assert rec["parity_crash"] and rec["parity_drain"], \
    "durability gate: tokens diverged from the oracle run " \
    "(crash parity %s, drain parity %s)" % (
        rec["parity_crash"], rec["parity_drain"])
assert rec["crash"]["counters"].get("migrated", 0) >= 1, \
    "durability gate: the crash leg never migrated an in-flight request"
assert rec["crash"]["counters"].get("replays", 0) >= 1, \
    "durability gate: no migrated request replayed on a survivor"
assert rec["drain"]["counters"].get("drained", 0) >= 2, \
    "durability gate: the rolling restart drained %s replicas, want 2" \
    % rec["drain"]["counters"].get("drained", 0)
print("durability gate passed: parity %s, crash counters %s, "
      "drain counters %s" % (rec["value"], rec["crash"]["counters"],
                             rec["drain"]["counters"]))
PY

# -- serve-durability smoke: migration/drain/anti-thrash unit coverage ----
./run_tests.sh --serve-durability-smoke

# -- disaggregation gate (docs/serving.md "Disaggregated
# prefill/decode") --------------------------------------------------------
# colocated vs prefill/decode-split fleet at EQUAL chips on the burst
# trace (Poisson short-prompt/long-output background + periodic
# long-prompt storms): the disagg leg must keep background decode
# inter-token p99 STRICTLY lower (storms queue on the prefill role
# instead of stalling decode streams), ttft no worse, token-for-token
# output parity (the handoff resumes the exact uniform resume tuple),
# nonzero handoffs with zero fails, zero leaked blocks and zero
# steady-state compiles on BOTH legs (the decode role's restore-scatter
# buckets join the frozen warmup set); artifact lands in
# bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    SERVE_REQUESTS=48 \
    python bench.py --serve --disagg | tee /tmp/nightly_serve_disagg.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_disagg.log").read().strip().splitlines()[-1])
for leg in ("colocated", "disagg"):
    r = rec[leg]
    assert r["hung"] == 0, \
        "disagg gate (%s): %d hung requests" % (leg, r["hung"])
    assert r["completed"] == r["requests"], \
        "disagg gate (%s): %s/%s completed (errors: %s)" % (
            leg, r["completed"], r["requests"], r.get("errors"))
    assert r["blocks"]["leaked"] == 0, \
        "disagg gate (%s): %d blocks leaked" % (leg, r["blocks"]["leaked"])
    assert r["steady_state_recompiles"] == 0, \
        "disagg gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
    assert r["steady_state_retrace_events"] == 0, \
        "disagg gate (%s): watchdog fired %d times" % (
            leg, r["steady_state_retrace_events"])
assert rec["parity"], \
    "disagg gate: outputs diverged between colocated and disagg legs"
assert rec["value"] > 1.0, \
    "disagg gate: %sx background inter-token p99 — role separation " \
    "must keep decode strictly flatter under storms" % rec["value"]
colo_ttft, dis_ttft = (rec["ttft_p50_ms"]["colocated"],
                       rec["ttft_p50_ms"]["disagg"])
assert dis_ttft <= colo_ttft * 1.25, \
    "disagg gate: ttft p50 regressed (%s -> %s ms)" % (colo_ttft,
                                                       dis_ttft)
assert rec["handoffs"] >= 1, \
    "disagg gate: the disagg leg never handed off a prefill"
assert rec["handoff_fails"] == 0, \
    "disagg gate: %d handoff transfers died" % rec["handoff_fails"]
print("disagg gate passed: itl p99 %sx (%s -> %s ms), ttft p50 "
      "%s -> %s ms, %s handoffs" % (
          rec["value"], rec["itl_p99_ms"]["colocated"],
          rec["itl_p99_ms"]["disagg"], colo_ttft, dis_ttft,
          rec["handoffs"]))
PY

# -- disaggregation smoke: handoff parity/failure/affinity/drain-fence
# unit coverage (run_tests.sh --serve-disagg-smoke)
./run_tests.sh --serve-disagg-smoke

# -- sharded-replica serve gate (docs/serving.md "Sharded replicas") ------
# equal-chip A/B on the CPU mesh with an expert-parallel MoE model:
# k single-device replicas (each holding the FULL model — only possible
# here because the virtual CPU devices share host RAM) vs ONE k-device
# sub-mesh replica.  The AOT memory accounting is the existence proof
# the sharded path exists for: a synthetic per-chip budget strictly
# between the sharded leg's per-device slice and the replicated leg's
# full-model footprint names a config that CANNOT serve unsharded but
# serves sharded — with greedy token parity request-for-request, zero
# leaked blocks, and zero steady-state recompiles on both legs (every
# pjit launch joins the frozen per-mesh-signature warmup set);
# artifact lands in bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    SERVE_REQUESTS=24 SERVE_RATE=12 SERVE_SEQ=64 SERVE_NEW=8 \
    SERVE_PROMPT_MAX=16 SERVE_EMBED=256 SERVE_HEADS=4 \
    SERVE_SHARD_DEVICES=4 SERVE_MOE_EXPERTS=4 \
    python bench.py --serve --sharded | tee /tmp/nightly_sharded.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_sharded.log").read().strip().splitlines()[-1])
rep, sha = rec["replicated"], rec["sharded"]
for leg, r in (("replicated", rep), ("sharded", sha)):
    assert r["completed"] == r["requests"], \
        "sharded gate (%s): %s/%s completed (errors: %s)" % (
            leg, r["completed"], r["requests"], r.get("errors"))
    assert r["steady_state_recompiles"] == 0, \
        "sharded gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
    assert r["steady_state_retrace_events"] == 0, \
        "sharded gate (%s): watchdog fired %d times" % (
            leg, r["steady_state_retrace_events"])
    assert r["blocks"]["leaked"] == 0, \
        "sharded gate (%s): %d blocks leaked" % (leg, r["blocks"]["leaked"])
assert rec["parity"], \
    "sharded gate: outputs diverged between replicated and sharded legs"
rep_dev = rep["memory"]["per_device_bytes"]
sha_dev = sha["memory"]["per_device_bytes"]
# the sub-mesh must buy REAL per-chip headroom: at least a third of the
# full-model footprint (params + the KV pool's embed axis split k ways;
# replicated norms/tables keep it from 1/k exactly)
assert sha_dev <= rep_dev * 2 / 3, \
    "sharded gate: per-device %s bytes is not under 2/3 of the " \
    "full-model %s — sharding bought no memory headroom" % (
        sha_dev, rep_dev)
budget = (sha_dev + rep_dev) // 2
moe = sha["moe"]
assert moe and moe["experts"] == 4 and sum(moe["expert_load"]) > 0, \
    "sharded gate: expert-parallel decode routed nothing (%s)" % (moe,)
print("sharded gate passed: tok/s/chip ratio %s, per-device %s -> %s "
      "bytes (a %s-byte chip serves ONLY sharded), moe imbalance %s" % (
          rec["value"], rep_dev, sha_dev, budget,
          moe["load_imbalance"]))
PY

# -- sharded smoke: oracle parity (T=0 + seeded T>0), kill-switch
# bit-parity, per-shard-count zero-retrace, chaos with a sub-mesh
# replica, MoE expert-parallel unit coverage
# (run_tests.sh --serve-sharded-smoke)
./run_tests.sh --serve-sharded-smoke

# -- tracing gate (docs/observability.md "Request tracing") ---------------
# tracing-on vs MXNET_SERVE_TRACING=0 at equal everything on the disagg
# burst trace: traced tok/s within 3% of untraced, output_sig bit for
# bit, zero steady-state compiles and zero retrace events on BOTH legs
# (the span layer is host-side bookkeeping only), one ok root per
# completed request with no orphan spans, at least one span tree
# crossing the prefill->decode boundary when handoffs happened,
# interval phases tiling >=80% of e2e, and ZERO span records on the =0
# leg; artifact lands in bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    SERVE_REQUESTS=48 \
    python bench.py --serve --tracing | tee /tmp/nightly_serve_trace.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_trace.log").read().strip().splitlines()[-1])
for leg in ("traced", "untraced"):
    r = rec[leg]
    assert r["hung"] == 0, \
        "tracing gate (%s): %d hung requests" % (leg, r["hung"])
    assert r["steady_state_recompiles"] == 0, \
        "tracing gate (%s): %d steady-state recompiles" % (
            leg, r["steady_state_recompiles"])
    assert r["steady_state_retrace_events"] == 0, \
        "tracing gate (%s): watchdog fired %d times" % (
            leg, r["steady_state_retrace_events"])
assert rec["parity"], \
    "tracing gate: outputs diverged between traced and untraced legs"
assert rec["value"] >= 0.97, \
    "tracing gate: traced throughput is %sx untraced — span overhead " \
    "must stay within 3%%" % rec["value"]
sp = rec["spans"]
assert sp["roots_ok"] == rec["traced"]["completed"], \
    "tracing gate: %s ok span roots for %s completed requests" % (
        sp["roots_ok"], rec["traced"]["completed"])
assert sp["orphans"] == 0, \
    "tracing gate: %d orphan spans (parent sid unresolved)" % sp["orphans"]
if sp["handoffs"] >= 1:
    assert sp["cross_replica_traces"] >= 1, \
        "tracing gate: %d handoffs but no span tree crosses replicas" \
        % sp["handoffs"]
assert sp["attributed_frac"] is not None and \
    sp["attributed_frac"] >= 0.8, \
    "tracing gate: interval phases cover only %s of e2e" \
    % sp["attributed_frac"]
assert rec["untraced_span_records"] == 0, \
    "tracing gate: MXNET_SERVE_TRACING=0 leg emitted %d span records" \
    % rec["untraced_span_records"]
print("tracing gate passed: %sx tok/s, %s spans / %s traces, "
      "%s cross-replica, attribution %s, %s recorder dumps" % (
          rec["value"], sp["records"], sp["traces"],
          sp["cross_replica_traces"], sp["attributed_frac"],
          sp["recorder_dumps"]))
PY

# -- tracing smoke: span continuity / flight recorder / kill-switch unit
# coverage (run_tests.sh --trace-smoke)
./run_tests.sh --trace-smoke

# -- elastic-soak gate (docs/serving.md "Gateway & autoscaling") ----------
# the HTTP/SSE gateway fronting an autoscaled fleet through a Poisson
# soak with a mid-run load step: the fleet must scale UP during the
# burst and back DOWN after (every scale-up warming compile-free from
# the shared AOT cache), zero failed requests across the resize, ttfb
# at the gateway within 10% of engine ttft (joined per-trace from the
# span stream), bounded gateway memory (open_conns returns to 0), the
# serve.gateway.* / serve.scale_ups / serve.scale_downs counters
# consistent with the request log, and all three gateway chaos clauses
# (client_disconnect, slow_consumer, conn_flood) green alone AND
# composed with engine_crash under the autoscaler; artifact lands in
# bench_results/serve_bench.json
env PYTHONPATH= JAX_PLATFORMS=cpu \
    python bench.py --serve --elastic | tee /tmp/nightly_serve_elastic.log
python - <<'PY'
import json
rec = json.loads(
    open("/tmp/nightly_serve_elastic.log").read().strip().splitlines()[-1])
g, soak = rec["gates"], rec["soak"]
assert g["zero_failed"], \
    "elastic gate: %s failed / %s hung requests" % (soak["failed"],
                                                    soak["hung"])
assert g["zero_steady_state_compiles"], \
    "elastic gate: %s compiles after warmup (scale-up must be " \
    "compile-free off the shared AOT cache)" % soak["steady_state_compiles"]
assert g["scaled_up_and_down"], \
    "elastic gate: fleet never grew AND shrank back (fleet %s, " \
    "scale_ups %s, scale_downs %s)" % (soak["fleet"], soak["scale_ups"],
                                       soak["scale_downs"])
assert g["ttfb_within_10pct_of_ttft"], \
    "elastic gate: gateway ttfb %s ms vs engine ttft %s ms" % (
        soak["ttfb_ms_mean"], soak["ttft_ms_mean"])
assert g["gateway_memory_bounded"], \
    "elastic gate: open_conns peaked at %s (conn_max %s)" % (
        soak["open_conns_peak"], soak["conn_max"])
assert g["counters_consistent"], \
    "elastic gate: serve.gateway.* counters disagree with the request log"
assert g["chaos_legs_green"], \
    "elastic gate: gateway chaos legs failed: %s" % [
        leg for leg in rec["chaos_legs"] if not leg["green"]]
assert rec["all_gates_passed"]
print("elastic gate passed: fleet 1->%s->%s, %s ups / %s downs, "
      "ttfb %s vs ttft %s ms, %s/%s served, %s tok/s" % (
          soak["fleet"]["peak"], soak["fleet"]["end"],
          soak["scale_ups"], soak["scale_downs"],
          soak["ttfb_ms_mean"], soak["ttft_ms_mean"],
          soak["requests"] - soak["failed"], soak["requests"],
          rec["value"]))
PY

# -- gateway smoke: HTTP/SSE parity, backpressure failure matrix,
# autoscaler hysteresis, session-drain migration, kill-switch unit
# coverage (run_tests.sh --gateway-smoke)
./run_tests.sh --gateway-smoke
echo "nightly: all gates passed"
