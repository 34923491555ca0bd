"""Megastep decode & token streaming (ISSUE-16).

Contracts under test:

1. Parity: the m-step fused megastep emits token-for-token what m
   sequential single-step launches would — across the EOS, max_new and
   sequence-depth stopping edges (in-graph retirement applies the exact
   host rules mid-scan), at T=0 and under seeded T>0 sampling (the
   position-folded RNG is fed the CARRIED position per fused step), for
   any m, and with speculation on (where the megastep is the no-draft
   fallback program).
2. Kill-switch: `MXNET_SERVE_MEGASTEP=0` / megastep=False builds no
   megastep programs and leaves the PR-15 single-step loop untouched;
   the megastep needs the paged cache and a sane m.
3. Zero-retrace: every (bucket, m) megastep shape joins the frozen
   warmup set; steady state compiles nothing, the watchdog stays
   silent, nothing leaks, and the decode-loop accounting
   (`megasteps`/`megastep_tokens`/`ingraph_retired`, the `host_frac`
   gauge) moves.
4. Streaming: `req.stream()` yields each generated token exactly once,
   in order, with `result()` parity; a failed request raises its typed
   error at stream end; the per-wait timeout raises `ServeTimeout`; the
   `on_token` callback fires once per token and a consumer exception
   never kills the scheduler.
5. Streaming x durability (the ISSUE-16 regression): `engine_crash`
   mid-megastep and mid-stream migrates the request via the journal and
   the stream resumes at the positional high-water mark — no token is
   re-delivered, none is skipped, and the final stream equals the
   undisturbed oracle.
6. Chaos composition: block_exhaust/prefix_evict with the megastep on
   keep oracle parity with zero leaked blocks.
"""
import threading

import numpy as np
import pytest

from mxnet_tpu import chaos, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (ReplicaRouter, ServingEngine,
                               TransformerKVModel, ServeCancelled,
                               ServeTimeout)

V, S, L, H, E = 61, 32, 2, 2, 32


@pytest.fixture
def model_and_params():
    model = TransformerKVModel(V, S, num_layers=L, num_heads=H, num_embed=E)
    return model, model.init_params(np.random.RandomState(7))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv("MXNET_CHAOS", raising=False)
    monkeypatch.delenv("MXNET_SERVE_MEGASTEP", raising=False)
    monkeypatch.setenv("MXNET_CHAOS_SEED", "0")
    telemetry.reset()
    chaos.reset()
    yield
    telemetry.reset()
    chaos.reset()


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("decode_buckets", [4])
    kw.setdefault("prefill_buckets", [16])
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("sampling", False)
    return ServingEngine(model, params, **kw)


def _mega_engine(model, params, m=4, **kw):
    return _engine(model, params, megastep=True, megastep_steps=m, **kw)


def _run(eng, reqs_kw, timeout=300):
    reqs = [eng.submit(**kw) for kw in reqs_kw]
    eng.run_until_idle(timeout=timeout)
    return [r.result(5) for r in reqs]


def _prompts(seed=0, sizes=(3, 9, 14, 6)):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, V, size=n)) for n in sizes]


# ---------------------------------------------------------------------------
# 1. parity vs the sequential single-step oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "m", [1, pytest.param(3, marks=pytest.mark.slow), 4])
def test_megastep_token_parity_t0(model_and_params, m):
    """Greedy parity across the max_new edge (mid-megastep retirement at
    every m alignment: 5, 7, 8 new tokens) and the sequence-depth edge
    (prompt 14 + max_new 40 runs into seq_len=32)."""
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=n)
               for p, n in zip(_prompts(0), (5, 7, 40, 8))]
    base = _run(_engine(model, params, max_new_tokens=40), reqs_kw)
    eng = _mega_engine(model, params, m=m, max_new_tokens=40)
    eng.warmup()
    outs = _run(eng, reqs_kw)
    assert outs == base
    assert len(base[2]) < 40       # the depth edge really fired
    assert eng.leaked_blocks() == 0


def test_megastep_eos_edge_parity(model_and_params):
    """EOS mid-megastep: pick the oracle's 3rd greedy token as eos_id, so
    both legs must stop in-flight at the same position — in-graph for
    the fused leg, host-side for the sequential one."""
    model, params = model_and_params
    prompts = _prompts(3)
    plain = _engine(model, params)
    base0 = _run(plain, [dict(prompt=prompts[0], max_new_tokens=8)])[0]
    eos = int(base0[2])
    reqs_kw = [dict(prompt=p, max_new_tokens=8, eos_id=eos)
               for p in prompts]
    base = _run(plain, reqs_kw)
    # stopped AT the (emitted) eos token, mid-span, not at max_new
    assert len(base[0]) <= 3 and base[0][-1] == eos
    eng = _mega_engine(model, params)
    eng.warmup()
    assert _run(eng, reqs_kw) == base
    assert eng.stats["ingraph_retired"] > 0
    assert eng.leaked_blocks() == 0


def test_megastep_sampled_parity(model_and_params):
    """T>0 parity: each fused draw folds in the carried position, so the
    megastep consumes exactly the sequential RNG stream."""
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8, temperature=t, top_k=tk,
                    top_p=tp, seed=s)
               for p, t, tk, tp, s in zip(
                   _prompts(5), (0.0, 0.9, 1.3, 0.7), (0, 8, 0, 5),
                   (1.0, 1.0, 0.9, 1.0), (11, 12, 13, 14))]
    base = _run(_engine(model, params, sampling=True), reqs_kw)
    eng = _mega_engine(model, params, sampling=True)
    eng.warmup()
    assert _run(eng, reqs_kw) == base
    assert eng.leaked_blocks() == 0


@pytest.mark.slow
def test_megastep_with_spec_is_the_fallback_program(model_and_params):
    """Speculation on + megastep on: spec rounds keep the draft/verify
    path and the megastep replaces the plain single-token fallback —
    output parity vs the plain oracle either way."""
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8) for p in _prompts(7)]
    base = _run(_engine(model, params), reqs_kw)
    eng = _engine(model, params, spec=True, spec_k=3, megastep=True,
                  megastep_steps=4)
    eng.warmup()
    assert _run(eng, reqs_kw) == base
    assert eng.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# 2. kill-switch / config validation
# ---------------------------------------------------------------------------

def test_megastep_kill_switch_builds_nothing(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params)   # MXNET_SERVE_MEGASTEP unset -> off
    assert eng._mega_m == 0
    eng.warmup()
    assert not [k for k in eng._aot.keys() if k[0] == "megastep"]
    off = _engine(model, params, megastep=False)
    assert off._mega_m == 0


def test_megastep_rejects_zero_steps(model_and_params):
    model, params = model_and_params
    with pytest.raises(MXNetError):
        _mega_engine(model, params, m=0)


@pytest.mark.slow
def test_megastep_respawn_carries_config_and_compiles_nothing(
        model_and_params):
    model, params = model_and_params
    eng = _mega_engine(model, params, m=3)
    eng.warmup()
    fresh = eng.respawn()
    c0 = fresh._aot.compiles
    fresh.warmup()
    assert fresh._aot.compiles == c0   # shared AOT set: pure hits
    assert fresh._mega_m == 3
    outs = _run(fresh, [dict(prompt=_prompts(8, sizes=(6,))[0],
                             max_new_tokens=6)])
    assert len(outs[0]) == 6


# ---------------------------------------------------------------------------
# 3. zero-retrace + decode-loop accounting
# ---------------------------------------------------------------------------

def test_megastep_zero_retrace_and_accounting(model_and_params):
    model, params = model_and_params
    eng = _mega_engine(model, params, sampling=True)
    eng.warmup()
    keys = eng._aot.keys()
    assert ("megastep", 4, 4) in keys
    reg = telemetry.registry()
    c0 = reg.counter("serve.aot.compiles").value
    _run(eng, [dict(prompt=p, max_new_tokens=8, temperature=t, seed=4)
               for p, t in zip(_prompts(6), (0.0, 0.9, 0.0, 1.1))])
    assert reg.counter("serve.aot.compiles").value == c0
    assert reg.counter("serve.aot.frozen_compiles").value == 0
    assert not [e for e in telemetry.events("retrace")
                if str(e.get("site", "")).startswith("serving.")]
    # every decode token came from a fused launch; requests whose
    # stopping rule fired mid-scan retired in-graph
    st = eng.stats
    assert st["megasteps"] > 0
    assert 0 < st["megastep_tokens"] <= st["tokens"]
    assert st["megastep_tokens"] <= st["megasteps"] * eng._mega_m * \
        eng.max_batch
    assert st["ingraph_retired"] > 0
    assert reg.counter("serve.megastep_tokens").value == \
        st["megastep_tokens"]
    assert reg.counter("serve.ingraph_retired").value == \
        st["ingraph_retired"]
    # the exposed-host gauge is live (its VALUE is hardware-dependent)
    assert reg.gauge("serve.replica0.host_frac").value is not None
    assert eng.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# 4. streaming
# ---------------------------------------------------------------------------

def test_stream_yields_each_token_once_in_order(model_and_params):
    model, params = model_and_params
    eng = _mega_engine(model, params)
    eng.warmup()
    req = eng.submit(_prompts(9, sizes=(5,))[0], max_new_tokens=8)
    eng.run_until_idle(timeout=300)
    streamed = list(req.stream(timeout=5))
    assert streamed == req.result(1)
    assert len(streamed) == 8
    # a second iterator replays the full stream (per-consumer cursors)
    assert list(req.stream(timeout=5)) == streamed


def test_stream_live_consumer_and_on_token_callback(model_and_params):
    """Consume the stream WHILE the scheduler generates; a second
    request's broken callback must not disturb either."""
    model, params = model_and_params
    eng = _mega_engine(model, params)
    eng.warmup()
    seen = []

    def boom(t):
        raise RuntimeError("consumer bug")

    eng.start()
    try:
        req = eng.submit(_prompts(9, sizes=(5,))[0], max_new_tokens=8,
                         on_token=seen.append)
        bad = eng.submit(_prompts(9, sizes=(4,))[0], max_new_tokens=6,
                         on_token=boom)
        streamed = list(req.stream(timeout=60))
    finally:
        eng.stop()
    assert streamed == req.tokens
    assert seen == req.tokens            # callback: once per token
    assert len(bad.result(5)) == 6       # the broken consumer's request
    assert eng.leaked_blocks() == 0      # still finished normally


def test_stream_timeout_and_typed_error(model_and_params):
    model, params = model_and_params
    eng = _mega_engine(model, params)
    req = eng.submit(_prompts(9, sizes=(4,))[0], max_new_tokens=6)
    # nothing is serving: the per-wait timeout fires
    with pytest.raises(ServeTimeout):
        next(req.stream(timeout=0.05))
    req.cancel()
    eng.run_until_idle(timeout=300)
    # a failed request's stream drains, then raises the typed error
    with pytest.raises(ServeCancelled):
        list(req.stream(timeout=5))


# ---------------------------------------------------------------------------
# 5. streaming x durability: crash mid-megastep, mid-stream
# ---------------------------------------------------------------------------

def test_stream_survives_crash_without_restream(model_and_params,
                                                monkeypatch):
    """engine_crash kills replica0 with a megastep in flight and a live
    stream consumer attached: the journal migrates the request, replay
    regenerates only unfetched tokens, and the stream/callback see each
    position exactly once — final delivery equals the undisturbed
    oracle."""
    model, params = model_and_params
    prompt = [3, 4, 5]
    oracle = _run(_engine(model, params, max_new_tokens=12),
                  [dict(prompt=prompt, max_new_tokens=12)])[0]
    engines = [_mega_engine(model, params, max_batch=2, decode_buckets=[2],
                            max_new_tokens=12)
               for _ in range(2)]
    engines[1].name = "replica1"
    engines[1]._gauge = "serve.replica1."
    router = ReplicaRouter(engines, respawn=False)
    router.warmup()
    monkeypatch.setenv("MXNET_CHAOS", "engine_crash:2:replica0")
    chaos.reset()
    cb_seen = []
    req = engines[0].submit(prompt, deadline_ms=60000,
                            on_token=cb_seen.append)
    streamed = []

    def consume():
        for t in req.stream(timeout=120):
            streamed.append(t)

    consumer = threading.Thread(target=consume)
    consumer.start()
    router.start()
    try:
        assert req.result(timeout=120) == oracle
    finally:
        router.stop()
    consumer.join(timeout=30)
    assert not consumer.is_alive()
    assert engines[0]._dead is not None      # the crash really happened
    assert telemetry.registry().counter("serve.migrated").value == 1
    assert streamed == oracle                # exactly-once by position
    assert cb_seen == oracle
    assert engines[1].leaked_blocks() == 0


# ---------------------------------------------------------------------------
# 6. chaos composition
# ---------------------------------------------------------------------------

def test_chaos_block_exhaust_and_prefix_evict_with_megastep(
        model_and_params, monkeypatch):
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8) for p in _prompts(10)]
    base = _run(_engine(model, params), reqs_kw)
    monkeypatch.setenv("MXNET_CHAOS", "block_exhaust:0.15,prefix_evict:0.2")
    chaos.reset()
    eng = _mega_engine(model, params)
    eng.warmup()
    outs = _run(eng, reqs_kw)
    assert outs == base
    assert eng.leaked_blocks() == 0
