"""Quantization subsystem: int8/fp8 serving weights + int8 paged KV
(ISSUE-14).

Contracts under test:

1. Codec: symmetric per-channel/per-row round-trip error is bounded by
   half a quantization step per channel (int8) and the e4m3 mantissa
   (fp8); the wire format round-trips exactly through encode/decode and
   shrinks the payload; kill-switch spellings resolve to None.
2. `quantize_params`: the matmul weights (and only those) quantize to
   1-byte storage with `<name>_qscale` beside them; idempotent.
3. Output parity: `quant.parity_report` against the bf16 oracle passes
   the default logit-error/token-match gate, and a quantized ENGINE
   emits (leading-)matching greedy streams vs its bf16 twin on the
   same request set.
4. Kill-switch: `MXNET_SERVE_QUANT=0` builds no guard, no scales, a
   plain-array pool, and bit-for-bit identical tokens run to run.
5. Composition: prefix sharing + CoW carry the per-row scales (repeat
   prompt bootstraps, CoWs, and matches the unshared oracle);
   speculative decoding under quant is token-for-token the quantized
   sequential path; the host tier spills/restores int8 pairs at a
   fraction of the f32 bytes with zero leaks in either tier.
6. Runtime integrity: `scale_corrupt:P` chaos NaNs held-block scales —
   every affected request resolves typed (`ServeQuantError` after the
   one replay retry), never with silent wrong tokens; composes with
   `block_exhaust` + `engine_crash` under a 2-replica router.
7. Zero-retrace: quantized programs join the frozen warmup bucket set —
   zero steady-state compiles, no serving.* retrace events.
8. PS wire: `MXNET_PS_QUANT=int8` round-trips through a live
   ParameterServer within the group-scale error bound with a smaller
   payload; `=0` is bit-for-bit.
"""
import os
import socket
import threading

import numpy as np
import pytest

from mxnet_tpu import chaos, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.quant import (QuantSpec, resolve, fp8_supported, quantize,
                             dequantize, quantize_rows, encode_wire,
                             decode_wire, wire_nbytes, parity_report)
from mxnet_tpu.serving import (ServingEngine, ReplicaRouter,
                               TransformerKVModel, PrefixCache,
                               HostBlockTier, ServeQuantError, ServeError)

V, S, L, H, E = 61, 64, 2, 2, 32
BS = 4          # block size used by every engine below


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerKVModel(V, S, num_layers=L, num_heads=H, num_embed=E)
    return model, model.init_params(np.random.RandomState(7))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    telemetry.reset()
    chaos.reset()
    monkeypatch.delenv("MXNET_CHAOS", raising=False)
    monkeypatch.delenv("MXNET_SERVE_QUANT", raising=False)
    monkeypatch.delenv("MXNET_SERVE_KV_QUANT", raising=False)
    yield
    telemetry.reset()
    chaos.reset()


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("sampling", False)
    kw.setdefault("block_size", BS)
    kw.setdefault("n_blocks", 33)
    eng = ServingEngine(model, params, **kw)
    eng.warmup()
    return eng


def _serve(eng, prompts, max_new=6):
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run_until_idle(timeout=300)
    return [r.result(5) for r in reqs]


def _prompts(n=4, seed=3, lo=3, hi=20):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, V, size=int(rng.randint(lo, hi))))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# 1-2. codec + quantize_params
# ---------------------------------------------------------------------------

def test_codec_roundtrip_int8_bounds():
    w = np.random.RandomState(0).randn(8, 48).astype(np.float32)
    q, s = quantize(w, "int8", axis=0)
    assert q.dtype == np.int8 and s.shape == (8,)
    err = np.abs(np.asarray(dequantize(q, s, axis=0)) - w)
    step = np.abs(w).max(axis=1) / 127.0
    assert (err.max(axis=1) <= step * 0.5 + 1e-7).all()
    # per-row layout: one scale per leading index
    q2, s2 = quantize_rows(w, resolve("int8"))
    assert s2.shape == (8,)
    err2 = np.abs(np.asarray(dequantize(q2, s2)) - w)
    assert (err2.max(axis=1) <= step * 0.5 + 1e-7).all()
    # zero channels round-trip to exact zeros (scale guard)
    z = np.zeros((2, 4), np.float32)
    qz, sz = quantize(z, "int8", axis=0)
    assert np.array_equal(np.asarray(dequantize(qz, sz, axis=0)), z)


@pytest.mark.skipif(not fp8_supported(), reason="no fp8 on this platform")
def test_codec_roundtrip_fp8():
    w = np.random.RandomState(1).randn(4, 64).astype(np.float32) * 3
    q, s = quantize(w, "fp8", axis=0)
    wd = np.asarray(dequantize(q, s, axis=0))
    # e4m3: 3 mantissa bits -> relative error <= 2^-4 per value after
    # the amax scaling (plus the subnormal floor near zero)
    assert np.abs(wd - w).max() <= np.abs(w).max() * (2 ** -4) + 1e-6
    assert resolve("fp8") == QuantSpec("fp8")


def test_codec_wire_and_resolve():
    arr = (np.random.RandomState(2).randn(1000).astype(np.float32) * 5
           ).reshape(10, 100)
    msg = encode_wire(arr, "int8")
    out = decode_wire(msg)
    assert out.shape == arr.shape and out.dtype == arr.dtype
    assert wire_nbytes(msg) < arr.nbytes / 3
    step = np.abs(arr).max() / 127.0
    assert np.abs(out - arr).max() <= step * 0.5 + 1e-7
    # decode is deterministic and exact on the quantized bits
    assert np.array_equal(out, decode_wire(encode_wire(arr, "int8")))
    for off in (None, "", "0", "none", "off", "false"):
        assert resolve(off) is None
    with pytest.raises(MXNetError):
        resolve("int4")
    with pytest.raises(MXNetError):
        quantize(arr, None)


def test_quantize_params_names_and_idempotence(model_and_params):
    model, params = model_and_params
    qm = model.with_quant("int8", "int8")
    qp = qm.quantize_params(params)
    names = set(qm._quant_weight_names())
    assert "embed_weight" in names and "pred_weight" in names
    for n in names:
        assert qp[n].dtype == np.int8
        assert qp[n + "_qscale"].dtype == np.float32
    # LN/bias/positional stay full precision
    assert qp["final_ln_gamma"].dtype == model.dtype
    assert qp["pos_embed_weight"].dtype == model.dtype
    assert "layer0_ln1_gamma_qscale" not in qp
    assert qm.quantize_params(qp) is qp  # idempotent
    # the original model object is untouched (with_quant is a view)
    assert model.quant is None and model.kv_quant is None
    assert model.quantize_params(params) is params


# ---------------------------------------------------------------------------
# 3. output parity vs the bf16 oracle
# ---------------------------------------------------------------------------

def test_parity_report_gate(model_and_params):
    model, params = model_and_params
    qm = model.with_quant("int8", "int8")
    qp = qm.quantize_params(params)
    rep = parity_report(model, params, qm, qp, _prompts(4), max_new=6,
                        block_size=BS)
    assert rep["logit_err_rel"] <= 0.05, rep
    assert rep["token_match_rate"] >= 0.75, rep
    g = telemetry.registry().gauge("serve.quant_logit_err").value
    assert g == rep["logit_err_rel"]


def test_engine_parity_vs_bf16(model_and_params):
    model, params = model_and_params
    prompts = _prompts(5)
    base = _serve(_engine(model, params, quant="0"), prompts)
    qt = _serve(_engine(model, params, quant="int8"), prompts)
    lead = []
    for a, b in zip(base, qt):
        n = 0
        for x, y in zip(a, b):
            if x != y:
                break
            n += 1
        lead.append(n / float(max(len(a), 1)))
    assert np.mean(lead) >= 0.8, (base, qt)


def test_weight_only_quant_and_fp8(model_and_params):
    """Weight quant without KV quant (explicit =0) keeps the pool a
    plain array and still serves; fp8 weights serve where supported."""
    model, params = model_and_params
    eng = _engine(model, params, quant="int8", kv_quant="0")
    assert not isinstance(eng._cache, tuple)
    toks = _serve(eng, _prompts(2))
    assert all(len(t) > 0 for t in toks)
    if fp8_supported():
        eng8 = _engine(model, params, quant="fp8", kv_quant="0")
        toks8 = _serve(eng8, _prompts(2))
        assert all(len(t) > 0 for t in toks8)
        assert eng8.warmup()["quant"] == {"weights": "fp8", "kv": None}


# ---------------------------------------------------------------------------
# 4. kill-switch
# ---------------------------------------------------------------------------

def test_kill_switch_bit_for_bit(model_and_params):
    model, params = model_and_params
    prompts = _prompts(4)
    eng = _engine(model, params, quant="0")
    assert eng._quant is None and eng._kv_quant is None
    assert not eng._quant_gate
    assert not isinstance(eng._cache, tuple)
    assert not any(k.endswith("_qscale") for k in eng._params)
    assert eng.warmup()["quant"] is None
    a = _serve(eng, prompts)
    b = _serve(_engine(model, params, quant="0"), prompts)
    c = _serve(_engine(model, params), prompts)  # env default: off
    assert a == b == c
    assert eng.stats["quant_trips"] == 0


# ---------------------------------------------------------------------------
# 5. composition: prefix/CoW, spec decode, host tier
# ---------------------------------------------------------------------------

def test_prefix_cow_carry_scales(model_and_params):
    model, params = model_and_params
    shared = list(np.random.RandomState(11).randint(0, V, size=3 * BS))
    oracle = _serve(_engine(model, params, quant="int8", prefix=False),
                    [shared], max_new=5)[0]
    eng = _engine(model, params, quant="int8")
    t1 = _serve(eng, [shared], max_new=5)[0]
    t2 = _serve(eng, [shared], max_new=5)[0]  # full-cover bootstrap
    assert t1 == t2 == oracle
    assert eng.stats["prefix_bootstraps"] >= 1
    assert eng.stats["cow_copies"] >= 1  # the bootstrap write block
    assert eng.leaked_blocks() == 0


def test_spec_accept_parity_under_quant(model_and_params):
    model, params = model_and_params
    tmpl = list(np.random.RandomState(12).randint(0, V, size=8))
    outs = []
    for kw in ({"spec": True, "spec_k": 3, "spec_drafter": "ngram"}, {}):
        eng = _engine(model, params, quant="int8", max_new_tokens=8, **kw)
        a = _serve(eng, [tmpl], max_new=8)[0]
        b = _serve(eng, [tmpl], max_new=8)[0]  # repeat drafts off the store
        outs.append((a, b))
        assert eng.leaked_blocks() == 0
        if kw:
            assert eng.stats["spec_accepted"] > 0
    assert outs[0] == outs[1]


def test_model_drafter_pool_quantizes_identically(model_and_params):
    """The mirrored draft pool must be the quantized pair too — and the
    self-draft configuration accepts ~everything, proving the draft
    arithmetic matches the target's."""
    model, params = model_and_params
    eng = _engine(model, params, quant="int8", spec=True, spec_k=2,
                  spec_drafter="model", max_new_tokens=6)
    assert isinstance(eng._drafter._pool, tuple)
    assert eng._drafter.model.kv_quant == resolve("int8")
    t = _serve(eng, _prompts(2, seed=13), max_new=6)
    assert all(len(x) > 0 for x in t)
    assert eng.stats["spec_accepted"] > 0
    assert eng.leaked_blocks() == 0


def test_tier_spills_quantized_blocks(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params, quant="int8", tier=True, host_blocks=32,
                  n_blocks=9)
    p = list(np.random.RandomState(14).randint(0, V, size=3 * BS))
    ta = _serve(eng, [p], max_new=4)[0]
    evicted = eng._prefix.evict(eng._alloc.capacity)
    eng._alloc.reclaim(evicted)
    assert eng.stats["spilled"] > 0
    # the tier stores the POOL's dtype: int8 rows + per-row f32 scales,
    # a fraction of what f32 blocks would cost (the counter-asserted
    # host-DRAM / PCIe halving of ISSUE 14)
    per_block = eng._tier.bytes / eng._tier.used
    f32_per_block = L * 2 * BS * E * 4
    assert per_block <= 0.5 * f32_per_block, (per_block, f32_per_block)
    tb = _serve(eng, [p], max_new=4)[0]
    assert ta == tb
    assert eng.stats["restored"] > 0
    assert eng.leaked_blocks() == 0 and eng.leaked_host_blocks() == 0


# ---------------------------------------------------------------------------
# 6. runtime integrity: scale corruption fails typed
# ---------------------------------------------------------------------------

def test_scale_corrupt_trips_typed(model_and_params, monkeypatch):
    model, params = model_and_params
    monkeypatch.setenv("MXNET_CHAOS", "scale_corrupt:1")
    chaos.reset()
    eng = _engine(model, params, quant="int8")
    reqs = [eng.submit(p, max_new_tokens=4) for p in _prompts(3, seed=15)]
    eng.run_until_idle(timeout=300)
    done = quar = 0
    for r in reqs:
        try:
            toks = r.result(5)
            assert all(t >= 0 for t in toks)  # never the sentinel
            done += 1
        except ServeQuantError:
            quar += 1
    assert done + quar == len(reqs)
    assert quar >= 1  # P=1 corrupts every step: retries trip again
    assert eng.stats["quant_trips"] > 0
    assert eng.stats["scale_corrupts"] > 0
    assert eng.leaked_blocks() == 0
    trips = [e for e in telemetry.events("serve_quant_trip")]
    assert trips


def test_scale_corrupt_noop_without_kv_quant(model_and_params,
                                             monkeypatch):
    model, params = model_and_params
    monkeypatch.setenv("MXNET_CHAOS", "scale_corrupt:1")
    chaos.reset()
    eng = _engine(model, params, quant="0")
    toks = _serve(eng, _prompts(2, seed=16), max_new=4)
    assert all(len(t) == 4 for t in toks)
    assert eng.stats["scale_corrupts"] == 0
    assert eng.stats["quant_trips"] == 0


def test_scale_corrupt_scrubs_prefix(model_and_params, monkeypatch):
    """After a trip, the tripped row's blocks must leave the prefix
    index (a later lookup may not re-acquire corrupted scales)."""
    model, params = model_and_params
    eng = _engine(model, params, quant="int8")
    shared = list(np.random.RandomState(17).randint(0, V, size=3 * BS))
    _serve(eng, [shared], max_new=4)
    assert eng._prefix.cached_blocks > 0
    monkeypatch.setenv("MXNET_CHAOS", "scale_corrupt:1")
    chaos.reset()
    req = eng.submit(shared, max_new_tokens=4)
    eng.run_until_idle(timeout=300)
    with pytest.raises(ServeQuantError):
        req.result(5)
    # every block the tripped request read was scrubbed (parked or
    # shared alike): a fresh lookup of the same prompt misses
    assert eng._prefix.lookup(shared) == []
    assert eng.leaked_blocks() == 0


def test_stale_nan_scales_in_free_block_harmless(model_and_params):
    """A freed block carrying NaN per-row scales (a scale-corrupted
    victim released it) must NOT poison the next sequence that grows
    into it: never-attended rows contribute exact zeros (the
    attention-side guard), so only rows the new owner actually WRITES
    are ever dequantized — the innocent request completes clean."""
    import jax.numpy as jnp
    model, params = model_and_params
    eng = _engine(model, params, quant="int8")
    clean = _serve(eng, _prompts(2, seed=20), max_new=6)
    # fresh engine: poison EVERY free block's scales up front, as if a
    # corrupted victim had cycled the whole pool through the free list
    eng2 = _engine(model, params, quant="int8")
    pool, scales = eng2._cache
    eng2._cache = (pool, jnp.full_like(scales, jnp.nan))
    toks = _serve(eng2, _prompts(2, seed=20), max_new=6)
    assert toks == clean
    assert eng2.stats["quant_trips"] == 0
    assert eng2.leaked_blocks() == 0


@pytest.mark.slow
def test_scale_corrupt_composed_chaos(model_and_params, monkeypatch):
    """scale_corrupt + block_exhaust + engine_crash under a 2-replica
    router with the journal: every request resolves (tokens with no
    sentinel, or typed), nothing hangs, nothing leaks, compiles stay
    frozen on the surviving replicas."""
    import jax
    model, params = model_and_params
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    monkeypatch.setenv(
        "MXNET_CHAOS",
        "engine_crash:4:replica0,block_exhaust:0.1,scale_corrupt:0.3")
    chaos.reset()
    router = ReplicaRouter.from_mesh(
        model, params, n_replicas=2, max_batch=2,
        prefill_buckets=[8, 16], max_new_tokens=4, sampling=False,
        block_size=BS, n_blocks=33, quant="int8")
    router.warmup()
    rng = np.random.RandomState(18)
    reqs = []
    for _ in range(8):
        try:
            reqs.append(router.submit(
                list(rng.randint(0, V, size=int(rng.randint(3, 12)))),
                max_new_tokens=4, deadline_ms=60000))
        except ServeError:
            pass
    router.start()
    resolved = 0
    for r in reqs:
        try:
            toks = r.result(120)
            assert all(t >= 0 for t in toks)
            resolved += 1
        except ServeError:
            resolved += 1
    router.stop()
    assert resolved == len(reqs)
    for e in router.engines:
        if e._dead is None:
            assert e.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# 7. zero-retrace gate
# ---------------------------------------------------------------------------

def test_quant_zero_steady_state_compiles(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params, quant="int8", spec=True, spec_k=2,
                  spec_drafter="ngram", tier=True, host_blocks=16)
    compiled = eng._aot.compiles
    _serve(eng, _prompts(4, seed=19), max_new=6)
    assert eng._aot.compiles == compiled
    reg = telemetry.registry()
    assert reg.counter("serve.aot.frozen_compiles").value == 0
    steady = [e for e in telemetry.events("retrace")
              if str(e.get("site", "")).startswith("serving.")]
    assert steady == []


def test_prefix_invalidate_unit():
    """`PrefixCache.invalidate` detaches the node AND its subtree,
    returns detached parked blocks, and drops host handles."""
    pc = PrefixCache(2)
    toks = [1, 2, 3, 4, 5, 6]
    pc.insert(toks, [10, 11, 12], 3)
    dropped = []
    pc.host_drop_hook = dropped.append
    pc.park(12)  # leaf parked; 10/11 still "live"
    freed = pc.invalidate([11])
    assert pc.lookup(toks) == [10]  # 11's subtree (12) went with it
    assert freed == [12]            # the parked descendant to reclaim
    assert not pc.contains(11) and not pc.contains(12)
    # invalidating an unknown block is a no-op
    assert pc.invalidate([99]) == []


# ---------------------------------------------------------------------------
# 8. dist-PS wire quantization
# ---------------------------------------------------------------------------

def _ps_roundtrip(monkeypatch, quant):
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.dist import DistKVStore, ParameterServer

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    monkeypatch.setenv("MXNET_PS_QUANT", quant)
    monkeypatch.setenv("DMLC_PS_ROOT_URI", "127.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", str(port))
    monkeypatch.setenv("DMLC_RANK", "0")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    monkeypatch.setenv("DMLC_NUM_SERVER", "1")
    monkeypatch.setenv("MXNET_PS_HEARTBEAT_INTERVAL", "0")
    telemetry.reset()
    ps = ParameterServer("127.0.0.1", port, num_workers=1)
    threading.Thread(target=ps.run, daemon=True).start()
    kv = DistKVStore("dist_sync")
    w = np.linspace(-3, 3, 2048).astype(np.float32)
    g = (np.random.RandomState(0).randn(2048) * 0.1).astype(np.float32)
    kv.init(3, mx.nd.array(w))
    kv.push(3, mx.nd.array(g))
    out = mx.nd.zeros((64,))
    kv.pull(3, out=out)
    # the push is an engine op of its own: count the bytes only once
    # `close()` has drained it, or a leg reads with or without its push
    kv.close()
    sent = telemetry.registry().counter("dist.bytes_sent").value
    return np.asarray(out.asnumpy()), sent, g


def test_ps_wire_quant_roundtrip(monkeypatch):
    plain, b_plain, g = _ps_roundtrip(monkeypatch, "0")
    quant, b_quant, _ = _ps_roundtrip(monkeypatch, "int8")
    # dequantize-before-reduce: the applied result tracks the plain one
    # within the per-group half-step bound of push AND pull encodes
    step = 2 * (np.abs(plain).max() / 127.0 + np.abs(g).max() / 127.0)
    assert np.abs(quant - plain).max() <= step
    assert b_quant < b_plain
    # kill-switch bit-for-bit: a second plain run is identical
    plain2, _, _ = _ps_roundtrip(monkeypatch, "0")
    assert np.array_equal(plain, plain2)
