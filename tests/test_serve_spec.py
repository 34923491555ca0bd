"""Speculative decoding: draft-verify serving (ISSUE-11).

Contracts under test:

1. `verify_attention` is the length-masked multi-query generalization
   of `chunk_attention` (length == c reproduces it bit-for-bit; chunk
   keys past `length` are masked for real queries, padded queries stay
   finite), and `TransformerKVModel.verify_paged` scores a whole fed
   span with the numerics sequential `decode_paged` would produce.
2. T=0 token parity vs the non-speculative oracle for BOTH drafters
   (ngram/prompt-lookup and the in-graph scan model drafter) — and the
   same at T>0 under seeded sampling, where the position-folded RNG
   makes the accept rule deterministic rejection sampling.
3. Batch-composition invariance: spec engines serving mixed traffic
   (greedy + sampled rows, staggered admissions) reproduce each
   request's solo-run output.
4. Accept accounting is deterministic: identical runs accept identical
   counts.
5. Preemption mid-speculation (pool pressure): outputs unchanged, zero
   leaked blocks — rejected-token rewind and preempt-resume compose.
6. Rejected-token rewind on a row whose tail block is shared/registered
   drops exactly ONE ref through `_drop_refs` (parks registered blocks,
   never frees a block another holder still reads) — the ISSUE-11
   bugfix regression.
7. Zero-retrace: warmup compiles the verify/draft shapes into the
   frozen AotCache bucket set; steady state compiles nothing and the
   watchdog stays silent.  `MXNET_SERVE_SPEC=0` (spec=False) restores
   the PR-10 single-token path: no spec programs exist, no verify
   rounds run.
8. Chaos: `draft_junk:P` corrupts proposals deterministically — parity
   holds at a lower accept rate; `block_exhaust`/`prefix_evict` stay
   green with speculation on; a failing DRAFT launch degrades accept,
   never output (draft state is not correctness-critical).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu import chaos, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops.attention import chunk_attention, verify_attention
from mxnet_tpu.serving import (ModelDrafter, NgramDrafter, ServingEngine,
                               TransformerKVModel, TRASH_BLOCK)

V, S, L, H, E = 61, 32, 2, 2, 32


@pytest.fixture
def model_and_params():
    model = TransformerKVModel(V, S, num_layers=L, num_heads=H, num_embed=E)
    return model, model.init_params(np.random.RandomState(7))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    chaos.reset()
    yield
    telemetry.reset()
    chaos.reset()


def _engine(model, params, **kw):
    # one bucket per program family: warmup compiles are the dominant
    # test cost and bucketing itself is covered by the PR-7/9 suites
    kw.setdefault("max_batch", 4)
    kw.setdefault("decode_buckets", [4])
    kw.setdefault("prefill_buckets", [16])
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("sampling", False)
    return ServingEngine(model, params, **kw)


def _spec_engine(model, params, drafter="ngram", **kw):
    kw.setdefault("spec_k", 3)
    return _engine(model, params, spec=True, spec_drafter=drafter, **kw)


def _run(eng, reqs_kw, timeout=300):
    reqs = [eng.submit(**kw) for kw in reqs_kw]
    eng.run_until_idle(timeout=timeout)
    return [r.result(5) for r in reqs]


def _prompts(seed=0, sizes=(3, 9, 14, 6)):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, V, size=n)) for n in sizes]


# ---------------------------------------------------------------------------
# 1. the verify attention / verify_paged numerics
# ---------------------------------------------------------------------------

def test_verify_attention_full_length_matches_chunk_attention():
    rng = np.random.RandomState(0)
    b, c, s = 3, 4, 16
    q = jnp.asarray(rng.randn(b, c, E).astype(np.float32))
    k = jnp.asarray(rng.randn(b, s, E).astype(np.float32))
    v = jnp.asarray(rng.randn(b, s, E).astype(np.float32))
    start = jnp.asarray(np.array([0, 3, 9], np.int32))
    full = jnp.full((b,), c, jnp.int32)
    out = verify_attention(q, k, v, start, full, H)
    ref = chunk_attention(q, k, v, start, H)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_verify_attention_length_masks_chunk_tail_keys():
    rng = np.random.RandomState(1)
    b, c, s = 2, 4, 12
    start = np.array([2, 5], np.int32)
    length = np.array([2, 3], np.int32)
    q = rng.randn(b, c, E).astype(np.float32)
    k = rng.randn(b, s, E).astype(np.float32)
    v = rng.randn(b, s, E).astype(np.float32)
    out = np.asarray(verify_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(start), jnp.asarray(length), H))
    # garbage in the chunk rows past `length` must not change the
    # outputs of the real (i < length) queries
    k2, v2 = k.copy(), v.copy()
    for r in range(b):
        lo, hi = start[r] + length[r], start[r] + c
        k2[r, lo:hi] = 1e3
        v2[r, lo:hi] = -1e3
    out2 = np.asarray(verify_attention(
        jnp.asarray(q), jnp.asarray(k2), jnp.asarray(v2),
        jnp.asarray(start), jnp.asarray(length), H))
    for r in range(b):
        np.testing.assert_allclose(out[r, :length[r]], out2[r, :length[r]],
                                   rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(out2))  # padded queries stay finite


def test_verify_paged_matches_sequential_decode(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params, block_size=4)
    rng = np.random.RandomState(3)
    prompt = list(rng.randint(0, V, size=6))
    fed = list(rng.randint(0, V, size=4))  # arbitrary teacher-forced span
    # sequential truth: decode_paged one token at a time
    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    pool = model.init_block_pool(eng.n_blocks, 4)
    blocks = list(range(1, 1 + 4))
    tables = jnp.asarray(np.array([blocks + [TRASH_BLOCK] * 4], np.int32))
    toks = np.zeros((1, 8), np.int32)
    toks[0, :6] = prompt
    _, pool = model.prefill_paged(
        jparams, pool, jnp.asarray(toks),
        jnp.asarray(np.zeros(1, np.int32)),
        jnp.asarray(np.array([6], np.int32)), tables)
    seq_logits = []
    p2 = pool
    for j, t in enumerate(fed):
        lg, p2 = model.decode_paged(
            jparams, p2, jnp.asarray(np.array([t], np.int32)),
            jnp.asarray(np.array([6 + j], np.int32)), tables)
        seq_logits.append(np.asarray(lg)[0])
    # one verify launch over the same span
    vg, _ = model.verify_paged(
        jparams, pool, jnp.asarray(np.array([fed], np.int32)),
        jnp.asarray(np.array([6], np.int32)),
        jnp.asarray(np.array([4], np.int32)), tables)
    vg = np.asarray(vg)[0]
    for j in range(4):
        np.testing.assert_allclose(vg[j], seq_logits[j],
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# 2-4. parity, determinism, batch composition
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("drafter", ["ngram", "model"])
def test_spec_token_parity_t0(model_and_params, drafter):
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8) for p in _prompts()]
    base = _run(_engine(model, params), reqs_kw)
    eng = _spec_engine(model, params, drafter)
    eng.warmup()
    outs = _run(eng, reqs_kw)
    assert outs == base
    assert eng.leaked_blocks() == 0
    assert eng.stats["verify_steps"] > 0 or eng.stats["decode_steps"] > 0
    if drafter == "model":
        assert eng._drafter.launches > 0


@pytest.mark.parametrize("drafter", [
    "ngram", pytest.param("model", marks=pytest.mark.slow)])
def test_spec_sampled_parity_and_deterministic_accept(model_and_params,
                                                      drafter):
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8, temperature=t, top_k=tk,
                    top_p=tp, seed=s)
               for p, (t, tk, tp, s) in zip(
                   _prompts(1), [(0.9, 8, 1.0, 11), (0.0, 0, 1.0, 5),
                                 (1.2, 0, 0.9, 3), (0.7, 5, 0.8, 9)])]
    base = _run(_engine(model, params, sampling=True), reqs_kw)
    accepts = []
    for _ in range(2):
        eng = _spec_engine(model, params, drafter, sampling=True)
        eng.warmup()
        outs = _run(eng, reqs_kw)
        assert outs == base
        assert eng.leaked_blocks() == 0
        accepts.append((eng.stats["spec_accepted"],
                        eng.stats["spec_proposed"]))
    assert accepts[0] == accepts[1]  # accept accounting is deterministic


@pytest.mark.slow
def test_spec_batch_composition_invariance(model_and_params):
    model, params = model_and_params
    prompts = _prompts(2, sizes=(4, 11, 7, 16, 5))
    kws = [dict(prompt=p, max_new_tokens=6,
                temperature=(0.8 if i % 2 else 0.0), seed=100 + i)
           for i, p in enumerate(prompts)]
    solo = []
    for kw in kws:
        eng = _spec_engine(model, params, "ngram", sampling=True)
        eng.warmup()
        solo.extend(_run(eng, [kw]))
    eng = _spec_engine(model, params, "ngram", sampling=True)
    eng.warmup()
    # staggered admission: submit in two batches mid-flight
    reqs = [eng.submit(**kw) for kw in kws[:3]]
    for _ in range(2):
        eng.step()
    reqs += [eng.submit(**kw) for kw in kws[3:]]
    eng.run_until_idle(timeout=300)
    outs = [r.result(5) for r in reqs]
    assert outs == solo
    assert eng.leaked_blocks() == 0


def test_spec_repeat_requests_accept_from_generation_store(model_and_params):
    model, params = model_and_params
    eng = _spec_engine(model, params, "ngram")
    eng.warmup()
    prompt = _prompts(4, sizes=(8,))[0]
    first = _run(eng, [dict(prompt=prompt, max_new_tokens=8)])
    s0 = (eng.stats["spec_accepted"], eng.stats["verify_steps"])
    repeat = _run(eng, [dict(prompt=prompt, max_new_tokens=8)])
    assert repeat == first
    # the repeat drafts off the finished stream: nearly every draft
    # accepted, far fewer iterations than tokens
    assert eng.stats["spec_accepted"] - s0[0] >= 5
    assert eng.stats["verify_steps"] - s0[1] <= 4


# ---------------------------------------------------------------------------
# 5-6. preemption + the rewind-sharing regression
# ---------------------------------------------------------------------------

def test_spec_preemption_mid_speculation(model_and_params):
    model, params = model_and_params
    kw = dict(block_size=4, n_blocks=17)  # tight pool: growth preempts
    reqs_kw = [dict(prompt=p, max_new_tokens=10)
               for p in _prompts(5, sizes=(9, 12, 7, 10))]
    base = _run(_engine(model, params, **kw), reqs_kw)
    eng = _spec_engine(model, params, "model", **kw)
    eng.warmup()
    outs = _run(eng, reqs_kw)
    assert outs == base
    assert eng.stats["preemptions"] > 0  # the pressure actually bit
    assert eng.leaked_blocks() == 0


def test_rewind_drops_exactly_one_ref_on_shared_tail(model_and_params):
    """ISSUE-11 bugfix regression: a speculative tail block that is
    SHARED (another request holds a ref) and REGISTERED (the prefix
    index vouches for it) must rewind through release-one-ref — parked,
    never reclaimed to the free list, never stolen from the other
    holder."""
    model, params = model_and_params
    eng = _spec_engine(model, params, "ngram", block_size=4)
    eng.warmup()
    req = eng.submit(list(range(1, 9)), max_new_tokens=6)
    eng.step()  # admit + prefill
    assert eng._active, "row should be decoding"
    row, seq = next(iter(eng._active.items()))
    # build the hazard by hand: give the row a speculative tail block
    # that a concurrent holder shares and the prefix index registered
    tail = eng._alloc.alloc(1)[0]
    seq.blocks.append(tail)
    eng._alloc.acquire([tail])          # the other request's ref
    eng._prefix._by_block[tail] = type(
        "N", (), {"key": None, "block": tail, "parent": None,
                  "children": {}})()
    assert eng._alloc.refcount(tail) == 2
    eng._rewind_blocks(seq)
    assert tail not in seq.blocks       # this row let go...
    assert eng._alloc.refcount(tail) == 1   # ...of exactly ONE ref
    # and the block was not reclaimed: the other holder still owns it
    assert tail not in eng._alloc._free_set
    assert eng.stats["spec_rollbacks"] >= 1
    # cleanup: drop the synthetic holder so the drain leaks nothing
    eng._prefix._by_block.pop(tail, None)
    eng._drop_refs([tail])
    req.cancel()
    eng.run_until_idle(timeout=60)
    assert eng.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# 7. zero-retrace / kill-switch
# ---------------------------------------------------------------------------

def test_spec_zero_retrace_with_frozen_verify_buckets(model_and_params):
    model, params = model_and_params
    eng = _spec_engine(model, params, "model", sampling=True)
    eng.warmup()
    keys = eng._aot.keys()
    assert any(k[0] == "verify" for k in keys)
    assert any(k[0] == "draft_propose" for k in keys)
    assert any(k[0] == "draft_prefill" for k in keys)
    assert any(k[0] == "decode_paged" for k in keys)  # fallback program
    reg = telemetry.registry()
    c0 = reg.counter("serve.aot.compiles").value
    _run(eng, [dict(prompt=p, max_new_tokens=8, temperature=t, seed=4)
               for p, t in zip(_prompts(6), (0.0, 0.9, 0.0, 1.1))])
    assert reg.counter("serve.aot.compiles").value == c0
    assert reg.counter("serve.aot.frozen_compiles").value == 0
    assert not [e for e in telemetry.events("retrace")
                if str(e.get("site", "")).startswith("serving.")]


def test_spec_kill_switch_restores_plain_decode(model_and_params):
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8) for p in _prompts(7)]
    eng_off = _engine(model, params, spec=False)
    eng_off.warmup()
    outs = _run(eng_off, reqs_kw)
    # no spec programs exist, no verify rounds ran, warmup reports none
    assert not [k for k in eng_off._aot.keys()
                if k[0] in ("verify", "draft_propose", "draft_prefill",
                            "draft_cow")]
    assert eng_off.stats["verify_steps"] == 0
    assert eng_off.stats["spec_proposed"] == 0
    assert eng_off.warmup()["spec"] is None
    # and a spec engine reproduces its outputs token for token
    eng_on = _spec_engine(model, params, "ngram")
    eng_on.warmup()
    assert _run(eng_on, reqs_kw) == outs


def test_spec_respawn_carries_config_and_compiles_nothing(model_and_params):
    model, params = model_and_params
    eng = _spec_engine(model, params, "model")
    eng.warmup()
    fresh = eng.respawn()
    c0 = fresh._aot.compiles
    fresh.warmup()
    assert fresh._aot.compiles == c0  # shared AOT set: pure hits
    assert fresh._spec and fresh._spec_k == eng._spec_k
    assert fresh._drafter.name == "model"
    outs = _run(fresh, [dict(prompt=_prompts(8, sizes=(6,))[0],
                             max_new_tokens=6)])
    assert len(outs[0]) == 6


# ---------------------------------------------------------------------------
# 8. chaos
# ---------------------------------------------------------------------------

def test_chaos_draft_junk_parity_at_lower_accept(model_and_params,
                                                 monkeypatch):
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8) for p in _prompts(9)]
    base = _run(_engine(model, params), reqs_kw)
    monkeypatch.setenv("MXNET_CHAOS", "draft_junk:1.0")
    chaos.reset()
    eng = _spec_engine(model, params, "model")
    eng.warmup()
    outs = _run(eng, reqs_kw)
    assert outs == base
    assert eng.stats["spec_junk_rounds"] > 0
    # every proposal corrupted: accepts collapse to chance coincidence
    assert eng.stats["spec_accepted"] <= eng.stats["spec_proposed"] // 4
    assert eng.leaked_blocks() == 0


def test_chaos_block_exhaust_and_prefix_evict_with_spec(model_and_params,
                                                        monkeypatch):
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8) for p in _prompts(10)]
    base = _run(_engine(model, params), reqs_kw)
    monkeypatch.setenv("MXNET_CHAOS",
                       "block_exhaust:0.15,prefix_evict:0.2,draft_junk:0.3")
    chaos.reset()
    eng = _spec_engine(model, params, "ngram")
    eng.warmup()
    outs = _run(eng, reqs_kw)
    assert outs == base
    assert eng.leaked_blocks() == 0


def test_model_drafter_failure_degrades_never_corrupts(model_and_params,
                                                       monkeypatch):
    model, params = model_and_params
    reqs_kw = [dict(prompt=p, max_new_tokens=8) for p in _prompts(11)]
    base = _run(_engine(model, params), reqs_kw)
    eng = _spec_engine(model, params, "model")
    eng.warmup()

    def boom(b):
        raise RuntimeError("draft device hiccup")

    monkeypatch.setattr(eng._drafter, "_compiled_propose", boom)
    outs = _run(eng, reqs_kw)
    assert outs == base  # draft state is never correctness-critical
    assert telemetry.registry().counter("serve.draft_degraded").value > 0
    assert eng.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# drafter unit behavior
# ---------------------------------------------------------------------------

def test_ngram_drafter_lookup_and_confidence():
    d = NgramDrafter(max_n=3, min_n=1)
    # local repetition: ... 5 6 7 [5 6] -> continue 7
    toks, conf = d._lookup([1, 5, 6, 7, 2, 5, 6], 3)
    assert toks[0] == 7 and conf
    # no repetition: filler, not confident
    toks, conf = d._lookup([1, 2, 3, 4, 5], 3)
    assert toks == [5, 5, 5] and not conf
    # the generation store answers with the finished stream
    d.on_retire([1, 2, 3, 4, 5, 6, 7, 8])
    toks, conf = d._lookup([9, 9, 3, 4, 5], 3)
    assert toks == [6, 7, 8] and conf
    # unigram store hits propose but are not confident
    toks, conf = d._lookup([9, 9, 5], 3)
    assert toks == [6, 7, 8] and not conf


def test_ngram_store_cap_bounds_memory():
    d = NgramDrafter(max_n=2, min_n=1, store_cap=8)
    for i in range(20):
        d.on_retire([i, i + 1, i + 2, i + 3])
    assert len(d._store) <= 8


def test_model_drafter_rejects_vocab_mismatch(model_and_params):
    model, params = model_and_params
    other = TransformerKVModel(V + 1, S, num_layers=1, num_heads=H,
                               num_embed=E)
    with pytest.raises(MXNetError, match="vocab"):
        _spec_engine(model, params,
                     ModelDrafter(other, other.init_params())).warmup()
