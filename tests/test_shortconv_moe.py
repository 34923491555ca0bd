"""`ShortConvMoEKVModel` (gated short convolutions with a per-sequence state
beside a grouped-query paged K/V pool, a sparse expert layer with every
expert held) against the plain float32 reference
`benchmark/reference/lfm2_moe.py`, at a small size on the CPU, with seeded
random weights; the engine's seam for per-sequence state (the slot's life:
taken, carried over chunks and into decode, reused, rebuilt after a
preemption); grouped-query attention through the Pallas interpreter; the
options that are refused by name.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.pallas_kernels import paged_attention as kernel
from mxnet_tpu.serving import (LatentMoEKVModel, ServingEngine,
                               ShortConvMoEKVModel, TransformerKVModel)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import lfm2_moe as ref  # noqa: E402

#: the published pattern's first six types, at a small size
CFG = dict(conv_L_cache=3, hidden_size=64, intermediate_size=96,
           layer_types=["conv", "conv", "full_attention", "conv", "conv",
                        "conv"],
           moe_intermediate_size=32, norm_eps=1e-5, num_attention_heads=4,
           num_key_value_heads=2, num_dense_layers=2, num_experts=8,
           num_experts_per_tok=2, num_hidden_layers=6, rope_theta=1000000,
           routed_scaling_factor=1, vocab_size=509)
BS, TABLE = 8, 16            # block size; table entries (128 positions)


def build(cfg=CFG, dtype=np.float32, **over):
    cfg = dict(cfg, **over)
    return ShortConvMoEKVModel(
        cfg["vocab_size"], BS * TABLE, cfg["layer_types"],
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["conv_L_cache"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["num_experts"], cfg["num_experts_per_tok"],
        num_dense_layers=cfg["num_dense_layers"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        eps=cfg["norm_eps"], rope_theta=cfg["rope_theta"], dtype=dtype)


def params_of(model, seed=1, scale=0.2):
    return model.init_params(np.random.RandomState(seed), scale=scale)


def fresh_cache(model, slots=5, fill=0.0):
    """(pool, state); ``fill`` puts that value in every slot, as a slot's
    last holder might have left it."""
    return (model.init_block_pool(4 * TABLE + 1, BS),
            model.init_state(slots) + fill)


def own_table(row=0):
    """A row's own blocks, in order, none the trash block."""
    return (1 + row * TABLE + np.arange(TABLE, dtype=np.int32))[None]


def through_the_cache(model, params, prompt, n_decode, chunk=16, slot=1,
                      cache=None, bucket=4):
    """Logits at every prompt chunk's last token and at ``n_decode`` greedy
    decode steps, through the pool and the state: [(position, logits)], the
    whole token sequence and the cache."""
    cache = fresh_cache(model) if cache is None else cache
    spare = cache[1].shape[1] - 1
    table = own_table(slot)
    prefill = jax.jit(model.prefill_paged)
    decode = jax.jit(model.decode_paged)
    out, done = [], 0
    while done < len(prompt):
        n = min(chunk, len(prompt) - done)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = prompt[done:done + n]
        logits, cache = prefill(params, cache, toks,
                                np.array([done], np.int32),
                                np.array([n], np.int32), table,
                                slots=np.array([slot], np.int32))
        done += n
        out.append((done - 1, np.asarray(logits[0], np.float32)))
    seq = list(prompt)
    for _ in range(n_decode):
        seq.append(int(np.argmax(out[-1][1])))
        # a bucket: one real row, the rest padding rows on the spare slot
        tables = np.zeros((bucket, TABLE), np.int32)
        tables[0] = table[0]
        token = np.zeros((bucket,), np.int32)
        pos = np.zeros((bucket,), np.int32)
        slots = np.full((bucket,), spare, np.int32)
        token[0], pos[0], slots[0] = seq[-1], len(seq) - 1, slot
        logits, cache = decode(params, cache, token, pos, tables,
                               slots=slots)
        out.append((len(seq) - 1, np.asarray(logits[0], np.float32)))
    return out, seq, cache


def worst(got, want):
    return max(float(np.abs(g - want[at]).max()) for at, g in got)


PROMPT = np.random.RandomState(2).randint(0, 509, size=37).tolist()


# -- (a) prefill in chunks, then decode, against the full forward -------------


@pytest.mark.parametrize("chunk", [8, 16, 24, 40])
def test_chunked_prefill_then_decode_agree_with_the_reference(chunk):
    """Chunks of several sizes: 8 and 24 put a boundary inside a conv
    window at other positions than 16 does, 40 holds the prompt whole."""
    model = build()
    params = params_of(model)
    got, seq, _ = through_the_cache(model, params, PROMPT, n_decode=6,
                                    chunk=chunk)
    want = np.asarray(ref.forward(params, seq, CFG))
    # the same float32 products summed in another order: logits of size ~5
    assert worst(got, want) < 1e-4


def test_a_chunk_boundary_inside_a_conv_window_changes_nothing():
    model = build()
    params = params_of(model)
    whole, _, _ = through_the_cache(model, params, PROMPT, 3, chunk=40)
    cut, _, _ = through_the_cache(model, params, PROMPT, 3, chunk=8)
    # the last prompt position and the decode steps after it
    for (at, a), (at2, b) in zip(whole, cut[-4:]):
        assert at == at2
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_reused_slot_gives_the_logits_the_request_gets_alone():
    """The slot's last holder's state, and its blocks' rows, are not the
    next request's: a first chunk starts from nothing."""
    model = build()
    params = params_of(model)
    alone, _, _ = through_the_cache(model, params, PROMPT, 3)
    other = np.random.RandomState(5).randint(0, 509, size=29).tolist()
    _, _, cache = through_the_cache(model, params, other, 4)
    assert float(jnp.abs(cache[1][:, 1]).max()) > 0      # the slot was used
    again, _, _ = through_the_cache(model, params, PROMPT, 3, cache=cache)
    for (_, a), (_, b) in zip(alone, again):
        np.testing.assert_array_equal(a, b)
    # and a state full of garbage in every slot reads as zeros too
    dirty, _, _ = through_the_cache(model, params, PROMPT, 3,
                                    cache=fresh_cache(model, fill=7.0))
    for (_, a), (_, b) in zip(alone, dirty):
        np.testing.assert_array_equal(a, b)


def test_a_rows_result_does_not_depend_on_the_rest_of_its_batch():
    model = build()
    params = params_of(model)
    cache = fresh_cache(model)
    prefill = jax.jit(model.prefill_paged)
    decode = jax.jit(model.decode_paged)
    prompts = [np.random.RandomState(s).randint(0, 509, size=n).tolist()
               for s, n in ((11, 13), (12, 16), (13, 9))]
    for r, prompt in enumerate(prompts):
        toks = np.zeros((1, 16), np.int32)
        toks[0, :len(prompt)] = prompt
        _, cache = prefill(params, cache, toks, np.zeros((1,), np.int32),
                           np.array([len(prompt)], np.int32), own_table(r),
                           slots=np.array([r], np.int32))
    tables = np.concatenate([own_table(r) for r in range(3)])
    token = np.array([5, 6, 7], np.int32)
    pos = np.array([len(p) for p in prompts], np.int32)
    slots = np.arange(3, dtype=np.int32)
    # the donated-looking cache is a value: both launches read the same one
    together, _ = decode(params, cache, token, pos, tables, slots=slots)
    for r in range(3):
        bucket = np.zeros((2, TABLE), np.int32)
        bucket[0] = tables[r]
        one, _ = decode(params, cache, np.array([token[r], 0], np.int32),
                        np.array([pos[r], 0], np.int32), bucket,
                        slots=np.array([r, 4], np.int32))
        np.testing.assert_array_equal(np.asarray(together[r]),
                                      np.asarray(one[0]))


# -- (b) the engine's seam ----------------------------------------------------


def engine_of(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("n_blocks", 40)
    return ServingEngine(model, params, block_size=BS,
                         prefill_buckets=[8, 16], decode_buckets=[1, 2, 4],
                         sampling=False, name=kw.pop("name", "sc"), **kw)


def serve(engine, prompts, new=6):
    reqs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    engine.run_until_idle(timeout=300)
    return [r.result(timeout=5) for r in reqs]


def greedy_reference(params, prompt, new):
    seq = list(prompt)
    for _ in range(new):
        seq.append(int(np.argmax(np.asarray(
            ref.forward(params, seq, CFG))[-1])))
    return seq[len(prompt):]


def test_the_engine_serves_the_reference_tokens_and_reuses_slots():
    """Seven requests through four rows: every slot is taken, carried over
    chunks (prompts of up to five chunks) and into decode, released and
    taken again; the tokens are the reference's greedy tokens."""
    import time
    from mxnet_tpu import telemetry
    model = build()
    params = params_of(model)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 509, size=n).tolist()
               for n in (37, 9, 70, 16, 23, 50, 12)]
    engine = engine_of(model, params, name="sc_reuse")
    engine.warmup()
    resets = telemetry.registry().counter("serve.sc_reuse.state_resets")
    t0, r0 = time.perf_counter(), resets.value
    got = serve(engine, prompts)
    for prompt, tokens in zip(prompts, got):
        assert tokens == greedy_reference(params, prompt, 6)
    assert engine.leaked_blocks() == 0
    live = [r["attrs"]["state_slots_live"]
            for r in tracing.window("sc_reuse", t0, time.perf_counter())
            if r["phase"] == "iteration"]
    assert max(live) == 4 and min(live) >= 1
    assert resets.value - r0 == 7
    assert telemetry.registry().gauge(
        "serve.sc_reuse.state_slots_live").value == live[-1]


def test_a_preempted_request_continues_with_the_same_tokens():
    """A pool too small for every row at full depth: rows are preempted,
    prefilled again (the state rebuilt from position 0) and continue."""
    model = build()
    params = params_of(model)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 509, size=n).tolist() for n in (30, 28, 31, 26)]
    roomy = serve(engine_of(model, params, name="sc_roomy"), prompts, new=24)
    tight = engine_of(model, params, name="sc_tight", n_blocks=19,
                      min_progress=0)
    got = serve(tight, prompts, new=24)
    assert tight.stats["preemptions"] > 0
    assert got == roomy
    assert tight.leaked_blocks() == 0


def test_the_engine_refuses_each_unsupported_option_by_name():
    model = build()
    params = params_of(model)
    assert engine_of(model, params, name="sc_plain")._prefix is None
    for kw, word in ((dict(prefix=True), "prefix"),
                     (dict(spec=True), "spec"),
                     (dict(megastep=True), "megastep"),
                     (dict(quant="int8"), "quant"),
                     (dict(kv_quant="int8"), "kv_quant")):
        with pytest.raises(MXNetError, match="ShortConvMoEKVModel does not "
                           "serve with %s yet" % word):
            engine_of(model, params, name="sc_no", **kw)
    # the tier rides the prefix index: asked for with it, the prefix refuses
    with pytest.raises(MXNetError, match="does not serve with prefix"):
        engine_of(model, params, name="sc_no", prefix=True, tier=True)
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    with pytest.raises(MXNetError, match="does not serve with mesh"):
        ServingEngine(model, params, ctx=mesh, block_size=BS, name="sc_no")


def test_the_environment_asking_for_prefix_sharing_is_refused(monkeypatch):
    model = build()
    params = params_of(model)
    monkeypatch.setenv("MXNET_SERVE_PREFIX", "1")
    with pytest.raises(MXNetError, match="does not serve with prefix"):
        engine_of(model, params, name="sc_env")
    monkeypatch.setenv("MXNET_SERVE_PREFIX", "0")
    assert engine_of(model, params, name="sc_env")._prefix is None


def _todays_programs(engine, model):
    """The prefill and decode programs as the engine built them before it
    knew of per-sequence state, lowered with the engine's own operands."""
    def prefill(params, pool, tokens, start, length, tables):
        tape = []
        logits, pool = model.prefill_paged(params, pool, tokens, start,
                                           length, tables, moe_tape=tape)
        return (engine._pick(logits, (), start + length),
                pool) + engine._moe_out(tape)

    def decode(params, pool, token, pos, tables):
        tape = []
        logits, pool = model.decode_paged(params, pool, token, pos, tables,
                                          moe_tape=tape)
        return (engine._pick(logits, (), pos + 1),
                pool) + engine._moe_out(tape)

    prefill.__name__ = prefill.__qualname__ = "serve_prefill_s16"
    decode.__name__ = decode.__qualname__ = "serve_decode_b2"
    return prefill, decode


@pytest.mark.parametrize("kind", ["kv_pair", "latent"])
def test_the_state_seam_leaves_the_other_models_programs_unchanged(kind):
    if kind == "kv_pair":
        model = TransformerKVModel(97, BS * TABLE, num_layers=2, num_heads=4,
                                   num_embed=32)
        params = model.init_params()
    else:
        model = LatentMoEKVModel(97, BS * TABLE, 2, 32, 2, 12, 8, 8, 4, 8,
                                 48, 16, 8, (0, 4), 2)
        params = model.init_params()
    engine = engine_of(model, params, name="seam_" + kind)
    assert engine._state_slots == 0 and engine._slots((0,), 2) == ()
    cache = engine._cache
    assert not isinstance(cache, tuple)
    prefill, decode = _todays_programs(engine, model)
    toks = engine._put(np.zeros((1, 16), np.int32))
    one = engine._put(np.ones((1,), np.int32))
    table1 = engine._put(np.zeros((1, engine._n_table), np.int32))
    z = engine._put(np.zeros((2,), np.int32))
    table2 = engine._put(np.zeros((2, engine._n_table), np.int32))
    want_p = jax.jit(prefill, donate_argnums=(1,)).lower(
        engine._params, cache, toks, one, one, table1).as_text()
    want_d = jax.jit(decode, donate_argnums=(1,)).lower(
        engine._params, cache, z, z, table2).as_text()
    # what the engine lowers today: its builders, stopped before `compile`
    got = {}

    class Lowered(Exception):
        pass

    real = engine._jit

    def lowering_jit(prog, donate, outs, name):
        fn = real(prog, donate, outs, name)

        class Stop:
            def lower(self, *args):
                got[name] = fn.lower(*args).as_text()
                raise Lowered()
        return Stop()

    engine._jit = lowering_jit
    for build_it in (lambda: engine._compiled_prefill(16),
                     lambda: engine._compiled_decode(2)):
        with pytest.raises(Lowered):
            build_it()
    assert got["serve_prefill_s16"] == want_p
    assert got["serve_decode_b2"] == want_d


# -- (c) grouped-query attention ---------------------------------------------


def _paged_case(heads, kv_heads, hd=64, rows=3, bs=16, m=5, dtype=np.float32,
                seed=0):
    rng = np.random.RandomState(seed)
    n_blocks = rows * m + 1
    pool = rng.randn(2, 2, n_blocks, bs, kv_heads * hd).astype(dtype)
    q = rng.randn(rows, heads * hd).astype(dtype)
    tables = (1 + np.arange(rows * m, dtype=np.int32)).reshape(rows, m)
    pos = np.array([0, bs * m - 1, 37][:rows], np.int32)
    return jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables), \
        jnp.asarray(pos)


@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 4), (32, 8)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_paged_kernel_equals_decode_attention(heads, kv_heads, dtype,
                                                  monkeypatch):
    """`paged_decode_attn` through the Pallas interpreter against the
    `jax.numpy` body, at 4 : 1 and 1 : 1 head ratios."""
    monkeypatch.setattr(kernel, "_INTERPRET", True)
    dt = np.float32 if dtype == "float32" else jnp.bfloat16
    q, pool, tables, pos = _paged_case(heads, kv_heads, dtype=np.float32)
    q, pool = q.astype(dt), pool.astype(dt)
    assert att.paged_decode_kernel_applies(pool, heads, kv_heads)
    got = kernel.paged_decode_attn(q, pool, 1, tables, pos, heads,
                                   kv_heads=None if heads == kv_heads
                                   else kv_heads)
    want = att.decode_attention(
        q, att.gather_paged_kv(pool, 1, 0, tables),
        att.gather_paged_kv(pool, 1, 1, tables), pos, heads,
        kv_heads=kv_heads)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if dtype == "float32" else 2e-2)


def test_grouped_attention_reads_head_h_over_group():
    """Query head h reads K/V head h // group: equal to full multi-head
    attention over the K/V heads repeated, in both `jax.numpy` forms."""
    rng = np.random.RandomState(7)
    b, c, s, heads, kvh, hd = 2, 8, 24, 4, 2, 16
    q = jnp.asarray(rng.randn(b, c, heads * hd), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, kvh * hd), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, kvh * hd), jnp.float32)
    start = jnp.asarray([0, 16], jnp.int32)

    def repeated(x):
        return jnp.repeat(x.reshape(b, s, kvh, hd), heads // kvh,
                          axis=2).reshape(b, s, heads * hd)

    np.testing.assert_allclose(
        att.chunk_attention(q, k, v, start, heads, kv_heads=kvh),
        att.chunk_attention(q, repeated(k), repeated(v), start, heads),
        atol=1e-5)
    pos = jnp.asarray([5, 23], jnp.int32)
    np.testing.assert_allclose(
        att.decode_attention(q[:, 0], k, v, pos, heads, kv_heads=kvh),
        att.decode_attention(q[:, 0], repeated(k), repeated(v), pos, heads),
        atol=1e-5)
    with pytest.raises(MXNetError, match="not a multiple of kv_heads"):
        att.decode_attention(q[:, 0], k, v, pos, heads, kv_heads=3)


# -- (d) small repairs --------------------------------------------------------


def test_a_layer_without_a_shared_expert_is_the_routed_sum():
    rng = np.random.RandomState(9)
    n, d, f, e = 12, 16, 8, 6
    u = jnp.asarray(rng.randn(n, d), jnp.float32)
    router = jnp.asarray(rng.randn(e, d), jnp.float32)
    bias = jnp.asarray(rng.randn(e) * 0.1, jnp.float32)
    banks = tuple(jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
                  for shape in ((e, d, f), (e, d, f), (e, f, d)))
    y, counts = moe.expert_layer(u, router, bias, banks, top_k=2, scale=1.0,
                                 experts_held=(0, e), eps=1e-6)
    idx, w = moe.route(u, router, bias, 2, 1.0, 1e-6)
    want = np.zeros((n, d), np.float32)
    for r in range(n):
        for j in range(2):
            ex = int(idx[r, j])
            want[r] += float(w[r, j]) * np.asarray(moe.swiglu(
                u[r:r + 1], banks[0][ex].T, banks[1][ex].T, banks[2][ex].T))[0]
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    assert int(counts.sum()) == 2 * n
    # the renormalisation's epsilon is the caller's: the published 1e-6
    # against the default the DeepSeek-V3 reference follows
    _, w0 = moe.route(u, router, bias, 2, 1.0)
    assert float(jnp.abs(jnp.sum(w0, axis=1) - 1).max()) < 1e-6
    assert float(jnp.sum(w, axis=1).max()) < 1.0


def test_the_head_is_tied_unless_the_parameters_bring_one():
    model = build()
    params = params_of(model)
    assert "pred_weight" not in model.param_shapes()
    got, seq, _ = through_the_cache(model, params, PROMPT[:9], 1)
    own = dict(params, pred_weight=params["embed_weight"][::-1])
    other, _, _ = through_the_cache(model, own, PROMPT[:9], 0)
    np.testing.assert_allclose(other[0][1], got[0][1][::-1], atol=1e-6)
    want = np.asarray(ref.forward(own, PROMPT[:9], CFG))
    assert worst(other, want) < 1e-4


def test_sizes_asked_of_the_model():
    model = build()
    # 1 attention layer: K and V of 2 heads of 16, float32
    assert model.block_bytes(BS) == 1 * 2 * BS * 32 * 4
    # 5 conv layers keep 2 values of 64 a sequence
    assert model.state_slot_bytes() == 5 * 2 * 64 * 4
    assert model.init_state(5).shape == (5, 5, 2, 64)
    assert model.init_block_pool(9, BS).shape == (1, 2, 9, BS, 32)
    assert model.moe_pairs_per_row == 2 * 4
    with pytest.raises(MXNetError, match="layer_types"):
        build(layer_types=["conv", "window"])


def test_the_reference_imports_nothing_of_mxnet_tpu():
    src = open(ref.__file__).read()
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_moves_the_reference(fault):
    """The faults the benchmark's comparison is tried against are faults:
    each moves the logits at the small size, where a sound gap is 0."""
    model = build()
    params = params_of(model)
    seq = PROMPT + [3, 4, 5]
    sound = np.asarray(ref.forward(params, seq, CFG))
    bad = np.asarray(ref.forward(params, seq, CFG, fault=fault, cuts=(16,)))
    assert np.abs(bad - sound)[-4:].max() > 1e-3
