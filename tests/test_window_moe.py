"""`HybridWindowMoEKVModel` (window layers with a learned sink, kept in a
per-sequence ring, beside full grouped-query layers over the paged pool, q/k
heads wider than v heads, partial rotary positions, a share of a sparse
expert layer) against the plain float32 reference
`benchmark/reference/mimo_v2.py`, at a small size on the CPU, with seeded
random weights; the ring's life in the engine's state slot (taken, carried
over chunks and wraps, reused, rebuilt after a preemption); the decode
kernel at q/k 192 and v 128 through the Pallas interpreter and the chunk
path that walks the table a step at a time; the options refused by name.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry, tracing
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import attention as att
from mxnet_tpu.ops import lm_parts
from mxnet_tpu.ops.pallas_kernels import paged_attention as kernel
from mxnet_tpu.serving import HybridWindowMoEKVModel, ServingEngine

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import mimo_v2 as ref  # noqa: E402

#: the published pattern's first seven kinds, at a small size: 8 query heads
#: over 1 (full) or 2 (window) K/V heads, q/k 24 and v 16, 8 of them rotated,
#: a window of 8, 4 of 16 experts held
CFG = dict(attention_value_scale=0.707, head_dim=24, hidden_size=64,
           hybrid_layer_pattern=[0, 1, 1, 1, 1, 0, 1],
           intermediate_size=96, layernorm_epsilon=1e-5,
           moe_intermediate_size=32, moe_layer_freq=[0, 1, 1, 1, 1, 1, 1],
           n_routed_experts=16, experts_held=[0, 4], num_attention_heads=8,
           num_experts_per_tok=4, num_key_value_heads=1,
           partial_rotary_factor=0.334, rope_theta=10000000,
           routed_scaling_factor=None, sliding_window=8,
           swa_num_key_value_heads=2, swa_rope_theta=10000, v_head_dim=16,
           vocab_size=509)
BS, TABLE = 8, 16            # block size; table entries (128 positions)


def build(cfg=CFG, dtype=np.float32, seq_len=BS * TABLE):
    return HybridWindowMoEKVModel(
        cfg["vocab_size"], seq_len, cfg["hybrid_layer_pattern"],
        cfg["moe_layer_freq"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["swa_num_key_value_heads"], cfg["head_dim"], cfg["v_head_dim"],
        ref.rotary_dim(cfg), cfg["sliding_window"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"], cfg["n_routed_experts"],
        cfg["experts_held"], cfg["num_experts_per_tok"],
        rope_theta=cfg["rope_theta"],
        window_rope_theta=cfg["swa_rope_theta"],
        value_scale=cfg["attention_value_scale"],
        eps=cfg["layernorm_epsilon"], dtype=dtype)


def params_of(model, seed=1, scale=0.2):
    params = model.init_params(np.random.RandomState(seed), scale=scale)
    # sinks of a size that takes a share of the mass
    for n in params:
        if n.endswith("attn_sink_bias"):
            params[n] = params[n] * 5.0
    return params


def fresh_cache(model, slots=5, fill=0.0):
    """(pool, ring); ``fill`` puts that value in every slot, as a slot's
    last holder might have left it."""
    return (model.init_block_pool(4 * TABLE + 1, BS),
            tuple(r + fill for r in model.init_state(slots)))


def own_table(row=0):
    """A row's own blocks, in order, none the trash block."""
    return (1 + row * TABLE + np.arange(TABLE, dtype=np.int32))[None]


def through_the_cache(model, params, prompt, n_decode, chunk=16, slot=1,
                      cache=None, bucket=4):
    """Logits at every prompt chunk's last token and at ``n_decode`` greedy
    decode steps, through the pool and the ring: [(position, logits)], the
    whole token sequence and the cache."""
    cache = fresh_cache(model) if cache is None else cache
    spare = cache[1][0].shape[1] - 1
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


# -- (a) prefill in chunks, then decode past several wraps ---------------------


@pytest.mark.parametrize("chunk", [8, 16, 24, 40])
def test_chunked_prefill_then_decode_agree_with_the_reference(chunk):
    """Chunks of several sizes, 24 and 40 not multiples of the window of 8
    (the ring then wraps inside a chunk), then 20 decode steps: the ring
    turns over more than twice."""
    model = build()
    params = params_of(model)
    got, seq, _ = through_the_cache(model, params, PROMPT, n_decode=20,
                                    chunk=chunk)
    want = np.asarray(ref.forward(params, seq, CFG))
    # the same float32 products summed in another order
    assert worst(got, want) < 1e-4


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_fails_the_tolerance(fault):
    """The faults the benchmark's comparison is tried against, planted in
    the reference: each moves the logits past the tolerance the program
    holds (a sound gap is ~1e-5 at this size)."""
    model = build()
    params = params_of(model)
    got, seq, _ = through_the_cache(model, params, PROMPT, n_decode=6)
    bad = np.asarray(ref.forward(params, seq, CFG, fault=fault))
    assert worst(got, bad) > 1e-3


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """Sixteen experts held four to a chip over four chips, and the head
    over eight slices of the vocabulary: with what every chip computes alike
    (attention, the router, the residual) counted once, the chips' layers
    add up to the layer that holds every expert, and their logits side by
    side are the whole head's (the guide's test of a cut)."""
    full = dict(CFG, experts_held=[0, 16])
    model = build(full)
    params = params_of(model)
    x = jnp.asarray(np.random.RandomState(8).randn(11, 64), jnp.float32)
    lp = {n[len("layer1_"):]: v for n, v in params.items()
          if n.startswith("layer1_")}

    def layer(banks, held):
        cfg = dict(full, experts_held=held)
        frozen = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list)
                        else cfg[k]) for k in ref._KEYS)
        sub = dict(lp, moe_gate=banks[0], moe_up=banks[1],
                   moe_down=banks[2])
        return np.asarray(ref._layer(x, sub, window=True, dense=False,
                                     frozen=frozen, mode="f32", fault=None,
                                     q_block=8))

    banks = tuple(lp["moe_" + n] for n in ("gate", "up", "down"))
    whole = layer(banks, [0, 16])
    shares = [layer(tuple(b[lo:lo + 4] for b in banks), [lo, lo + 4])
              for lo in (0, 4, 8, 12)]
    # a share with no expert's weight left: attention and the residual alone
    alike = layer(tuple(jnp.zeros_like(b[:4]) for b in banks), [0, 4])
    np.testing.assert_allclose(sum(shares) - 3 * alike, whole, atol=1e-4)
    assert np.abs(whole - alike).max() > 1e-2
    head = params["pred_weight"]
    sliced = [np.asarray(ref.logits_of(dict(params, pred_weight=head[a:b]),
                                       x, full))
              for a, b in zip(range(0, 509, 64), range(64, 573, 64))]
    np.testing.assert_allclose(np.concatenate(sliced, axis=1),
                               np.asarray(ref.logits_of(params, x, full)),
                               atol=1e-5)


def test_a_reused_slot_gives_the_logits_the_request_gets_alone():
    """The slot's last holder's ring, and its blocks' rows, are not the
    next request's: a first chunk reads nothing below position 0."""
    model = build()
    params = params_of(model)
    alone, _, _ = through_the_cache(model, params, PROMPT, 3)
    other = np.random.RandomState(5).randint(0, 509, size=29).tolist()
    _, _, cache = through_the_cache(model, params, other, 4)
    assert float(jnp.abs(cache[1][0][:, 1]).max()) > 0   # the slot was used
    again, _, _ = through_the_cache(model, params, PROMPT, 3, cache=cache)
    for (_, a), (_, b) in zip(alone, again):
        np.testing.assert_array_equal(a, b)
    # and a ring full of garbage in every slot reads as nothing too
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
                         sampling=False, name=kw.pop("name", "win"), **kw)


def serve(engine, prompts, new=6):
    reqs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    engine.run_until_idle(timeout=300)
    return [r.result(timeout=5) for r in reqs]


def reference_picks(params, prompt, tokens):
    """The reference's best token at each served position, the served
    tokens fed back (greedy: equal to the served ones where both agree)."""
    seq = list(prompt) + list(tokens[:-1])
    logits = np.asarray(ref.forward(params, seq, CFG))
    return [int(t) for t in np.argmax(logits[len(prompt) - 1:], axis=-1)]


def test_the_engine_serves_the_reference_tokens_and_reuses_slots():
    """Seven requests through four rows: every slot's ring is taken, carried
    over chunks (prompts of up to five chunks) and into decode, released and
    taken again; the tokens are the reference's greedy tokens; each first
    chunk counts a ring reset, and each decode launch the full layers'
    blocks it reads."""
    import time
    model = build()
    params = params_of(model)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 509, size=n).tolist()
               for n in (37, 9, 70, 16, 23, 50, 12)]
    engine = engine_of(model, params, name="win_reuse")
    engine.warmup()
    resets = telemetry.registry().counter("serve.win_reuse.ring_resets")
    t0, r0 = time.perf_counter(), resets.value
    got = serve(engine, prompts, new=12)
    for prompt, tokens in zip(prompts, got):
        assert tokens == reference_picks(params, prompt, tokens)
    assert engine.leaked_blocks() == 0
    recs = [r["attrs"] for r in tracing.window("win_reuse", t0,
                                               time.perf_counter())
            if r["phase"] == "iteration"]
    live = [a["state_slots_live"] for a in recs]
    assert max(live) == 4 and min(live) >= 1
    assert resets.value - r0 == 7
    full = [(a["full_ctx_blocks"], a["ctx_blocks"]) for a in recs
            if "ctx_blocks" in a]
    assert full and all(f == 2 * c for f, c in full)


def test_a_preempted_request_continues_with_the_same_tokens():
    """A pool too small for every row at full depth: rows are preempted,
    prefilled again (the ring rebuilt from position 0) and continue."""
    model = build()
    params = params_of(model)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 509, size=n).tolist() for n in (30, 28, 31, 26)]
    roomy = serve(engine_of(model, params, name="win_roomy"), prompts,
                  new=24)
    tight = engine_of(model, params, name="win_tight", n_blocks=19,
                      min_progress=0)
    got = serve(tight, prompts, new=24)
    assert tight.stats["preemptions"] > 0
    assert got == roomy
    assert tight.leaked_blocks() == 0


def test_the_engine_refuses_each_unsupported_option_by_name():
    model = build()
    params = params_of(model)
    assert engine_of(model, params, name="win_plain")._prefix is None
    for kw, word in ((dict(prefix=True), "prefix"),
                     (dict(spec=True), "spec"),
                     (dict(megastep=True), "megastep"),
                     (dict(quant="int8"), "quant"),
                     (dict(kv_quant="int8"), "kv_quant")):
        with pytest.raises(MXNetError, match="HybridWindowMoEKVModel does "
                           "not serve with %s yet" % word):
            engine_of(model, params, name="win_no", **kw)
    with pytest.raises(MXNetError, match="does not serve with prefix"):
        engine_of(model, params, name="win_no", prefix=True, tier=True)
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    with pytest.raises(MXNetError, match="does not serve with mesh"):
        ServingEngine(model, params, ctx=mesh, block_size=BS, name="win_no")
    from mxnet_tpu.serving.tiers import check_cache_kind
    with pytest.raises(MXNetError):
        check_cache_kind(model, "handoff")


# -- (c) the kernels at q/k 192 and v 128 --------------------------------------


def _pair_case(heads, kv_heads, hd, vd, rows=3, bs=16, m=5, seed=0):
    """q, the pair pool (K's and V's arrays at their own widths), tables and
    positions; where ``hd == vd`` also the one-array pool that holds the
    same K and V."""
    rng = np.random.RandomState(seed)
    n_blocks = rows * m + 1
    k = rng.randn(2, 1, n_blocks, bs, kv_heads * hd).astype(np.float32)
    v = rng.randn(2, 1, n_blocks, bs, kv_heads * vd).astype(np.float32)
    q = rng.randn(rows, heads * hd).astype(np.float32)
    tables = (1 + np.arange(rows * m, dtype=np.int32)).reshape(rows, m)
    pos = np.array([0, bs * m - 1, 37][:rows], np.int32)
    return (jnp.asarray(q), (jnp.asarray(k), jnp.asarray(v)),
            jnp.asarray(tables), jnp.asarray(pos))


@pytest.mark.parametrize("heads,kv_heads,hd,vd",
                         [(64, 4, 192, 128), (32, 2, 192, 128),
                          (8, 2, 64, 64), (4, 4, 128, 128)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_widened_decode_kernel_equals_decode_attention(
        heads, kv_heads, hd, vd, dtype, monkeypatch):
    """`paged_decode_attn` through the Pallas interpreter against the
    `jax.numpy` body: at q/k 192 and v 128 with 16 query heads a K/V head
    over a pair pool, and at today's shapes over one array."""
    monkeypatch.setattr(kernel, "_INTERPRET", True)
    dt = np.float32 if dtype == "float32" else jnp.bfloat16
    q, (k, v), tables, pos = _pair_case(heads, kv_heads, hd, vd)
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    pool = (k, v) if hd != vd else jnp.concatenate([k, v], axis=1)
    assert att.paged_decode_kernel_applies(pool, heads, kv_heads)
    got = kernel.paged_decode_attn(q, pool, 1, tables, pos, heads,
                                   kv_heads=None if heads == kv_heads
                                   else kv_heads)
    want = att.decode_attention(
        q, att.gather_paged_kv(k, 1, 0, tables),
        att.gather_paged_kv(v, 1, 0, tables), pos, heads,
        kv_heads=kv_heads)
    assert got.shape == (q.shape[0], heads * vd) and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-5 if dtype == "float32" else 2e-2)
    # and the model's own wrapper takes the kernel over a pair
    np.testing.assert_allclose(
        np.asarray(att.paged_decode_attention(q, pool, 1, tables, pos, heads,
                                              kv_heads=kv_heads),
                   np.float32), np.asarray(got, np.float32), atol=1e-6)


def test_the_kernel_refuses_what_it_does_not_take(monkeypatch):
    monkeypatch.setattr(kernel, "_INTERPRET", True)
    k = jnp.zeros((1, 1, 9, 16, 4 * 192), jnp.float32)
    v = jnp.zeros((1, 1, 9, 16, 4 * 128), jnp.float32)
    assert kernel.applies((k, v), 4)
    # 192-wide V heads, or a 192-wide head in one array of alike widths
    assert not kernel.applies((k, k), 4)
    assert not kernel.applies(jnp.zeros((1, 2, 9, 16, 4 * 192)), 4)
    # blocks of different sizes
    assert not kernel.applies((k, v[:, :, :, :8]), 4)
    # the prefill kernel takes one array alone
    assert not kernel.prefill_applies((k, v), 4, 16, 8)


@pytest.mark.parametrize("heads,kv_heads,hd,vd,c,start",
                         [(64, 4, 192, 128, 16, 48), (8, 2, 24, 16, 8, 0),
                          (4, 4, 32, 32, 24, 40)])
def test_the_streamed_chunk_equals_chunk_attention(heads, kv_heads, hd, vd,
                                                   c, start, monkeypatch):
    """`paged_chunk_stream` over steps of 32 positions (several steps, a
    table that does not fill its last) against `chunk_attention` over the
    gathered table: at q/k 192 and v 128 with 16 : 1, and at today's
    alike widths."""
    q, (k, v), tables, _ = _pair_case(heads, kv_heads, hd, vd, rows=2, m=5)
    q = jnp.asarray(np.random.RandomState(3).randn(2, c, heads * hd),
                    jnp.float32)
    st = jnp.asarray([start, 16], jnp.int32)
    monkeypatch.setattr(att, "_STREAM_STEP_TOKENS", 32)
    got = att.paged_chunk_stream(q, (k, v), 1, tables, st, heads,
                                 kv_heads=kv_heads)
    want = att.chunk_attention(q, att.gather_paged_kv(k, 1, 0, tables),
                               att.gather_paged_kv(v, 1, 0, tables), st,
                               heads, kv_heads=kv_heads)
    assert got.shape == (2, c, heads * vd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # a pair pool's chunk goes that way through the wrapper
    np.testing.assert_allclose(
        np.asarray(att.paged_chunk_attention(q, (k, v), 1, tables, st, heads,
                                             kv_heads=kv_heads)),
        np.asarray(want), atol=2e-5)


def test_the_streamed_chunk_holds_no_table_wide_scores():
    """Lowered at 64 query heads over 4 of 192 / 128, a chunk of 512 over a
    table of 16,384 positions: no value of the program holds the chunk
    against the table's width, and the loop runs the live steps alone."""
    k = jax.ShapeDtypeStruct((2, 1, 257, 64, 768), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((2, 1, 257, 64, 512), jnp.bfloat16)
    q = jax.ShapeDtypeStruct((1, 512, 64 * 192), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    start = jax.ShapeDtypeStruct((1,), jnp.int32)
    text = jax.jit(lambda q, k, v, t, s: att.paged_chunk_stream(
        q, (k, v), 1, t, s, 64, kv_heads=4)).lower(
            q, k, v, tables, start).as_text()
    assert "while" in text
    dims = [[int(x) for x in d.split(",")]
            for d in __import__("re").findall(r"f32\[([\d,]+)\]", text)]
    assert not [d for d in dims if 16384 in d and 512 in d]


# -- (d) small repairs --------------------------------------------------------


def test_rope_rotates_its_rotary_width_alone():
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(5, 3, 24), jnp.float32)
    pos = jnp.arange(5)[:, None]
    inv = lm_parts.rope_inv_freq(8, 1e4)
    got = lm_parts.rope(x, pos, inv, rotary=8)
    np.testing.assert_array_equal(np.asarray(got[..., 8:]),
                                  np.asarray(x[..., 8:]))
    np.testing.assert_allclose(np.asarray(got[..., :8]), np.asarray(
        lm_parts.rope(x[..., :8], pos, inv)), atol=1e-6)
    # the default, and a width of the whole head, are today's rotation
    whole = lm_parts.rope_inv_freq(24, 1e4)
    np.testing.assert_array_equal(
        np.asarray(lm_parts.rope(x, pos, whole, rotary=24)),
        np.asarray(lm_parts.rope(x, pos, whole)))


def test_sizes_asked_of_the_model():
    model = build()
    # 2 full layers: K of 1 head of 24 and V of 1 head of 16, float32
    assert (model.k_width, model.v_width) == (24, 16)
    assert model.block_bytes(BS) == 2 * BS * (24 + 16) * 4
    # 5 window layers keep 8 positions of 2 heads of 24 + 16 a sequence
    assert model.ring_width == 2 * 40
    assert model.state_slot_bytes() == 5 * 8 * 80 * 4
    assert [r.shape for r in model.init_state(5)] == [(5, 5, 8, 48),
                                                      (5, 5, 8, 32)]
    k, v = model.init_block_pool(9, BS)
    assert k.shape == (2, 1, 9, BS, 24) and v.shape == (2, 1, 9, BS, 16)
    assert model.moe_pairs_per_row == 4 * 6
    assert model.moe_experts == 4
    assert "attn_sink_bias" not in "".join(
        n for n in model.param_shapes() if n.startswith(("layer0_",
                                                         "layer5_")))
    assert model.param_shapes()["layer1_attn_sink_bias"] == (8,)
    assert model.param_shapes()["layer1_moe_router_weight"] == (16, 64)
    assert model.param_shapes()["layer1_moe_gate"] == (4, 64, 32)
    with pytest.raises(MXNetError, match="layer_pattern"):
        build(dict(CFG, hybrid_layer_pattern=[1] * 7))
    with pytest.raises(MXNetError, match="experts_held"):
        build(dict(CFG, experts_held=[12, 20]))


def test_the_reference_imports_nothing_of_mxnet_tpu():
    src = open(ref.__file__).read()
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src
