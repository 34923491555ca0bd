"""`HybridDeltaKVModel` (gated delta-rule linear attention layers whose state
lives in a per-sequence slot, beside full multi-head layers over the paged
pool, each sublayer normed after it) against the plain float32 reference
`benchmark/reference/olmo_hybrid.py`, at a small size on the CPU with seeded
random weights: the chunked rule and the decode step against the
position-by-position recurrence; served logits against the reference's full
forward; the state's life in the engine's slot (carried over chunks, left
alone by a chunk's padding, reused after a holder that left garbage,
rebuilt after a preemption); the options refused by name.
"""
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import delta_rule
from mxnet_tpu.serving import HybridDeltaKVModel, ServingEngine

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import olmo_hybrid as ref  # noqa: E402

_LIN, _FULL = "linear_attention", "full_attention"
#: the published pattern's first eight layers, at a small size: 4 heads of
#: 16 in the full layers; 4 linear heads, k 16 and v 32; sub-chunk 8
CFG = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
           num_key_value_heads=4, rms_norm_eps=1e-6,
           layer_types=[_LIN, _LIN, _LIN, _FULL] * 2,
           linear_num_key_heads=4, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=32,
           linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
           vocab_size=509)
BS, TABLE, SUB = 8, 16, 8    # block size; table entries (128 positions)
#: float32 products summed in another order, through eight layers
TOL = 2e-3


def build(seq_len=BS * TABLE):
    return HybridDeltaKVModel(
        CFG["vocab_size"], seq_len, CFG["layer_types"], CFG["hidden_size"],
        CFG["num_attention_heads"], CFG["num_key_value_heads"],
        CFG["linear_num_value_heads"], CFG["linear_key_head_dim"],
        CFG["linear_value_head_dim"], CFG["linear_conv_kernel_dim"],
        CFG["intermediate_size"], chunk_size=SUB, eps=CFG["rms_norm_eps"])


MODEL = build()
PARAMS = MODEL.init_params(np.random.RandomState(1), scale=0.1)
PREFILL = jax.jit(MODEL.prefill_paged)
DECODE = jax.jit(MODEL.decode_paged)
PROMPT = np.random.RandomState(2).randint(0, 509, size=37).tolist()


def fresh_cache(slots=5, fill=0.0):
    """(pool, (conv tails, S)); ``fill`` puts that value everywhere, as a
    slot's last holder might have left it."""
    return (MODEL.init_block_pool(4 * TABLE + 1, BS) + fill,
            tuple(s + fill for s in MODEL.init_state(slots)))


def own_table(row=0):
    return (1 + row * TABLE + np.arange(TABLE, dtype=np.int32))[None]


def chunk(cache, toks, start, n, slot, c, row=0):
    padded = np.zeros((1, c), np.int32)
    padded[0, :len(toks)] = toks
    return PREFILL(PARAMS, cache, padded, np.array([start], np.int32),
                   np.array([n], np.int32), own_table(row),
                   slots=np.array([slot], np.int32))


def through_the_cache(prompt, n_decode, c=16, slot=1, cache=None, bucket=4):
    """Logits at every chunk's last token and at ``n_decode`` greedy decode
    steps through pool and slot: [(position, logits)], the token sequence
    and the cache."""
    cache = fresh_cache() if cache is None else cache
    spare = 4
    out, done = [], 0
    while done < len(prompt):
        n = min(c, len(prompt) - done)
        logits, cache = chunk(cache, prompt[done:done + n], done, n, slot, c)
        done += n
        out.append((done - 1, np.asarray(logits[0], np.float32)))
    seq = list(prompt)
    for _ in range(n_decode):
        seq.append(int(np.argmax(out[-1][1])))
        # one real row, the rest padding rows on the spare slot
        tables = np.zeros((bucket, TABLE), np.int32)
        tables[0] = own_table()[0]
        token = np.zeros((bucket,), np.int32)
        pos = np.zeros((bucket,), np.int32)
        slots = np.full((bucket,), spare, np.int32)
        token[0], pos[0], slots[0] = seq[-1], len(seq) - 1, slot
        logits, cache = DECODE(PARAMS, cache, token, pos, tables,
                               slots=slots)
        out.append((len(seq) - 1, np.asarray(logits[0], np.float32)))
    return out, seq, cache


@functools.lru_cache(maxsize=None)
def served(c=16, n_decode=8):
    out, seq, _ = through_the_cache(PROMPT, n_decode, c=c)
    return out, seq


def worst(got, want):
    return max(float(np.abs(g - want[at]).max()) for at, g in got)


# -- (a) the two forms of the rule against the recurrence ---------------------


@pytest.mark.parametrize("t", [16, 21])
def test_the_chunked_rule_and_the_step_equal_the_recurrence(t):
    """Runs of ``t`` positions in two calls of `chunk_gated_delta` (a length
    that is a multiple of the sub-chunk and one that is not), the state
    carried from one into the next and into three decode steps, with beta
    over (1, 2) (the write reflects: a negative eigenvalue) and each head's
    decays near 0, near 1, or spread between."""
    rng = np.random.RandomState(t)
    b, h, dk, dv, n = 2, 3, 8, 16, t + 3
    q = delta_rule.l2_normalize(rng.randn(b, n, h, dk))
    k = delta_rule.l2_normalize(rng.randn(b, n, h, dk))
    v = jnp.asarray(rng.randn(b, n, h, dv), jnp.float32)
    # log(-g) a head: near 0 (exp(-e^2) ~ 6e-4), near 1, spread
    lo, hi = np.array([2, -10, -7]), np.array([3, -8, 1])
    g = jnp.asarray(-np.exp(lo + (hi - lo) * rng.uniform(size=(b, n, h))),
                    jnp.float32)
    beta = jnp.asarray(rng.uniform(1, 2, (b, n, h)), jnp.float32)
    want = [ref.delta_recurrence(q[r], k[r], v[r], g[r], beta[r])
            for r in range(b)]
    state = jnp.zeros((b, h, dk, dv), jnp.float32)
    outs = []
    for lo_t, hi_t in ((0, t // 2), (t // 2, t)):
        o, state = delta_rule.chunk_gated_delta(
            *(x[:, lo_t:hi_t] for x in (q, k, v, g, beta)), state,
            chunk=SUB)
        outs.append(o)
    for j in range(t, n):
        o, state = delta_rule.gated_delta_step(
            *(x[:, j] for x in (q, k, v, g, beta)), state)
        outs.append(o[:, None])
    got = np.concatenate([np.asarray(o) for o in outs], axis=1)
    for r in range(b):
        np.testing.assert_allclose(got[r], np.asarray(want[r][0]),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(state[r]),
                                   np.asarray(want[r][1]), atol=2e-5)


def test_the_decode_kernel_equals_the_gathered_step(monkeypatch):
    """`delta_decode_step` through the Pallas interpreter against the
    `jax.numpy` form (each row's slot gathered, stepped, scattered back):
    the rows' outputs, their slots stepped in place, every other slot and
    the zero lanes past the value head left as they were; padding rows on
    the spare slot touch nothing else."""
    from mxnet_tpu.ops.pallas_kernels import delta_step

    rng = np.random.RandomState(4)
    layers, slots_n, h, dk, dv, lanes, b = 3, 7, 4, 16, 32, 128, 5
    state = np.asarray(rng.randn(layers, slots_n, h * dk, lanes) * 0.1,
                       np.float32)
    state[..., dv:] = 0
    state = jnp.asarray(state)
    q = delta_rule.l2_normalize(rng.randn(b, h, dk))
    k = delta_rule.l2_normalize(rng.randn(b, h, dk))
    v = jnp.pad(jnp.asarray(rng.randn(b, h, dv), jnp.float32),
                ((0, 0), (0, 0), (0, lanes - dv)))
    g = jnp.asarray(-np.exp(rng.uniform(-5, 1, (b, h))), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 2, (b, h)), jnp.float32)
    slots = jnp.asarray([2, 0, 5, 6, 6], jnp.int32)      # 6: the spare
    want_o, want_s = delta_rule.gated_delta_slots(q, k, v, g, beta, state,
                                                  1, slots)
    monkeypatch.setattr(delta_step, "_INTERPRET", True)
    assert delta_step.applies(state, h)
    got_o, got_s = delta_rule.gated_delta_slots(q, k, v, g, beta, state, 1,
                                                slots)
    np.testing.assert_allclose(np.asarray(got_o[:3]),
                               np.asarray(want_o[:3]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_s[:, :6]),
                               np.asarray(want_s[:, :6]), atol=1e-6)
    assert float(jnp.abs(got_s[..., dv:]).max()) == 0.0


# -- (b) the served logits against the reference's full forward --------------


@pytest.mark.parametrize("c", [8, 16, 32])
def test_chunked_prefill_then_decode_agree_with_the_reference(c):
    """Chunks of several sizes (37 positions: the last chunk short), then
    eight decode steps through the pool and the slot."""
    got, seq = served(c)
    want = np.asarray(ref.forward(PARAMS, seq, CFG, q_block=16))
    assert worst(got, want) < TOL


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_planted_fault_fails_the_tolerance(fault):
    """Each fault the benchmark's comparison is tried against, planted in
    the reference, moves the logits past the tolerance the program holds:
    the faults the chip's comparison cannot see (PERF.md) are caught
    here."""
    got, seq = served()
    bad = np.asarray(ref.forward(PARAMS, seq, CFG, fault=fault, q_block=16))
    assert worst(got, bad) > 10 * TOL


def test_a_short_last_chunks_padding_leaves_the_state_untouched():
    """A chunk of 16 holding 13 real tokens leaves the slot as 8 + 5 real
    tokens in two chunks leave it, whatever the padding holds."""
    toks = PROMPT[:13]
    _, a = chunk(fresh_cache(), toks, 0, 13, 1, 16)
    junk = toks + [7, 300, 11]
    _, b = chunk(fresh_cache(), junk, 0, 13, 1, 16)
    _, c = chunk(fresh_cache(), toks[:8], 0, 8, 1, 8)
    _, c = chunk(c, toks[8:], 8, 5, 1, 8)
    for x, y in zip(jax.tree_util.tree_leaves(a[1]),
                    jax.tree_util.tree_leaves(b[1])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree_util.tree_leaves(a[1]),
                    jax.tree_util.tree_leaves(c[1])):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), atol=1e-5)


def test_a_reused_slot_gives_the_logits_the_request_gets_alone():
    """A slot whose last holder left its state, or a slot and pool full of
    garbage, give a request the logits it gets from a fresh cache: a first
    chunk reads zeros in the slot's place."""
    alone, _, _ = through_the_cache(PROMPT, 3)
    other = np.random.RandomState(5).randint(0, 509, size=29).tolist()
    _, _, cache = through_the_cache(other, 4)
    assert float(jnp.abs(cache[1][1]).max()) > 0        # the slot was used
    for start in (cache, fresh_cache(fill=7.0)):
        again, _, _ = through_the_cache(PROMPT, 3, cache=start)
        for (_, a), (_, b) in zip(alone, again):
            np.testing.assert_array_equal(a, b)


def test_a_rows_result_does_not_depend_on_the_rest_of_its_batch():
    cache = fresh_cache()
    prompts = [np.random.RandomState(s).randint(0, 509, size=n).tolist()
               for s, n in ((11, 13), (12, 16), (13, 9))]
    for r, prompt in enumerate(prompts):
        _, cache = chunk(cache, prompt, 0, len(prompt), r, 16, row=r)
    tables = np.concatenate([own_table(r) for r in range(3)])
    token = np.array([5, 6, 7, 0], np.int32)
    pos = np.array([len(p) for p in prompts] + [0], np.int32)
    slots = np.array([0, 1, 2, 4], np.int32)
    together, _ = DECODE(PARAMS, cache, token, pos,
                         np.concatenate([tables, np.zeros((1, TABLE),
                                                          np.int32)]),
                         slots=slots)
    for r in range(3):
        bucket = np.zeros((4, TABLE), np.int32)
        bucket[0] = tables[r]
        one, _ = DECODE(PARAMS, cache, np.array([token[r], 0, 0, 0],
                                                np.int32),
                        np.array([pos[r], 0, 0, 0], np.int32), bucket,
                        slots=np.array([r, 4, 4, 4], np.int32))
        np.testing.assert_array_equal(np.asarray(together[r]),
                                      np.asarray(one[0]))


# -- (c) the engine's seam ----------------------------------------------------


def engine_of(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("n_blocks", 40)
    return ServingEngine(MODEL, PARAMS, block_size=BS,
                         prefill_buckets=[8, 16], decode_buckets=[2, 4],
                         sampling=False, name=kw.pop("name", "delta"), **kw)


def serve(engine, prompts, new):
    reqs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    engine.run_until_idle(timeout=300)
    return [r.result(timeout=5) for r in reqs]


def test_the_engine_serves_the_reference_tokens_through_preemptions():
    """Five requests through four rows over a pool too small for every row
    at full depth: slots are taken, carried over chunks and into decode,
    released and taken again; rows are preempted and prefilled again from
    position 0 (the state rebuilt, a reset each time); every token is the
    reference's greedy token, so a preempted request continues with the
    logits it had."""
    from mxnet_tpu import telemetry

    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 509, size=n).tolist()
               for n in (30, 9, 28, 31, 26)]
    engine = engine_of(name="delta_tight", n_blocks=19, min_progress=0)
    assert engine.stats["state_slot_bytes"] == MODEL.state_slot_bytes()
    resets = telemetry.registry().counter("serve.delta_tight.state_resets")
    got = serve(engine, prompts, new=24)
    assert engine.stats["preemptions"] > 0
    assert resets.value == len(prompts) + engine.stats["preemptions"]
    assert engine.leaked_blocks() == 0
    for prompt, tokens in zip(prompts, got):
        # one shape for every request: padding after a row changes nothing
        # before it
        seq = np.zeros((64,), np.int32)
        seq[:len(prompt) + len(tokens) - 1] = list(prompt) + tokens[:-1]
        logits = np.asarray(ref.forward(PARAMS, seq, CFG, q_block=16))
        picks = np.argmax(logits[len(prompt) - 1:][:len(tokens)], axis=-1)
        assert tokens == [int(t) for t in picks]


def test_the_engine_refuses_each_unsupported_option_by_name():
    for kw, word in ((dict(prefix=True), "prefix"),
                     (dict(spec=True), "spec"),
                     (dict(megastep=True), "megastep"),
                     (dict(quant="int8"), "quant"),
                     (dict(kv_quant="int8"), "kv_quant")):
        with pytest.raises(MXNetError, match="HybridDeltaKVModel does not "
                           "serve with %s yet" % word):
            engine_of(name="delta_no", **kw)
    with pytest.raises(MXNetError, match="does not serve with prefix"):
        engine_of(name="delta_no", prefix=True, tier=True)
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    with pytest.raises(MXNetError, match="does not serve with mesh"):
        ServingEngine(MODEL, PARAMS, ctx=mesh, block_size=BS,
                      name="delta_no")
    from mxnet_tpu.serving.tiers import check_cache_kind
    with pytest.raises(MXNetError):
        check_cache_kind(MODEL, "handoff")


# -- (d) sizes ----------------------------------------------------------------


def test_sizes_asked_of_the_model():
    # 2 full layers: K and V of 4 heads of 16, float32
    assert MODEL.kv_width == 64
    assert MODEL.block_bytes(BS) == 2 * 2 * BS * 64 * 4
    # 6 linear layers: 3 rows of 4 x (16 + 16 + 32) conv channels, and S of
    # 4 heads of 16 x 32 at whole 128-lane tiles
    assert MODEL.conv_channels == 256 and MODEL.state_lanes == 128
    assert MODEL.state_slot_bytes() == 6 * (3 * 256 * 4 + 4 * 16 * 128 * 4)
    conv, s = MODEL.init_state(5)
    assert conv.shape == (6, 5 * 3, 256) and s.shape == (6, 5, 64, 128)
    assert MODEL.init_block_pool(9, BS).shape == (2, 2, 9, BS, 64)
    shapes = MODEL.param_shapes()
    assert shapes["layer0_conv_weight"] == (256, 4)
    assert shapes["layer0_gate_weight"] == (128, 64)
    assert shapes["layer3_q_norm_gamma"] == (64,)
    assert "layer3_a_log" not in shapes
    with pytest.raises(MXNetError, match="layer_types"):
        HybridDeltaKVModel(509, 128, [_LIN] * 4, 64, 4, 4, 4, 16, 32, 4, 96)


def test_the_reference_imports_nothing_of_mxnet_tpu():
    src = open(ref.__file__).read()
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src
