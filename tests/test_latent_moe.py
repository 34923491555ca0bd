"""`LatentMoEKVModel` (latent attention over a paged latent cache, a share of
a sparse expert layer) against the plain float32 reference
`benchmark/reference/kimi_k2.py`, at a small size on the CPU, with seeded random
weights; and the parts of it one by one: the absorbed and the expanded
attention, the decode kernel through the Pallas interpreter, the router by a
hand-worked case, the shares that add up, batch invariance, YaRN's numbers,
the options that are refused by name.
"""
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from mxnet_tpu import telemetry, tracing
from mxnet_tpu.base import MXNetError, bfloat16
from mxnet_tpu.ops import latent_attention as la
from mxnet_tpu.ops import moe
from mxnet_tpu.ops.pallas_kernels import latent_attention as kernel
from mxnet_tpu.serving import (LatentMoEKVModel, ServingEngine,
                               TransformerKVModel, tiers)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.reference import kimi_k2 as ref  # noqa: E402

YARN = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=4096, type="yarn")
CFG = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
           kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
           n_routed_experts=16, num_experts_per_tok=2,
           first_k_dense_replace=1, routed_scaling_factor=2.827,
           rms_norm_eps=1e-5, rope_theta=50000, rope_scaling=YARN,
           num_hidden_layers=3, vocab_size=509, experts_held=[4, 8])
BS, TABLE = 8, 16            # block size; table entries (128 positions)


def build(cfg=CFG, dtype=np.float32, **over):
    cfg = dict(cfg, **over)
    return LatentMoEKVModel(
        cfg["vocab_size"], BS * TABLE, cfg["num_hidden_layers"],
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"],
        cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["intermediate_size"],
        cfg["moe_intermediate_size"], cfg["n_routed_experts"],
        cfg["experts_held"], cfg["num_experts_per_tok"],
        first_dense=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"], dtype=dtype)


def params_of(model, seed=1, scale=0.2):
    return model.init_params(np.random.RandomState(seed), scale=scale)


def own_table(rows=1):
    """Every row its own blocks, in order, none the trash block."""
    return (1 + np.arange(rows * TABLE, dtype=np.int32)).reshape(rows, TABLE)


def through_the_cache(model, params, prompt, n_decode, chunk=16):
    """Logits at every prompt chunk's last token and at ``n_decode`` greedy
    decode steps, through the paged latent cache: [(position, logits)] and
    the whole token sequence."""
    pool = model.init_block_pool(TABLE + 1, BS)
    table = own_table()
    prefill = jax.jit(model.prefill_paged)
    decode = jax.jit(model.decode_paged)
    out, done = [], 0
    while done < len(prompt):
        n = min(chunk, len(prompt) - done)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = prompt[done:done + n]
        logits, pool = prefill(params, pool, toks, np.array([done], np.int32),
                               np.array([n], np.int32), table)
        done += n
        out.append((done - 1, np.asarray(logits[0], np.float32)))
    seq = list(prompt)
    for _ in range(n_decode):
        seq.append(int(np.argmax(out[-1][1])))
        # a bucket of 4: one real row and three padding rows
        tables = np.zeros((4, TABLE), np.int32)
        tables[0] = table[0]
        logits, pool = decode(params, pool,
                              np.array([seq[-1], 0, 0, 0], np.int32),
                              np.array([len(seq) - 1, 0, 0, 0], np.int32),
                              tables)
        out.append((len(seq) - 1, np.asarray(logits[0], np.float32)))
    return out, seq


# -- (a) prefill in chunks, then decode, against the full forward -------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_prefill_then_decode_agree_with_the_reference(dtype):
    model = build(dtype=np.float32 if dtype == "float32" else bfloat16)
    params = params_of(model)
    prompt = np.random.RandomState(2).randint(0, 509, size=37).tolist()
    got, seq = through_the_cache(model, params, prompt, n_decode=6)
    want = np.asarray(ref.forward(params, seq, CFG))
    err = np.array([np.abs(g - want[at]).max() for at, g in got])
    if dtype == "float32":
        # the same float32 products summed in another order (three chunks,
        # blocks of the cache, tiles of expert rows): logits of size ~5
        assert err.max() < 1e-4, err
        return
    # bfloat16: both read the same bf16-rounded weights; the program also
    # rounds every activation to 8 bits of mantissa, 1-2 % of a logit of
    # size ~5 after three layers (0.05-0.18 read here).  A rounded
    # activation can also flip a near-tie at the router's cut (here half of
    # all flips involve one of the 4 of 16 experts held), which moves that
    # one position's logits as a dropped expert would (2.3 read here, at
    # one position of nine): so all positions but the worst are held to the
    # rounding, and the worst to the size of a logit
    assert np.median(err) < 0.15 and np.sort(err)[-2] < 0.3 \
        and err.max() < 5.0, err


def test_engine_serves_what_the_reference_would():
    """The unchanged `ServingEngine`, default path: several chunks a prompt,
    several rows a launch.  Every served token is the reference's own best
    (float32: ties apart), and the engine counts the held pairs."""
    model = build()
    params = params_of(model)
    tracing.reset()
    engine = ServingEngine(model, params, max_batch=4, block_size=BS,
                           n_blocks=64, prefill_buckets=[8, 16],
                           decode_buckets=[1, 4], name="latent")
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 509, size=n).tolist() for n in (37, 5, 20)]
    t0 = time.perf_counter()
    engine.start()
    try:
        served = [engine.submit(p, max_new_tokens=6) for p in prompts]
        served = [r.result(120) for r in served]
    finally:
        engine.stop()
    for prompt, out in zip(prompts, served):
        gaps, _ = ref.served_gaps(params, prompt, out, CFG,
                                  len(prompt) + len(out))
        assert gaps.shape == (len(out),) and gaps.max() < 1e-4, (gaps, out)
    stats = engine.stats
    rows = stats["decode_rows"] + stats["prefill_tokens"]
    assert stats["moe_pairs_routed"] == rows * 2 * 2   # top-2, 2 layers
    assert 0 < stats["moe_pairs_held"] < stats["moe_pairs_routed"]
    load = engine.expert_load()
    assert load.shape == (4,) and load.sum() == stats["moe_pairs_held"]
    assert [telemetry.registry().gauge("serve.latent.expert_load.%d" % e)
            .value for e in range(4)] == load.tolist()
    records = [r["attrs"] for r in tracing.window("latent", t0,
                                                  time.perf_counter())
               if r["phase"] == "iteration"]
    assert records and all(
        {"expert_rows", "expert_load_max", "expert_hits", "ctx_blocks",
         "attn_kernel", "rows"} <= set(a) for a in records)
    assert sum(a["expert_rows"] for a in records) == stats["moe_pairs_held"]
    assert all(a["expert_load_max"] <= a["expert_rows"] for a in records)
    # a row's attention walks the blocks its position has reached
    assert all(a["rows"] <= a["ctx_blocks"] <= a["rows"] * TABLE
               for a in records)


# -- (b) the absorbed and the expanded attention ------------------------------


def test_absorbed_and_expanded_attention_agree(monkeypatch):
    """One query a row over the same cached rows: `latent_decode_attention`
    (absorbed: the query carried through W_kvb^K, the result back through
    W_kvb^V) and `latent_prefill_attention` (expanded, blockwise, here in
    several steps of its loop) with a chunk of one."""
    monkeypatch.setattr(la, "PREFILL_BLOCK_TOKENS", 32)
    rs = np.random.RandomState(5)
    h, nope, rope, vd, rank, width = 4, 16, 8, 16, 16, 128
    b, pos = 3, np.array([0, 21, 63], np.int32)
    pool = np.zeros((2, 1 + b * TABLE, BS, width), np.float32)
    pool[..., :rank + rope] = rs.randn(2, 1 + b * TABLE, BS, rank + rope)
    tables = own_table(b)
    q_nope = rs.randn(b, h, nope).astype(np.float32)
    q_pe = rs.randn(b, h, rope).astype(np.float32)
    w_kvb = rs.randn(h * (nope + vd), rank).astype(np.float32)
    w = w_kvb.reshape(h, nope + vd, rank)
    scale = 0.17
    q_abs = np.zeros((b, h, width), np.float32)
    q_abs[..., :rank] = np.einsum("bhd,hdr->bhr", q_nope, w[:, :nope])
    q_abs[..., rank:rank + rope] = q_pe
    o = la.latent_decode_attention(jnp.asarray(q_abs), jnp.asarray(pool), 1,
                                   tables, pos, rank, scale)
    absorbed = np.einsum("bhr,hor->bho", np.asarray(o), w[:, nope:])
    expanded = la.latent_prefill_attention(
        jnp.asarray(q_nope[:, None]), jnp.asarray(q_pe[:, None]),
        jnp.asarray(pool), 1, tables, pos, jnp.asarray(w_kvb), rank=rank,
        v_dim=vd, scale=scale)
    np.testing.assert_allclose(absorbed.reshape(b, -1),
                               np.asarray(expanded)[:, 0], rtol=2e-5,
                               atol=2e-5)


# -- (c) the decode kernel, through the interpreter ---------------------------


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel's gate sees the interpreter; two blocks to a chunk, so
    that short tables walk several chunks and both buffer slots."""
    monkeypatch.setattr(kernel, "_INTERPRET", True)
    monkeypatch.setattr(kernel, "_CHUNK_TOKENS", 16)


@pytest.mark.parametrize("dtype,heads", [("float32", 4), ("bfloat16", 4),
                                         ("bfloat16", 64)])
def test_decode_kernel_matches_its_body(interpreted, monkeypatch, dtype,
                                        heads):
    """Live blocks only: blocks past a row's position hold NaN and change
    nothing; a padding row (position 0, an all-trash table) is inert."""
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    rs = np.random.RandomState(7)
    rank, rope, width, b = 128, 8, 256, 4
    pos = np.array([0, 9, 40, 0], np.int32)
    tables = own_table(b)
    tables[3] = 0                                    # the padding row
    pool = np.full((2, 1 + b * TABLE, BS, width), np.nan, np.float32)
    for r in range(3):
        live = tables[r, :pos[r] // BS + 1]
        pool[:, live] = 0.0
        pool[:, live, :, :rank + rope] = rs.randn(2, len(live), BS,
                                                  rank + rope)
    pool[:, 0] = rs.randn(2, BS, width)              # the trash block
    q = np.zeros((b, heads, width), np.float32)
    q[..., :rank + rope] = rs.randn(b, heads, rank + rope)
    q, pool = jnp.asarray(q, dt), jnp.asarray(pool, dt)
    assert la.latent_decode_kernel_applies(pool, rank)
    got = jax.jit(lambda *a: la.latent_decode_attention(
        a[0], a[1], 1, a[2], a[3], rank, 0.11))(q, pool, tables, pos)
    assert got.shape == (b, heads, rank) and got.dtype == dt
    monkeypatch.setattr(kernel, "_INTERPRET", False)   # the body
    assert not la.latent_decode_kernel_applies(pool, rank)
    want = la.latent_decode_attention(q, pool, 1, tables, pos, rank, 0.11)
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    # the same products summed in another order; in bfloat16 the kernel
    # also carries its probabilities as two bf16 terms (16 bits) and both
    # round the result to 8
    tol = 2e-5 if dtype == "float32" else 1.6e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


# -- (c') the prefill kernel, through the interpreter -------------------------


@pytest.fixture
def interpreted_prefill(monkeypatch):
    """The prefill kernel's gate sees the interpreter; four blocks (32
    positions) to a step of its walk and two heads to a group, so that a
    table of 128 positions walks several steps and both buffer slots, and
    four heads make two groups."""
    monkeypatch.setattr(kernel, "_INTERPRET", True)
    monkeypatch.setattr(kernel, "_PREFILL_STEP_TOKENS", 32)
    monkeypatch.setattr(kernel, "_PREFILL_HEADS", 2)


@pytest.mark.parametrize("dtype,c,starts", [
    ("float32", 8, [0]),              # a one-block chunk, an empty prefix
    ("bfloat16", 8, [0]),
    ("float32", 64, [0]),             # several steps on the diagonal
    ("bfloat16", 64, [64]),           # steps before the chunk, then on it
    ("float32", 16, [40]),            # starts inside a step of the walk
    ("bfloat16", 16, [40]),
    ("float32", 32, [64]),            # starts on a step's edge
    ("bfloat16", 32, [64]),
    ("float32", 32, [96]),            # the table's last entry is live
    ("bfloat16", 32, [96]),
    ("float32", 16, [24, 112]),       # two rows, each its own prefix
    ("bfloat16", 16, [24, 112]),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, list) else str(v))
def test_prefill_kernel_matches_the_loop(interpreted_prefill, monkeypatch,
                                         dtype, c, starts):
    """`latent_prefill_attn` through the interpreter against the `lax` loop
    over the same pool.  Live rows only: the rows of a block past the
    chunk's end and every block the chunk has not reached hold NaN, and
    contribute nothing."""
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    rs = np.random.RandomState(13)
    h, nope, rope, vd, rank, width = 4, 16, 8, 16, 128, 256
    b, starts = len(starts), np.array(starts, np.int32)
    tables = own_table(b)
    pool = np.full((2, 1 + b * TABLE, BS, width), np.nan, np.float32)
    for r in range(b):
        n = starts[r] + c                       # positions written so far
        live = tables[r, :-(-n // BS)]
        rows = np.full((len(live) * BS, width), np.nan, np.float32)
        rows[:n] = 0.0
        rows[:n, :rank + rope] = rs.randn(n, rank + rope)
        pool[1, live] = rows.reshape(len(live), BS, width)
    pool[:, 0] = rs.randn(2, BS, width)              # the trash block
    q_nope = jnp.asarray(rs.randn(b, c, h, nope), dt)
    q_pe = jnp.asarray(rs.randn(b, c, h, rope), dt)
    w_kvb = jnp.asarray(rs.randn(h * (nope + vd), rank) * 0.1, dt)
    pool = jnp.asarray(pool, dt)
    kw = dict(rank=rank, v_dim=vd, scale=0.11)
    assert la.latent_prefill_kernel_applies(pool, rank, c)
    got = jax.jit(lambda *a: la.latent_prefill_attention(
        a[0], a[1], a[2], 1, a[3], a[4], a[5], **kw))(
            q_nope, q_pe, pool, tables, starts, w_kvb)
    assert got.shape == (b, c, h * vd) and got.dtype == dt
    monkeypatch.setattr(kernel, "_INTERPRET", False)   # the loop
    assert not la.latent_prefill_kernel_applies(pool, rank, c)
    monkeypatch.setattr(la, "PREFILL_BLOCK_TOKENS", 48)
    want = la.latent_prefill_attention(q_nope, q_pe, pool, 1, tables, starts,
                                       w_kvb, **kw)
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all() and np.isfinite(want).all()
    # the same products, rounded at the same places, summed in steps of
    # another size; in bfloat16 both round the result to 8 bits
    tol = 2e-5 if dtype == "float32" else 1.6e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("why", ["one_device", "mesh_of_two", "ragged_pool",
                                 "ragged_chunk", "int8_pool"])
def test_the_prefill_kernels_gate(monkeypatch, why):
    """The kernel is taken by what the code can observe: a TPU backend, a
    one-device program, a float pool and a chunk of whole tiles."""
    from mxnet_tpu.parallel.mesh import MeshContext

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = jnp.zeros((2, 4, 16, 640), jnp.bfloat16)
    assert not la.latent_prefill_kernel_applies(pool[0], 512, 64)
    if why == "one_device":
        assert la.latent_prefill_kernel_applies(pool, 512, 64)
        with MeshContext(Mesh(np.array(jax.devices()[:1]), ("model",))):
            assert la.latent_prefill_kernel_applies(pool, 512, 64)
    elif why == "mesh_of_two":
        with MeshContext(Mesh(np.array(jax.devices()[:2]), ("model",))):
            assert not la.latent_prefill_kernel_applies(pool, 512, 64)
    elif why == "ragged_pool":
        # a width, a compression or a block that is not whole tiles
        assert not la.latent_prefill_kernel_applies(pool[..., :576], 512, 64)
        assert not la.latent_prefill_kernel_applies(pool, 448, 64)
        assert not la.latent_prefill_kernel_applies(pool[:, :, :8], 512, 64)
    elif why == "ragged_chunk":
        assert not la.latent_prefill_kernel_applies(pool, 512, 8)
        assert la.latent_prefill_kernel_applies(
            pool.astype(jnp.float32), 512, 8)
    else:
        assert not la.latent_prefill_kernel_applies(
            pool.astype(jnp.int8), 512, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not la.latent_prefill_kernel_applies(pool, 512, 64)


# -- (d) the shares add up ----------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up():
    """16 experts in 4 shares of 4: the four partial routed sums plus the
    shared expert, counted once, are the uncut reference's layer."""
    rs = np.random.RandomState(11)
    n, d, f, experts, top_k = 24, 64, 32, 16, 3
    u = rs.randn(n, d).astype(np.float32)
    lp = {"moe_router_weight": rs.randn(experts, d).astype(np.float32) * .2,
          "moe_router_bias": rs.randn(experts).astype(np.float32) * .1,
          "moe_gate": rs.randn(experts, d, f).astype(np.float32) * .2,
          "moe_up": rs.randn(experts, d, f).astype(np.float32) * .2,
          "moe_down": rs.randn(experts, f, d).astype(np.float32) * .2}
    shared = [rs.randn(*s).astype(np.float32) * .2
              for s in ((f, d), (f, d), (d, f))]
    lp.update(zip(("shared_gate_weight", "shared_up_weight",
                   "shared_down_weight"), shared))
    cfg = dict(CFG, num_experts_per_tok=top_k, experts_held=[0, experts])
    whole = np.asarray(ref._expert_layer(
        jnp.asarray(u), {k: jnp.asarray(v) for k, v in lp.items()}, cfg,
        "f32", None))
    idx, w = moe.route(jnp.asarray(u), lp["moe_router_weight"],
                       lp["moe_router_bias"], top_k, 2.827)
    total = np.asarray(moe.swiglu(jnp.asarray(u), *shared), np.float32)
    rows = 0
    for lo in range(0, experts, 4):
        part, counts = moe.held_share(
            jnp.asarray(u), idx, w, lp["moe_gate"][lo:lo + 4],
            lp["moe_up"][lo:lo + 4], lp["moe_down"][lo:lo + 4],
            experts_held=(lo, lo + 4))
        total = total + np.asarray(part)
        rows += int(counts.sum())
    assert rows == n * top_k            # every pair lands on one share
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


# -- (e) routing, by hand -----------------------------------------------------


def test_routing_against_a_hand_worked_case():
    """u = (2, 1, 0, -1) through an identity router: sc = sigmoid(u) =
    (.8808, .7311, .5, .2689).  The bias (0, 0, .3, 0) lifts expert 2 to .8,
    over expert 1: the two largest of sc + b are experts 0 and 2.  Their
    weights are sc WITHOUT the bias, (.8808, .5), over their sum 1.3808 =
    (.63789, .36211), times 2.827 = (1.80331, 1.02369)."""
    u = jnp.asarray([[2.0, 1.0, 0.0, -1.0]], jnp.float32)
    idx, w = moe.route(u, jnp.eye(4), jnp.asarray([0.0, 0.0, 0.3, 0.0]), 2,
                       2.827)
    assert idx.tolist() == [[0, 2]]
    np.testing.assert_allclose(np.asarray(w), [[1.80331, 1.02369]],
                               atol=2e-5)
    # without the bias the cut falls the other way
    idx, _ = moe.route(u, jnp.eye(4), jnp.zeros(4), 2, 2.827)
    assert idx.tolist() == [[0, 1]]


# -- (f) batch invariance -----------------------------------------------------


def test_a_rows_logits_do_not_depend_on_the_batch():
    """One decode program (a bucket of 8): the row alone among padding, and
    the same row among 7 others, give the same logits bit for bit."""
    model = build()
    params = params_of(model)
    rs = np.random.RandomState(13)
    pool = jnp.asarray(rs.randn(3, 1 + 8 * TABLE, BS, model.pool_width)
                       .astype(np.float32))
    decode = jax.jit(model.decode_paged)
    tables = own_table(8)
    token = rs.randint(0, 509, size=8).astype(np.int32)
    pos = rs.randint(8, 100, size=8).astype(np.int32)
    among, _ = decode(params, pool, token, pos, tables)
    alone_tables = np.zeros_like(tables)
    alone_tables[0] = tables[0]
    alone, _ = decode(params, pool, np.where(np.arange(8) == 0, token, 0),
                      np.where(np.arange(8) == 0, pos, 0), alone_tables)
    assert np.array_equal(np.asarray(among[0]), np.asarray(alone[0]))
    # and the padding rows were routed nowhere
    tape = []
    model.decode_paged(params, pool, np.where(np.arange(8) == 0, token, 0),
                       np.where(np.arange(8) == 0, pos, 0), alone_tables,
                       moe_tape=tape)
    assert all(int(c.sum()) <= 2 for c in tape)      # one row, top-2


# -- (g) YaRN -----------------------------------------------------------------


def test_yarn_frequencies_and_the_softmax_scale_by_hand():
    """Kimi-K2's rope: 64 dims, theta 50,000, factor 64 over 4,096.  The
    correction dims: 64 ln(4096 / (32 x 2 pi)) / (2 ln 50000) = 8.91 -> 8
    and 64 ln(4096 / (2 pi)) / (2 ln 50000) = 19.16 -> 20.  Up to pair 8 the
    frequency is theta^(-2i/64) itself, from pair 20 on it is that over 64,
    and pair 14 lies halfway up the ramp.  s = 192^-0.5 (0.1 ln 64 + 1)^2 =
    0.0721688 x 2.0047397 = 0.144680."""
    for inv in (la.yarn_inv_freq(64, 50000.0, YARN),
                ref.yarn_inv_freq(64, 50000.0, YARN)):
        plain = 50000.0 ** (-np.arange(32) / 32.0)
        assert inv.shape == (32,)
        np.testing.assert_allclose(inv[:9], plain[:9], rtol=1e-12)
        assert inv[8] == pytest.approx(0.0668740, rel=1e-5)
        np.testing.assert_allclose(inv[20:], plain[20:] / 64, rtol=1e-12)
        assert inv[14] == pytest.approx(plain[14] * (0.5 + 0.5 / 64),
                                        rel=1e-12)
    assert la.softmax_scale(192, YARN) == pytest.approx(0.144680, rel=1e-5)
    assert ref.softmax_scale(dict(CFG, qk_nope_head_dim=128,
                                  qk_rope_head_dim=64)) \
        == pytest.approx(0.144680, rel=1e-5)
    assert la.rope_factor(YARN) == 1.0
    # no scaling: the plain frequencies and 1 / sqrt(d)
    np.testing.assert_allclose(la.yarn_inv_freq(64, 50000.0), plain)
    assert la.softmax_scale(64) == 0.125


# -- (h) what the latent pool cannot do yet is refused by name ----------------


@pytest.mark.parametrize("option,kwargs", [
    ("kv_quant", {"kv_quant": "int8"}),
    ("quant", {"quant": "int8"}),
    ("megastep", {"megastep": True}),
    ("spec", {"spec": True}),
    ("tier", {"tier": True}),
    ("mesh", {"ctx": "mesh"}),
])
def test_unsupported_engine_options_are_refused_by_name(option, kwargs):
    model = build()
    if kwargs.get("ctx") == "mesh":
        kwargs = {"ctx": Mesh(np.array(jax.devices()[:2]), ("model",))}
    with pytest.raises(MXNetError, match="LatentMoEKVModel does not serve "
                                         "with %s yet" % option):
        ServingEngine(model, params_of(model), max_batch=2, block_size=BS,
                      n_blocks=8, prefill_buckets=[8], **kwargs)


def test_block_runs_refuse_the_latent_pool_by_name():
    """`tiers.pack_block_run` (the host tier's and the handoff's packing)
    and the router's role wiring know the K/V-pair layout only."""
    model = build()
    with pytest.raises(MXNetError, match="cache kind is 'latent'"):
        tiers.pack_block_run(model, BS, [], 2)
    with pytest.raises(MXNetError, match="handoff.*'latent'"):
        tiers.check_cache_kind(model, "prefill/decode handoff tickets")
    tiers.check_cache_kind(TransformerKVModel(61, 32), "anything")


# -- the pool's bytes are the model's to say ----------------------------------


@pytest.mark.parametrize("kind", ["latent", "kv_pair", "kv_pair_int8"])
def test_block_bytes_is_what_the_model_allocates(kind):
    if kind == "latent":
        model = build(dtype=bfloat16)
    else:
        model = TransformerKVModel(61, 32, num_layers=2, num_heads=2,
                                   num_embed=32, dtype=bfloat16)
        if kind == "kv_pair_int8":
            model = model.with_quant(None, "int8")
    pool = model.init_block_pool(10, BS)
    held = sum(a.nbytes for a in jax.tree_util.tree_leaves(pool))
    assert 10 * model.block_bytes(BS) == held
    assert model.cache_kind == kind.replace("_int8", "")


# -- the reference stands apart from the code it judges ----------------------


def test_the_reference_imports_nothing_of_mxnet_tpu():
    assert "mxnet_tpu" not in "".join(
        line for line in open(ref.__file__)
        if line.startswith(("import", "from")))
