"""The paged prefill attention kernel against its `jax.numpy` reference.

The kernel body (`ops/pallas_kernels/paged_attention.py`:
`paged_prefill_attn`) runs through the Pallas interpreter on the CPU; the
reference is what every pool the kernel does not take still runs:
`chunk_attention` over `gather_paged_kv` of the layer's K and V blocks.  One
test per contract, one case per shape, so that each counts.  (That the
kernel lowers for the chip at the cells' widths is
`tests/test_aot_compile.py`'s.)
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import tracing
from mxnet_tpu.ops.attention import (chunk_attention, gather_paged_kv,
                                     paged_chunk_attention,
                                     paged_chunk_kernel_applies)
from mxnet_tpu.ops.pallas_kernels import paged_attention_mod as pa
from mxnet_tpu.parallel.mesh import MeshContext, make_mesh
from mxnet_tpu.serving import ServingEngine, TransformerKVModel
from mxnet_tpu.serving.shortconv import ShortConvMoEKVModel

LAYERS, LAYER = 2, 1


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel's gate sees the interpreter; steps of 32 tokens and
    blocks of 128 queries, so that short tables still walk several steps
    and long chunks skip the blocks a step is too late for."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pa, "_PREFILL_STEP_TOKENS", 32)
    monkeypatch.setattr(pa, "_PREFILL_QUERY_ROWS", 128)


def _reference(q, pool, tables, start, heads, kv_heads):
    return chunk_attention(q, gather_paged_kv(pool, LAYER, 0, tables),
                           gather_paged_kv(pool, LAYER, 1, tables), start,
                           heads, kv_heads=kv_heads)


def _kernel(q, pool, tables, start, heads, kv_heads):
    assert paged_chunk_kernel_applies(pool, heads, kv_heads, q.shape[1],
                                      tables.shape[1])
    out = jax.jit(lambda *a: paged_chunk_attention(
        a[0], a[1], LAYER, a[2], a[3], heads, kv_heads=kv_heads))(
        q, pool, tables, start)
    assert out.shape == q.shape and out.dtype == q.dtype
    return np.asarray(out, np.float32)


# (query heads, K/V heads, head, block size, table entries, chunk, start,
# dtype, what else): the two served layouts (GPT-2's 20 heads of 64, LFM2's
# 32 over 8) and heads of 128; blocks of 16 and 64; chunks of 16, 64 and 512
# at position 0, in the middle of a block, and reaching the table's last
# entry or past it (a short last chunk's bucket); a padding row; NaN in the
# trash block and in a freed block
CASES = {
    "mha20x64-bs16-c16-at0": (20, None, 64, 16, 4, 16, 0, "bf16", ""),
    "mha20x64-bs16-c64-midblock": (20, None, 64, 16, 8, 64, 40, "bf16", ""),
    "mha20x64-bs64-c512-at0": (20, None, 64, 64, 9, 512, 0, "bf16", ""),
    "mha2x128-bs16-c64-last-entry": (2, None, 128, 16, 6, 64, 32, "f32",
                                     ""),
    "mha2x128-bs64-c16-midblock": (2, None, 128, 64, 3, 16, 70, "bf16", ""),
    "gqa32x64over8-bs64-c64-at0": (32, 8, 64, 64, 3, 64, 0, "bf16", ""),
    "gqa32x64over8-bs16-c16-midblock": (32, 8, 64, 16, 5, 16, 40, "f32",
                                        ""),
    "gqa32x64over8-bs64-c512-last-entry": (32, 8, 64, 64, 10, 512, 128,
                                           "bf16", ""),
    "gqa32x64over8-bs16-c64-past-table": (32, 8, 64, 16, 5, 64, 48, "bf16",
                                          ""),
    "mha20x64-bs16-padding-row": (20, None, 64, 16, 4, 16, 8, "bf16",
                                  "padding"),
    "gqa32x64over8-bs16-nan-trash-and-freed": (32, 8, 64, 16, 6, 32, 24,
                                               "f32", "nan"),
    "mha2x128-bs16-nan-trash-and-freed": (2, None, 128, 16, 6, 16, 48,
                                          "bf16", "nan"),
    # the served steps and query blocks (256 and 256): statistics carried
    # across whole lane tiles of a step
    "mha2x128-bs16-c64-served-steps": (2, None, 128, 16, 20, 64, 256,
                                       "bf16", "served"),
    "gqa8x64over2-bs64-c256-served-steps": (8, 2, 64, 64, 6, 256, 64,
                                            "f32", "served"),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_chunk_attention(interpreted, monkeypatch, case):
    heads, kvh, hd, bs, m, c, start, dt, what = CASES[case]
    if what == "served":
        monkeypatch.setattr(pa, "_PREFILL_STEP_TOKENS", 256)
        monkeypatch.setattr(pa, "_PREFILL_QUERY_ROWS", 256)
    dtype = jnp.bfloat16 if dt == "bf16" else jnp.float32
    rs = np.random.RandomState(len(case))
    rows = 2 if what == "padding" else 1
    n_blocks = rows * m + 3
    e = (kvh or heads) * hd
    pool = jnp.asarray(rs.randn(LAYERS, 2, n_blocks, bs, e), dtype)
    tables = jnp.asarray((1 + rs.permutation(n_blocks - 1)[:rows * m])
                         .reshape(rows, m), jnp.int32)
    q = jnp.asarray(rs.randn(rows, c, heads * hd), dtype)
    starts = jnp.asarray([start] * rows, jnp.int32)
    if what == "padding":
        # the second row pads the launch: position 0, an all-trash table
        tables = tables.at[1].set(0)
        starts = starts.at[1].set(0)
    if what == "nan":
        # the row owns the blocks its chunk reaches; its table's later
        # entries name the trash block, NaN like a freed block of no table
        live = (start + c - 1) // bs + 1
        tables = tables.at[0, live:].set(0)
        freed = min(set(range(1, n_blocks))
                    - set(np.asarray(tables[0]).tolist()))
        pool = pool.at[:, :, 0].set(jnp.nan).at[:, :, freed].set(jnp.nan)
    got = _kernel(q, pool, tables, starts, heads, kvh)
    want = np.asarray(_reference(q, pool, tables, starts, heads, kvh),
                      np.float32)
    # the same products summed in another order; bf16 probabilities for
    # a bf16 pool's values, then rounded to q's dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert np.isfinite(got[0]).all()
    np.testing.assert_allclose(got[0], want[0], rtol=tol, atol=tol)


def test_heads_do_not_mix(interpreted):
    """Another head's query moves only its own lanes of the output, in both
    layouts (a grouped head's lanes are rolled to its K/V head's and back)."""
    rs = np.random.RandomState(11)
    for heads, kvh in ((4, None), (8, 2)):
        pool = jnp.asarray(rs.randn(LAYERS, 2, 6, 16, (kvh or heads) * 64),
                           jnp.float32)
        tables = jnp.asarray([[3, 1, 4, 2]], jnp.int32)
        q = jnp.asarray(rs.randn(1, 16, heads * 64), jnp.float32)
        start = jnp.asarray([20], jnp.int32)
        got = _kernel(q, pool, tables, start, heads, kvh)
        for h in (1, heads - 1):
            lanes = slice(h * 64, (h + 1) * 64)
            moved = np.abs(_kernel(q.at[..., lanes].multiply(2), pool,
                                   tables, start, heads, kvh) - got) > 0
            assert moved[..., lanes].any()
            assert not np.delete(moved, np.arange(h * 64, (h + 1) * 64),
                                 axis=-1).any()


def test_gate_refuses_what_the_kernel_does_not_take(monkeypatch):
    pool = jnp.zeros((1, 2, 4, 16, 128), jnp.bfloat16)
    assert not paged_chunk_kernel_applies(pool, 2, None, 16, 4)  # no TPU
    monkeypatch.setattr(pa, "_INTERPRET", True)
    assert paged_chunk_kernel_applies(pool, 2, None, 16, 4)
    assert paged_chunk_kernel_applies(pool, 8, 2, 512, 4)      # grouped
    # the int8 pool (its rows are dequantized as they are gathered)
    assert not paged_chunk_kernel_applies(pool.astype(jnp.int8), 2, None,
                                          32, 4)
    # heads of 96
    assert not paged_chunk_kernel_applies(
        jnp.zeros((1, 2, 4, 16, 384), jnp.bfloat16), 4, None, 16, 4)
    # half a sublane tile of queries
    assert not paged_chunk_kernel_applies(pool, 2, None, 8, 4)
    # a table whose K and V would not fit the kernel's VMEM
    assert not paged_chunk_kernel_applies(pool, 2, None, 16, 1 << 16)
    # a program built for a mesh of more than one device
    mesh = make_mesh(devices=jax.devices()[:2])
    with MeshContext(mesh):
        assert not paged_chunk_kernel_applies(pool, 2, None, 16, 4)
    with MeshContext(make_mesh(devices=jax.devices()[:1])):
        assert paged_chunk_kernel_applies(pool, 2, None, 16, 4)


# -- the two models' prefill programs ----------------------------------------


def _prefill_both_ways(model, params, cache, monkeypatch, c, start, length):
    """Logits and cache of `prefill_paged` over the same chunk, with the
    kernel (interpreted) and with the `jax.numpy` body."""
    rs = np.random.RandomState(c)
    m = model.seq_len // (cache[0] if isinstance(cache, tuple)
                          else cache).shape[3]
    tokens = jnp.asarray(rs.randint(1, 60, (1, c)), jnp.int32)
    tables = jnp.asarray([np.arange(1, m + 1)], jnp.int32)
    extra = {}
    if isinstance(model, ShortConvMoEKVModel):
        extra["slots"] = jnp.asarray([1], jnp.int32)
    out = {}
    for kernel in (False, True):
        monkeypatch.setattr(pa, "_INTERPRET", kernel)
        assert bool(model.paged_prefill_kernel(cache, c)) == kernel
        logits, new = jax.jit(lambda p, cc, *a: model.prefill_paged(
            p, cc, *a, **extra))(params, cache, tokens,
                                 jnp.asarray([start], jnp.int32),
                                 jnp.asarray([length], jnp.int32), tables)
        out[kernel] = np.asarray(logits, np.float32), new
    return out


def test_gpt2_prefill_gives_the_same_logits_through_both_paths(monkeypatch):
    model = TransformerKVModel(61, 64, num_layers=2, num_heads=2,
                               num_embed=128)
    params = model.init_params(np.random.RandomState(5))
    pool = jnp.asarray(np.random.RandomState(6).randn(2, 2, 9, 8, 128),
                       jnp.float32)
    out = _prefill_both_ways(model, params, pool, monkeypatch, 16, 24, 13)
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-4,
                               atol=1e-4)
    # the chunk's K and V are scattered alike either way (a later layer's
    # from the earlier layers' outputs)
    np.testing.assert_allclose(np.asarray(out[True][1]),
                               np.asarray(out[False][1]), atol=1e-5)


def test_lfm2_prefill_gives_the_same_logits_through_both_paths(monkeypatch):
    kinds = ["conv", "full_attention", "conv", "full_attention"]
    # 8 query heads of 64 over 2 K/V heads: one 128-lane tile of the pool
    model = ShortConvMoEKVModel(61, 64, kinds, 512, 8, 2, 3, 64, 32, 4, 2,
                                num_dense_layers=1)
    params = model.init_params(np.random.RandomState(5), scale=0.2)
    pool = jnp.asarray(np.random.RandomState(6).randn(2, 2, 9, 8, 128),
                       jnp.float32)
    state = model.init_state(3)
    out = _prefill_both_ways(model, params, (pool, state), monkeypatch, 32,
                             8, 30)
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-4,
                               atol=1e-4)


# -- the engine ---------------------------------------------------------------


def _serve(name, prompts, monkeypatch, kernel):
    monkeypatch.setattr(pa, "_INTERPRET", kernel)
    model = TransformerKVModel(61, 64, num_layers=2, num_heads=2,
                               num_embed=128)
    eng = ServingEngine(model, model.init_params(np.random.RandomState(5)),
                        max_batch=4, block_size=8, n_blocks=40,
                        prefill_buckets=[8, 16], decode_buckets=[2, 4],
                        max_new_tokens=4, sampling=False, name=name)
    eng.warmup()
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_idle(timeout=300)
    assert all(r.error is None for r in reqs) and eng.leaked_blocks() == 0
    chunks = [s["attrs"] for s in tracing.window(name, t0, time.perf_counter())
              if s["phase"] == "prefill_chunk"]
    assert chunks
    return [r.result() for r in reqs], {a["attn_kernel"] for a in chunks}


def test_prefill_chunk_spans_say_which_attention_ran(monkeypatch):
    """Each `prefill_chunk` span carries `attn_kernel`: 1 where the chunk's
    program attends with the kernel, 0 where with the `jax.numpy` body; the
    tokens are the same."""
    prompts = [list(range(1, 22)), list(range(20, 26))]
    plain, flag = _serve("ppk0", prompts, monkeypatch, kernel=False)
    assert flag == {0}
    served, flag = _serve("ppk1", prompts, monkeypatch, kernel=True)
    assert flag == {1}
    assert served == plain
