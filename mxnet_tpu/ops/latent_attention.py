"""Multi-head latent attention (MLA) over a paged latent cache: rotary
positions with YaRN, the absorbed decode step and the blockwise prefill.

What is cached, a token a layer, is one row shared by all heads: ``c_kv``
after its norm (``rank`` wide) and ``k_pe`` after RoPE beside it.  The pool is
``(layers, n_blocks, block_size, width)``, ``width`` being ``rank + rope`` in
whole 128-lane tiles with zeros in the spare lanes; block 0 is the trash
block.

* **decode** (`latent_decode_attention`): the absorbed form.  The caller
  carries each head's ``q_nope`` through ``W_kvb^K`` into the latent space,
  so all heads score against the cached rows as they lie and sum ``c_kv``
  itself; ``W_kvb^V`` brings the result back to the head's width afterwards.
  On a TPU the Pallas kernel `latent_decode_attn` walks each row's live
  blocks in place; elsewhere a `jax.numpy` body gathers the table-wide
  context, and is the kernel's reference in the tests.
* **prefill** (`latent_prefill_attention`): the expanded form, blockwise.  A
  chunk of ``c`` queries attends to the cached prefix and to itself (its own
  rows are already in the pool) in context blocks of bounded size: each
  block's latent rows are taken through the table, expanded through
  ``W_kvb`` to per-head keys and values, and folded into a running float32
  softmax.  The trip count follows the context the chunk can see, not the
  table's width, and no temporary is chunk x table width x heads.
  Re-expanding a cached token costs 2 x rank x heads x (nope + v) operations
  a chunk; absorbing instead would cost (rank + rope + rank) / (nope + rope
  + v) = 3.4 times the attention's own operations for every query, which is
  more from a chunk of ~170 tokens up.  On a TPU the Pallas flash kernel
  `latent_prefill_attn` walks the live blocks in place and keeps a group of
  heads' scores and running statistics in VMEM; elsewhere (a CPU, a sharded
  mesh) a `lax` loop gathers each block and leaves its scores to XLA, and
  is the kernel's reference in the tests.

Which path a program takes is decided where it is traced, by what the code
can observe (`latent_decode_kernel_applies`, `latent_prefill_kernel_applies`:
the backend, the mesh, the pool's dtype and tiles), and by no option.
"""
from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .lm_parts import rope_inv_freq
from .pallas_kernels import latent_attention as latent_attention_mod

_NEG = -1e30

#: cached positions one step of the prefill loop attends to: the float32
#: scores of a step are chunk x heads x this many
PREFILL_BLOCK_TOKENS = 1024


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, scaling=None):
    """RoPE's ``dim // 2`` inverse frequencies, float64.  With a YaRN
    ``scaling`` (``factor``, ``original_max_position_embeddings``,
    ``beta_fast``, ``beta_slow``) each is blended between ``theta^(-2i/dim)``
    (kept: it turns more than ``beta_fast`` times in the original context)
    and that over ``factor`` (stretched: fewer than ``beta_slow`` turns) by
    the linear ramp between the two correction dims, as the published
    DeepSeek-V3 code computes it."""
    extra = rope_inv_freq(dim, theta)
    if not scaling or scaling.get("factor", 1) <= 1:
        return extra
    factor = float(scaling["factor"])
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def softmax_scale(qk_head_dim, scaling=None):
    """``qk_head_dim^-0.5 * m^2`` with ``m`` YaRN's temperature for
    ``mscale_all_dim`` (1 without scaling)."""
    m = 1.0
    if scaling and scaling.get("mscale_all_dim"):
        m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    return qk_head_dim ** -0.5 * m * m


def rope_factor(scaling=None):
    """What the cos/sin tables are multiplied by: ``mscale / mscale_all_dim``
    temperatures' ratio (1 for the published Kimi-K2 scaling)."""
    if not scaling or scaling.get("factor", 1) <= 1:
        return 1.0
    return yarn_mscale(scaling["factor"], scaling.get("mscale", 1)) \
        / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0))


def _one_device_program():
    from ..parallel.mesh import get_mesh

    mesh = get_mesh()
    return mesh is None or mesh.size == 1


def latent_decode_kernel_applies(pool, rank):
    """Whether `latent_decode_attention` over ``pool``, traced here, is the
    Pallas kernel: a one-device program on a TPU (or the interpreter)."""
    return _one_device_program() and latent_attention_mod.applies(pool, rank)


def latent_prefill_kernel_applies(pool, rank, c):
    """Whether `latent_prefill_attention` of a chunk of ``c`` queries over
    ``pool``, traced here, is the Pallas kernel: a one-device program on a
    TPU (or the interpreter), a pool and a chunk of whole tiles."""
    return _one_device_program() \
        and latent_attention_mod.prefill_applies(pool, rank, c)


def latent_decode_attention(q, pool, layer, block_tables, pos, rank, scale):
    """Absorbed decode attention of every row over layer ``layer`` of the
    latent pool, under the scope `decode_attention`.

    q: (b, heads, width), the pool's width with zeros in its spare lanes;
    returns (b, heads, rank) in q's dtype.  The `jax.numpy` body and the
    kernel agree up to the order of the float32 sums; masked positions
    contribute exact zeros either way."""
    with jax.named_scope("decode_attention"):
        if latent_decode_kernel_applies(pool, rank):
            return latent_attention_mod.latent_decode_attn(
                q, pool, layer, block_tables, pos, rank, scale)
        b, m = block_tables.shape
        ctx = pool[layer, block_tables]                    # (b, m, bs, w)
        ctx = ctx.reshape(b, m * pool.shape[2], -1).astype(jnp.float32)
        s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), ctx,
                       precision=lax.Precision.HIGHEST) * scale
        seen = jnp.arange(ctx.shape[1], dtype=jnp.int32)[None] \
            <= pos.astype(jnp.int32)[:, None]                # (b, s)
        p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
        c = jnp.where(seen[..., None], ctx[..., :rank], 0.0)
        return jnp.einsum("bhs,bsr->bhr", p, c,
                          precision=lax.Precision.HIGHEST).astype(q.dtype)


def latent_prefill_attention(q_nope, q_pe, pool, layer, block_tables, start,
                             w_kvb, *, rank, v_dim, scale):
    """Chunked-prefill attention over the latent pool, expanded, blockwise.

    q_nope: (b, c, heads, nope); q_pe: (b, c, heads, rope), rotated
    pool:   (layers, n_blocks, block_size, width); the chunk's own rows are
            already written
    start:  (b,) int32: the chunk's first absolute position
    w_kvb:  (heads * (nope + v_dim), rank): a head's key part then its
            value part
    Returns (b, c, heads * v_dim) in q's dtype.  Every operation is under
    the scope `mla_prefill_attention`.  Where
    `latent_prefill_kernel_applies`, that is the Pallas kernel
    `latent_prefill_attn`; elsewhere the `lax` loop below, the kernel's
    reference in the tests, whose loop operation around the steps has
    `mla_prefill_loop` to itself (a device trace holds it as one event
    around its steps' operations, which must not count twice).
    """
    scope = jax.named_scope("mla_prefill_attention")
    b, c, h, nope = q_nope.shape
    if latent_prefill_kernel_applies(pool, rank, c):
        with scope:
            return latent_attention_mod.latent_prefill_attn(
                q_nope, q_pe, pool, layer, block_tables, start, w_kvb,
                rank=rank, v_dim=v_dim, scale=scale)
    bs = pool.shape[2]
    m = block_tables.shape[1]
    nb = max(1, min(PREFILL_BLOCK_TOKENS // bs, m))  # table entries a step
    t = nb * bs
    dt = q_nope.dtype
    start = start.astype(jnp.int32)
    qpos = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]   # (b, c)
    w = w_kvb.reshape(h, nope + v_dim, rank)
    precision = lax.Precision.HIGHEST if dt == jnp.float32 else None

    @scope
    def body(j, carry):
        m_run, l_run, acc = carry
        ent = j * nb + jnp.arange(nb, dtype=jnp.int32)
        blk = jnp.take(block_tables, jnp.minimum(ent, m - 1), axis=1)
        blk = jnp.where(ent[None] < m, blk, 0)                 # (b, nb)
        lat = pool[layer, blk].reshape(b, t, -1)
        kpos = j * t + jnp.arange(t, dtype=jnp.int32)          # (t,)
        # rows past the chunk's end are stale: zeroed, so that a weight of
        # 0 multiplies no garbage
        written = kpos[None, :, None] < (start + c)[:, None, None]
        lat = jnp.where(written, lat, jnp.zeros((), lat.dtype)).astype(dt)
        kv = jnp.einsum("btr,hor->bhto", lat[..., :rank], w,
                        precision=precision,
                        preferred_element_type=jnp.float32).astype(dt)
        s = (jnp.einsum("bchd,bhtd->bhct", q_nope, kv[..., :nope],
                        precision=precision,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bchd,btd->bhct", q_pe,
                          lat[..., rank:rank + q_pe.shape[-1]],
                          precision=precision,
                          preferred_element_type=jnp.float32)) * scale
        seen = kpos[None, None, :] <= qpos[:, :, None]          # (b, c, t)
        s = jnp.where(seen[:, None], s, _NEG)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])       # exactly 0 where unseen
        l_new = alpha * l_run + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhct,bhto->bhco", p.astype(dt), kv[..., nope:],
                        precision=precision,
                        preferred_element_type=jnp.float32)
        return m_new, l_new, alpha[..., None] * acc + pv

    with scope:
        steps = (jnp.max(start) + c + t - 1) // t
        init = (jnp.full((b, h, c), _NEG, jnp.float32),
                jnp.zeros((b, h, c), jnp.float32),
                jnp.zeros((b, h, c, v_dim), jnp.float32))
    with jax.named_scope("mla_prefill_loop"):
        _, l_run, acc = lax.fori_loop(0, steps, body, init)
    with scope:
        out = acc / l_run[..., None]                           # (b, h, c, v)
        return out.transpose(0, 2, 1, 3).reshape(b, c, h * v_dim).astype(dt)
