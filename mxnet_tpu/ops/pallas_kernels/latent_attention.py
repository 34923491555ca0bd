"""Paged decode attention over a latent cache (Pallas TPU kernel).

Multi-head latent attention caches one row a token a layer, shared by all
heads: the normalised compression ``c_kv`` (``rank`` wide) and the rotated
position key ``k_pe`` beside it.  In the absorbed form a generated token's
query is carried into that space (``q' = q_nope W_kvb^K`` a head, with
``q_pe`` beside it), so every head scores against the same cached row and
sums the same ``c_kv``: one "head" of ``rank + rope`` for the scores and
``rank`` for the values, with all the model's heads as the rows of one
matmul.  `paged_attention.paged_decode_attn` cannot compute that (it splits
a K/V pair of model width into heads); this kernel is its sibling and keeps
its structure: for row ``r`` it copies in only the blocks the row has
reached, entries ``0 .. min(pos[r] // block_size, m - 1)`` of its table, in
the pool's own dtype, double-buffered, ``chunk`` blocks to a step (the next
chunk's copies, or the next row's first, fly while this one is attended),
with a running maximum, sum and accumulator in float32.

Layout.  The pool is ``(layers, n_blocks, block_size, width)`` as
`serving.latent.LatentMoEKVModel.init_block_pool` makes it, ``width`` being
``rank + rope`` in whole 128-lane tiles with zeros in the spare lanes (a copy
must be whole tiles, and HBM holds whole tiles whatever the shape says); it
reaches the kernel whole, in HBM, and ``pool_ref.at[layer, block]`` is one
DMA.  The query comes as wide, zeros in the same lanes, so the scores are one
matmul over ``width``.

Arithmetic.  bf16 x bf16 products are exact in float32 and the MXU sums them
in float32.  The probabilities are float32, split into ``_P_TERMS`` bf16
terms (2 x 8 bits of mantissa) that each multiply ``c_kv`` exactly, so
their rounding stays under that of the bf16 rows they weigh.  A float32
pool takes float32 matmuls at `Precision.HIGHEST`.  Positions
``j > pos[r]`` score -inf and their cached rows are zeroed in the buffer, so
a freed block's garbage contributes nothing.

Rows.  A padding row (``pos`` 0, an all-trash table) walks one block.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"

# tokens attended in one step of a row's walk: both buffer slots hold
# 2 * _CHUNK_TOKENS * width * itemsize bytes of VMEM
_CHUNK_TOKENS = 512

_P_TERMS = 2


def applies(pool, rank):
    """Whether the kernel can attend over ``pool`` here: a TPU backend (or
    the interpreter), float32 or bfloat16 blocks that are whole sublane
    tiles, and a width and a compression that are whole 128-lane tiles."""
    if pool.ndim != 4 or pool.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if _INTERPRET:
        return True
    sublanes = 32 // pool.dtype.itemsize
    return (jax.default_backend() == "tpu" and rank % 128 == 0
            and pool.shape[3] % 128 == 0 and pool.shape[2] % sublanes == 0)


def _chunk_copies(tables_ref, pos_ref, pool_ref, buf, sem, layer, row, chunk,
                  slot, *, n_table, block_size, chunk_blocks):
    """[(live, copy)] for every block of chunk ``chunk`` of row ``row`` into
    buffer ``slot``; ``live`` is whether the row has reached the block.  The
    pool's layout is known here and nowhere else in the kernel."""
    last = jnp.minimum(pos_ref[row] // block_size, n_table - 1)
    out = []
    for c in range(chunk_blocks):
        ent = chunk * chunk_blocks + c
        blk = tables_ref[row * n_table + jnp.minimum(ent, n_table - 1)]
        out.append((ent <= last, pltpu.make_async_copy(
            pool_ref.at[layer, blk],
            buf.at[slot, pl.ds(c * block_size, block_size)], sem.at[slot])))
    return out


def _split(p, terms):
    """float32 ``p`` as ``terms`` bf16 terms, stacked on the row axis, whose
    float32 sum is ``p`` to ``8 * terms`` bits."""
    out, rest = [], p
    for _ in range(terms):
        part = rest.astype(jnp.bfloat16)
        out.append(part)
        rest = rest - part.astype(jnp.float32)
    return jnp.concatenate(out, axis=0)


def _kernel(tables_ref, pos_ref, layer_ref, q_ref, pool_ref, o_ref,
            buf, sem, acc_ref, m_ref, l_ref, slot_ref, *,
            n_table, block_size, chunk_blocks, rank, scale):
    r = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    hp = acc_ref.shape[0]
    width = buf.shape[2]
    t = chunk_blocks * block_size
    exact = buf.dtype == jnp.bfloat16 and q_ref.dtype == jnp.bfloat16
    copies = functools.partial(
        _chunk_copies, tables_ref, pos_ref, pool_ref, buf, sem, layer,
        n_table=n_table, block_size=block_size, chunk_blocks=chunk_blocks)

    def each_live_copy(do, row, chunk, slot):
        for live, copy in copies(row, chunk, slot):
            @pl.when(live)
            def _():
                do(copy)

    start = functools.partial(each_live_copy, lambda copy: copy.start())
    wait = functools.partial(each_live_copy, lambda copy: copy.wait())

    @pl.when(r == 0)
    def _():
        slot_ref[0] = 0
        start(0, 0, 0)

    # the slot this row's first chunk is already on its way into
    base = slot_ref[0]
    pos = jnp.minimum(pos_ref[r], n_table * block_size - 1)
    n_chunks = pos // t + 1

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                              # (hp, width)
    if not exact:
        q = q.astype(jnp.float32)
    precision = None if exact else lax.Precision.HIGHEST
    nt = (((1,), (1,)), ((), ()))

    def body(i, _):
        slot = (base + i) % 2
        last = i + 1 == n_chunks

        @pl.when(jnp.logical_not(last))
        def _():
            start(r, i + 1, 1 - slot)

        @pl.when(last & (r + 1 < n_rows))
        def _():
            start(r + 1, 0, 1 - slot)

        wait(r, i, slot)

        @pl.when(last)
        def _():
            # past the row's position the buffer holds a block's unwritten
            # tail or an earlier chunk's rows: exact zeros, so that a
            # probability of 0 multiplies no garbage (0 * NaN).  (Selects
            # are made in float32: a mask of 32-bit lanes does not lay out
            # over packed bf16 rows.)
            j = i * t + lax.broadcasted_iota(jnp.int32, (t, width), 0)
            buf[slot] = jnp.where(j <= pos, buf[slot].astype(jnp.float32),
                                  0.0).astype(buf.dtype)

        kv = buf[slot]
        if not exact:
            kv = kv.astype(jnp.float32)
        c = kv[:, :rank]
        s = lax.dot_general(q, kv, nt, precision=precision,
                            preferred_element_type=jnp.float32) * scale
        j = i * t + lax.broadcasted_iota(jnp.int32, (hp, t), 1)
        s = jnp.where(j <= pos, s, -jnp.inf)                  # (hp, t)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        if exact:
            pv = lax.dot_general(_split(p, _P_TERMS), c,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            pv = sum(pv[k * hp:(k + 1) * hp] for k in range(_P_TERMS))
        else:
            pv = lax.dot_general(p, c, (((1,), (0,)), ((), ())),
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = alpha * acc_ref[...] + pv              # (hp, rank)
        m_ref[...] = m_new
        return ()

    lax.fori_loop(0, n_chunks, body, ())
    slot_ref[0] = (base + n_chunks) % 2
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def latent_decode_attn(q, pool, layer, block_tables, pos, rank, scale):
    """Absorbed single-query attention of every row over its live blocks of
    layer ``layer`` of the latent pool (`applies(pool, rank)` must hold).

    q:            (b, heads, width): ``q_nope W_kvb^K`` beside ``q_pe``, zeros
                  in the spare lanes
    pool:         (layers, n_blocks, block_size, width), read in place
    layer:        int (static or traced)
    block_tables: (b, m) int32
    pos:          (b,) int32: the position the query occupies; its latent
                  row is already in the pool
    Returns (b, heads, rank) in q's dtype: each head's probabilities over
    ``c_kv``, for `W_kvb^V` to carry back to the head's width.
    """
    chunk_blocks = max(1, min(_CHUNK_TOKENS // pool.shape[2],
                              block_tables.shape[1]))
    return _latent_decode(q, pool, jnp.asarray(layer, jnp.int32),
                          block_tables.astype(jnp.int32),
                          pos.astype(jnp.int32), rank=int(rank),
                          scale=float(scale), chunk_blocks=chunk_blocks,
                          interpret=_INTERPRET)


# A function jitted on its own, the layer an operand: a model's layers all
# call one traced and lowered function (see `paged_attention._paged_decode`).
@functools.partial(jax.jit, static_argnames=("rank", "scale", "chunk_blocks",
                                             "interpret"))
def _latent_decode(q, pool, layer, block_tables, pos, *, rank, scale,
                   chunk_blocks, interpret):
    b, h, width = q.shape
    m = block_tables.shape[1]
    bs = pool.shape[2]
    t = chunk_blocks * bs
    # head rows padded to whole sublane tiles of the matmul operands (a
    # pad of nothing at the served width)
    hp = -(-h // 16) * 16
    q = jnp.pad(q, ((0, 0), (0, hp - h), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, n_table=m, block_size=bs,
                          chunk_blocks=chunk_blocks, rank=rank, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,       # tables, positions, the layer
            grid=(b,),
            in_specs=[pl.BlockSpec((1, hp, width), lambda r, *_: (r, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, hp, rank), lambda r, *_: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, t, width), pool.dtype),    # latent chunks
                pltpu.SemaphoreType.DMA((2,)),            # one a slot
                pltpu.VMEM((hp, rank), jnp.float32),      # accumulator
                pltpu.VMEM((hp, 1), jnp.float32),         # running maximum
                pltpu.VMEM((hp, 1), jnp.float32),         # running sum
                pltpu.SMEM((1,), jnp.int32),              # slot of next row
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hp, rank), q.dtype),
        # rows in order: each starts the next row's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attn",
    )(block_tables.reshape(-1), pos, layer.reshape(1), q, pool)
    return out[:, :h]
