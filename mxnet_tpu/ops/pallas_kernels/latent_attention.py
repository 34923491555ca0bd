"""Paged attention over a latent cache (Pallas TPU kernels): the absorbed
decode step `latent_decode_attn` and, further down, the expanded prefill
chunk `latent_prefill_attn`.

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
import math
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


def _each_live_copy(copies, do, row, chunk, slot):
    """``do(copy)`` for every block of ``copies(row, chunk, slot)`` that the
    row has reached."""
    for live, copy in copies(row, chunk, slot):
        @pl.when(live)
        def _():
            do(copy)


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

    start = functools.partial(_each_live_copy, copies,
                              lambda copy: copy.start())
    wait = functools.partial(_each_live_copy, copies,
                             lambda copy: copy.wait())

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


# -- the prefill chunk --------------------------------------------------------
#
# A chunk of ``c`` queries a row attends to the row's cached prefix and to
# itself (its own latent rows are already in the pool) in the expanded form:
# each head has keys of ``nope + rope`` and values of ``v`` that
# ``W_kvb`` makes from a cached row.  The kernel walks the context as the
# decode kernel does, ``_PREFILL_STEP_TOKENS`` cached tokens a step through
# the block table, in place and double-buffered; what it adds is that a
# step's latent rows are expanded to a head's keys and values in VMEM, and
# that the scores, the probabilities and the running softmax of a group of
# ``_PREFILL_HEADS`` heads never leave it.  The grid is (row, group of
# heads): a group's queries, its slice of ``W_kvb`` and its float32
# accumulator stay in VMEM for the whole walk, and the latent rows are read
# once a group.
#
# Arithmetic, the `lax` loop's (`ops.latent_attention`): the expansion is
# rounded to the activations' dtype, scores are float32 sums of two products
# (``q_nope`` over the keys' first ``nope`` columns, ``q_pe`` over the cached
# ``k_pe``), the softmax statistics are float32, and the probabilities are
# rounded to the activations' dtype for the product with the values.  Every
# step takes the causal select, also those that end before the chunk's first
# position and need none: on the chip a second loop without it was no faster
# and doubled the kernel's code (`PERF.md`, PR 33).  Rows at positions past
# the chunk's end are zeroed in the buffer (stale blocks must multiply
# nothing: 0 x NaN).

# cached tokens attended in one step of a chunk's walk
_PREFILL_STEP_TOKENS = 512

# heads a grid step keeps resident (queries, ``W_kvb`` rows, accumulators)
_PREFILL_HEADS = 8

_NEG = -1e30

# what a group's blocks, both buffered, its scratch and a step's scores may
# take of VMEM (the default scoped limit is 16 MiB of the chip's 128)
_PREFILL_VMEM_BYTES = 64 * 1024 * 1024


def prefill_applies(pool, rank, c):
    """Whether `latent_prefill_attn` can attend a chunk of ``c`` queries
    over ``pool`` here: what `applies` asks of the pool, and a chunk that is
    whole sublane tiles."""
    if not applies(pool, rank):
        return False
    return _INTERPRET or c % (32 // pool.dtype.itemsize) == 0


def _prefill_kernel(tables_ref, last_ref, start_ref, layer_ref, qn_ref,
                    qp_ref, w_ref, pool_ref, o_ref, buf, sem, acc_ref, m_ref,
                    l_ref, *, n_table, block_size, chunk_blocks, group, nope,
                    v_dim, rank, scale):
    r = pl.program_id(0)
    layer = layer_ref[0]
    c = qn_ref.shape[1]
    width = buf.shape[2]
    rope_w = width - rank
    t = chunk_blocks * block_size
    dt = qn_ref.dtype
    dot = functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if dt == jnp.float32 else None)
    nt = (((1,), (1,)), ((), ()))
    copies = functools.partial(
        _chunk_copies, tables_ref, last_ref, pool_ref, buf, sem, layer,
        n_table=n_table, block_size=block_size, chunk_blocks=chunk_blocks)
    start = functools.partial(_each_live_copy, copies,
                              lambda copy: copy.start(), r)
    wait = functools.partial(_each_live_copy, copies,
                             lambda copy: copy.wait(), r)

    first = start_ref[r]                 # the chunk's first position
    last = last_ref[r]                   # its last, inside the table
    n_steps = last // t + 1

    start(0, 0)
    m_ref[...] = jnp.full_like(m_ref, _NEG)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(i, _):
        slot = i % 2

        @pl.when(i + 1 < n_steps)
        def _():
            start(i + 1, 1 - slot)

        wait(i, slot)

        @pl.when(i + 1 == n_steps)
        def _():
            # past the chunk's end the buffer holds a block's unwritten
            # tail or an earlier step's rows (selects are made in float32:
            # see the decode kernel)
            j = i * t + lax.broadcasted_iota(jnp.int32, (t, width), 0)
            buf[slot] = jnp.where(j <= last, buf[slot].astype(jnp.float32),
                                  0.0).astype(buf.dtype)

        seen = (i * t + lax.broadcasted_iota(jnp.int32, (c, t), 1)
                <= first + lax.broadcasted_iota(jnp.int32, (c, t), 0))
        lat = buf[slot].astype(dt)
        c_kv, k_pe = lat[:, :rank], lat[:, rank:]
        for j in range(group):
            w = w_ref[j * (nope + v_dim):(j + 1) * (nope + v_dim)]
            kv = dot(c_kv, w, nt).astype(dt)             # (t, nope + v)
            s = (dot(qn_ref[0, :, j * nope:(j + 1) * nope], kv[:, :nope], nt)
                 + dot(qp_ref[0, :, j * rope_w:(j + 1) * rope_w], k_pe, nt)
                 ) * scale                               # (c, t)
            s = jnp.where(seen, s, _NEG)
            m_prev = m_ref[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)               # exactly 0 where unseen
            l_ref[j] = alpha * l_ref[j] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[j] = alpha * acc_ref[j] + dot(
                p.astype(dt), kv[:, nope:], (((1,), (0,)), ((), ())))
            m_ref[j] = m_new
        return ()

    lax.fori_loop(0, n_steps, step, ())
    for j in range(group):
        o_ref[0, :, j * v_dim:(j + 1) * v_dim] = (
            acc_ref[j] / l_ref[j]).astype(o_ref.dtype)


def latent_prefill_attn(q_nope, q_pe, pool, layer, block_tables, start,
                        w_kvb, *, rank, v_dim, scale):
    """Expanded causal attention of a chunk of queries a row over the row's
    live blocks of layer ``layer`` of the latent pool
    (`prefill_applies(pool, rank, c)` must hold).

    q_nope:       (b, c, heads, nope); q_pe: (b, c, heads, rope), rotated
    pool:         (layers, n_blocks, block_size, width), read in place; the
                  chunk's own rows are already written
    layer:        int (static or traced)
    block_tables: (b, m) int32
    start:        (b,) int32: the chunk's first absolute position
    w_kvb:        (heads * (nope + v_dim), rank): a head's key part then its
                  value part
    Returns (b, c, heads * v_dim) in q's dtype.
    """
    m, bs = block_tables.shape[1], pool.shape[2]
    return _latent_prefill(
        q_nope, q_pe, pool, jnp.asarray(layer, jnp.int32),
        block_tables.astype(jnp.int32), start.astype(jnp.int32), w_kvb,
        rank=int(rank), v_dim=int(v_dim), scale=float(scale),
        chunk_blocks=max(1, min(_PREFILL_STEP_TOKENS // bs, m)),
        group=math.gcd(_PREFILL_HEADS, q_nope.shape[2]),
        interpret=_INTERPRET)


@functools.partial(jax.jit, static_argnames=(
    "rank", "v_dim", "scale", "chunk_blocks", "group", "interpret"))
def _latent_prefill(q_nope, q_pe, pool, layer, block_tables, start, w_kvb, *,
                    rank, v_dim, scale, chunk_blocks, group, interpret):
    b, c, h, nope = q_nope.shape
    m, bs, width = block_tables.shape[1], pool.shape[2], pool.shape[3]
    rope_w = width - rank
    t = chunk_blocks * bs
    # ``q_pe`` as wide as the pool's lanes past ``c_kv``, zeros over its
    # spare lanes (zeros in the pool too), so that both are whole tiles
    q_pe = jnp.pad(q_pe, ((0, 0),) * 3 + ((0, rope_w - q_pe.shape[-1]),))
    # the last position each row's chunk wrote, inside the table
    last = jnp.minimum(start + c, m * bs) - 1
    kernel = functools.partial(
        _prefill_kernel, n_table=m, block_size=bs, chunk_blocks=chunk_blocks,
        group=group, nope=nope, v_dim=v_dim, rank=rank, scale=scale)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,       # tables, last, start, the layer
            grid=(b, h // group),
            in_specs=[
                pl.BlockSpec((1, c, group * nope), lambda r, g, *_: (r, 0, g)),
                pl.BlockSpec((1, c, group * rope_w),
                             lambda r, g, *_: (r, 0, g)),
                pl.BlockSpec((group * (nope + v_dim), rank),
                             lambda r, g, *_: (g, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, c, group * v_dim),
                                   lambda r, g, *_: (r, 0, g)),
            scratch_shapes=[
                pltpu.VMEM((2, t, width), pool.dtype),    # latent steps
                pltpu.SemaphoreType.DMA((2,)),            # one a slot
                pltpu.VMEM((group, c, v_dim), jnp.float32),  # accumulators
                pltpu.VMEM((group, c, 1), jnp.float32),   # running maxima
                pltpu.VMEM((group, c, 1), jnp.float32),   # running sums
            ]),
        out_shape=jax.ShapeDtypeStruct((b, c, h * v_dim), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="latent_prefill_attn",
    )(block_tables.reshape(-1), last, start, layer.reshape(1),
      q_nope.reshape(b, c, h * nope), q_pe.reshape(b, c, h * rope_w), w_kvb,
      pool)
