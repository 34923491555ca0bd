"""Paged attention over the K/V-pair pool (Pallas TPU kernels): the decode
step `paged_decode_attn` and, further down, the prefill chunk
`paged_prefill_attn`.

One generated token per row attends to that row's context in the serving
engine's paged K/V pool.  The `jax.numpy` form (`ops.attention`:
`gather_paged_kv` + `decode_attention`) copies every entry of every row's
block table out of the pool, live or trash, writes the copy again as float32
split into heads, and reads that twice; as it stood, slicing the layer's K
and V pools out before the gather, the TPU compiler copied those whole as
well: together 97 % of a decode launch on the chip (PERF.md, PR 25 and
PR 31).  This kernel leaves the pool where it is.  For row ``r`` it
copies in only the blocks the row has reached, entries
``0 .. min(pos[r] // block_size, m - 1)`` of its table, in the pool's own
dtype, double-buffered, ``chunk`` blocks to a step (the next chunk's copies,
or the next row's first, fly while this one is attended), and keeps a running
maximum, sum and accumulator in float32: the online softmax of the training
kernels.

Layout.  The pool is ``(layers, 2, n_blocks, block_size, embed)`` as
`serving.decode.TransformerKVModel.init_block_pool` makes it, K at index 0
and V at 1 of the second axis, ``embed`` head-major (heads side by side in the
lanes); or, where K and V heads differ in width, a pair of ``(layers, 1,
n_blocks, block_size, width)`` arrays, K's and V's, each at its own width
(`serving.window.HybridWindowMoEKVModel.init_block_pool`; `pair`).  This
kernel's copies (`_chunk_copies`) are the one place outside those modules that
knows it: the pool reaches the kernel whole, in HBM, and ``k_ref.at[layer, 0,
block]`` (``v_ref.at[layer, 1 | 0, block]``) is one `(block_size, width)` DMA.
Nothing is transposed or split into heads in HBM.  In VMEM the heads are told apart by
a block-diagonal query: row ``h`` of ``(heads, embed)`` holds the query's
lanes of head ``h`` and zeros elsewhere, so ``q_bd @ K^T`` is every head's
score row in one matmul and ``p @ V`` masked by the same pattern is every
head's output.

Grouped-query attention (``kv_heads`` fewer than ``num_heads``: ``group``
query heads read one cached head).  The pool's rows are ``kv_heads`` heads
wide and the block-diagonal query has ``num_heads`` rows over them: row
``j * kv_heads + k`` holds the query of head ``k * group + j`` in the lanes of
K/V head ``k``, so the same two matmuls score and sum every query head
against the cached head it reads.  Rows of one K/V head share lanes, so the
rows cannot be folded into one in the kernel: the wrapper lays the query out
(`_grouped_query`) and folds the ``(rows, embed)`` result back
(`_ungroup`), both in XLA, over a few KB a row.  With ``kv_heads ==
num_heads`` neither exists and the program is the one-to-one kernel's.

Arithmetic.  bf16 x bf16 products are exact in float32 and the MXU sums them
in float32, so with a bf16 pool K and V go to the matmuls as they are; the
probabilities stay float32, split into three bf16 terms whose sum is the
float32 value (3 x 8 bits of mantissa), each multiplied by V exactly.  A
float32 pool takes float32 matmuls at `Precision.HIGHEST`.  Positions
``j > pos[r]`` get a score of -inf and a V of exact zero: a freed block's
garbage (NaN included) contributes nothing, as in `decode_attention`.

Rows.  A padding row (``pos`` 0, an all-trash table) walks one block.  The
megastep's dead row (``pos = m * block_size``) walks all ``m`` entries; its
output is discarded by the caller.
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

# MXNET_PALLAS_INTERPRET=1: run the kernel through the interpreter so the
# CPU mesh executes the real kernel body (see flash_attention.py)
_INTERPRET = os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"

# tokens attended in one step of a row's walk: the K and V chunks of both
# buffer slots are 4 * _CHUNK_TOKENS * embed * itemsize bytes of VMEM
_CHUNK_TOKENS = 256

_HEAD_DIMS = (64, 128)

# q/k heads the decode kernel takes: a V head's widths, and 192 beside a
# narrower V head (its K rows are whole 128-lane tiles at 4 heads a row)
_QK_HEAD_DIMS = (64, 128, 192)


def pair(pool):
    """(K's array, V's array, V's index on their second axis) of a paged
    K/V pool: one ``(layers, 2, n_blocks, block_size, embed)`` array, K at 0
    and V at 1, or a pair of ``(layers, 1, n_blocks, block_size, width)``
    arrays, K's and V's, each at its own width."""
    if isinstance(pool, (tuple, list)):
        return pool[0], pool[1], 0
    return pool, pool, 1


def applies(pool, num_heads):
    """Whether the decode kernel can attend over ``pool`` (an unquantised
    paged pool, or a pair of them, `pair`) of ``num_heads`` heads a row
    here: a TPU backend (or the interpreter), float32 or bfloat16 blocks
    that are whole sublane tiles, and K and V rows that are whole 128-lane
    tiles of whole heads: V heads 64 or 128 wide, K heads as wide as V's or
    192 (the q/k width of a model whose V heads are narrower)."""
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return False
    k_pool, v_pool, _ = pair(pool)
    if any(p.ndim != 5 or p.dtype not in (jnp.bfloat16, jnp.float32)
           for p in (k_pool, v_pool)):
        return False
    if k_pool.dtype != v_pool.dtype or k_pool.shape[2:4] \
            != v_pool.shape[2:4]:
        return False
    bs, e = k_pool.shape[3:]
    ev = v_pool.shape[4]
    sublanes = 32 // k_pool.dtype.itemsize     # 8 for float32, 16 for bf16
    return (e % num_heads == 0 and ev % num_heads == 0
            and ev // num_heads in _HEAD_DIMS
            and e // num_heads in (_HEAD_DIMS if e == ev else _QK_HEAD_DIMS)
            and e % 128 == 0 and ev % 128 == 0 and bs % sublanes == 0)


def _chunk_copies(tables_ref, pos_ref, k_ref, v_ref, v_at, k_buf, v_buf,
                  sem, layer, row, chunk, slot, *, n_table, block_size,
                  chunk_blocks):
    """[(live, K copy, V copy)] for every block of chunk ``chunk`` of row
    ``row`` into buffer ``slot``.  ``live`` is whether the row has reached
    the block: entry index <= min(pos // block_size, n_table - 1).  The
    pool's layout (`init_block_pool`: K in ``k_ref`` at 0, V in ``v_ref`` at
    ``v_at``) is known here and nowhere else in the kernel."""
    last = jnp.minimum(pos_ref[row] // block_size, n_table - 1)
    out = []
    for c in range(chunk_blocks):
        ent = chunk * chunk_blocks + c
        blk = tables_ref[row * n_table + jnp.minimum(ent, n_table - 1)]
        dst = pl.ds(c * block_size, block_size)
        out.append((
            ent <= last,
            pltpu.make_async_copy(k_ref.at[layer, 0, blk],
                                  k_buf.at[slot, dst], sem.at[slot, 0]),
            pltpu.make_async_copy(v_ref.at[layer, v_at, blk],
                                  v_buf.at[slot, dst], sem.at[slot, 1])))
    return out


def _each_live_copy(copies, do, row, chunk, slot):
    """``do(copy)`` for the K and the V copy of every block of ``copies(row,
    chunk, slot)`` that the row has reached."""
    for live, ck, cv in copies(row, chunk, slot):
        @pl.when(live)
        def _():
            do(ck)
            do(cv)


def _split3(p):
    """float32 ``p`` as three bf16 terms, stacked on the row axis, whose
    float32 sum is ``p``."""
    hi = p.astype(jnp.bfloat16)
    r1 = p - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _kernel(tables_ref, pos_ref, layer_ref, q_ref, *refs, n_table,
            block_size, chunk_blocks, num_heads, scale, group=1,
            split=False):
    if split:
        # K and V each from its own array (`pair`)
        k_ref, v_ref, o_ref, k_buf, v_buf, sem, acc_ref, m_ref, l_ref, \
            slot_ref = refs
        v_at = 0
    else:
        pool_ref, o_ref, k_buf, v_buf, sem, acc_ref, m_ref, l_ref, \
            slot_ref = refs
        k_ref = v_ref = pool_ref
        v_at = 1
    r = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    hp, ev = acc_ref.shape
    e = k_buf.shape[2]
    kv_heads = num_heads // group
    hd = e // kv_heads
    t = chunk_blocks * block_size
    exact = k_buf.dtype == jnp.bfloat16 and q_ref.dtype == jnp.bfloat16
    copies = functools.partial(
        _chunk_copies, tables_ref, pos_ref, k_ref, v_ref, v_at, k_buf, v_buf,
        sem, layer, n_table=n_table, block_size=block_size,
        chunk_blocks=chunk_blocks)

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
    # the last position attended: the table's coverage bounds a dead row's
    pos = jnp.minimum(pos_ref[r], n_table * block_size - 1)
    n_chunks = pos // t + 1

    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def owned(width, hd):
        # head h owns lanes [h * hd, (h + 1) * hd)
        lane = lax.broadcasted_iota(jnp.int32, (hp, width), 1)
        head = lax.broadcasted_iota(jnp.int32, (hp, width), 0) * hd
        if group > 1:
            # row j * kv_heads + k reads K/V head k
            head = head % width
        return (lane >= head) & (lane < head + hd)

    own = owned(e, hd)
    if group == 1:
        # (selects are made in float32: a mask of 32-bit lanes does not lay
        # out over packed bf16 rows)
        q_bd = jnp.where(own, q_ref[0].astype(jnp.float32), 0.0)  # (hp, e)
        if exact:
            q_bd = q_bd.astype(jnp.bfloat16)
    else:
        q_bd = q_ref[0]               # laid out by `_grouped_query`
    precision = None if exact else lax.Precision.HIGHEST

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
            # probability of 0 multiplies no garbage (0 * NaN)
            j = i * t + lax.broadcasted_iota(jnp.int32, (t, ev), 0)
            v_buf[slot] = jnp.where(j <= pos,
                                    v_buf[slot].astype(jnp.float32),
                                    0.0).astype(v_buf.dtype)

        k = k_buf[slot]
        v = v_buf[slot]
        if not exact:
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        s = lax.dot_general(q_bd, k, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32) * scale
        j = i * t + lax.broadcasted_iota(jnp.int32, (hp, t), 1)
        s = jnp.where(j <= pos, s, -jnp.inf)                  # (hp, t)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        if exact:
            pv = lax.dot_general(_split3(p), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            pv = pv[:hp] + pv[hp:2 * hp] + pv[2 * hp:]
        else:
            pv = lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 precision=precision,
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = alpha * acc_ref[...] + pv              # (hp, ev)
        m_ref[...] = m_new
        return ()

    lax.fori_loop(0, n_chunks, body, ())
    slot_ref[0] = (base + n_chunks) % 2

    # each head's own lanes of its accumulator row, over its sum (a V
    # head's lanes, where they are narrower than its K head's)
    if ev != e:
        own = owned(ev, ev // kv_heads)
    out = jnp.where(own, acc_ref[...] / l_ref[...], 0.0)
    if group == 1:
        o_ref[0] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)
    else:
        o_ref[0] = out.astype(o_ref.dtype)     # folded by `_ungroup`


def _grouped_query(q, kv_heads, group, hd, hp):
    """(b, num_heads * hd) queries as the kernel's block-diagonal rows
    (b, hp, kv_heads * hd): row ``j * kv_heads + k`` holds head ``k * group
    + j`` in the lanes of K/V head ``k``, zeros elsewhere and in the rows
    past ``num_heads``."""
    b = q.shape[0]
    qg = q.reshape(b, kv_heads, group, hd).transpose(0, 2, 1, 3)
    eye = jnp.eye(kv_heads, dtype=q.dtype)
    rows = (qg[:, :, :, None, :] * eye[None, None, :, :, None]).reshape(
        b, group * kv_heads, kv_heads * hd)
    return jnp.pad(rows, ((0, 0), (0, hp - group * kv_heads), (0, 0)))


def _ungroup(out, kv_heads, group, hd):
    """The kernel's (b, hp, kv_heads * hd) rows, each zero outside its own
    head's lanes, as (b, num_heads * hd) head-major."""
    b = out.shape[0]
    rows = out[:, :group * kv_heads].reshape(b, group, kv_heads,
                                             kv_heads * hd)
    # one row of a group's ``kv_heads`` is non-zero in a lane: an exact sum
    own = jnp.sum(rows.astype(jnp.float32), axis=2).astype(out.dtype)
    return own.reshape(b, group, kv_heads, hd).transpose(0, 2, 1, 3) \
        .reshape(b, group * kv_heads * hd)


def paged_decode_attn(q, pool, layer, block_tables, pos, num_heads, *,
                      scale=None, kv_heads=None):
    """Single-query attention of every row over its live blocks of layer
    ``layer`` of the paged pool (`applies(pool, kv_heads)` must hold;
    ``kv_heads`` None is ``num_heads``).

    q:            (b, num_heads * head)
    pool:         (layers, 2, n_blocks, block_size, kv_heads * head), read
                  in place, or the pair of K's (layers, 1, ..., kv_heads *
                  head) and V's (layers, 1, ..., kv_heads * v_head) (`pair`)
    layer:        int (static or traced)
    block_tables: (b, m) int32
    pos:          (b,) int32: the position the query occupies; its K/V row
                  is already in the pool
    Returns (b, num_heads * v_head) in q's dtype (q's shape where K and V
    heads are alike), equal to
    `decode_attention(q, gather_paged_kv(pool, layer, 0, block_tables),
    gather_paged_kv(pool, layer, 1, block_tables), pos, num_heads,
    kv_heads=kv_heads)` up to the order of the float32 sums.
    """
    hd = q.shape[1] // num_heads
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    chunk_blocks = max(1, min(_CHUNK_TOKENS // pair(pool)[0].shape[3],
                              block_tables.shape[1]))
    return _paged_decode(q, pool, jnp.asarray(layer, jnp.int32),
                         block_tables.astype(jnp.int32),
                         pos.astype(jnp.int32), num_heads=num_heads,
                         scale=float(scale), chunk_blocks=chunk_blocks,
                         interpret=_INTERPRET,
                         group=1 if kv_heads is None
                         else num_heads // int(kv_heads))


# A function jitted on its own: the layer is an operand, so a model's layers
# all call one traced and lowered function, and a decode program's lowering
# (paid at every start, to look the program up in the compile cache) holds
# the kernel once, not once a layer.
@functools.partial(jax.jit, static_argnames=("num_heads", "scale",
                                             "chunk_blocks", "interpret",
                                             "group"))
def _paged_decode(q, pool, layer, block_tables, pos, *, num_heads, scale,
                  chunk_blocks, interpret, group=1):
    b = q.shape[0]
    k_pool, v_pool, _ = pair(pool)
    split = k_pool is not v_pool
    e, ev = k_pool.shape[4], v_pool.shape[4]
    m = block_tables.shape[1]
    bs = k_pool.shape[3]
    t = chunk_blocks * bs
    # head rows padded to whole sublane tiles of the matmul operands
    hp = -(-num_heads // 16) * 16
    kv_heads = num_heads // group
    # grouped: the query laid out as the kernel's rows, and as many rows out
    rows = 1 if group == 1 else hp
    q_rows = None if group == 1 else _grouped_query(q, kv_heads, group,
                                                    e // kv_heads, hp)
    row = pl.BlockSpec((1, rows, e), lambda r, *_: (r, 0, 0))
    out_row = row if ev == e else pl.BlockSpec((1, rows, ev),
                                               lambda r, *_: (r, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, n_table=m, block_size=bs,
                          chunk_blocks=chunk_blocks, num_heads=num_heads,
                          scale=scale, group=group, split=split),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,       # tables, positions, the layer
            grid=(b,),
            in_specs=[row] + [pl.BlockSpec(memory_space=pl.ANY)]
            * (1 + split),
            out_specs=out_row,
            scratch_shapes=[
                pltpu.VMEM((2, t, e), k_pool.dtype),      # K chunks
                pltpu.VMEM((2, t, ev), v_pool.dtype),     # V chunks
                pltpu.SemaphoreType.DMA((2, 2)),          # [slot, K | V]
                pltpu.VMEM((hp, ev), jnp.float32),        # accumulator
                pltpu.VMEM((hp, 1), jnp.float32),         # running maximum
                pltpu.VMEM((hp, 1), jnp.float32),         # running sum
                pltpu.SMEM((1,), jnp.int32),              # slot of next row
            ]),
        out_shape=jax.ShapeDtypeStruct((b, rows, ev), q.dtype),
        # rows in order: each starts the next row's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attn",
    )(block_tables.reshape(-1), pos, layer.reshape(1),
      q.reshape(b, 1, e) if group == 1 else q_rows,
      k_pool, *[v_pool] * split)
    if group == 1:
        return out.reshape(b, ev)
    return _ungroup(out, kv_heads, group, ev // kv_heads)


# -- the prefill chunk --------------------------------------------------------
#
# A chunk of ``c`` queries a row attends to the row's cached prefix and to
# itself, causally; its own K and V rows are already in the pool.  The
# `jax.numpy` form (`ops.attention`: `gather_paged_kv` + `chunk_attention`)
# gathers the table's every entry, promotes it to float32 and scores every
# query against the table's whole width, whatever the prompt's length.  This
# kernel walks entries ``0 .. min((start + c - 1) // block_size, m - 1)``
# only, ``_PREFILL_STEP_TOKENS`` tokens a step, in the pool's own dtype.
#
# Grid (row, 128-lane tile of the pool's rows).  A tile holds ``128 // hd``
# K/V heads, read by the ``group`` query heads each whose lanes the query's
# block of the same index holds (head-major layouts on both sides).  The
# row's live context is copied in once, whole blocks, by the row's first tile
# (`_chunk_copies`: a step's copies fly while the step before is attended)
# and stays in VMEM for the others, so the pool is read once a row however
# many tiles it has.  A query head is laid in the lanes of the K/V head it
# reads (rolled by half a tile where they differ), zeros elsewhere, so that
# its scores are one matmul with the tile's keys and its values one with the
# tile's values, of which it keeps its head's lanes.
#
# Arithmetic, the training flash kernels' and `latent_prefill_attn`'s:
# scores, the softmax statistics and the accumulator are float32; a bf16
# pool's K and V go to the MXU as they are, and the probabilities are rounded
# to bf16 for the product with V.  A float32 pool takes float32 matmuls at
# `Precision.HIGHEST`.  Positions past the chunk's last row score -inf and
# their V rows are zeroed in the buffer, so a stale block (NaN included)
# contributes nothing, as in `chunk_attention`.

# tokens of the context attended in one step of a tile's walk, and queries
# a block of a step: a block whose last query comes before the step's first
# position is skipped, a quarter of the diagonal of a chunk of 512 (on the
# chip 256 and 256 took 0.039 ms a GPT-2 layer for a chunk of 512 at
# position 0, 128 and 128 took 0.054; PERF.md, PR 35)
_PREFILL_STEP_TOKENS = 256
_PREFILL_QUERY_ROWS = 256

# what a row's live K and V context may take of VMEM: it is kept whole
_PREFILL_CONTEXT_BYTES = 24 * 1024 * 1024

# what the context, a tile's scratch and a step's scores may take of VMEM
# (the default scoped limit is 16 MiB of the chip's 128)
_PREFILL_VMEM_BYTES = 64 * 1024 * 1024


def _prefill_step_blocks(block_size, n_table):
    return max(1, min(_PREFILL_STEP_TOKENS // block_size, n_table))


def prefill_applies(pool, kv_heads, c, n_table):
    """Whether `paged_prefill_attn` can attend a chunk of ``c`` queries over
    tables of ``n_table`` entries of ``pool`` here: one array whose K and V
    heads are alike (not a `pair` of widths), what `applies` asks of it, a
    chunk of whole sublane tiles, and a table whose K and V fit the kernel's
    share of VMEM."""
    if isinstance(pool, (tuple, list)) or not applies(pool, kv_heads):
        return False
    bs, e = pool.shape[3:]
    step = _prefill_step_blocks(bs, n_table)
    rows = -(-n_table // step) * step * bs
    return (c % (32 // pool.dtype.itemsize) == 0
            and 2 * rows * e * pool.dtype.itemsize <= _PREFILL_CONTEXT_BYTES)


def _lanes(x, src, dst, hd, lane):
    """The ``hd`` lanes of ``x`` (float32, 128 wide) from ``src`` moved to
    ``dst``, zeros in the others."""
    if src != dst:
        x = pltpu.roll(x, (dst - src) % 128, 1)
    return jnp.where((lane >= dst) & (lane < dst + hd), x, 0.0)


def _prefill_kernel(tables_ref, last_ref, start_ref, layer_ref, q_ref,
                    pool_ref, o_ref, k_buf, v_buf, sem, q_bd, acc_ref, m_ref,
                    l_ref, *, n_table, block_size, step_blocks, query_rows,
                    hd, group, scale):
    r, g = pl.program_id(0), pl.program_id(1)
    c, qw = q_ref.shape[1:]
    n_q = qw // hd                        # query heads of the tile
    t = step_blocks * block_size
    exact = k_buf.dtype == jnp.bfloat16 and q_ref.dtype == jnp.bfloat16
    dot = functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=None if exact else lax.Precision.HIGHEST)
    copies = functools.partial(
        _chunk_copies, tables_ref, last_ref, pool_ref, pool_ref, 1, k_buf,
        v_buf, sem, layer_ref[0], n_table=n_table, block_size=block_size,
        chunk_blocks=step_blocks)
    # step ``i``'s blocks go to the buffers' rows ``i`` under semaphores ``i``
    start = functools.partial(_each_live_copy, copies,
                              lambda copy: copy.start(), r)
    wait = functools.partial(_each_live_copy, copies,
                             lambda copy: copy.wait(), r)

    first = start_ref[r]                 # the chunk's first position
    last = last_ref[r]                   # its last, inside the table
    n_steps = last // t + 1

    @pl.when(g == 0)
    def _():
        start(0, 0)

    # a power of two scales the query exactly; any other scales the scores
    folded = scale == 2.0 ** round(math.log2(scale))
    lane = lax.broadcasted_iota(jnp.int32, (c, 128), 1)
    for j in range(n_q):
        # (selects are made in float32: a mask of 32-bit lanes does not lay
        # out over packed bf16 rows)
        lo = j * hd // 128 * 128
        x = q_ref[0, :, lo:lo + 128].astype(jnp.float32)
        if folded:
            x = x * scale
        q_bd[j] = _lanes(x, j * hd % 128, j // group * hd, hd,
                         lane).astype(q_bd.dtype)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    tile = pl.ds(pl.multiple_of(g * 128, 128), 128)

    def step(i, _):
        @pl.when(g == 0)
        def _():
            @pl.when(i + 1 < n_steps)
            def _():
                start(i + 1, i + 1)

            wait(i, i)

            @pl.when(i + 1 == n_steps)
            def _():
                # past the chunk's end the buffer holds a block's unwritten
                # tail or an earlier row's context: exact zeros, so that a
                # probability of 0 multiplies no garbage (0 * NaN)
                j = i * t + lax.broadcasted_iota(jnp.int32, v_buf.shape[1:],
                                                 0)
                v_buf[i] = jnp.where(j <= last,
                                     v_buf[i].astype(jnp.float32),
                                     0.0).astype(v_buf.dtype)

        k = k_buf[i, :, tile]
        v = v_buf[i, :, tile]
        if not exact:
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)
        qb = query_rows
        for a in range(c // qb):
            rows = slice(a * qb, (a + 1) * qb)

            # the block's last query sees the step's first row
            @pl.when(i * t <= first + (a + 1) * qb - 1)
            def _():
                # query ``x`` of the block (position first + a * qb + x)
                # sees the step's row ``y`` (position i * t + y) where
                # y <= first + a * qb - i * t + x, and never past the
                # table's last position (a short last chunk's bucket may
                # reach beyond it)
                limit = jnp.minimum(
                    first + a * qb - i * t
                    + lax.broadcasted_iota(jnp.int32, (qb, 1), 0),
                    last - i * t)
                seen = lax.broadcasted_iota(jnp.int32, (qb, t), 1) <= limit
                for j in range(n_q):
                    s = dot(q_bd[j, rows], k, (((1,), (1,)), ((), ())))
                    if not folded:
                        s = s * scale
                    s = jnp.where(seen, s, -jnp.inf)             # (qb, t)
                    m_prev = m_ref[j, rows]
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s, axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    # (the statistics are kept in all 128 lanes: as a
                    # (rows, 1) column they cost a relayout at every use,
                    # 40 % of a step on the chip; PERF.md, PR 35)
                    m_t = m_new[:, :1] if t % 128 else pltpu.repeat(
                        m_new, t // 128, 1)
                    p = jnp.exp(s - m_t)         # exactly 0 where unseen
                    l_ref[j, rows] = alpha * l_ref[j, rows] + jnp.sum(
                        p, axis=1, keepdims=True)
                    acc_ref[j, rows] = alpha * acc_ref[j, rows] + dot(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())))
                    m_ref[j, rows] = m_new

        return ()

    lax.fori_loop(0, n_steps, step, ())
    # each head's lanes of its accumulator, over its sum, back where the
    # query's layout has them
    for u in range(qw // 128):
        out = jnp.zeros((c, 128), jnp.float32)
        for j in range(u * 128 // hd, (u + 1) * 128 // hd):
            out = out + _lanes(acc_ref[j] / l_ref[j], j // group * hd,
                               j * hd % 128, hd, lane)
        o_ref[0, :, u * 128:(u + 1) * 128] = out.astype(o_ref.dtype)


def paged_prefill_attn(q, pool, layer, block_tables, start, num_heads, *,
                       scale=None, kv_heads=None):
    """Causal attention of a chunk of queries a row over the row's live
    blocks of layer ``layer`` of the paged pool (`prefill_applies(pool,
    kv_heads, c, m)` must hold; ``kv_heads`` None is ``num_heads``).

    q:            (b, c, num_heads * head)
    pool:         (layers, 2, n_blocks, block_size, kv_heads * head), read
                  in place; the chunk's own rows are already written
    layer:        int (static or traced)
    block_tables: (b, m) int32
    start:        (b,) int32: the chunk's first absolute position
    Returns q's shape and dtype, equal to `chunk_attention(q,
    gather_paged_kv(pool, layer, 0, block_tables), gather_paged_kv(pool,
    layer, 1, block_tables), start, num_heads, kv_heads=kv_heads)` up to the
    order of the float32 sums and the probabilities' rounding.
    """
    kv_heads = num_heads if kv_heads is None else int(kv_heads)
    hd = pool.shape[4] // kv_heads
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    return _paged_prefill(
        q, pool, jnp.asarray(layer, jnp.int32),
        block_tables.astype(jnp.int32), start.astype(jnp.int32),
        hd=hd, group=num_heads // kv_heads, scale=float(scale),
        step_blocks=_prefill_step_blocks(pool.shape[3],
                                         block_tables.shape[1]),
        query_rows=math.gcd(q.shape[1], _PREFILL_QUERY_ROWS),
        interpret=_INTERPRET)


# A function jitted on its own, the layer an operand (`_paged_decode`).
@functools.partial(jax.jit, static_argnames=("hd", "group", "scale",
                                             "step_blocks", "query_rows",
                                             "interpret"))
def _paged_prefill(q, pool, layer, block_tables, start, *, hd, group, scale,
                   step_blocks, query_rows, interpret):
    b, c, _ = q.shape
    m, bs, e = block_tables.shape[1], pool.shape[3], pool.shape[4]
    t = step_blocks * bs
    steps = -(-m // step_blocks)
    qw = 128 * group                     # a tile's query lanes
    n_q = qw // hd
    # the last position each row's chunk wrote, inside the table
    last = jnp.minimum(start + c, m * bs) - 1
    block = pl.BlockSpec((1, c, qw), lambda r, g, *_: (r, 0, g))
    return pl.pallas_call(
        functools.partial(_prefill_kernel, n_table=m, block_size=bs,
                          step_blocks=step_blocks, query_rows=query_rows,
                          hd=hd, group=group, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,       # tables, last, start, the layer
            grid=(b, e // 128),
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((steps, t, e), pool.dtype),    # the row's K
                pltpu.VMEM((steps, t, e), pool.dtype),    # and V
                pltpu.SemaphoreType.DMA((steps, 2)),      # [step, K | V]
                pltpu.VMEM((n_q, c, 128), q.dtype),       # laid-out queries
                pltpu.VMEM((n_q, c, 128), jnp.float32),   # accumulators
                pltpu.VMEM((n_q, c, 128), jnp.float32),   # running maxima
                pltpu.VMEM((n_q, c, 128), jnp.float32),   # running sums
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # a row's tiles in order: the first copies the context the others
        # read
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_PREFILL_VMEM_BYTES),
        interpret=interpret,
        name="paged_prefill_attn",
    )(block_tables.reshape(-1), last, start, layer.reshape(1), q, pool)
