"""Paged decode attention (Pallas TPU kernel).

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
lanes).  This kernel's copies (`_chunk_copies`) are the one place outside that
module that knows it: the pool reaches the kernel whole, in HBM, and
``pool_ref.at[layer, 0 | 1, block]`` is one `(block_size, embed)` DMA.  Nothing
is transposed or split into heads in HBM.  In VMEM the heads are told apart by
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


def applies(pool, num_heads):
    """Whether the kernel can attend over ``pool`` (an unquantised paged
    pool) here: a TPU backend (or the interpreter), float32 or bfloat16
    blocks that are whole sublane tiles, and an embed axis that is a whole
    number of 64- or 128-wide heads and of 128-lane tiles."""
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return False
    if pool.ndim != 5 or pool.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    bs, e = pool.shape[3:]
    sublanes = 32 // pool.dtype.itemsize       # 8 for float32, 16 for bf16
    return (e % num_heads == 0 and e // num_heads in _HEAD_DIMS
            and e % 128 == 0 and bs % sublanes == 0)


def _chunk_copies(tables_ref, pos_ref, pool_ref, k_buf, v_buf, sem, layer,
                  row, chunk, slot, *, n_table, block_size, chunk_blocks):
    """[(live, K copy, V copy)] for every block of chunk ``chunk`` of row
    ``row`` into buffer ``slot``.  ``live`` is whether the row has reached
    the block: entry index <= min(pos // block_size, n_table - 1).  The
    pool's layout (`init_block_pool`) is known here and nowhere else in
    the kernel."""
    last = jnp.minimum(pos_ref[row] // block_size, n_table - 1)
    out = []
    for c in range(chunk_blocks):
        ent = chunk * chunk_blocks + c
        blk = tables_ref[row * n_table + jnp.minimum(ent, n_table - 1)]
        dst = pl.ds(c * block_size, block_size)
        out.append((
            ent <= last,
            pltpu.make_async_copy(pool_ref.at[layer, 0, blk],
                                  k_buf.at[slot, dst], sem.at[slot, 0]),
            pltpu.make_async_copy(pool_ref.at[layer, 1, blk],
                                  v_buf.at[slot, dst], sem.at[slot, 1])))
    return out


def _split3(p):
    """float32 ``p`` as three bf16 terms, stacked on the row axis, whose
    float32 sum is ``p``."""
    hi = p.astype(jnp.bfloat16)
    r1 = p - hi.astype(jnp.float32)
    mid = r1.astype(jnp.bfloat16)
    lo = (r1 - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([hi, mid, lo], axis=0)


def _kernel(tables_ref, pos_ref, layer_ref, q_ref, pool_ref, o_ref,
            k_buf, v_buf, sem, acc_ref, m_ref, l_ref, slot_ref, *,
            n_table, block_size, chunk_blocks, num_heads, scale, group=1):
    r = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    hp, e = acc_ref.shape
    kv_heads = num_heads // group
    hd = e // kv_heads
    t = chunk_blocks * block_size
    exact = k_buf.dtype == jnp.bfloat16 and q_ref.dtype == jnp.bfloat16
    copies = functools.partial(
        _chunk_copies, tables_ref, pos_ref, pool_ref, k_buf, v_buf, sem,
        layer, n_table=n_table, block_size=block_size,
        chunk_blocks=chunk_blocks)

    def each_live_copy(do, row, chunk, slot):
        for live, ck, cv in copies(row, chunk, slot):
            @pl.when(live)
            def _():
                do(ck)
                do(cv)

    start = functools.partial(each_live_copy, lambda copy: copy.start())
    wait = functools.partial(each_live_copy, lambda copy: copy.wait())

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

    # head h owns lanes [h * hd, (h + 1) * hd)
    lane = lax.broadcasted_iota(jnp.int32, (hp, e), 1)
    head = lax.broadcasted_iota(jnp.int32, (hp, e), 0) * hd
    if group > 1:
        # row j * kv_heads + k reads K/V head k
        head = head % e
    own = (lane >= head) & (lane < head + hd)
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
            j = i * t + lax.broadcasted_iota(jnp.int32, (t, e), 0)
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
        acc_ref[...] = alpha * acc_ref[...] + pv              # (hp, e)
        m_ref[...] = m_new
        return ()

    lax.fori_loop(0, n_chunks, body, ())
    slot_ref[0] = (base + n_chunks) % 2

    # each head's own lanes of its accumulator row, over its sum
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
                  in place
    layer:        int (static or traced)
    block_tables: (b, m) int32
    pos:          (b,) int32: the position the query occupies; its K/V row
                  is already in the pool
    Returns q's shape and dtype, equal to
    `decode_attention(q, gather_paged_kv(pool, layer, 0, block_tables),
    gather_paged_kv(pool, layer, 1, block_tables), pos, num_heads,
    kv_heads=kv_heads)` up to the order of the float32 sums.
    """
    hd = q.shape[1] // num_heads
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    chunk_blocks = max(1, min(_CHUNK_TOKENS // pool.shape[3],
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
    e = pool.shape[4]
    m = block_tables.shape[1]
    bs = pool.shape[3]
    t = chunk_blocks * bs
    # head rows padded to whole sublane tiles of the matmul operands
    hp = -(-num_heads // 16) * 16
    kv_heads = num_heads // group
    # grouped: the query laid out as the kernel's rows, and as many rows out
    rows = 1 if group == 1 else hp
    q_rows = None if group == 1 else _grouped_query(q, kv_heads, group,
                                                    e // kv_heads, hp)
    row = pl.BlockSpec((1, rows, e), lambda r, *_: (r, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, n_table=m, block_size=bs,
                          chunk_blocks=chunk_blocks, num_heads=num_heads,
                          scale=scale, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,       # tables, positions, the layer
            grid=(b,),
            in_specs=[row, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, t, e), pool.dtype),        # K chunks
                pltpu.VMEM((2, t, e), pool.dtype),        # V chunks
                pltpu.SemaphoreType.DMA((2, 2)),          # [slot, K | V]
                pltpu.VMEM((hp, e), jnp.float32),         # accumulator
                pltpu.VMEM((hp, 1), jnp.float32),         # running maximum
                pltpu.VMEM((hp, 1), jnp.float32),         # running sum
                pltpu.SMEM((1,), jnp.int32),              # slot of next row
            ]),
        out_shape=jax.ShapeDtypeStruct((b, rows, e), q.dtype),
        # rows in order: each starts the next row's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attn",
    )(block_tables.reshape(-1), pos, layer.reshape(1),
      q.reshape(b, 1, e) if group == 1 else q_rows, pool)
    if group == 1:
        return out.reshape(b, e)
    return _ungroup(out, kv_heads, group, e // kv_heads)
