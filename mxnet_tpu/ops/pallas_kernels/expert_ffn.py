"""Routed experts as one Pallas TPU kernel, `expert_ffn`: the held experts'
SwiGLU for every (row, expert) pair of one layer that falls on them, in one
call.

`ops.moe.held_share` sorts the layer's pairs by expert and cuts each
expert's rows into tiles of up to ``tile`` rows.  The kernel walks the tiles
that hold a row in that order, ascending expert, each in ``blocks`` steps
over the FFN width ``f``.  A step's slices of its expert's gate, up and down
matrices are copied from HBM into one of two VMEM slots while the step
before it computes, across tile and expert boundaries, so the experts'
weights stream back to back, each read once a tile.  A tile that holds no row
is not walked (the walk's length is a value, not a shape), so an expert no
row chose is never copied.  ``h = silu(x W_g) * (x W_u)`` stays in VMEM.

Rows in and out.  The layer's rows ``u`` are copied into VMEM once and the
routed sums accumulate in a float32 ``(n, d)`` buffer that stays in VMEM and
is copied out once.  A tile's rows are gathered, and its weighted results
added to their rows, by one-hot matmuls over all ``n`` rows (the TPU compiler
takes no load or store at a dynamic row that is not a whole sublane tile):
each output has one term that is not zero, so both are exact.  The gather's
one-hot is 0/1 in the rows' dtype; the weighted results ``w * y`` (float32)
are split into three bf16 terms whose float32 sum is the value, each
multiplied by the one-hot exactly.  Rows that are padding (``valid``) are
zeroed in the copy first, so that whatever they hold multiplies nothing (0 x
NaN is NaN).

Arithmetic, `held_share`'s loop's: rows and weights in their dtype, float32
products, ``h`` rounded to the rows' dtype before the down product, ``y = h
W_d`` in float32 (summed over the width in ``blocks`` parts), ``w * y`` added
to the row's float32 accumulator, a row's experts in ascending order.  So a
row's result does not depend on the other rows of the launch.  A float32
bank takes float32 matmuls at `Precision.HIGHEST`.
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
# CPU executes the real kernel body (see flash_attention.py)
_INTERPRET = os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"

#: most rows of one expert a tile holds (`ops.moe._TILE`)
_TILE = 128

#: rows in whole bf16 sublane tiles
_SUBLANES = 16

#: VMEM for the weights' two slots: the FFN width is cut into the fewest
#: blocks whose gate, up and down slices fit
_WEIGHT_BYTES = 48 * 1024 * 1024

#: what the kernel may take of VMEM in all (the chip has 128 MiB, the
#: default scoped limit is 16)
_VMEM_BYTES = 100 * 1024 * 1024

#: accumulator columns a one-hot product writes at a time
_COLS = 512


def padded_rows(n):
    """Rows of the kernel's copy of a layer of ``n`` rows: whole sublane
    tiles."""
    return -(-n // _SUBLANES) * _SUBLANES


def tile_rows(n):
    """Rows of one tile for a layer of ``n`` rows: up to `_TILE`, in whole
    sublane tiles."""
    return min(_TILE, padded_rows(n))


def max_tiles(n, top_k, n_held, tile):
    """The most tiles that ``n`` rows of ``top_k`` choices fill on ``n_held``
    experts: an expert holds at most ``n`` rows (a row picks an expert once),
    and only the last tile of each expert that has a row is partly filled."""
    pairs = n * min(top_k, n_held)
    hit = min(n_held, pairs)
    return max(1, min(n_held * -(-n // tile),
                      (pairs + hit * (tile - 1)) // tile))


def ffn_block(d, f, itemsize):
    """The widest block of the FFN width (a divisor of ``f`` in whole
    128-lane tiles) whose gate, up and down slices fit `_WEIGHT_BYTES` in two
    slots; None if none does."""
    for parts in range(1, f // 128 + 1):
        fb = f // parts
        if f % parts == 0 and fb % 128 == 0 \
                and 6 * d * fb * itemsize <= _WEIGHT_BYTES:
            return fb
    return None


def _vmem_bytes(n, d, fb, itemsize):
    """VMEM the kernel takes for ``n`` rows of width ``d``: the weights'
    slots, the rows and the accumulator, a tile's rows and sums, and the
    products' and one-hot products' values."""
    n, tile = padded_rows(n), tile_rows(n)
    cols = min(d, _COLS)
    return (6 * d * fb * itemsize + n * d * (itemsize + 4)
            + tile * d * (itemsize + 4 + 4 + 3 * 2)
            + tile * fb * (4 + 4 + itemsize) + n * tile * 4
            + 4 * n * cols * 4)


def applies(n, d, f, dtype):
    """Whether the kernel takes ``n`` rows of width ``d`` over banks of FFN
    width ``f``, rows and banks in ``dtype``, here: a TPU backend (or the
    interpreter), bf16 or f32, widths in whole 128-lane tiles, and what it
    keeps in VMEM inside `_VMEM_BYTES`."""
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return False
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.bfloat16, jnp.float32):
        return False
    if d % 128 or f % 128:
        return False
    fb = ffn_block(d, f, dtype.itemsize)
    return fb is not None \
        and _vmem_bytes(n, d, fb, dtype.itemsize) <= _VMEM_BYTES


def _column(row):
    """A (1, k) row as a (k, 1) float32 column (a select on the diagonal
    and a sum of one term a row: exact)."""
    k = row.shape[1]
    eye = lax.broadcasted_iota(jnp.int32, (k, k), 0) \
        == lax.broadcasted_iota(jnp.int32, (k, k), 1)
    return jnp.sum(jnp.where(eye, row.astype(jnp.float32), 0.0), axis=1,
                   keepdims=True)


def _split3(y):
    """float32 ``y`` as three bf16 terms whose float32 sum is ``y``."""
    hi = y.astype(jnp.bfloat16)
    r = y - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _kernel(live_ref, expert_ref, rows_ref, wts_ref, valid_ref, u_hbm, wg_hbm,
            wu_hbm, wd_hbm, o_hbm, u_ref, acc_ref, x_ref, y_ref, wg_buf,
            wu_buf, wd_buf, sem, io_sem, *, blocks):
    n, d = acc_ref.shape
    tile = x_ref.shape[0]
    fb = wg_buf.shape[2]
    steps = live_ref[0] * blocks
    dot = functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=None if u_ref.dtype == jnp.bfloat16
        else lax.Precision.HIGHEST)
    cols = [slice(c, min(c + _COLS, d)) for c in range(0, d, _COLS)]

    def copies(s, slot):
        # step ``s`` is block ``s % blocks`` of tile ``s // blocks``
        e = expert_ref[s // blocks]
        f = pl.ds(pl.multiple_of(s % blocks * fb, 128), fb)
        return (pltpu.make_async_copy(wg_hbm.at[e, :, f], wg_buf.at[slot],
                                      sem.at[slot, 0]),
                pltpu.make_async_copy(wu_hbm.at[e, :, f], wu_buf.at[slot],
                                      sem.at[slot, 1]),
                pltpu.make_async_copy(wd_hbm.at[e, f, :], wd_buf.at[slot],
                                      sem.at[slot, 2]))

    @pl.when(steps > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    rows_in = pltpu.make_async_copy(u_hbm, u_ref, io_sem.at[0])
    rows_in.start()
    acc_ref[...] = jnp.zeros_like(acc_ref)
    rows_in.wait()
    valid = valid_ref[...] != 0                                   # (n, 1)
    for c in cols:
        # (selects are made in float32: a mask of 32-bit lanes does not lay
        # out over packed bf16 rows)
        u_ref[:, c] = jnp.where(valid, u_ref[:, c].astype(jnp.float32),
                                0.0).astype(u_ref.dtype)

    def gather(t):
        # row i of the tile is row ``rows[t, i]`` of u, or zeros where -1
        hot = (lax.broadcasted_iota(jnp.int32, (tile, n), 1).astype(
            jnp.float32) == _column(rows_ref[t])).astype(u_ref.dtype)
        for c in cols:
            x_ref[:, c] = dot(hot, u_ref[:, c], (((1,), (0,)), ((), ()))
                              ).astype(x_ref.dtype)

    def combine(t, y):
        # row ``rows[t, i]`` of the accumulator += w[t, i] * y[i]
        wy = y * _column(wts_ref[t])
        hot = (lax.broadcasted_iota(jnp.int32, (n, tile), 0)
               == rows_ref[t]).astype(jnp.bfloat16)               # (n, tile)
        for c in cols:
            hi, mid, lo = (dot(hot, p, (((1,), (0,)), ((), ())))
                           for p in _split3(wy[:, c]))
            acc_ref[:, c] += (hi + mid) + lo

    def step(s, _):
        t = s // blocks
        k = s % blocks
        slot = s % 2

        @pl.when(s + 1 < steps)
        def _():
            for c in copies(s + 1, 1 - slot):
                c.start()

        @pl.when(k == 0)
        def _():
            gather(t)
            if blocks > 1:
                y_ref[...] = jnp.zeros_like(y_ref)

        for c in copies(s, slot):
            c.wait()
        x = x_ref[...]
        mm = (((1,), (0,)), ((), ()))
        g = dot(x, wg_buf[slot], mm)
        up = dot(x, wu_buf[slot], mm)
        h = (jax.nn.silu(g) * up).astype(x.dtype)
        part = dot(h, wd_buf[slot], mm)                          # (tile, d)
        if blocks == 1:
            combine(t, part)
        else:
            y_ref[...] += part

            @pl.when(k == blocks - 1)
            def _():
                combine(t, y_ref[...])

        return ()

    lax.fori_loop(0, steps, step, ())
    out = pltpu.make_async_copy(acc_ref, o_hbm, io_sem.at[1])
    out.start()
    out.wait()


def expert_ffn(u, rows, wts, experts, live, w_gate, w_up, w_down,
               valid=None):
    """The routed sum of every tile's pairs, ``(n, d)`` float32 (`applies`
    must hold for ``u`` and the banks).

    u:       (n, d) rows
    rows:    (tiles, tile) int32: the row of u each place of a tile holds,
             -1 where none; a tile's rows are one expert's
    wts:     (tiles, tile) float32: each place's routing weight (0 where -1)
    experts: (tiles,) int32: each tile's expert, an index into the banks
    live:    int32 scalar: the tiles that hold a row, which come first and
             in ascending expert
    w_gate, w_up: (experts, d, f); w_down: (experts, f, d)
    valid:   (n,) bool or None: rows that are padding are zeroed first
    """
    n, d = u.shape
    n_pad = padded_rows(n)
    if valid is None:
        valid = jnp.ones((n,), jnp.int32)
    valid = jnp.pad(valid.astype(jnp.int32), (0, n_pad - n))
    if n_pad != n:
        u = jnp.pad(u, ((0, n_pad - n), (0, 0)))
    tiles, tile = rows.shape
    fb = ffn_block(d, w_gate.shape[2], u.dtype.itemsize)
    out = _expert_ffn(
        jnp.reshape(live, (1,)).astype(jnp.int32), experts.astype(jnp.int32),
        rows.reshape(tiles, 1, tile).astype(jnp.int32),
        wts.reshape(tiles, 1, tile).astype(jnp.float32),
        valid.reshape(n_pad, 1), u, w_gate, w_up, w_down, fb=fb,
        interpret=_INTERPRET)
    return out[:n]


# A function jitted on its own, so that a model's expert layers all call one
# traced and lowered function (`paged_attention._paged_decode`).
@functools.partial(jax.jit, static_argnames=("fb", "interpret"))
def _expert_ffn(live, experts, rows, wts, valid, u, w_gate, w_up, w_down, *,
                fb, interpret):
    n, d = u.shape
    tile = rows.shape[2]
    f = w_gate.shape[2]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, blocks=f // fb),
        in_specs=[smem, smem, vmem, vmem, vmem, hbm, hbm, hbm, hbm],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((n, d), u.dtype),                  # the rows
            pltpu.VMEM((n, d), jnp.float32),              # the accumulator
            pltpu.VMEM((tile, d), u.dtype),               # a tile's rows
            pltpu.VMEM((tile, d), jnp.float32),           # their y
            pltpu.VMEM((2, d, fb), w_gate.dtype),         # gate slots
            pltpu.VMEM((2, d, fb), w_up.dtype),           # up slots
            pltpu.VMEM((2, fb, d), w_down.dtype),         # down slots
            pltpu.SemaphoreType.DMA((2, 3)),              # [slot, matrix]
            pltpu.SemaphoreType.DMA((2,)),                # rows in, sums out
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="expert_ffn",
    )(live, experts, rows, wts, valid, u, w_gate, w_up, w_down)
