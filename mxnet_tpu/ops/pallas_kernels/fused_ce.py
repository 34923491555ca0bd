"""Fused projection + softmax cross-entropy head (flash-style loss).

The reference's LM head is FullyConnected -> SoftmaxOutput
(`src/operator/fully_connected-inl.h`, `softmax_output-inl.h`): the
(tokens x vocab) logits are materialized, softmaxed, stored as the backward
residual and re-read to form `(p - onehot) * grad_scale`.  At GPT vocab
sizes that is the single largest HBM consumer of the whole training step
(~13 GB/step at 32k x 32k bf16 on one v5e chip — see
`docs/mfu_roofline.md`).

TPU-native redesign: the logits never exist.

* **Forward**: one Pallas kernel, grid (vocab tiles, token blocks) with the
  vocab tile as the sequentially-iterated major axis.  Each step computes
  one (block_n x block_v) logit tile on the MXU and folds it into a running
  online-softmax state (m, l) plus the picked label logit, held in a VMEM
  scratch slab indexed by token block — the whole per-token state is
  3 x N x f32, kilobytes.  Output is the per-token negative log-likelihood
  and the logsumexp residual.
* **Backward** (loss-head semantics: the incoming cotangent is ignored and
  `grad_scale` applied, exactly `softmax_output-inl.h` Backward): two
  kernels, each recomputing its logit tiles from the saved lse —
  flash-attention-style recompute-instead-of-store.
  - dx: grid (token blocks, vocab tiles), per-token-block accumulator
    `dx += dl @ W_tile` in VMEM, written once.
  - dW/db: grid (vocab tiles, token blocks), per-vocab-tile accumulator
    `dW += dl^T @ x_block` in VMEM, written once.
  dl = (softmax - onehot) * grad_scale is formed tile-at-a-time in
  registers and consumed immediately by the MXU.

Cost: 5 logit-tile matmul passes total (1 fwd + 2 recompute + dx + dW) vs
3 for the dense head — ~1.67x head FLOPs traded for ~10 GB/step of HBM
traffic, a large win on a bandwidth-limited chip.

**Single-pass structure** (round 6, `MXNET_CE_SINGLE_PASS=1`, the default):
the round-5 depth bisection measured the 5-pass recompute at 1.67x head
FLOPs with no tiling able to recover it, so the recompute is killed where
it is killable.  Under `jax.vjp` the forward kernel sweeps each (token
block, vocab tile) ONCE and, alongside the online-softmax state, folds the
unnormalized `exp(s - m) @ W_tile` product into a flash-style rescaled
(block_n, d) VMEM accumulator — the per-block residual `p @ W` is stored
(f32, n x d: the size of x, kilobytes per block) instead of the dx
backward recomputing every logit tile from scratch.  Backward then
computes `dx = r * (p@W - W[label])` from the stored residual plus one
cheap XLA gather, and only the dW/db kernel still recomputes its tiles
(its accumulation axis is transposed — storing its residual would BE the
logits).  Cost: 4 logit-tile matmul passes (2 fwd-rule + 2 dW) vs 5 —
head FLOPs drop from 1.67x to 1.33x of the dense pair while the logits
still never exist.  `MXNET_CE_SINGLE_PASS=0` restores the 5-pass
structure bit-for-bit.

**Vocab sharding** (`fused_softmax_ce_sharded`, used inside `shard_map`):
the TPU-first form of the reference PS's range-partitioned big arrays
(`kvstore_dist.h:230-268`) — each device holds a V/n_shards slice of the
head weight, computes local online-softmax stats over its slice, and the
logsumexp reduce rides the mesh (`pmax` + `psum` over the "model" axis).
The per-shard backward is entirely local (dW/db live on the shard);
only the (n, d)-sized dx partial is psum'd.  See `FusedSoftmaxCE`
(`ops/loss.py`) for the `MXNET_CE_SHARD=1` auto-wiring.

Everywhere else (CPU test meshes, tiny vocabs) the same math runs as a
`lax.scan` over vocab tiles.
"""
from __future__ import annotations

import functools
import os as _os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._spmd import call_local, out_struct, reduce_like, vary_alike

_NEG_INF = -1e30

# MXNET_PALLAS_INTERPRET=1: run kernels through the interpreter so CPU CI
# executes the real kernel bodies (see flash_attention.py)
_INTERPRET = _os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"


def _use_pallas(x, w):
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return False
    n, d = x.shape
    v = w.shape[0]
    # tiling wants MXU-aligned dims; tiny heads are better served by XLA
    if d % 128 != 0 or n < 256 or v < 1024:
        return False
    # the forward kernel's online-softmax state is 3 x n x f32 in VMEM
    # scratch: cap so it never crowds out the working blocks
    return 3 * n * 4 <= 8 * 1024 * 1024


# ---------------------------------------------------------------------------
# Pallas forward: grid (vocab tiles j, token blocks i), j major
# ---------------------------------------------------------------------------


def _fwd_kernel(x_ref, w_ref, b_ref, lbl_ref, nll_ref, lse_ref,
                m_s, l_s, a_s, *, block_v, vocab, n_valid, block_n,
                grad_scale, ignore_label, use_ignore):
    j = pl.program_id(0)
    i = pl.program_id(1)
    num_j = pl.num_programs(0)

    @pl.when(j == 0)
    def _init():
        m_s[i, :] = jnp.full((block_n,), _NEG_INF, jnp.float32)
        l_s[i, :] = jnp.zeros((block_n,), jnp.float32)
        a_s[i, :] = jnp.zeros((block_n,), jnp.float32)

    x = x_ref[...]
    w = w_ref[...]
    s = lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s + b_ref[0, :][None, :].astype(jnp.float32)
    col = j * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < vocab, s, _NEG_INF)

    lbl = lbl_ref[0, :]                                   # (bn,) int32
    picked = jnp.sum(jnp.where(col == lbl[:, None], s, 0.0), axis=1)
    a_s[i, :] = a_s[i, :] + picked

    m_prev = m_s[i, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    l_s[i, :] = l_s[i, :] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(s - m_new[:, None]), axis=1)
    m_s[i, :] = m_new

    @pl.when(j == num_j - 1)
    def _fin():
        lse = m_s[i, :] + jnp.log(l_s[i, :])
        nll = lse - a_s[i, :]
        row = i * block_n + lax.iota(jnp.int32, block_n)
        valid = row < n_valid
        if use_ignore:
            valid = jnp.logical_and(valid, lbl != int(ignore_label))
        nll_ref[0, :] = jnp.where(valid, nll, 0.0)
        lse_ref[0, :] = lse


def _fwd_pallas(x, w, b, label, grad_scale, ignore_label, use_ignore,
                block_n, block_v):
    n, d = x.shape
    v = w.shape[0]
    pad_n = (-n) % block_n
    pad_v = (-v) % block_v
    xp = jnp.pad(x, ((0, pad_n), (0, 0))) if pad_n else x
    wp = jnp.pad(w, ((0, pad_v), (0, 0))) if pad_v else w
    bp = jnp.pad(b, (0, pad_v)) if pad_v else b
    lblp = jnp.pad(label, (0, pad_n)) if pad_n else label
    np_, vp_ = n + pad_n, v + pad_v
    num_i, num_j = np_ // block_n, vp_ // block_v

    kernel = functools.partial(
        _fwd_kernel, block_v=block_v, vocab=v, n_valid=n, block_n=block_n,
        grad_scale=grad_scale, ignore_label=ignore_label,
        use_ignore=use_ignore)
    # INVARIANT: the nll/lse out blocks map to (0, i) independent of j, so
    # the buffer is flushed to HBM once per j sweep and earlier sweeps
    # write garbage that the FINAL j = num_j-1 sweep (where _fin runs)
    # overwrites.  Correct only because grid dim 0 (j) executes
    # sequentially — marked 'arbitrary' below to pin that assumption; the
    # redundant flushes cost O(num_j * n) bytes, negligible next to the
    # num_j x-tile re-reads.
    nll, lse = pl.pallas_call(
        kernel,
        grid=(num_j, num_i),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
        ],
        out_shape=[
            out_struct((1, np_), jnp.float32, xp, wp, bp, lblp),
            out_struct((1, np_), jnp.float32, xp, wp, bp, lblp),
        ],
        scratch_shapes=[
            pltpu.VMEM((num_i, block_n), jnp.float32),
            pltpu.VMEM((num_i, block_n), jnp.float32),
            pltpu.VMEM((num_i, block_n), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * np_ * vp_ * d,
            bytes_accessed=(xp.size * num_j * xp.dtype.itemsize
                            + wp.size * wp.dtype.itemsize),
            transcendentals=np_ * vp_,
        ),
        interpret=_INTERPRET,
        name="fused_ce_fwd",
    )(xp, wp, bp.reshape(1, -1), lblp.reshape(1, -1))
    return nll[0, :n], lse[0, :n]


# ---------------------------------------------------------------------------
# Pallas backward kernels
# ---------------------------------------------------------------------------


def _dl_tile(x, w, b, lse, lbl, j, block_v, vocab, n_valid, row0,
             grad_scale, ignore_label, use_ignore):
    """One (block_n x block_v) tile of dl = (softmax - onehot) * grad_scale,
    recomputed from the saved logsumexp."""
    s = lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s + b[None, :].astype(jnp.float32)
    col = j * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < vocab, s, _NEG_INF)
    p = jnp.exp(s - lse[:, None])
    dl = p - jnp.where(col == lbl[:, None], 1.0, 0.0)
    # build the row mask in 2-D: minor-dim insertion on 1-bit vectors is
    # not supported by Mosaic
    row = row0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    valid = row < n_valid
    if use_ignore:
        valid = jnp.logical_and(valid, lbl[:, None] != int(ignore_label))
    return jnp.where(valid, dl * grad_scale, 0.0)


def _bwd_dx_kernel(x_ref, w_ref, b_ref, lbl_ref, lse_ref, dx_ref, acc,
                   *, block_v, vocab, n_valid, block_n, grad_scale,
                   ignore_label, use_ignore, out_dtype):
    i = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    dl = _dl_tile(x_ref[...], w_ref[...], b_ref[0, :], lse_ref[0, :],
                  lbl_ref[0, :], j, block_v, vocab, n_valid, i * block_n,
                  grad_scale, ignore_label, use_ignore)
    acc[...] += lax.dot_general(
        dl.astype(w_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == num_j - 1)
    def _fin():
        dx_ref[...] = acc[...].astype(out_dtype)


def _bwd_dw_kernel(x_ref, w_ref, b_ref, lbl_ref, lse_ref, dw_ref, db_ref,
                   wacc, bacc, *, block_v, vocab, n_valid, block_n,
                   grad_scale, ignore_label, use_ignore, out_dtype):
    j = pl.program_id(0)
    i = pl.program_id(1)
    num_i = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        wacc[...] = jnp.zeros_like(wacc)
        bacc[...] = jnp.zeros_like(bacc)

    x = x_ref[...]
    dl = _dl_tile(x, w_ref[...], b_ref[0, :], lse_ref[0, :],
                  lbl_ref[0, :], j, block_v, vocab, n_valid, i * block_n,
                  grad_scale, ignore_label, use_ignore)
    dlc = dl.astype(x.dtype)
    wacc[...] += lax.dot_general(dlc, x, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    bacc[...] += jnp.sum(dl, axis=0)[None, :]

    @pl.when(i == num_i - 1)
    def _fin():
        dw_ref[...] = wacc[...].astype(out_dtype)
        db_ref[...] = bacc[...].astype(out_dtype)


def _bwd_pallas(x, w, b, label, lse, grad_scale, ignore_label, use_ignore,
                block_n, block_v):
    n, d = x.shape
    v = w.shape[0]
    # the backward kernels carry a (block, d) f32 accumulator on top of the
    # double-buffered inputs and the (bn, bv) p/dl tile; bv=2048 blows the
    # 16M scoped-vmem limit at d=768, so cap the backward vocab tile
    block_v = min(block_v, 1024)
    pad_n = (-n) % block_n
    pad_v = (-v) % block_v
    xp = jnp.pad(x, ((0, pad_n), (0, 0))) if pad_n else x
    wp = jnp.pad(w, ((0, pad_v), (0, 0))) if pad_v else w
    bp = (jnp.pad(b, (0, pad_v)) if pad_v else b).reshape(1, -1)
    lblp = (jnp.pad(label, (0, pad_n)) if pad_n else label).reshape(1, -1)
    lsep = (jnp.pad(lse, (0, pad_n)) if pad_n else lse).reshape(1, -1)
    np_, vp_ = n + pad_n, v + pad_v
    num_i, num_j = np_ // block_n, vp_ // block_v

    common = dict(block_v=block_v, vocab=v, n_valid=n, block_n=block_n,
                  grad_scale=grad_scale, ignore_label=ignore_label,
                  use_ignore=use_ignore)

    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, out_dtype=x.dtype, **common),
        grid=(num_i, num_j),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=out_struct((np_, d), x.dtype, xp, wp, bp, lblp, lsep),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=4 * np_ * vp_ * d,
            bytes_accessed=(wp.size * num_i * wp.dtype.itemsize
                            + xp.size * xp.dtype.itemsize * 2),
            transcendentals=np_ * vp_,
        ),
        interpret=_INTERPRET,
        name="fused_ce_bwd_dx",
    )(xp, wp, bp, lblp, lsep)

    dw, db = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, out_dtype=w.dtype, **common),
        grid=(num_j, num_i),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
        ],
        out_shape=[
            out_struct((vp_, d), w.dtype, xp, wp, bp, lblp, lsep),
            out_struct((1, vp_), w.dtype, xp, wp, bp, lblp, lsep),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_v, d), jnp.float32),
            pltpu.VMEM((1, block_v), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * np_ * vp_ * d,
            bytes_accessed=(xp.size * num_j * xp.dtype.itemsize
                            + wp.size * wp.dtype.itemsize * 2),
            transcendentals=np_ * vp_,
        ),
        interpret=_INTERPRET,
        name="fused_ce_bwd_dw",
    )(xp, wp, bp, lblp, lsep)

    if pad_n:
        dx = dx[:n]
    if pad_v:
        dw, db = dw[:v], db[:, :v]
    return dx, dw, db[0]


# ---------------------------------------------------------------------------
# jnp fallback: same math as a lax.scan over vocab tiles
# ---------------------------------------------------------------------------


def _tiles(w, b, block_v):
    v, d = w.shape
    block_v = min(block_v, v)
    pad_v = (-v) % block_v
    if pad_v:
        w = jnp.pad(w, ((0, pad_v), (0, 0)))
        b = jnp.pad(b, (0, pad_v))
    num_j = (v + pad_v) // block_v
    return (w.reshape(num_j, block_v, d), b.reshape(num_j, block_v),
            num_j, block_v)


def _fwd_jnp(x, w, b, label, grad_scale, ignore_label, use_ignore, block_v):
    n, d = x.shape
    v = w.shape[0]
    wt, bt, num_j, block_v = _tiles(w, b, block_v)
    xf = x.astype(jnp.float32)

    def body(carry, xs):
        m, l, a = carry
        j, w_j, b_j = xs
        s = xf @ w_j.astype(jnp.float32).T + b_j.astype(jnp.float32)
        col = j * block_v + jnp.arange(block_v)[None, :]
        s = jnp.where(col < v, s, _NEG_INF)
        a = a + jnp.sum(jnp.where(col == label[:, None], s, 0.0), axis=1)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(s - m_new[:, None]), axis=1)
        return (m_new, l, a), None

    # derive the carry from x so its type matches under shard_map (the
    # callers give every operand one type: `_spmd.vary_alike`)
    z = jnp.zeros_like(xf[:, 0])
    (m, l, a), _ = lax.scan(
        body, (z + _NEG_INF, z, z),
        (jnp.arange(num_j), wt, bt))
    lse = m + jnp.log(l)
    nll = lse - a
    if use_ignore:
        nll = jnp.where(label != int(ignore_label), nll, 0.0)
    return nll, lse


def _bwd_jnp(x, w, b, label, lse, grad_scale, ignore_label, use_ignore,
             block_v):
    n, d = x.shape
    v = w.shape[0]
    wt, bt, num_j, block_v = _tiles(w, b, block_v)
    xf = x.astype(jnp.float32)
    valid = jnp.ones((n,), jnp.float32)
    if use_ignore:
        valid = jnp.where(label != int(ignore_label), valid, 0.0)

    def body(dx, xs):
        j, w_j, b_j = xs
        s = xf @ w_j.astype(jnp.float32).T + b_j.astype(jnp.float32)
        col = j * block_v + jnp.arange(block_v)[None, :]
        s = jnp.where(col < v, s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dl = (p - jnp.where(col == label[:, None], 1.0, 0.0))
        dl = dl * (grad_scale * valid)[:, None]
        dlc = dl.astype(x.dtype)
        dx = dx + lax.dot_general(dlc, w_j, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dw_j = lax.dot_general(dlc, x, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        return dx, (dw_j.astype(w.dtype), jnp.sum(dl, axis=0))

    dx0 = xf * 0.0
    dx, (dw_t, db_t) = lax.scan(body, dx0, (jnp.arange(num_j), wt, bt))
    dw = dw_t.reshape(-1, d)[:v]
    db = db_t.reshape(-1)[:v].astype(w.dtype)
    return dx.astype(x.dtype), dw, db


# ---------------------------------------------------------------------------
# Single-pass structure (MXNET_CE_SINGLE_PASS=1, default): the vjp forward
# computes the online-softmax stats AND the p@W residual in ONE sweep over
# the logit tiles; backward recomputes tiles only for dW/db.
# ---------------------------------------------------------------------------

# sentinel local label that matches no column (sharded path: labels are
# shifted by the shard offset; out-of-shard rows must pick nothing)
_NO_LABEL = -(1 << 30)
# lse pad value for masked-out token rows in the backward: exp(s - BIG) == 0
_LSE_PAD = 1e30


def single_pass_enabled():
    """MXNET_CE_SINGLE_PASS (default 1) — `0` restores the round-5 5-pass
    recompute structure bit-for-bit (the kill-switch contract)."""
    return _os.environ.get("MXNET_CE_SINGLE_PASS", "1") != "0"


def _fwd_sp_kernel(x_ref, w_ref, b_ref, lbl_ref, lse_ref, a_ref, dxp_ref,
                   m_s, l_s, a_s, acc, *, block_v, vocab, block_n):
    """Stats + residual forward: grid (token blocks i, vocab tiles j) with
    i outer, so the per-block state lives in plain (1, block_n)/(block_n, d)
    scratch re-initialized per block — no per-token slab.  Each (i, j) step
    computes its logit tile once and folds BOTH the softmax stats and the
    rescaled `exp(s - m) @ W_tile` residual accumulator."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    del i  # block selection is entirely in the index maps

    @pl.when(j == 0)
    def _init():
        m_s[0, :] = jnp.full((block_n,), _NEG_INF, jnp.float32)
        l_s[0, :] = jnp.zeros((block_n,), jnp.float32)
        a_s[0, :] = jnp.zeros((block_n,), jnp.float32)
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...]
    w = w_ref[...]
    s = lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s + b_ref[0, :][None, :].astype(jnp.float32)
    col = j * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < vocab, s, _NEG_INF)

    lbl = lbl_ref[0, :]
    a_s[0, :] = a_s[0, :] + jnp.sum(
        jnp.where(col == lbl[:, None], s, 0.0), axis=1)

    m_prev = m_s[0, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])          # masked cols underflow to 0
    factor = jnp.exp(m_prev - m_new)
    l_s[0, :] = l_s[0, :] * factor + jnp.sum(p, axis=1)
    # flash-style rescale: the accumulator lives in exp(. - m) space and is
    # renormalized whenever the running max moves
    acc[...] = acc[...] * factor[:, None] + lax.dot_general(
        p.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[0, :] = m_new

    @pl.when(j == num_j - 1)
    def _fin():
        l = l_s[0, :]
        lse_ref[0, :] = m_s[0, :] + jnp.log(l)
        a_ref[0, :] = a_s[0, :]
        dxp_ref[...] = acc[...] / l[:, None]


def _fwd_sp_pallas(x, w, b, label, block_n, block_v):
    """(lse, picked_logit, p@W residual) in one sweep over logit tiles."""
    n, d = x.shape
    v = w.shape[0]
    # same scoped-vmem cap as _bwd_pallas: this kernel carries the
    # (block_n, d) f32 accumulator on top of the double-buffered
    # (block_v, d) weight blocks, the footprint bv=2048 blows at d=768
    block_v = min(block_v, 1024)
    pad_n = (-n) % block_n
    pad_v = (-v) % block_v
    xp = jnp.pad(x, ((0, pad_n), (0, 0))) if pad_n else x
    wp = jnp.pad(w, ((0, pad_v), (0, 0))) if pad_v else w
    bp = (jnp.pad(b, (0, pad_v)) if pad_v else b).reshape(1, -1)
    lblp = (jnp.pad(label, (0, pad_n)) if pad_n else label).reshape(1, -1)
    np_, vp_ = n + pad_n, v + pad_v
    num_i, num_j = np_ // block_n, vp_ // block_v

    kernel = functools.partial(_fwd_sp_kernel, block_v=block_v, vocab=v,
                               block_n=block_n)
    lse, a, dxp = pl.pallas_call(
        kernel,
        grid=(num_i, num_j),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        ],
        out_shape=[
            out_struct((1, np_), jnp.float32, xp, wp, bp, lblp),
            out_struct((1, np_), jnp.float32, xp, wp, bp, lblp),
            out_struct((np_, d), jnp.float32, xp, wp, bp, lblp),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_n), jnp.float32),
            pltpu.VMEM((1, block_n), jnp.float32),
            pltpu.VMEM((1, block_n), jnp.float32),
            pltpu.VMEM((block_n, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * np_ * vp_ * d,
            bytes_accessed=(wp.size * num_i * wp.dtype.itemsize
                            + xp.size * xp.dtype.itemsize
                            + np_ * d * 4),
            transcendentals=np_ * vp_,
        ),
        interpret=_INTERPRET,
        name="fused_ce_fwd",
    )(xp, wp, bp, lblp)
    return lse[0, :n], a[0, :n], dxp[:n]


def _fwd_sp_jnp(x, w, b, label, block_v):
    n, d = x.shape
    v = w.shape[0]
    wt, bt, num_j, block_v = _tiles(w, b, block_v)
    xf = x.astype(jnp.float32)
    z = jnp.zeros_like(xf[:, 0])

    def body(carry, xs):
        m, l, a, acc = carry
        j, w_j, b_j = xs
        s = xf @ w_j.astype(jnp.float32).T + b_j.astype(jnp.float32)
        col = j * block_v + jnp.arange(block_v)[None, :]
        s = jnp.where(col < v, s, _NEG_INF)
        a = a + jnp.sum(jnp.where(col == label[:, None], s, 0.0), axis=1)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        factor = jnp.exp(m - m_new)
        l = l * factor + jnp.sum(p, axis=1)
        acc = acc * factor[:, None] + lax.dot_general(
            p.astype(x.dtype), w_j, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (m_new, l, a, acc), None

    (m, l, a, acc), _ = lax.scan(
        body, (z + _NEG_INF, z, z, xf * 0.0),
        (jnp.arange(num_j), wt, bt))
    lse = m + jnp.log(l)
    return lse, a, acc / l[:, None]


def _fwd_sp_impl(x, w, b, label, block_n, block_v):
    x, w, b, label = vary_alike(x, w, b, label)
    if _use_pallas(x, w):
        return _fwd_sp_pallas(x, w, b, label, block_n, block_v)
    return _fwd_sp_jnp(x, w, b, label, block_v)


# -- row-scaled backward kernels ------------------------------------------
# dl = (exp(s - lse) - onehot(lbl)) * r[row]: every per-row condition
# (grad_scale, ignore_label, padded tokens, shard validity) is folded into
# the traced coefficient vector r, so these kernels need no static
# masking params and serve both the single-pass and the vocab-sharded
# paths (where the shard offset — and hence the ignore comparison — is a
# traced value that could never be a static kernel param).


def _dl_rs_tile(x, w, b, lse, lbl, r, j, block_v, vocab):
    s = lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s + b[None, :].astype(jnp.float32)
    col = j * block_v + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < vocab, s, _NEG_INF)
    p = jnp.exp(s - lse[:, None])
    dl = p - jnp.where(col == lbl[:, None], 1.0, 0.0)
    return dl * r[:, None]


def _bwd_dw_rs_kernel(x_ref, w_ref, b_ref, lbl_ref, lse_ref, r_ref,
                      dw_ref, db_ref, wacc, bacc, *, block_v, vocab,
                      out_dtype):
    j = pl.program_id(0)
    i = pl.program_id(1)
    num_i = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        wacc[...] = jnp.zeros_like(wacc)
        bacc[...] = jnp.zeros_like(bacc)

    x = x_ref[...]
    dl = _dl_rs_tile(x, w_ref[...], b_ref[0, :], lse_ref[0, :],
                     lbl_ref[0, :], r_ref[0, :], j, block_v, vocab)
    dlc = dl.astype(x.dtype)
    wacc[...] += lax.dot_general(dlc, x, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    bacc[...] += jnp.sum(dl, axis=0)[None, :]

    @pl.when(i == num_i - 1)
    def _fin():
        dw_ref[...] = wacc[...].astype(out_dtype)
        db_ref[...] = bacc[...].astype(out_dtype)


def _bwd_dx_rs_kernel(x_ref, w_ref, b_ref, lbl_ref, lse_ref, r_ref,
                      dx_ref, acc, *, block_v, vocab, out_dtype):
    i = pl.program_id(0)
    j = pl.program_id(1)
    num_j = pl.num_programs(1)
    del i

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    dl = _dl_rs_tile(x_ref[...], w_ref[...], b_ref[0, :], lse_ref[0, :],
                     lbl_ref[0, :], r_ref[0, :], j, block_v, vocab)
    acc[...] += lax.dot_general(
        dl.astype(w_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == num_j - 1)
    def _fin():
        dx_ref[...] = acc[...].astype(out_dtype)


def _rs_pad(x, w, b, label, lse, r, block_n, block_v):
    n, d = x.shape
    v = w.shape[0]
    pad_n = (-n) % block_n
    pad_v = (-v) % block_v
    xp = jnp.pad(x, ((0, pad_n), (0, 0))) if pad_n else x
    wp = jnp.pad(w, ((0, pad_v), (0, 0))) if pad_v else w
    bp = (jnp.pad(b, (0, pad_v)) if pad_v else b).reshape(1, -1)
    lblp = (jnp.pad(label, (0, pad_n), constant_values=_NO_LABEL)
            if pad_n else label).reshape(1, -1)
    # padded rows: r = 0 kills their dl; lse = BIG makes exp(s - lse)
    # underflow before the multiply so no inf*0
    lsep = (jnp.pad(lse, (0, pad_n), constant_values=_LSE_PAD)
            if pad_n else lse).reshape(1, -1)
    rp = (jnp.pad(r, (0, pad_n)) if pad_n else r).reshape(1, -1)
    return xp, wp, bp, lblp, lsep, rp, n + pad_n, v + pad_v


def _bwd_dw_rs_pallas(x, w, b, label, lse, r, block_n, block_v):
    n, d = x.shape
    v = w.shape[0]
    block_v = min(block_v, 1024)  # same scoped-vmem cap as _bwd_pallas
    xp, wp, bp, lblp, lsep, rp, np_, vp_ = _rs_pad(
        x, w, b, label, lse, r, block_n, block_v)
    num_i, num_j = np_ // block_n, vp_ // block_v
    dw, db = pl.pallas_call(
        functools.partial(_bwd_dw_rs_kernel, block_v=block_v, vocab=v,
                          out_dtype=w.dtype),
        grid=(num_j, num_i),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
            pl.BlockSpec((1, block_n), lambda j, i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((block_v, d), lambda j, i: (j, 0)),
            pl.BlockSpec((1, block_v), lambda j, i: (0, j)),
        ],
        out_shape=[
            out_struct((vp_, d), w.dtype, xp, wp, bp, lblp, lsep, rp),
            out_struct((1, vp_), w.dtype, xp, wp, bp, lblp, lsep, rp),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_v, d), jnp.float32),
            pltpu.VMEM((1, block_v), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=4 * np_ * vp_ * d,
            bytes_accessed=(xp.size * num_j * xp.dtype.itemsize
                            + wp.size * wp.dtype.itemsize * 2),
            transcendentals=np_ * vp_,
        ),
        interpret=_INTERPRET,
        name="fused_ce_bwd_dw",
    )(xp, wp, bp, lblp, lsep, rp)
    if vp_ != v:
        dw, db = dw[:v], db[:, :v]
    return dw, db[0]


def _bwd_dx_rs_pallas(x, w, b, label, lse, r, block_n, block_v):
    n, d = x.shape
    v = w.shape[0]
    block_v = min(block_v, 1024)
    xp, wp, bp, lblp, lsep, rp, np_, vp_ = _rs_pad(
        x, w, b, label, lse, r, block_n, block_v)
    num_i, num_j = np_ // block_n, vp_ // block_v
    dx = pl.pallas_call(
        functools.partial(_bwd_dx_rs_kernel, block_v=block_v, vocab=v,
                          out_dtype=x.dtype),
        grid=(num_i, num_j),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=out_struct((np_, d), x.dtype, xp, wp, bp, lblp, lsep, rp),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=4 * np_ * vp_ * d,
            bytes_accessed=(wp.size * num_i * wp.dtype.itemsize
                            + xp.size * xp.dtype.itemsize * 2),
            transcendentals=np_ * vp_,
        ),
        interpret=_INTERPRET,
        name="fused_ce_bwd_dx",
    )(xp, wp, bp, lblp, lsep, rp)
    return dx[:n] if np_ != n else dx


def _bwd_dw_rs_jnp(x, w, b, label, lse, r, block_v):
    n, d = x.shape
    v = w.shape[0]
    wt, bt, num_j, block_v = _tiles(w, b, block_v)
    xf = x.astype(jnp.float32)

    def body(_, xs):
        j, w_j, b_j = xs
        s = xf @ w_j.astype(jnp.float32).T + b_j.astype(jnp.float32)
        col = j * block_v + jnp.arange(block_v)[None, :]
        s = jnp.where(col < v, s, _NEG_INF)
        dl = (jnp.exp(s - lse[:, None])
              - jnp.where(col == label[:, None], 1.0, 0.0)) * r[:, None]
        dlc = dl.astype(x.dtype)
        dw_j = lax.dot_general(dlc, x, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
        return None, (dw_j.astype(w.dtype), jnp.sum(dl, axis=0))

    _, (dw_t, db_t) = lax.scan(body, None, (jnp.arange(num_j), wt, bt))
    dw = dw_t.reshape(-1, d)[:v]
    db = db_t.reshape(-1)[:v].astype(w.dtype)
    return dw, db


def _bwd_dx_rs_jnp(x, w, b, label, lse, r, block_v):
    n, d = x.shape
    v = w.shape[0]
    wt, bt, num_j, block_v = _tiles(w, b, block_v)
    xf = x.astype(jnp.float32)

    def body(dx, xs):
        j, w_j, b_j = xs
        s = xf @ w_j.astype(jnp.float32).T + b_j.astype(jnp.float32)
        col = j * block_v + jnp.arange(block_v)[None, :]
        s = jnp.where(col < v, s, _NEG_INF)
        dl = (jnp.exp(s - lse[:, None])
              - jnp.where(col == label[:, None], 1.0, 0.0)) * r[:, None]
        dlc = dl.astype(x.dtype)
        return dx + lax.dot_general(dlc, w_j, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32), None

    dx, _ = lax.scan(body, xf * 0.0, (jnp.arange(num_j), wt, bt))
    return dx.astype(x.dtype)


def _bwd_dw_rs_impl(x, w, b, label, lse, r, block_n, block_v):
    x, w, b, label, lse, r = vary_alike(x, w, b, label, lse, r)
    if _use_pallas(x, w):
        return _bwd_dw_rs_pallas(x, w, b, label, lse, r, block_n, block_v)
    return _bwd_dw_rs_jnp(x, w, b, label, lse, r, block_v)


def _bwd_dx_rs_impl(x, w, b, label, lse, r, block_n, block_v):
    x, w, b, label, lse, r = vary_alike(x, w, b, label, lse, r)
    if _use_pallas(x, w):
        return _bwd_dx_rs_pallas(x, w, b, label, lse, r, block_n, block_v)
    return _bwd_dx_rs_jnp(x, w, b, label, lse, r, block_v)


def _valid_coef(label_int, grad_scale, ignore_label, use_ignore):
    """Per-row gradient coefficient r and validity mask."""
    valid = jnp.ones(label_int.shape, jnp.float32)
    if use_ignore:
        valid = jnp.where(label_int != int(ignore_label), valid, 0.0)
    return grad_scale * valid, valid


def _label_zero_cot(label):
    if jnp.issubdtype(label.dtype, jnp.integer):
        import numpy as _np

        from jax import dtypes as _dtypes

        return _np.zeros(label.shape, _dtypes.float0)
    return jnp.zeros_like(label)


# -- single-pass custom_vjp (unsharded) -----------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fused_ce_sp(x, w, b, label, grad_scale, ignore_label, use_ignore,
                 block_n, block_v):
    # the plain (non-vjp) forward needs no residual: the existing 1-pass
    # stats forward is reused unchanged
    nll, _ = _fused_ce_fwd_impl(x, w, b, label, grad_scale, ignore_label,
                                use_ignore, block_n, block_v)
    return nll


def _fused_ce_sp_fwd_rule(x, w, b, label, grad_scale, ignore_label,
                          use_ignore, block_n, block_v):
    lbl = label.astype(jnp.int32)
    lse, a, dxp = _fwd_sp_impl(x, w, b, lbl, block_n, block_v)
    r, valid = _valid_coef(lbl, grad_scale, ignore_label, use_ignore)
    nll = jnp.where(valid > 0, lse - a, 0.0)
    # the -onehot @ W term of dx is a plain row gather — O(n*d) bytes,
    # no matmul pass.  Out-of-range labels (e.g. -1 padding with
    # use_ignore unset) match no onehot column in the 5-pass structure,
    # so they must subtract nothing here too.
    v = w.shape[0]
    in_range = jnp.logical_and(lbl >= 0, lbl < v)
    wl = jnp.where(in_range[:, None],
                   w[jnp.clip(lbl, 0, v - 1)].astype(jnp.float32), 0.0)
    dx = (r[:, None] * (dxp - wl)).astype(x.dtype)
    return nll, (x, w, b, label, lse, r, dx)


def _fused_ce_sp_bwd_rule(grad_scale, ignore_label, use_ignore, block_n,
                          block_v, res, g):
    # loss-head contract: incoming cotangent ignored (softmax_output-inl.h)
    x, w, b, label, lse, r, dx = res
    lbl = label.astype(jnp.int32)
    dw, db = _bwd_dw_rs_impl(x, w, b, lbl, lse, r, block_n, block_v)
    return (reduce_like(dx, x), reduce_like(dw, w),
            reduce_like(db.astype(b.dtype), b), _label_zero_cot(label))


_fused_ce_sp.defvjp(_fused_ce_sp_fwd_rule, _fused_ce_sp_bwd_rule)


# ---------------------------------------------------------------------------
# Vocab-sharded head: local stats per shard, lse reduce over the mesh axis
# ---------------------------------------------------------------------------


def _combine_lse(lse_loc, axis):
    """Global logsumexp from per-shard logsumexps: the reduce that rides
    the mesh (pmax + psum over ICI) instead of a gathered logit matrix."""
    m = lax.pmax(lse_loc, axis)
    return m + jnp.log(lax.psum(jnp.exp(lse_loc - m), axis))


def _local_label(label_int, axis, v_loc):
    """Global class ids -> this shard's local column ids; out-of-shard
    rows become the sentinel (a raw shifted id could collide with a
    PADDED column of a later tile, picking up its -inf mask)."""
    loc = label_int - (lax.axis_index(axis) * v_loc).astype(jnp.int32)
    in_shard = jnp.logical_and(loc >= 0, loc < v_loc)
    return jnp.where(in_shard, loc, _NO_LABEL), in_shard


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _fused_ce_vs(x, w, b, label, axis, grad_scale, ignore_label,
                 use_ignore, block_n, block_v):
    v_loc = w.shape[0]
    lbl = label.astype(jnp.int32)
    lbl_loc, _ = _local_label(lbl, axis, v_loc)
    # local stats via the existing 1-pass forward (use_ignore handled
    # globally: out-of-shard labels match no local column, so nll_loc
    # recovers the picked logit a_loc = lse_loc - nll_loc exactly)
    nll_loc, lse_loc = _fused_ce_fwd_impl(
        x, w, b, lbl_loc, grad_scale, ignore_label, False, block_n, block_v)
    a = lax.psum(lse_loc - nll_loc, axis)
    lse_g = _combine_lse(lse_loc, axis)
    _, valid = _valid_coef(lbl, grad_scale, ignore_label, use_ignore)
    return jnp.where(valid > 0, lse_g - a, 0.0)


def _fused_ce_vs_fwd_rule(x, w, b, label, axis, grad_scale, ignore_label,
                          use_ignore, block_n, block_v):
    v_loc = w.shape[0]
    lbl = label.astype(jnp.int32)
    lbl_loc, in_shard = _local_label(lbl, axis, v_loc)
    r, valid = _valid_coef(lbl, grad_scale, ignore_label, use_ignore)
    if single_pass_enabled():
        lse_loc, a_loc, dxp_loc = _fwd_sp_impl(x, w, b, lbl_loc,
                                               block_n, block_v)
        lse_g = _combine_lse(lse_loc, axis)
        a = lax.psum(a_loc, axis)
        wl = jnp.where(
            in_shard[:, None],
            w[jnp.clip(lbl_loc, 0, v_loc - 1)].astype(jnp.float32), 0.0)
        # rescale the local residual from exp(.-lse_loc) space to the
        # global normalization, then one (n, d) psum carries dx
        contrib = dxp_loc * jnp.exp(lse_loc - lse_g)[:, None] - wl
        dx = (r[:, None] * lax.psum(contrib, axis)).astype(x.dtype)
    else:
        nll_loc, lse_loc = _fused_ce_fwd_impl(
            x, w, b, lbl_loc, grad_scale, ignore_label, False,
            block_n, block_v)
        lse_g = _combine_lse(lse_loc, axis)
        a = lax.psum(lse_loc - nll_loc, axis)
        dx = None
    nll = jnp.where(valid > 0, lse_g - a, 0.0)
    return nll, (x, w, b, label, lse_g, r, dx)


def _fused_ce_vs_bwd_rule(axis, grad_scale, ignore_label, use_ignore,
                          block_n, block_v, res, g):
    x, w, b, label, lse_g, r, dx = res
    v_loc = w.shape[0]
    lbl_loc, _ = _local_label(label.astype(jnp.int32), axis, v_loc)
    dw, db = _bwd_dw_rs_impl(x, w, b, lbl_loc, lse_g, r, block_n, block_v)
    if dx is None:  # 5-pass structure: recompute the dx tiles, then psum
        dx = lax.psum(
            _bwd_dx_rs_impl(x, w, b, lbl_loc, lse_g, r, block_n, block_v)
            .astype(jnp.float32), axis).astype(x.dtype)
    return (reduce_like(dx, x), reduce_like(dw, w),
            reduce_like(db.astype(b.dtype), b), _label_zero_cot(label))


_fused_ce_vs.defvjp(_fused_ce_vs_fwd_rule, _fused_ce_vs_bwd_rule)


def fused_softmax_ce_sharded(x, weight, bias, label, axis, *,
                             grad_scale=1.0, ignore_label=-1.0,
                             use_ignore=False, block_n=512, block_v=2048):
    """Vocab-sharded `fused_softmax_ce` for use INSIDE `shard_map`.

    ``weight``/``bias`` are the LOCAL (vocab/n_shards, features) /
    (vocab/n_shards,) slices of a head sharded over mesh axis ``axis`` in
    axis-index order; x/label are the local token shards (or replicated).
    Returns the same per-token NLL and gradients as the unsharded op on
    the gathered weight: the logsumexp combines across shards via
    pmax+psum (`_combine_lse`), dW/db stay shard-local, and only the
    (n, d) dx partial crosses the mesh.  Honors MXNET_CE_SINGLE_PASS.
    """
    if x.ndim != 2 or weight.ndim != 2:
        raise ValueError("fused_softmax_ce_sharded expects 2-D x and weight")
    block_n = int(_os.environ.get("MXNET_CE_BLOCK_N", block_n))
    block_v = int(_os.environ.get("MXNET_CE_BLOCK_V", block_v))
    if bias is None:
        bias = weight[:, 0] * 0
    return _fused_ce_vs(x, weight, bias, label, str(axis),
                        float(grad_scale), float(ignore_label),
                        bool(use_ignore), int(block_n), int(block_v))


# ---------------------------------------------------------------------------
# Public entry (custom_vjp with reference loss-head backward semantics)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _fused_ce(x, w, b, label, grad_scale, ignore_label, use_ignore,
              block_n, block_v):
    nll, _ = _fused_ce_fwd_impl(x, w, b, label, grad_scale, ignore_label,
                                use_ignore, block_n, block_v)
    return nll


def _fused_ce_fwd_impl(x, w, b, label, grad_scale, ignore_label, use_ignore,
                       block_n, block_v):
    x, w, b, lbl = vary_alike(x, w, b, label.astype(jnp.int32))
    if _use_pallas(x, w):
        return _fwd_pallas(x, w, b, lbl, grad_scale, ignore_label,
                           use_ignore, block_n, block_v)
    return _fwd_jnp(x, w, b, lbl, grad_scale, ignore_label, use_ignore,
                    block_v)


def _fused_ce_fwd_rule(x, w, b, label, grad_scale, ignore_label, use_ignore,
                       block_n, block_v):
    nll, lse = _fused_ce_fwd_impl(x, w, b, label, grad_scale, ignore_label,
                                  use_ignore, block_n, block_v)
    return nll, (x, w, b, label, lse)


def _fused_ce_bwd_rule(grad_scale, ignore_label, use_ignore, block_n,
                       block_v, res, g):
    # loss-head contract (`softmax_output-inl.h` Backward): the incoming
    # cotangent is ignored; grad_scale is baked into dl
    x, w, b, label, lse = res
    ops = vary_alike(x, w, b, label.astype(jnp.int32), lse)
    if _use_pallas(x, w):
        dx, dw, db = _bwd_pallas(*ops, grad_scale, ignore_label,
                                 use_ignore, block_n, block_v)
    else:
        dx, dw, db = _bwd_jnp(*ops, grad_scale, ignore_label, use_ignore,
                              block_v)
    if jnp.issubdtype(label.dtype, jnp.integer):
        # integer primals take a float0 cotangent under jax.grad/vjp
        import numpy as _np

        from jax import dtypes as _dtypes

        dlabel = _np.zeros(label.shape, _dtypes.float0)
    else:
        dlabel = jnp.zeros_like(label)
    return (reduce_like(dx, x), reduce_like(dw, w),
            reduce_like(db.astype(b.dtype), b), dlabel)


_fused_ce.defvjp(_fused_ce_fwd_rule, _fused_ce_bwd_rule)


def fused_softmax_ce(x, weight, bias, label, *, grad_scale=1.0,
                     ignore_label=-1.0, use_ignore=False,
                     block_n=512, block_v=2048):
    """Per-token CE loss of ``softmax(x @ weight.T + bias)`` vs ``label``,
    without materializing the logits.

    x: (tokens, features); weight: (vocab, features); bias: (vocab,) or
    None; label: (tokens,) class ids (float or int).  Returns float32
    (tokens,) negative log-likelihoods, zeroed where ``label ==
    ignore_label`` when ``use_ignore``.  ``grad_scale`` scales only the
    gradient (the reference's SoftmaxOutput contract), never the loss.

    Training gradient is the reference loss-head rule, not autodiff of the
    forward: dlogits = (softmax - onehot) * grad_scale, with the incoming
    cotangent ignored (`softmax_output-inl.h`).

    MXNET_CE_SINGLE_PASS=1 (default) takes the single-pass structure (the
    vjp forward stores the p@W residual; 4 logit-tile passes); `0` is the
    bit-for-bit kill-switch back to the round-5 5-pass recompute.
    """
    if x.ndim != 2 or weight.ndim != 2:
        raise ValueError("fused_softmax_ce expects 2-D x and weight")
    # in-model block A/B without rebuilding the model, mirroring
    # MXNET_FLASH_BLOCK_Q/K on the attention side
    block_n = int(_os.environ.get("MXNET_CE_BLOCK_N", block_n))
    block_v = int(_os.environ.get("MXNET_CE_BLOCK_V", block_v))
    if bias is None:
        # derive from weight (not a fresh constant) so its varying-manual-
        # axes type matches under shard_map
        bias = weight[:, 0] * 0
    fn = _fused_ce_sp if single_pass_enabled() else _fused_ce
    static = (float(grad_scale), float(ignore_label), bool(use_ignore),
              int(block_n), int(block_v))
    operands = (x, weight, bias, label)
    if not _use_pallas(x, weight):
        return fn(*operands, *static)
    # the kernels run per device: tokens split with the batch, the head
    # shared (`_spmd.call_local`)
    return call_local(lambda *a: fn(*a, *static), operands,
                      (True, False, False, True), True,
                      interpreted=_INTERPRET)
