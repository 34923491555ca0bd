"""Flash attention: fused blockwise softmax(Q K^T) V.

TPU-native replacement for what the reference era did with full S x S
score materialization (there is no attention op in the reference — this is
part of the long-context mandate).  Design:

* **Forward, TPU**: a Pallas kernel.  Grid = (batch, heads, Sq/block_q); each
  program holds one Q block in VMEM and streams K/V blocks from the full
  (per-head) K/V, maintaining the online-softmax recurrence
  (m, l, acc) so the S x S matrix never exists.  Scores accumulate in
  float32 on the MXU (`preferred_element_type`).  For causal masks the
  K-block loop is truncated at the diagonal (the diagonal position is
  computed from the q/k position offsets, so the same kernel serves ring
  attention where the offsets are traced per-device values).
* **Forward, non-TPU**: the same recurrence as a `lax.scan` over K blocks —
  identical math, used on the CPU test mesh.
* **Backward (both)**: flash-style recompute from the saved
  (q, k, v, o, lse) residuals, as a scan over K blocks:
  memory is O(S * block_k), never O(S^2).  The lse output's cotangent is
  propagated (d lse_i / d s_ij = p_ij), so ring attention's
  lse-weighted combination differentiates exactly.

`q_offset`/`k_offset` give the global position of row/col 0 for causal
masking: a query at global position q_offset+i attends to keys at global
positions <= q_offset+i.  They may be traced scalars (ring attention
passes `axis_index * shard_len`).
"""
from __future__ import annotations

import functools
import math
import os as _os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._spmd import call_local, out_struct

_NEG_INF = -1e30


def _stream_residency_fits(s, d, itemsize):
    """Whole-stream VMEM residency model of the loop kernels, with a
    safety margin.  The linear part is ~2 streams x 2 operands x S x d
    double-buffered (8*S*d*itemsize).  Round-5 on-chip anchors (d=128
    bf16): S=4096 compiles at block 512 (~10 MB scoped), S=8192 is
    Mosaic-rejected at ANY block size with "scoped allocation 24.5M >
    16M" — 24.5 MB is ~22% ABOVE what the linear model extrapolates
    (8*8192*128*2 = 20 MB), so Mosaic's true scoped allocation grows
    superlinearly in the never-measured band.  The 1.25x margin keeps
    every admitted shape at or below the verified S=4096 anchor's
    headroom; shapes in the extrapolated band (S=5120-6144 at d=128
    bf16) now FALL BACK instead of risking a hard Mosaic compile error
    with no fallback (ADVICE r5)."""
    return (5 * 8 * s * d * itemsize) // 4 <= 12 * 1024 * 1024


def _use_pallas(q, kv_len=None):
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return False
    # Pallas path wants the blocked dims tile-aligned; the wrapper pads S,
    # but tiny head_dim is better served by XLA.
    if q.shape[-1] < 32:
        return False
    # the loop kernels hold one head's full K/V (dq pass) or full Q/dO
    # (dk/dv pass) in VMEM, double-buffered by the Mosaic pipeline —
    # see `_stream_residency_fits` for the measured residency model.
    # Beyond the cap the blockwise jnp path or the grid-streamed bsd
    # kernels take over (ring attention shards S across devices long
    # before this matters).
    s = kv_len if kv_len is not None else q.shape[2]
    itemsize = jnp.dtype(q.dtype).itemsize
    return _stream_residency_fits(s, q.shape[-1], itemsize)


# MXNET_PALLAS_INTERPRET=1 runs every pallas_call through the interpreter
# so the CPU test mesh can execute the real kernel bodies (not just the
# jnp fallbacks) — the CI answer to "a kernel regression ships green"
_INTERPRET = _os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale, causal, block_q, block_k, kv_len):
    # q_ref: (1, 1, block_q, D); k_ref/v_ref: (1, 1, Skv_padded, D)
    qi = pl.program_id(2)
    q_off = qo_ref[0]
    k_off = ko_ref[0]
    q = q_ref[0, 0].astype(jnp.float32) * scale           # (bq, D)
    bq, d = q.shape

    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    num_kb = pl.cdiv(kv_len, block_k)
    if causal:
        # K blocks whose every key position exceeds the last query position
        # of this block contribute nothing: key j is visible iff
        # k_off + j <= q_off + i, max i = (qi+1)*block_q - 1.
        last_q = q_off + (qi + 1) * block_q - 1
        hi = (last_q - k_off) // block_k + 1
        num_kb = jnp.clip(hi, 0, num_kb)

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                   # (bq, bk)
        q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_rel = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = k_rel < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_off + k_rel)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # the TPU lowering requires >=2D tile-aligned blocks: lse carries a
    # broadcast 128-lane minor dim (sliced off by the wrapper)
    lse_ref[0, 0] = jnp.broadcast_to((m + jnp.log(l_safe))[:, None],
                                     (bq, 128))


def _flash_fwd_pallas(q, k, v, q_off, k_off, scale, causal,
                      block_q, block_k):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    sq_p, skv_p = sq + pad_q, skv + pad_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=skv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda i, j, k_, qo, ko: (i, j, k_, 0)),
            pl.BlockSpec((1, 1, skv_p, d), lambda i, j, k_, qo, ko: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, skv_p, d), lambda i, j, k_, qo, ko: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda i, j, k_, qo, ko: (i, j, k_, 0)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda i, j, k_, qo, ko: (i, j, k_, 0)),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            out_struct((b, h, sq_p, d), q.dtype, qp, kp, vp, q_off, k_off),
            out_struct((b, h, sq_p, 128), jnp.float32,
                       qp, kp, vp, q_off, k_off),
        ],
        # every program is independent (the K loop is inside the kernel):
        # let Mosaic parallelize/pipeline freely across the whole grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * sq_p * skv_p * d,
            bytes_accessed=(qp.size + kp.size + vp.size) * qp.dtype.itemsize,
            transcendentals=b * h * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_fwd",
    )(jnp.asarray([q_off], jnp.int32), jnp.asarray([k_off], jnp.int32),
      qp, kp, vp)
    lse = lse[..., 0]  # drop the broadcast lane dim
    if pad_q:
        out, lse = out[:, :, :sq], lse[:, :, :sq]
    return out, lse


# ---------------------------------------------------------------------------
# jnp blockwise fallback (same online-softmax recurrence)
# ---------------------------------------------------------------------------


def _flash_fwd_jnp(q, k, v, q_off, k_off, scale, causal, block_k):
    b, h, sq, d = q.shape
    skv = k.shape[2]
    block_k = min(block_k, skv)
    pad_k = (-skv) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    num_kb = (skv + pad_k) // block_k
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32).reshape(b, h, num_kb, block_k, d)
    vf = v.astype(jnp.float32).reshape(b, h, num_kb, block_k, d)
    q_pos = q_off + jnp.arange(sq)[:, None]

    def body(carry, xs):
        m, l, acc = carry
        kb, k_blk, v_blk = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk)
        k_rel = kb * block_k + jnp.arange(block_k)[None, :]
        mask = k_rel < skv
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_off + k_rel)
        s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_blk)
        return (m_new, l, acc), None

    # derive the initial carry from q (not fresh constants) so its
    # varying-manual-axes type matches the body output under shard_map
    acc0 = qf * 0.0
    m0 = acc0[..., 0] + _NEG_INF
    l0 = acc0[..., 0]
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0),
        (jnp.arange(num_kb),
         jnp.moveaxis(kf, 2, 0), jnp.moveaxis(vf, 2, 0)))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


# ---------------------------------------------------------------------------
# Pallas backward kernels: dq pass (grid over Q blocks) + dk/dv pass (grid
# over K blocks), each recomputing p from the saved lse — the round-2 jnp
# scan dragged the stacked K/V blocks through the while-loop carry (811 MB
# per layer at GPT-2-small shape); here every tile lives only in VMEM.
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, *, scale, causal, block_q, block_k,
                   kv_len, q_len):
    qi = pl.program_id(2)
    q_off = qo_ref[0]
    k_off = ko_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)                   # (bq, D)
    do = do_ref[0, 0].astype(jnp.float32)
    # lse/delta ride a trailing singleton axis: Mosaic requires the last
    # two block dims be (8k, 128k) or equal to the array dims, which
    # (block_q, 1) satisfies with no broadcast waste
    lse = lse_ref[0, 0, :, 0]                             # (bq,)
    delta = delta_ref[0, 0, :, 0]
    bq, d = q.shape

    num_kb = pl.cdiv(kv_len, block_k)
    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        hi = (last_q - k_off) // block_k + 1
        num_kb = jnp.clip(hi, 0, num_kb)

    q_rel = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 0)
    q_pos = q_off + q_rel

    def body(kb, dq):
        k = k_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_rel = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = jnp.logical_and(k_rel < kv_len, q_rel < q_len)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_off + k_rel)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k.astype(k_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_kb, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, scale, causal, block_q,
                    block_k, kv_len, q_len):
    ki = pl.program_id(2)
    q_off = qo_ref[0]
    k_off = ko_ref[0]
    k = k_ref[0, 0].astype(jnp.float32)                   # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    bk, d = k.shape
    sq_p = q_ref.shape[2]
    num_qb = sq_p // block_q

    lo = 0
    if causal:
        # q blocks whose last query precedes this K block's first key
        # contribute nothing: need q_off + (qi+1)*bq - 1 >= k_off + ki*bk
        first_k = k_off + ki * block_k
        lo = jnp.clip((first_k - q_off - block_q + 1 + block_q - 1)
                      // block_q, 0, num_qb)

    k_rel = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, bk), 1)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(qi * block_q, block_q), :].astype(
            jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q), 0]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_rel = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        mask = jnp.logical_and(k_rel < kv_len, q_rel < q_len)
        if causal:
            mask = jnp.logical_and(mask, q_off + q_rel >= k_off + k_rel)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dv = dv + jax.lax.dot_general(
            p.astype(do_ref.dtype), do.astype(do_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk = dk + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q.astype(q_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        lo, num_qb, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(scale, causal, block_q, block_k, res, grads):
    q, k, v, o, lse, q_off, k_off = res
    g, glse = grads
    b, h, sq, d = q.shape
    skv = k.shape[2]
    block_q = min(block_q, max(sq, 128))
    block_k = min(block_k, max(skv, 128))
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    dop = jnp.pad(g, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else g
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    sq_p, skv_p = sq + pad_q, skv + pad_k

    # delta_i = sum_j dO_ij O_ij - glse_i (the lse cotangent folds in here:
    # d lse_i / d s_ij = p_ij, same sign structure as the delta term)
    delta = (jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
             - glse.astype(jnp.float32))
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q))) if pad_q else lse
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q))) if pad_q else delta
    # trailing singleton axis so the (block, 1) tiles pass Mosaic's
    # last-two-dims rule without a broadcast lane dim (see kernel note)
    lsep = lsep[..., None]
    deltap = deltap[..., None]

    qo = jnp.asarray([q_off], jnp.int32)
    ko = jnp.asarray([k_off], jnp.int32)
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=skv, q_len=sq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, sq_p // block_q),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
                pl.BlockSpec((1, 1, skv_p, d),
                             lambda i, j, k_, qo, ko: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, skv_p, d),
                             lambda i, j, k_, qo, ko: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, block_q, d),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, d),
                                   lambda i, j, k_, qo, ko: (i, j, k_, 0)),
        ),
        out_shape=out_struct((b, h, sq_p, d), q.dtype,
                             qp, kp, vp, dop, lsep, deltap, qo, ko),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * h * sq_p * skv_p * d,
            bytes_accessed=(qp.size * 2 + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * h * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_bwd_dq",
    )(qo, ko, qp, kp, vp, dop, lsep, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, skv_p // block_k),
            in_specs=[
                pl.BlockSpec((1, 1, sq_p, d),
                             lambda i, j, k_, qo, ko: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
                pl.BlockSpec((1, 1, sq_p, d),
                             lambda i, j, k_, qo, ko: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, sq_p, 1),
                             lambda i, j, k_, qo, ko: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, sq_p, 1),
                             lambda i, j, k_, qo, ko: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_k, d),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
            ],
        ),
        out_shape=[
            out_struct((b, h, skv_p, d), k.dtype,
                       qp, kp, vp, dop, lsep, deltap, qo, ko),
            out_struct((b, h, skv_p, d), v.dtype,
                       qp, kp, vp, dop, lsep, deltap, qo, ko),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * sq_p * skv_p * d,
            bytes_accessed=(qp.size * 2 + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * h * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_bwd_dkv",
    )(qo, ko, qp, kp, vp, dop, lsep, deltap)

    if pad_q:
        dq = dq[:, :, :sq]
    if pad_k:
        dk, dv = dk[:, :, :skv], dv[:, :, :skv]
    zero_off = (jnp.asarray(q_off, jnp.float32) * 0,
                jnp.asarray(k_off, jnp.float32) * 0)
    return (dq, dk, dv) + zero_off


# ---------------------------------------------------------------------------
# Backward fallback: flash-style recompute, scan over K blocks
# ---------------------------------------------------------------------------


def _flash_bwd(scale, causal, block_k, res, grads):
    q, k, v, o, lse, q_off, k_off = res
    g, glse = grads  # cotangents of (out, lse)
    b, h, sq, d = q.shape
    skv = k.shape[2]
    block_k = min(block_k, skv)
    pad_k = (-skv) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    num_kb = (skv + pad_k) // block_k
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    glse_f = glse.astype(jnp.float32)
    kf = k.astype(jnp.float32).reshape(b, h, num_kb, block_k, d)
    vf = v.astype(jnp.float32).reshape(b, h, num_kb, block_k, d)
    # dL/ds_ij = p_ij * (dp_ij - delta_i) from the out cotangent plus
    # p_ij * glse_i from the lse cotangent (d lse_i / d s_ij = p_ij).
    delta = jnp.sum(gf * o.astype(jnp.float32), axis=-1) - glse_f  # (b,h,sq)
    q_pos = q_off + jnp.arange(sq)[:, None]

    def body(dq, xs):
        kb, k_blk, v_blk = xs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk) * scale
        k_rel = kb * block_k + jnp.arange(block_k)[None, :]
        mask = k_rel < skv
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_off + k_rel)
        s = jnp.where(mask[None, None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                       # (b,h,q,k)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_blk)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_blk)
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq, (dk_blk, dv_blk)

    dq0 = qf * 0.0  # see forward: carry type must match under shard_map
    dq, (dk_blks, dv_blks) = lax.scan(
        body, dq0,
        (jnp.arange(num_kb),
         jnp.moveaxis(kf, 2, 0), jnp.moveaxis(vf, 2, 0)))
    dk = jnp.moveaxis(dk_blks, 0, 2).reshape(b, h, skv + pad_k, d)
    dv = jnp.moveaxis(dv_blks, 0, 2).reshape(b, h, skv + pad_k, d)
    if pad_k:
        dk, dv = dk[:, :, :skv], dv[:, :, :skv]
    # zero tangents derived from the offsets themselves so their
    # varying-manual-axes type matches under shard_map
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            (q_off * 0).astype(jnp.float32), (k_off * 0).astype(jnp.float32))


# ---------------------------------------------------------------------------
# dS-layout kernels: operands shaped (b, h, D, S) so the minor dim is the
# sequence (a multiple of 128) and the second-minor is head_dim (a multiple
# of 8).  The original (b, h, S, D) kernels force dense {3,2,1,0} layouts
# whose 64-wide minor dim pads every bf16 tile 2x on TPU (T(8,128) tiling):
# at GPT-2-small shape that doubled every saved attention residual and
# every layout copy around the custom calls (96 MB temps for 48 MB
# tensors, measured OOM at batch 32).  In dS form the same buffers tile
# exactly; the boundary transposes fold into the model's own head
# split/merge transposes.  Math is the same online-softmax recurrence;
# scores stay (bq, bk) — only the operand orientation changes.
# ---------------------------------------------------------------------------


def _fwd_kernel_ds(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   m_sc, l_sc, acc_sc, *,
                   scale, causal, block_q, block_k, kv_len):
    # Grid (b, h, nq, nk); the K axis is the innermost sequential grid
    # dim, so Mosaic pipelines the (D, block_k) K/V block DMAs while the
    # online-softmax scratch (m, l, acc) carries across it.  (The first
    # version looped over K inside the kernel with lane-dim dynamic
    # slices — 3.5x slower than the hsd kernel; measured in /tmp/ab.log.)
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = qo_ref[0]
    k_off = ko_ref[0]

    @pl.when(kb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # causal: skip blocks whose every key is after this block's last query
    run = True
    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        first_k = k_off + kb * block_k
        run = first_k <= last_q

    @pl.when(run)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (D, bq)
        k = k_ref[0, 0].astype(jnp.float32)               # (D, bk)
        v = v_ref[0, 0].astype(jnp.float32)
        bq = q.shape[1]
        s = jax.lax.dot_general(
            q, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_rel = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = k_rel < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_off + k_rel)
        s = jnp.where(mask, s, _NEG_INF)
        m = m_sc[0]
        l = l_sc[0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        m_sc[0] = m_new
        l_sc[0] = l * corr + jnp.sum(p, axis=-1)
        acc_sc[...] = acc_sc[...] * corr[None, :] + jax.lax.dot_general(
            v, p, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (D, bq)

    @pl.when(kb == nk - 1)
    def _emit():
        l = l_sc[0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_sc[...] / l_safe[None, :]).astype(o_ref.dtype)
        # lse block (1, 1, 1, block_q): singleton second-minor passes the
        # Mosaic tile rule with no broadcast lanes
        lse_ref[0, 0] = (m_sc[0] + jnp.log(l_safe))[None, :]


def _flash_fwd_pallas_ds(q, k, v, q_off, k_off, scale, causal,
                         block_q, block_k):
    """q/k/v: (b, h, D, S[q|kv]).  Returns o (b, h, D, Sq), lse (b,h,Sq)."""
    b, h, d, sq = q.shape
    skv = k.shape[3]
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_q))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad_k))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad_k))) if pad_k else v
    sq_p, skv_p = sq + pad_q, skv + pad_k

    kernel = functools.partial(
        _fwd_kernel_ds, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=skv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, sq_p // block_q, skv_p // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, d, block_q),
                         lambda i, j, k_, kb, qo, ko: (i, j, 0, k_)),
            pl.BlockSpec((1, 1, d, block_k),
                         lambda i, j, k_, kb, qo, ko: (i, j, 0, kb)),
            pl.BlockSpec((1, 1, d, block_k),
                         lambda i, j, k_, kb, qo, ko: (i, j, 0, kb)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, d, block_q),
                         lambda i, j, k_, kb, qo, ko: (i, j, 0, k_)),
            pl.BlockSpec((1, 1, 1, block_q),
                         lambda i, j, k_, kb, qo, ko: (i, j, 0, k_)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((d, block_q), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            out_struct((b, h, d, sq_p), q.dtype, qp, kp, vp, q_off, k_off),
            out_struct((b, h, 1, sq_p), jnp.float32, qp, kp, vp, q_off, k_off),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * sq_p * skv_p * d,
            bytes_accessed=(qp.size + kp.size + vp.size) * qp.dtype.itemsize,
            transcendentals=b * h * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_fwd",
    )(jnp.asarray([q_off], jnp.int32), jnp.asarray([k_off], jnp.int32),
      qp, kp, vp)
    lse = lse[:, :, 0]
    if pad_q:
        out, lse = out[..., :sq], lse[..., :sq]
    return out, lse


def _bwd_dq_kernel_ds(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dq_sc, *, scale, causal, block_q,
                      block_k, kv_len, q_len):
    # grid (b, h, nq, nk): K innermost/sequential, dq accumulates in
    # scratch (same streaming structure as _fwd_kernel_ds)
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = qo_ref[0]
    k_off = ko_ref[0]

    @pl.when(kb == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    run = True
    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        run = k_off + kb * block_k <= last_q

    @pl.when(run)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)               # (D, bq)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]                            # (bq,)
        delta = delta_ref[0, 0, 0]
        k = k_ref[0, 0].astype(jnp.float32)               # (D, bk)
        v = v_ref[0, 0].astype(jnp.float32)
        bq = q.shape[1]
        s = jax.lax.dot_general(q, k, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_rel = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_rel = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = jnp.logical_and(k_rel < kv_len, q_rel < q_len)
        if causal:
            mask = jnp.logical_and(mask, q_off + q_rel >= k_off + k_rel)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)  # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale               # (bq, bk)
        dq_sc[...] = dq_sc[...] + jax.lax.dot_general(
            k.astype(k_ref.dtype), ds.astype(k_ref.dtype),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _emit():
        dq_ref[0, 0] = dq_sc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_ds(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_sc, dv_sc, *, scale,
                       causal, block_q, block_k, kv_len, q_len):
    # grid (b, h, nk, nq): Q innermost/sequential, dk/dv accumulate in
    # scratch while Q/dO/lse/delta blocks stream
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    q_off = qo_ref[0]
    k_off = ko_ref[0]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    run = True
    if causal:
        # skip q blocks whose last query precedes this K block's first key
        run = q_off + (qi + 1) * block_q - 1 >= k_off + ki * block_k

    @pl.when(run)
    def _update():
        k = k_ref[0, 0].astype(jnp.float32)               # (D, bk)
        v = v_ref[0, 0].astype(jnp.float32)
        q = q_ref[0, 0].astype(jnp.float32)               # (D, bq)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        bk = k.shape[1]
        s = jax.lax.dot_general(q, k, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_rel = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        k_rel = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 1)
        mask = jnp.logical_and(k_rel < kv_len, q_rel < q_len)
        if causal:
            mask = jnp.logical_and(mask, q_off + q_rel >= k_off + k_rel)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)  # (bq, bk)
        dv_sc[...] = dv_sc[...] + jax.lax.dot_general(
            do.astype(do_ref.dtype), p.astype(do_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_sc[...] = dk_sc[...] + jax.lax.dot_general(
            q.astype(q_ref.dtype), ds.astype(q_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0, 0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas_ds(scale, causal, block_q, block_k, res, grads):
    """res carries dS-layout tensors: (q, k, v, o) as (b, h, D, S)."""
    q, k, v, o, lse, q_off, k_off = res
    g, glse = grads                       # g: (b, h, Sq, D) — API layout
    b, h, d, sq = q.shape
    skv = k.shape[3]
    g = g.swapaxes(2, 3)                  # -> (b, h, D, Sq), unpadded copy
    block_q = min(block_q, max(sq, 128))
    block_k = min(block_k, max(skv, 128))
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, pad_q))) if pad_q else q
    dop = jnp.pad(g, ((0, 0), (0, 0), (0, 0), (0, pad_q))) if pad_q else g
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, pad_k))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad_k))) if pad_k else v
    sq_p, skv_p = sq + pad_q, skv + pad_k

    delta = (jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=2)
             - glse.astype(jnp.float32))  # (b, h, Sq)
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q))) if pad_q else lse
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q))) if pad_q else delta
    lsep = lsep[:, :, None, :]            # (b, h, 1, Sq_p)
    deltap = deltap[:, :, None, :]

    qo = jnp.asarray([q_off], jnp.int32)
    ko = jnp.asarray([k_off], jnp.int32)
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=skv, q_len=sq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_ds, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, sq_p // block_q, skv_p // block_k),
            in_specs=[
                pl.BlockSpec((1, 1, d, block_q),
                             lambda i, j, k_, kb, qo, ko: (i, j, 0, k_)),
                pl.BlockSpec((1, 1, d, block_k),
                             lambda i, j, k_, kb, qo, ko: (i, j, 0, kb)),
                pl.BlockSpec((1, 1, d, block_k),
                             lambda i, j, k_, kb, qo, ko: (i, j, 0, kb)),
                pl.BlockSpec((1, 1, d, block_q),
                             lambda i, j, k_, kb, qo, ko: (i, j, 0, k_)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda i, j, k_, kb, qo, ko: (i, j, 0, k_)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda i, j, k_, kb, qo, ko: (i, j, 0, k_)),
            ],
            out_specs=pl.BlockSpec((1, 1, d, block_q),
                                   lambda i, j, k_, kb, qo, ko:
                                   (i, j, 0, k_)),
            scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32)],
        ),
        out_shape=out_struct((b, h, d, sq_p), q.dtype,
                             qp, kp, vp, dop, lsep, deltap, qo, ko),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * h * sq_p * skv_p * d,
            bytes_accessed=(qp.size * 2 + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * h * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_bwd_dq",
    )(qo, ko, qp, kp, vp, dop, lsep, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_ds, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, skv_p // block_k, sq_p // block_q),
            in_specs=[
                pl.BlockSpec((1, 1, d, block_q),
                             lambda i, j, k_, qb, qo, ko: (i, j, 0, qb)),
                pl.BlockSpec((1, 1, d, block_k),
                             lambda i, j, k_, qb, qo, ko: (i, j, 0, k_)),
                pl.BlockSpec((1, 1, d, block_k),
                             lambda i, j, k_, qb, qo, ko: (i, j, 0, k_)),
                pl.BlockSpec((1, 1, d, block_q),
                             lambda i, j, k_, qb, qo, ko: (i, j, 0, qb)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda i, j, k_, qb, qo, ko: (i, j, 0, qb)),
                pl.BlockSpec((1, 1, 1, block_q),
                             lambda i, j, k_, qb, qo, ko: (i, j, 0, qb)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, d, block_k),
                             lambda i, j, k_, qb, qo, ko: (i, j, 0, k_)),
                pl.BlockSpec((1, 1, d, block_k),
                             lambda i, j, k_, qb, qo, ko: (i, j, 0, k_)),
            ],
            scratch_shapes=[
                pltpu.VMEM((d, block_k), jnp.float32),
                pltpu.VMEM((d, block_k), jnp.float32),
            ],
        ),
        out_shape=[
            out_struct((b, h, d, skv_p), k.dtype,
                       qp, kp, vp, dop, lsep, deltap, qo, ko),
            out_struct((b, h, d, skv_p), v.dtype,
                       qp, kp, vp, dop, lsep, deltap, qo, ko),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * h * sq_p * skv_p * d,
            bytes_accessed=(qp.size * 2 + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * h * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_bwd_dkv",
    )(qo, ko, qp, kp, vp, dop, lsep, deltap)

    if pad_q:
        dq = dq[..., :sq]
    if pad_k:
        dk, dv = dk[..., :skv], dv[..., :skv]
    # back to the API layout (unpadded copies; XLA folds them into the
    # model's own head-merge transposes)
    dq = dq.swapaxes(2, 3)
    dk = dk.swapaxes(2, 3)
    dv = dv.swapaxes(2, 3)
    zero_off = (jnp.asarray(q_off, jnp.float32) * 0,
                jnp.asarray(k_off, jnp.float32) * 0)
    return (dq, dk, dv) + zero_off


# ---------------------------------------------------------------------------
# bsd-layout kernels: operands stay in the model's natural (B, S, E)
# activation layout (E = num_heads * head_dim) and each head's lane slice
# is carved TILE-ALIGNED by the BlockSpec index map (lane offset
# h * head_dim, which is a 128-multiple when head_dim % 128 == 0).  The
# round-5 AOT glue attribution measured the (B,S,H,d)<->(B,H,S,d) head
# transposes plus the layout copies XLA inserts around the hsd custom
# calls at ~13 GB of the 133 GB TPU-geometry step — in bsd form neither
# exists: no transpose is ever built, and the kernel operand IS the
# projection output, so there is no boundary for a relayout to appear at.
# Same online-softmax recurrence as the hsd family; only the ref slicing
# differs (heads live on the lane axis of rank-3 refs instead of a
# dedicated array axis).  head_dim % 128 != 0 (e.g. GPT-2 parity d=64)
# falls back to the transpose path.
# ---------------------------------------------------------------------------


def _fwd_kernel_bsd(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                    scale, causal, block_q, block_k, kv_len):
    # q_ref: (1, block_q, d); k_ref/v_ref: (1, Skv_p, d) — one head's
    # tile-aligned lane slice of the (B, S, E) operand
    qi = pl.program_id(2)
    q_off = qo_ref[0]
    k_off = ko_ref[0]
    q = q_ref[0].astype(jnp.float32) * scale              # (bq, d)
    bq, d = q.shape

    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)

    num_kb = pl.cdiv(kv_len, block_k)
    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        hi = (last_q - k_off) // block_k + 1
        num_kb = jnp.clip(hi, 0, num_kb)

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_rel = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = k_rel < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_off + k_rel)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.broadcast_to((m + jnp.log(l_safe))[:, None],
                                     (bq, 128))


def _flash_fwd_pallas_bsd(q, k, v, q_off, k_off, scale, causal,
                          block_q, block_k, num_heads):
    """q/k/v: (B, S[q|kv], E).  Returns o (B, Sq, E), lse (B, H, Sq)."""
    b, sq, e = q.shape
    skv = k.shape[1]
    d = e // num_heads
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    sq_p, skv_p = sq + pad_q, skv + pad_k

    kernel = functools.partial(
        _fwd_kernel_bsd, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=skv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, num_heads, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda i, j, k_, qo, ko: (i, k_, j)),
            pl.BlockSpec((1, skv_p, d),
                         lambda i, j, k_, qo, ko: (i, 0, j)),
            pl.BlockSpec((1, skv_p, d),
                         lambda i, j, k_, qo, ko: (i, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda i, j, k_, qo, ko: (i, k_, j)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda i, j, k_, qo, ko: (i, j, k_, 0)),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            out_struct((b, sq_p, e), q.dtype, qp, kp, vp, q_off, k_off),
            out_struct((b, num_heads, sq_p, 128), jnp.float32,
                       qp, kp, vp, q_off, k_off),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * num_heads * sq_p * skv_p * d,
            bytes_accessed=(qp.size + kp.size + vp.size) * qp.dtype.itemsize,
            transcendentals=b * num_heads * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_fwd",
    )(jnp.asarray([q_off], jnp.int32), jnp.asarray([k_off], jnp.int32),
      qp, kp, vp)
    lse = lse[..., 0]
    if pad_q:
        out, lse = out[:, :sq], lse[:, :, :sq]
    return out, lse



def _delta_bhs(g, o, glse, b, sq, num_heads, d):
    """delta_i(h) = sum_d dO*O - glse on (B, S, E) operands.  Reshape
    first (a bitcast), cast INSIDE the einsum via the f32 accumulator —
    an astype before the reduce would materialize a full f32 copy of dO
    and O (~100 MB each per call at bench shape)."""
    gf = g.reshape(b, sq, num_heads, d)
    of = o.reshape(b, sq, num_heads, d)
    return jnp.einsum("bshd,bshd->bhs", gf, of,
                      preferred_element_type=jnp.float32) \
        - glse.astype(jnp.float32)


def _bwd_dq_kernel_bsd(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dq_ref, *, scale, causal,
                       block_q, block_k, kv_len, q_len):
    qi = pl.program_id(2)
    q_off = qo_ref[0]
    k_off = ko_ref[0]
    q = q_ref[0].astype(jnp.float32)                      # (bq, d)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]                             # (bq,)
    delta = delta_ref[0, 0, :, 0]
    bq, d = q.shape

    num_kb = pl.cdiv(kv_len, block_k)
    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        hi = (last_q - k_off) // block_k + 1
        num_kb = jnp.clip(hi, 0, num_kb)

    q_rel = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 0)
    q_pos = q_off + q_rel

    def body(kb, dq):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_rel = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = jnp.logical_and(k_rel < kv_len, q_rel < q_len)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_off + k_rel)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k.astype(k_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_kb, body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel_bsd(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                        lse_ref, delta_ref, dk_ref, dv_ref, *, scale,
                        causal, block_q, block_k, kv_len, q_len):
    ki = pl.program_id(2)
    q_off = qo_ref[0]
    k_off = ko_ref[0]
    k = k_ref[0].astype(jnp.float32)                      # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    sq_p = q_ref.shape[1]
    num_qb = sq_p // block_q

    lo = 0
    if causal:
        first_k = k_off + ki * block_k
        lo = jnp.clip((first_k - q_off - block_q + 1 + block_q - 1)
                      // block_q, 0, num_qb)

    k_rel = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, bk), 1)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q), 0]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_rel = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        mask = jnp.logical_and(k_rel < kv_len, q_rel < q_len)
        if causal:
            mask = jnp.logical_and(mask, q_off + q_rel >= k_off + k_rel)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dv = dv + jax.lax.dot_general(
            p.astype(do_ref.dtype), do.astype(do_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk = dk + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q.astype(q_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        lo, num_qb, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas_bsd(scale, causal, block_q, block_k, num_heads,
                          res, grads):
    q, k, v, o, lse, q_off, k_off = res   # (B, S, E) operands
    g, glse = grads
    b, sq, e = q.shape
    skv = k.shape[1]
    d = e // num_heads
    block_q = min(block_q, max(sq, 128))
    block_k = min(block_k, max(skv, 128))
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    dop = jnp.pad(g, ((0, 0), (0, pad_q), (0, 0))) if pad_q else g
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    sq_p, skv_p = sq + pad_q, skv + pad_k

    # delta_i(h) = sum_d dO O - glse, computed per head on the (B, S, E)
    # arrays (small output; XLA fuses the reduction into the readers)
    delta = _delta_bhs(g, o, glse, b, sq, num_heads, d)
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q))) if pad_q else lse
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q))) if pad_q \
        else delta
    lsep = lsep[..., None]
    deltap = deltap[..., None]

    qo = jnp.asarray([q_off], jnp.int32)
    ko = jnp.asarray([k_off], jnp.int32)
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=skv, q_len=sq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_bsd, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, num_heads, sq_p // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, k_, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, skv_p, d),
                             lambda i, j, k_, qo, ko: (i, 0, j)),
                pl.BlockSpec((1, skv_p, d),
                             lambda i, j, k_, qo, ko: (i, 0, j)),
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, k_, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda i, j, k_, qo, ko: (i, j, k_, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d),
                                   lambda i, j, k_, qo, ko: (i, k_, j)),
        ),
        out_shape=out_struct((b, sq_p, e), q.dtype,
                             qp, kp, vp, dop, lsep, deltap, qo, ko),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * num_heads * sq_p * skv_p * d,
            bytes_accessed=(qp.size * 2 + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * num_heads * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_bwd_dq",
    )(qo, ko, qp, kp, vp, dop, lsep, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_bsd, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, num_heads, skv_p // block_k),
            in_specs=[
                pl.BlockSpec((1, sq_p, d),
                             lambda i, j, k_, qo, ko: (i, 0, j)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, sq_p, d),
                             lambda i, j, k_, qo, ko: (i, 0, j)),
                pl.BlockSpec((1, 1, sq_p, 1),
                             lambda i, j, k_, qo, ko: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, sq_p, 1),
                             lambda i, j, k_, qo, ko: (i, j, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, qo, ko: (i, k_, j)),
            ],
        ),
        out_shape=[
            out_struct((b, skv_p, e), k.dtype,
                       qp, kp, vp, dop, lsep, deltap, qo, ko),
            out_struct((b, skv_p, e), v.dtype,
                       qp, kp, vp, dop, lsep, deltap, qo, ko),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * num_heads * sq_p * skv_p * d,
            bytes_accessed=(qp.size * 2 + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * num_heads * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_bwd_dkv",
    )(qo, ko, qp, kp, vp, dop, lsep, deltap)

    if pad_q:
        dq = dq[:, :sq]
    if pad_k:
        dk, dv = dk[:, :skv], dv[:, :skv]
    zero_off = (jnp.asarray(q_off, jnp.float32) * 0,
                jnp.asarray(k_off, jnp.float32) * 0)
    return (dq, dk, dv) + zero_off


# -- grid-streamed bsd variants (MXNET_FLASH_BSD_KERNEL=stream) ------------
# Same operand layout as the loop-family bsd kernels above, but K/V
# (resp. Q/dO) blocks stream through an innermost "arbitrary" grid axis
# with VMEM scratch accumulators instead of an in-kernel fori_loop over
# dynamic slices — the structure that measured 3-5x faster in isolation
# in round 4 (docs/mfu_roofline.md), and that lost in-model only through
# the hsd boundary copies, which the bsd layout does not have.  The
# round-5 AOT attribution shows S>=4096 is attention-compute-bound, so
# kernel-side streaming is the long-context lever; the on-chip
# variantsAB/longctx stages decide loop vs stream.


def _fwd_kernel_bsd_gs(qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, m_sc, l_sc, acc_sc, *, scale, causal,
                       block_q, block_k, kv_len):
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = qo_ref[0]
    k_off = ko_ref[0]

    @pl.when(kb == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    run = True
    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        run = k_off + kb * block_k <= last_q

    @pl.when(run)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        bq = q.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)
        q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_rel = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = k_rel < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_off + k_rel)
        s = jnp.where(mask, s, _NEG_INF)
        m = m_sc[0]
        l = l_sc[0]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        m_sc[0] = m_new
        l_sc[0] = l * corr + jnp.sum(p, axis=-1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, d)

    @pl.when(kb == nk - 1)
    def _emit():
        l = l_sc[0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            (m_sc[0] + jnp.log(l_safe))[:, None], lse_ref.shape[2:])


def _flash_fwd_pallas_bsd_gs(q, k, v, q_off, k_off, scale, causal,
                             block_q, block_k, num_heads):
    b, sq, e = q.shape
    skv = k.shape[1]
    d = e // num_heads
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    sq_p, skv_p = sq + pad_q, skv + pad_k

    kernel = functools.partial(
        _fwd_kernel_bsd_gs, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, kv_len=skv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, num_heads, sq_p // block_q, skv_p // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda i, j, k_, kb, qo, ko: (i, k_, j)),
            pl.BlockSpec((1, block_k, d),
                         lambda i, j, k_, kb, qo, ko: (i, kb, j)),
            pl.BlockSpec((1, block_k, d),
                         lambda i, j, k_, kb, qo, ko: (i, kb, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda i, j, k_, kb, qo, ko: (i, k_, j)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda i, j, k_, kb, qo, ko: (i, j, k_, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((1, block_q), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            out_struct((b, sq_p, e), q.dtype, qp, kp, vp, q_off, k_off),
            out_struct((b, num_heads, sq_p, 128), jnp.float32,
                       qp, kp, vp, q_off, k_off),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * num_heads * sq_p * skv_p * d,
            bytes_accessed=(qp.size + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * num_heads * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_fwd",
    )(jnp.asarray([q_off], jnp.int32), jnp.asarray([k_off], jnp.int32),
      qp, kp, vp)
    lse = lse[..., 0]
    if pad_q:
        out, lse = out[:, :sq], lse[:, :, :sq]
    return out, lse


def _bwd_dq_kernel_bsd_gs(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, delta_ref, dq_ref, dq_sc, *, scale,
                          causal, block_q, block_k, kv_len, q_len):
    qi = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = qo_ref[0]
    k_off = ko_ref[0]

    @pl.when(kb == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    run = True
    if causal:
        last_q = q_off + (qi + 1) * block_q - 1
        run = k_off + kb * block_k <= last_q

    @pl.when(run)
    def _update():
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        bq = q.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_rel = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 0)
        k_rel = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        mask = jnp.logical_and(k_rel < kv_len, q_rel < q_len)
        if causal:
            mask = jnp.logical_and(mask, q_off + q_rel >= k_off + k_rel)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_sc[...] = dq_sc[...] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k.astype(k_ref.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _emit():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_bsd_gs(qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                           lse_ref, delta_ref, dk_ref, dv_ref, dk_sc,
                           dv_sc, *, scale, causal, block_q, block_k,
                           kv_len, q_len):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    q_off = qo_ref[0]
    k_off = ko_ref[0]

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    run = True
    if causal:
        run = q_off + (qi + 1) * block_q - 1 >= k_off + ki * block_k

    @pl.when(run)
    def _update():
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32)                  # (bq, d)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        bk = k.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_rel = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        k_rel = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 1)
        mask = jnp.logical_and(k_rel < kv_len, q_rel < q_len)
        if causal:
            mask = jnp.logical_and(mask, q_off + q_rel >= k_off + k_rel)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dv_sc[...] = dv_sc[...] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do.astype(do_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk_sc[...] = dk_sc[...] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q.astype(q_ref.dtype),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _flash_bwd_pallas_bsd_gs(scale, causal, block_q, block_k, num_heads,
                             res, grads):
    q, k, v, o, lse, q_off, k_off = res
    g, glse = grads
    b, sq, e = q.shape
    skv = k.shape[1]
    d = e // num_heads
    block_q = min(block_q, max(sq, 128))
    block_k = min(block_k, max(skv, 128))
    pad_q = (-sq) % block_q
    pad_k = (-skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0))) if pad_q else q
    dop = jnp.pad(g, ((0, 0), (0, pad_q), (0, 0))) if pad_q else g
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0))) if pad_k else v
    sq_p, skv_p = sq + pad_q, skv + pad_k

    delta = _delta_bhs(g, o, glse, b, sq, num_heads, d)
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q))) if pad_q else lse
    deltap = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q))) if pad_q \
        else delta
    lsep = lsep[..., None]
    deltap = deltap[..., None]

    qo = jnp.asarray([q_off], jnp.int32)
    ko = jnp.asarray([k_off], jnp.int32)
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, kv_len=skv, q_len=sq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_bsd_gs, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, num_heads, sq_p // block_q, skv_p // block_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, k_, kb, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, kb, qo, ko: (i, kb, j)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, kb, qo, ko: (i, kb, j)),
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, k_, kb, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda i, j, k_, kb, qo, ko: (i, j, k_, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda i, j, k_, kb, qo, ko: (i, j, k_, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda i, j, k_, kb, qo, ko: (i, k_, j)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=out_struct((b, sq_p, e), q.dtype,
                             qp, kp, vp, dop, lsep, deltap, qo, ko),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=6 * b * num_heads * sq_p * skv_p * d,
            bytes_accessed=(qp.size * 2 + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * num_heads * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_bwd_dq",
    )(qo, ko, qp, kp, vp, dop, lsep, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_bsd_gs, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, num_heads, skv_p // block_k, sq_p // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, k_, qb, qo, ko: (i, qb, j)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, qb, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, qb, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, block_q, d),
                             lambda i, j, k_, qb, qo, ko: (i, qb, j)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda i, j, k_, qb, qo, ko: (i, j, qb, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda i, j, k_, qb, qo, ko: (i, j, qb, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, qb, qo, ko: (i, k_, j)),
                pl.BlockSpec((1, block_k, d),
                             lambda i, j, k_, qb, qo, ko: (i, k_, j)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            out_struct((b, skv_p, e), k.dtype,
                       qp, kp, vp, dop, lsep, deltap, qo, ko),
            out_struct((b, skv_p, e), v.dtype,
                       qp, kp, vp, dop, lsep, deltap, qo, ko),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=8 * b * num_heads * sq_p * skv_p * d,
            bytes_accessed=(qp.size * 2 + kp.size + vp.size)
            * qp.dtype.itemsize,
            transcendentals=b * num_heads * sq_p * skv_p,
        ),
        interpret=_INTERPRET,
        name="flash_bwd_dkv",
    )(qo, ko, qp, kp, vp, dop, lsep, deltap)

    if pad_q:
        dq = dq[:, :sq]
    if pad_k:
        dk, dv = dk[:, :skv], dv[:, :skv]
    zero_off = (jnp.asarray(q_off, jnp.float32) * 0,
                jnp.asarray(k_off, jnp.float32) * 0)
    return (dq, dk, dv) + zero_off


def _bsd_to_heads(t, num_heads):
    b, s, e = t.shape
    return t.reshape(b, s, num_heads, e // num_heads).transpose(0, 2, 1, 3)


def _heads_to_bsd(t):
    b, h, s, d = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _bsd_eligible(q, num_heads):
    """Backend/shape eligibility for ANY bsd Pallas kernel (structure-
    independent)."""
    e = q.shape[-1]
    d = e // num_heads
    if d % 128 != 0:
        return False  # lane slicing must be tile-aligned
    if jax.default_backend() != "tpu" and not _INTERPRET:
        forced = _os.environ.get("MXNET_FLASH_IMPL")
        return forced in ("pallas_hsd", "pallas_ds", "pallas_bsd")
    return True


def _bsd_loop_fits_vmem(q, num_heads, kv_len):
    # same margined whole-stream residency model as _use_pallas
    # (`_stream_residency_fits`; round-5 anchors: S=4096 fits, S=8192
    # Mosaic-OOMs at any block, ~22% above linear extrapolation).
    # The grid-streamed kernels hold only (block, d) tiles in VMEM, so
    # this cap does not apply to them — they exist precisely for the
    # contexts that exceed it.
    d = q.shape[-1] // num_heads
    itemsize = jnp.dtype(q.dtype).itemsize
    return _stream_residency_fits(kv_len, d, itemsize)


def _bsd_structure(q, num_heads, kv_len):
    """Pick the kernel structure: MXNET_FLASH_BSD_KERNEL pins it; unset,
    the loop kernels win wherever their whole-K/V VMEM residency fits
    (round-5: 52.6% vs 41.9% MFU at S=4096) and the grid-streamed
    kernels take over beyond the cap (S=8192: 46.9% MFU vs a jnp-scan
    fallback — auto-promotion instead of silently losing 5x).

    Unrecognized values raise (readable-failure contract of the
    MXNET_FLASH_IMPL pins): a typo like 'streamed' must not silently
    change which kernel a pinned A/B run measures."""
    raw = _os.environ.get("MXNET_FLASH_BSD_KERNEL")
    if raw in ("loop", "stream"):
        return raw
    if raw not in (None, "", "auto"):
        from ...base import MXNetError

        raise MXNetError(
            "MXNET_FLASH_BSD_KERNEL must be 'loop', 'stream' or "
            "unset/'auto', got %r" % raw)
    return "loop" if _bsd_loop_fits_vmem(q, num_heads, kv_len) \
        else "stream"


def _bsd_fwd_dispatch(q, k, v, qo, ko, scale, causal, block_q, block_k,
                      num_heads, impl):
    # impl carries the kernel structure: 'pallas_bsd' = in-kernel fori
    # over K/V slices (whole-K/V VMEM residency), 'pallas_bsd_gs' =
    # grid-streamed blocks with scratch accumulators (no residency cap)
    if impl == "pallas_bsd_gs":
        return _flash_fwd_pallas_bsd_gs(q, k, v, qo, ko, scale, causal,
                                        block_q, block_k, num_heads)
    return _flash_fwd_pallas_bsd(q, k, v, qo, ko, scale, causal,
                                 block_q, block_k, num_heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_bsd(q, k, v, q_off, k_off, scale, causal, block_q, block_k,
               num_heads, impl):
    qo = jnp.asarray(q_off, jnp.int32)
    ko = jnp.asarray(k_off, jnp.int32)
    if impl in ("pallas_bsd", "pallas_bsd_gs"):
        return _bsd_fwd_dispatch(q, k, v, qo, ko, scale, causal,
                                 block_q, block_k, num_heads, impl)
    out, lse = _flash_fwd_jnp(
        _bsd_to_heads(q, num_heads), _bsd_to_heads(k, num_heads),
        _bsd_to_heads(v, num_heads), qo, ko, scale, causal, block_k)
    return _heads_to_bsd(out), lse


def _flash_bsd_fwd_rule(q, k, v, q_off, k_off, scale, causal, block_q,
                        block_k, num_heads, impl):
    qo = jnp.asarray(q_off, jnp.int32)
    ko = jnp.asarray(k_off, jnp.int32)
    out, lse = _flash_bsd(q, k, v, q_off, k_off, scale, causal, block_q,
                          block_k, num_heads, impl)
    return (out, lse), (q, k, v, out, lse, qo, ko)


def _flash_bsd_bwd_rule(scale, causal, block_q, block_k, num_heads, impl,
                        res, grads):
    force_jnp = _os.environ.get("MXNET_FLASH_BWD", "pallas") == "jnp"
    if impl == "pallas_bsd_gs" and not force_jnp:
        return _flash_bwd_pallas_bsd_gs(scale, causal, block_q,
                                        block_k, num_heads, res,
                                        grads)
    if impl == "pallas_bsd" and not force_jnp:
        return _flash_bwd_pallas_bsd(scale, causal, block_q, block_k,
                                     num_heads, res, grads)
    q, k, v, o, lse, qo, ko = res
    res_h = (_bsd_to_heads(q, num_heads), _bsd_to_heads(k, num_heads),
             _bsd_to_heads(v, num_heads), _bsd_to_heads(o, num_heads),
             lse, qo, ko)
    g, glse = grads
    dq, dk, dv, dqo, dko = _flash_bwd(
        scale, causal, block_k, res_h, (_bsd_to_heads(g, num_heads), glse))
    return (_heads_to_bsd(dq), _heads_to_bsd(dk), _heads_to_bsd(dv),
            dqo, dko)


_flash_bsd.defvjp(_flash_bsd_fwd_rule, _flash_bsd_bwd_rule)


def flash_attention_bsd(q, k, v, num_heads, *, causal=False, scale=None,
                        q_offset=0.0, k_offset=0.0, block_q=0,
                        block_k=0, with_lse=False):
    """Fused attention over (batch, seq, embed) arrays — the transposeless
    TPU path (heads live on the lane axis; see the bsd section note).

    Falls back to the blockwise jnp path (via head split/merge) when the
    per-head width is not lane-aligned or the K/V stream exceeds the VMEM
    cap.  ``block_q``/``block_k`` <= 0 selects the measured per-impl
    default (`_auto_blocks`).  Returns (out [, lse (batch, num_heads,
    seq)])."""
    if q.ndim != 3 or k.ndim != 3 or v.ndim != 3:
        raise ValueError("flash_attention_bsd expects (B, S, E) inputs")
    if q.shape[-1] % num_heads != 0:
        raise ValueError("embed dim %d not divisible by num_heads %d"
                         % (q.shape[-1], num_heads))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1] // num_heads)
    block_q = int(_os.environ.get("MXNET_FLASH_BLOCK_Q", block_q))
    block_k = int(_os.environ.get("MXNET_FLASH_BLOCK_K", block_k))
    forced = _os.environ.get("MXNET_FLASH_IMPL")
    skv = k.shape[1]
    if forced == "pallas_bsd":
        # honor the pin with the same readable-failure contract as
        # _pick_impl: never silently hand a pinned A/B run to the jnp
        # fallback (that would mislabel recorded evidence)
        if not _bsd_eligible(q, num_heads) \
                or q.shape[1] * skv < 512 * 512:
            import warnings

            warnings.warn(
                "MXNET_FLASH_IMPL=pallas_bsd pinned, but the auto-router "
                "would reject this shape/backend (head_dim=%d, S=%dx%d) — "
                "the pinned kernel may fail to lower or spill"
                % (q.shape[-1] // num_heads, q.shape[1], skv))
        impl = "pallas_bsd"
    elif forced == "jnp":
        impl = "jnp_t"
    else:
        impl = "pallas_bsd" if (
            _bsd_eligible(q, num_heads)
            and q.shape[1] * skv >= 512 * 512) else "jnp_t"
    if impl == "pallas_bsd":
        structure = _bsd_structure(q, num_heads, skv)
        if forced == "pallas_bsd" and \
                _os.environ.get("MXNET_FLASH_BSD_KERNEL") not in (
                    "loop", "stream"):
            # a pinned impl with an auto-resolved structure can silently
            # mix two kernel structures across shapes in recorded evidence
            # (round-5 ADVICE); surface which one this shape resolved to
            import logging

            logging.getLogger(__name__).info(
                "MXNET_FLASH_IMPL=pallas_bsd pinned: auto-resolved kernel "
                "structure '%s' for S=%dx%d head_dim=%d (set "
                "MXNET_FLASH_BSD_KERNEL=loop|stream to pin the structure "
                "for A/B runs)",
                structure, q.shape[1], skv, q.shape[-1] // num_heads)
        if structure == "stream":
            impl = "pallas_bsd_gs"
        elif not _bsd_loop_fits_vmem(q, num_heads, skv):
            # only reachable when MXNET_FLASH_BSD_KERNEL=loop is pinned
            # (auto would have promoted to the streamed structure): honor
            # the pin but say why Mosaic is about to reject it
            import warnings

            warnings.warn(
                "MXNET_FLASH_BSD_KERNEL=loop pinned, but the whole-K/V "
                "VMEM residency of the loop kernels exceeds the ~12 MB "
                "model at kv_len=%d head_dim=%d — Mosaic will likely "
                "reject the kernel; unset the pin to auto-promote to the "
                "grid-streamed structure" % (skv, q.shape[-1] // num_heads))
    block_q, block_k = _auto_blocks(block_q, block_k, impl)
    q_off = jnp.asarray(q_offset, jnp.float32)
    k_off = jnp.asarray(k_offset, jnp.float32)
    static = (float(scale), bool(causal), int(block_q), int(block_k),
              int(num_heads), impl)
    out, lse = _call_flash(_flash_bsd, (q, k, v, q_off, k_off), static)
    return (out, lse) if with_lse else out


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, q_off, k_off, scale, causal, block_q, block_k, impl):
    qo = jnp.asarray(q_off, jnp.int32)
    ko = jnp.asarray(k_off, jnp.int32)
    if impl == "pallas_ds":
        o_ds, lse = _flash_fwd_pallas_ds(
            q.swapaxes(2, 3), k.swapaxes(2, 3), v.swapaxes(2, 3),
            qo, ko, scale, causal, block_q, block_k)
        return o_ds.swapaxes(2, 3), lse
    if impl == "pallas_hsd":
        return _flash_fwd_pallas(q, k, v, qo, ko, scale, causal,
                                 block_q, block_k)
    return _flash_fwd_jnp(q, k, v, qo, ko, scale, causal, block_k)


def _flash_fwd_rule(q, k, v, q_off, k_off, scale, causal, block_q, block_k,
                    impl):
    qo = jnp.asarray(q_off, jnp.int32)
    ko = jnp.asarray(k_off, jnp.int32)
    if impl == "pallas_ds":
        # residuals live in the unpadded dS layout: the API-layout q/k/v
        # die after the boundary swap, so the saved activations cost half
        # the HBM of the padded (.., S, 64) form
        q_ds, k_ds, v_ds = (t.swapaxes(2, 3) for t in (q, k, v))
        o_ds, lse = _flash_fwd_pallas_ds(q_ds, k_ds, v_ds, qo, ko, scale,
                                         causal, block_q, block_k)
        return ((o_ds.swapaxes(2, 3), lse),
                (q_ds, k_ds, v_ds, o_ds, lse, qo, ko))
    out, lse = _flash(q, k, v, q_off, k_off, scale, causal, block_q,
                      block_k, impl)
    return (out, lse), (q, k, v, out, lse, qo, ko)


def _flash_bwd_rule(scale, causal, block_q, block_k, impl, res, grads):
    # MXNET_FLASH_BWD=jnp forces the scan fallback (escape hatch while the
    # Pallas backward burns in on hardware)
    force_jnp = _os.environ.get("MXNET_FLASH_BWD", "pallas") == "jnp"
    if impl == "pallas_ds":
        if not force_jnp:
            return _flash_bwd_pallas_ds(scale, causal, block_q, block_k,
                                        res, grads)
        q, k, v, o, lse, qo, ko = res
        res = (q.swapaxes(2, 3), k.swapaxes(2, 3), v.swapaxes(2, 3),
               o.swapaxes(2, 3), lse, qo, ko)
        return _flash_bwd(scale, causal, block_k, res, grads)
    if impl == "pallas_hsd" and not force_jnp:
        return _flash_bwd_pallas(scale, causal, block_q, block_k, res,
                                 grads)
    return _flash_bwd(scale, causal, block_k, res, grads)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _call_flash(fn, operands, static):
    """``fn(*operands, *static)`` with impl = ``static[-1]``; a Pallas impl
    runs per device, q/k/v split with the batch and the position offsets
    shared (see `_spmd.call_local`)."""
    if not static[-1].startswith("pallas"):
        return fn(*operands, *static)
    return call_local(lambda *a: fn(*a, *static), operands,
                      (True, True, True, False, False), (True, True),
                      interpreted=_INTERPRET)


def _pick_impl(q, kv_len):
    """Static kernel choice (trace-time).  Size gate from on-chip
    measurement (the round-3 attnbwd diagnostic): at S=1024 the Pallas
    backward beats the jnp scan 10x, but below ~512x512 the kernel
    launches + boundary copies cost more than the scan's few fused blocks
    (0.5 ms jnp vs 3.6 ms pallas at 512x384).  MXNET_FLASH_LAYOUT=ds
    opts into the dS-layout kernels for A/B / capacity."""
    forced = _os.environ.get("MXNET_FLASH_IMPL")
    if forced in ("jnp", "pallas_ds", "pallas_hsd"):
        if forced != "jnp":
            # A pin bypasses the gates below; fail/warn readably instead of
            # erroring deep inside Mosaic on a non-TPU backend or an
            # over-VMEM-cap shape (round-4 advisor finding).
            if not _use_pallas(q, kv_len=kv_len):
                import warnings

                warnings.warn(
                    "MXNET_FLASH_IMPL=%s pinned, but the auto-router would "
                    "reject this shape/backend (backend=%s, head_dim=%d, "
                    "kv_len=%d: non-TPU, head_dim<32, or K/V stream over "
                    "the ~12MB VMEM cap) — the pinned kernel may fail to "
                    "lower or spill" % (forced, jax.default_backend(),
                                        q.shape[-1], kv_len))
        return forced
    if not _use_pallas(q, kv_len=kv_len):
        return "jnp"
    if q.shape[2] * kv_len < 512 * 512:
        return "jnp"
    # hsd default from the round-4 in-model A/B at GPT-2-small shape
    # (median windows, B=32 S=1024 d=64): hsd 77.6k tok/s > all-jnp 73.8k
    # > grid-ds 49.4k.  The dS kernels win in isolation but their
    # boundary (b,h,S,d)<->(b,h,d,S) transposes do not fold away inside
    # the compiled step; keep them selectable for capacity-bound runs.
    if _os.environ.get("MXNET_FLASH_LAYOUT", "hsd") == "ds":
        return "pallas_ds"
    return "pallas_hsd"


def _auto_blocks(block_q, block_k, impl):
    """Resolve block<=0 ("auto") to the measured in-model winners.

    Round-5 on-chip block sweep (S=1024..8192, h6/d128, full train step):
    the loop kernels are monotonically faster up to 512 (S=1024: 42.4%
    MFU at 128 -> 53.7% at 512; S=4096: 27.5% -> 52.6%) and VMEM-reject
    beyond it; the grid-streamed kernels peak at 1024 (S=8192: 9.4% at
    128 -> 46.9% at 1024, OOM at bq1024/bk2048).  The jnp scan and the
    dS kernels keep their prior 256 (the dS structure is unmeasured at
    512 and is a capacity knob, not a speed path).  MXNET_FLASH_BLOCK_Q/K
    still override everything.
    """
    auto = {"pallas_hsd": 512, "pallas_bsd": 512,
            "pallas_bsd_gs": 1024}.get(impl, 256)
    if block_q <= 0:
        block_q = auto
    if block_k <= 0:
        block_k = auto
    return block_q, block_k


def flash_attention(q, k, v, *, causal=False, scale=None,
                    q_offset=0.0, k_offset=0.0,
                    block_q=0, block_k=0, with_lse=False):
    """Fused attention over (batch, heads, seq, head_dim) arrays.

    ``scale`` defaults to 1/sqrt(head_dim).  ``q_offset``/``k_offset`` are
    the global positions of row/col 0 for causal masking (may be traced;
    passed as floats so gradients flow cleanly through `custom_vjp`).
    ``block_q``/``block_k`` <= 0 selects the measured per-impl default
    (`_auto_blocks`).  Returns the attention output; with ``with_lse=True``
    also returns the per-row logsumexp of the scaled scores (float32,
    (batch, heads, seq)) for cross-device combination (see
    `parallel/sequence.py`).
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError("flash_attention expects (B, H, S, D) inputs")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    impl = _pick_impl(q, k.shape[2])
    # Diagnostic pins: the DotProductAttention op builds into the model
    # with its own block params, so an in-model block-size A/B needs an
    # env override
    block_q = int(_os.environ.get("MXNET_FLASH_BLOCK_Q", block_q))
    block_k = int(_os.environ.get("MXNET_FLASH_BLOCK_K", block_k))
    block_q, block_k = _auto_blocks(block_q, block_k, impl)
    q_off = jnp.asarray(q_offset, jnp.float32)
    k_off = jnp.asarray(k_offset, jnp.float32)
    static = (float(scale), bool(causal), int(block_q), int(block_k), impl)
    out, lse = _call_flash(_flash, (q, k, v, q_off, k_off), static)
    return (out, lse) if with_lse else out
