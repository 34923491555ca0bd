"""Fused LayerNorm (Pallas TPU kernel).

LayerNorm is memory-bound: unfused, XLA materializes mean/var/normalized
intermediates as separate HBM passes in the backward.  This kernel does one
VMEM pass per row-block for the forward (statistics in f32 regardless of
input dtype) and one for the backward, emitting per-block partial
dgamma/dbeta that a single small reduction finishes — HBM traffic is
2 reads + 1 write per element instead of ~5.

Layout: x is (rows, N) with N the normalized axis; rows are blocked over
the grid, N stays whole in VMEM (embed dims up to ~16k fit comfortably).
Pallas engages on TPU when N is lane-aligned (N % 128 == 0); anything else
takes the identical-math jnp path (also the CPU-mesh test path).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._spmd import call_local, out_struct

_BLOCK_ROWS = 256

# MXNET_PALLAS_INTERPRET=1: run the kernels through the interpreter so the
# CPU mesh executes the real kernel bodies (see flash_attention.py)
_INTERPRET = os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"


def _use_pallas(x):
    # MXNET_LN_IMPL pins the choice (pallas/jnp) — needed when AOT-
    # compiling for a TPU topology from a CPU process, where the backend
    # check would silently swap the jnp body into the lowered program
    forced = os.environ.get("MXNET_LN_IMPL")
    if forced == "jnp":
        return False
    if forced != "pallas" and jax.default_backend() != "tpu" \
            and not _INTERPRET:
        return False
    return x.shape[-1] % 128 == 0


# -- kernels ---------------------------------------------------------------


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y_ref[...] = (xhat * g + b).astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref,
                dx_ref, dg_ref, db_ref):
    # the TPU grid is sequential: dgamma/dbeta accumulate into one block
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    rstd = rstd_ref[...]
    xhat = (x - mean) * rstd
    g = g_ref[...].astype(jnp.float32)
    gdy = dy * g
    m1 = jnp.mean(gdy, axis=1, keepdims=True)
    m2 = jnp.mean(gdy * xhat, axis=1, keepdims=True)
    dx_ref[...] = (rstd * (gdy - m1 - xhat * m2)).astype(dx_ref.dtype)
    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _pad_rows(x2d, block):
    rows = x2d.shape[0]
    pad = (-rows) % block
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    return x2d, rows, pad


def _fwd_pallas(x2d, gamma, beta, eps):
    xp, rows, pad = _pad_rows(x2d, _BLOCK_ROWS)
    n = xp.shape[-1]
    grid = xp.shape[0] // _BLOCK_ROWS
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, n), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            out_struct(xp.shape, x2d.dtype, xp, gamma, beta),
            out_struct((xp.shape[0], 1), jnp.float32, xp, gamma, beta),
            out_struct((xp.shape[0], 1), jnp.float32, xp, gamma, beta),
        ],
        interpret=_INTERPRET,
        name="layer_norm_fwd",
    )(xp, gamma.reshape(1, -1), beta.reshape(1, -1))
    return y[:rows], mean[:rows], rstd[:rows]


def _bwd_pallas(x2d, gamma, mean, rstd, dy2d):
    xp, rows, pad = _pad_rows(x2d, _BLOCK_ROWS)
    dyp, _, _ = _pad_rows(dy2d, _BLOCK_ROWS)
    meanp, _, _ = _pad_rows(mean, _BLOCK_ROWS)
    # padded rows: rstd 0 makes xhat/dx contributions zero
    rstdp, _, _ = _pad_rows(rstd, _BLOCK_ROWS)
    n = xp.shape[-1]
    grid = xp.shape[0] // _BLOCK_ROWS
    dx, dg, db = pl.pallas_call(
        _bwd_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_shape=[
            out_struct(xp.shape, x2d.dtype, xp, gamma, meanp, rstdp, dyp),
            out_struct((1, n), jnp.float32, xp, gamma, meanp, rstdp, dyp),
            out_struct((1, n), jnp.float32, xp, gamma, meanp, rstdp, dyp),
        ],
        interpret=_INTERPRET,
        name="layer_norm_bwd",
    )(xp, gamma.reshape(1, -1), meanp, rstdp, dyp)
    return dx[:rows], dg[0], db[0]


# -- jnp fallback (identical math; CPU mesh + unaligned N) ----------------


def _fwd_jnp(x2d, gamma, beta, eps):
    x = x2d.astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * gamma.astype(jnp.float32) + beta.astype(jnp.float32)
    return y.astype(x2d.dtype), mean, rstd


def _bwd_jnp(x2d, gamma, mean, rstd, dy2d):
    x = x2d.astype(jnp.float32)
    dy = dy2d.astype(jnp.float32)
    xhat = (x - mean) * rstd
    gdy = dy * gamma.astype(jnp.float32)
    m1 = jnp.mean(gdy, axis=1, keepdims=True)
    m2 = jnp.mean(gdy * xhat, axis=1, keepdims=True)
    dx = (rstd * (gdy - m1 - xhat * m2)).astype(x2d.dtype)
    return dx, jnp.sum(dy * xhat, axis=0), jnp.sum(dy, axis=0)


# -- public op -------------------------------------------------------------


def layer_norm(x, gamma, beta, eps=1e-5):
    """y = (x - mean)/sqrt(var+eps) * gamma + beta over the last axis."""
    if not _use_pallas(x):
        return _layer_norm(x, gamma, beta, eps)
    # the kernel runs per device: rows split with the batch, gamma/beta
    # shared (`_spmd.call_local`)
    return call_local(lambda *a: _layer_norm(*a, eps), (x, gamma, beta),
                      (True, False, False), True, interpreted=_INTERPRET)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm(x, gamma, beta, eps):
    return _ln_fwd(x, gamma, beta, eps)[0]


def _ln_fwd(x, gamma, beta, eps):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    if _use_pallas(x2d):
        y, mean, rstd = _fwd_pallas(x2d, gamma, beta, eps)
    else:
        y, mean, rstd = _fwd_jnp(x2d, gamma, beta, eps)
    return y.reshape(shape), (x2d, gamma, mean, rstd)


def _ln_fwd_vjp(x, gamma, beta, eps):
    y, res = _ln_fwd(x, gamma, beta, eps)
    return y, res


def _ln_bwd_vjp(eps, res, dy):
    x2d, gamma, mean, rstd = res
    dy2d = dy.reshape(x2d.shape)
    if _use_pallas(x2d):
        dx, dg, db = _bwd_pallas(x2d, gamma, mean, rstd, dy2d)
    else:
        dx, dg, db = _bwd_jnp(x2d, gamma, mean, rstd, dy2d)
    return (dx.reshape(dy.shape), dg.astype(gamma.dtype),
            db.astype(gamma.dtype))


_layer_norm.defvjp(_ln_fwd_vjp, _ln_bwd_vjp)
