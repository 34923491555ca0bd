"""The gated delta rule (Gated DeltaNet: Yang, Kautz and Hatamizadeh, 2024):
linear attention whose state is a matrix a head, decayed and corrected by a
delta-rule write at every position.

For one head, with ``k`` and ``q`` already of unit length, a position ``t``
with log decay ``g_t <= 0`` and write strength ``beta_t``:

    S <- exp(g_t) S
    u_t = beta_t (v_t - S^T k_t)
    S <- S + k_t u_t^T
    o_t = scale * S^T q_t

``S`` is ``(dk, dv)`` and float32.  Two forms compute it:

* `gated_delta_step`, one position a row: the decode step.
  `gated_delta_slots` runs it over each row's slot of the serving engine's
  state array, on a TPU as the kernel `pallas_kernels.delta_step` that
  steps every slot in place.
* `chunk_gated_delta`, a run of positions a row: the chunkwise-parallel form
  (the WY/UT representation of the delta rule's products of Householder-like
  factors).  Within a sub-chunk of ``C`` positions, with ``G`` the cumulative
  log decay from the sub-chunk's start and ``Gamma_ij = exp(G_i - G_j)`` for
  ``i >= j``::

      T = (I + tril(diag(beta) (K K^T) * Gamma, -1))^-1 diag(beta)
      W = T (K * exp(G)),   U = T V
      V' = U - W S
      O  = (Q * exp(G)) S + tril(Q K^T * Gamma) V'
      S <- exp(G_C) S + (K * exp(G_C - G))^T V'

  ``T`` is a triangular solve; ``W``, ``U`` and the intra-chunk scores do not
  depend on the state and are computed for every sub-chunk at once.  The
  sub-chunks then run in order in a `lax.scan`, not unrolled, so the program
  does not grow with the chunk.

A position with ``beta = 0`` and ``g = 0`` leaves ``S`` exactly as it was:
padding past a row's length is given those.  Everything here is float32; the
products that meet the state run at the highest matmul precision, so the
state is carried in float32 and not in the TPU's one-pass bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .pallas_kernels import delta_step

HIGHEST = lax.Precision.HIGHEST


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def l2_normalize(x, eps=1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32 (the `l2norm`
    of the flash-linear-attention kernels)."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


@jax.named_scope("delta_rule")
def gated_delta_step(q, k, v, g, beta, state, scale=None):
    """One position a row.  ``q`` and ``k`` (b, H, dk) of unit length, ``v``
    (b, H, dv), ``g`` and ``beta`` (b, H), ``state`` (b, H, dk, dv).
    Returns (o (b, H, dv), the new state), float32.

    Elementwise products and sums over the key axis, all float32: ``S^T k``
    and ``S^T q`` are read in one pass over the state, and the output is
    taken as ``exp(g) S^T q + u (k . q)``, which is ``S^T q`` of the written
    state, so the state is read once and written once."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    if scale is None:
        scale = q.shape[-1] ** -0.5
    alpha = jnp.exp(g)[..., None]                                # (b, H, 1)
    sk = jnp.sum(state * k[..., None], axis=-2)
    sq = jnp.sum(state * q[..., None], axis=-2)
    u = beta[..., None] * (v - alpha * sk)
    o = alpha * sq + u * jnp.sum(k * q, axis=-1, keepdims=True)
    new = alpha[..., None] * state + k[..., :, None] * u[..., None, :]
    return scale * o, new


@jax.named_scope("delta_rule")
def gated_delta_slots(q, k, v, g, beta, state, layer, slots, scale=None):
    """`gated_delta_step` of every row over its own slot of ``state``
    (layers, slots, H * dk, lanes) float32, the slots' rows ``slots`` (b,)
    of layer ``layer``; ``v`` (b, H, lanes).  Returns (o (b, H, lanes), the
    state with those slots stepped).

    On a TPU the kernel `pallas_kernels.delta_step.delta_decode_step` steps
    each slot in place (the array aliased in to out); elsewhere the slots
    are gathered, stepped and scattered back, the kernel's reference in the
    tests."""
    b, h, dk = k.shape
    if delta_step.applies(state, h):
        return delta_step.delta_decode_step(q, k, v, g, beta, state, layer,
                                            slots, scale)
    lanes = state.shape[3]
    o, new = gated_delta_step(q, k, v, g, beta,
                              state[layer, slots].reshape(b, h, dk, lanes),
                              scale)
    return o, state.at[layer, slots].set(new.reshape(b, h * dk, lanes))


@jax.named_scope("delta_rule")
def chunk_gated_delta(q, k, v, g, beta, state, *, chunk=64, scale=None):
    """A run of positions a row, in sub-chunks of ``chunk``.  ``q`` and
    ``k`` (b, T, H, dk) of unit length, ``v`` (b, T, H, dv), ``g`` and
    ``beta`` (b, T, H), ``state`` (b, H, dk, dv) as the row's position 0
    of the run finds it.  ``T`` need not be a multiple of ``chunk``: the
    rest is padded with positions that leave the state alone.  Returns (o (b, T,
    H, dv), the state after the run's last position), float32."""
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    if scale is None:
        scale = dk ** -0.5
    c = int(chunk)
    pad = -t % c
    n = (t + pad) // c
    f32 = jnp.float32

    def split(x):
        # (b, T, H, ...) -> (b, H, n, C, ...)
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad)) + ((0, 0),)
                    * (x.ndim - 2))
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = split(q) * scale, split(k), split(v)
    g, beta = split(g), split(beta)                          # (b, H, n, C)
    cum = jnp.cumsum(g, axis=-1)
    i = jnp.arange(c)[:, None]
    j = jnp.arange(c)[None, :]
    # Gamma, zero above the diagonal: every exponent taken is <= 0
    gamma = jnp.exp(jnp.where(i >= j, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    a = jnp.where(i > j, beta[..., :, None] * _dot(
        "bhncd,bhnsd->bhncs", k, k) * gamma, 0.0)
    rhs = beta[..., None] * jnp.concatenate(
        [v, k * jnp.exp(cum)[..., None]], axis=-1)
    eye = jnp.eye(c, dtype=f32)
    sol = lax.linalg.triangular_solve(a + eye, rhs, left_side=True,
                                      lower=True, unit_diagonal=True)
    u, w = sol[..., :dv], sol[..., dv:]
    scores = _dot("bhncd,bhnsd->bhncs", q, k) * gamma
    qg = q * jnp.exp(cum)[..., None]
    kd = k * jnp.exp(cum[..., -1:] - cum)[..., None]
    decay = jnp.exp(cum[..., -1])[..., None, None]           # (b, H, n, 1, 1)

    def body(s, xs):
        u, w, scores, qg, kd, decay = xs
        vp = u - _dot("bhck,bhkv->bhcv", w, s)
        o = _dot("bhck,bhkv->bhcv", qg, s) + _dot("bhcs,bhsv->bhcv",
                                                  scores, vp)
        return decay * s + _dot("bhck,bhcv->bhkv", kd, vp), o

    s, o = lax.scan(body, state.astype(f32),
                    tuple(jnp.moveaxis(x, 2, 0)
                          for x in (u, w, scores, qg, kd, decay)))
    # (n, b, H, C, dv) -> (b, T, H, dv)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * c, dv)[:, :, :t]
    return jnp.moveaxis(o, 1, 2), s

