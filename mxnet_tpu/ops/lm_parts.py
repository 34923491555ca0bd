"""Parts every served language-model block of the RMSNorm family shares:
the norm, the biasless projection, rotary positions.

`serving/latent.py` (the DeepSeek-V3 block) and `serving/shortconv.py` (the
LFM2 block) build their programs from these; `ops/latent_attention.py` has
YaRN's frequencies, this module the plain ones.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def rms_norm(x, gamma, eps):
    """RMSNorm over the last axis with a learned gain, float32 statistics,
    the result in ``x``'s dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def proj(x, w):
    """``x @ w.T`` for a weight stored (out, in), float32 sums."""
    return jnp.dot(x, w.T, preferred_element_type=jnp.float32).astype(x.dtype)


def rope_inv_freq(dim, theta):
    """RoPE's ``dim // 2`` inverse frequencies ``theta^(-2i/dim)``,
    float64."""
    return 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


def rope(x, positions, inv_freq, factor=1.0):
    """Rotate the last axis of ``x`` (..., dim) by ``positions`` (the shape of
    ``x`` without its last axis, or broadcastable to it).  Dim ``i`` pairs
    with dim ``i + dim // 2`` (the half-split, "rotate-half" layout; where a
    published checkpoint stores the pairs interleaved, that is this up to a
    fixed permutation of the projection's columns)."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)
