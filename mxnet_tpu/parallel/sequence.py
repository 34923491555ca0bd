"""Sequence / context parallelism: ring attention and Ulysses all-to-all.

The reference predates attention; its long-sequence story was bucketing and
model-parallel LSTM (SURVEY §5.7).  The TPU build's mandate is real sequence
scaling: shard the *sequence* axis of activations over a mesh axis so context
length scales with the number of chips.

Two standard schemes, both exact (not approximations):

* **Ring attention** (`ring_attention`): every device keeps its Q shard and
  rotates K/V shards around the mesh axis with `jax.lax.ppermute`.  Each
  visiting shard is folded by the flash kernel (blockwise, so no
  S_local x S_local score matrix ever exists) and combined exactly across
  shards via the kernel's logsumexp output.  Comms are nearest-neighbor so
  they ride ICI; compute of step i overlaps the transfer of step i+1
  thanks to XLA's async collectives.
* **Ulysses / all-to-all** (`ulysses_attention`): `jax.lax.all_to_all`
  re-shards activations from sequence-parallel to head-parallel, runs dense
  local attention (the Pallas flash kernel on TPU), and re-shards back.
  Cheaper comms for moderate S; requires num_heads % axis_size == 0.

Both are plain SPMD functions to be used inside `shard_map` (or any
`pjit`-traced function with manual axes) over a `Mesh` axis, and are fully
differentiable (`ppermute`/`all_to_all` have transpose rules; the diagonal
blocks use the custom-vjp flash kernel).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas_kernels import flash_attention

_NEG_INF = -1e30


def ring_attention(q, k, v, axis_name, *, causal=False, scale=None):
    """Exact attention over a sequence sharded on mesh axis ``axis_name``.

    Args: q, k, v — local shards, (batch, heads, S_local, head_dim); the
    global sequence is the concatenation of shards in axis-index order.
    Returns the local (batch, heads, S_local, head_dim) output shard.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, s_loc, d = q.shape

    # Running combination state: acc = softmax-weighted output so far,
    # lse_c = logsumexp of all scores folded so far.  Derived from q (not
    # fresh constants) so the scan carry has a consistent
    # varying-manual-axes type under shard_map.
    acc0 = q.astype(jnp.float32) * 0.0
    lse0 = acc0[..., 0] + _NEG_INF

    perm = [(i, (i + 1) % n) for i in range(n)]  # rotate K/V to the right

    def step(carry, _):
        (acc, lse_c), (k_cur, v_cur), rot = carry
        # Shard currently held arrived after `rot` rotations from device
        # (idx - rot) mod n; its global key offset decides the causal mask.
        kv_idx = (idx - rot) % n
        # The flash kernel folds this whole shard blockwise (never an
        # S_local x S_local score matrix in HBM) and reports the block's
        # logsumexp for exact cross-shard combination.
        o_blk, lse_blk = flash_attention(
            q, k_cur, v_cur, causal=causal, scale=scale,
            q_offset=idx * s_loc, k_offset=kv_idx * s_loc, with_lse=True)
        lse_new = jnp.logaddexp(lse_c, lse_blk)
        w_c = jnp.exp(lse_c - lse_new)[..., None]
        w_b = jnp.exp(lse_blk - lse_new)[..., None]
        acc = acc * w_c + o_blk.astype(jnp.float32) * w_b
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return ((acc, lse_new), (k_nxt, v_nxt), rot + 1), None

    carry = ((acc0, lse0), (k, v), jnp.int32(0))
    ((acc, _), _, _), _ = lax.scan(step, carry, None, length=n)
    return acc.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name, *, causal=False, scale=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style).

    Local shards (batch, heads, S_local, head_dim) are re-sharded so each
    device holds heads/n full-sequence heads, dense flash attention runs
    locally, and the output is re-sharded back to sequence-parallel.
    """
    n = lax.axis_size(axis_name)
    h = q.shape[1]
    if h % n != 0:
        raise ValueError(
            "ulysses_attention: num_heads (%d) must be divisible by the "
            "sequence-parallel axis size (%d)" % (h, n))

    def seq2head(x):
        # (b, h, s_loc, d) -> (b, h/n, s_glob, d)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    out = flash_attention(qh, kh, vh, causal=causal, scale=scale)
    return head2seq(out)
