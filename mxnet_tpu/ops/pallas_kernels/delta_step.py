"""The gated delta rule's decode step over the serving engine's state slots
(a Pallas TPU kernel): `delta_decode_step`.

Each batch row steps its own sequence's recurrent state ``S``, one
``(key_head_dim, lanes)`` float32 matrix a head, which lives in a slot of
the engine's state array ``(layers, slots, heads * key_head_dim, lanes)``
(`serving.linear.HybridDeltaKVModel.init_state`).  The `jax.numpy` form
gathers every row's slot out of that array, steps it and scatters it back:
as a gather and a scatter of whole rows, the TPU compiler runs the scatter a
row of 256 lanes at a time, 2,880 such rows a slot; as one slice a slot, it
copies the whole array first.  This kernel leaves the array where it is: for
row ``r`` the pipeline copies slot ``slots[r]`` of layer ``layer`` into VMEM
(the next row's copy in flight while this one computes), the step runs
there, and the result is written back over the same slot, the array aliased
from input to output so that nothing else of it moves.

The step, a head at a time (``alpha = exp(g)``), all float32 on the vector
unit:

    u = beta (v - alpha S^T k);   S' = alpha S + k u^T
    o = scale (alpha S^T q + u (k . q))          (= scale S'^T q)

``S^T k`` and ``S^T q`` are sums over the key axis, the sublanes of a
head's ``(key_head_dim, lanes)`` rows.  Lanes past the value head's width
hold zeros, and stay zero.  Padding rows of a bucket all name the spare
slot: their rows are stepped in turn over it, and what it holds is read by
no real row.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# MXNET_PALLAS_INTERPRET=1: run the kernel through the interpreter so the
# CPU mesh executes the real kernel body (see flash_attention.py)
_INTERPRET = os.environ.get("MXNET_PALLAS_INTERPRET", "0") == "1"

# VMEM for a row's slot in, out, and the next row's in flight (3 MB a slot
# at the published widths)
_VMEM_BYTES = 64 * 1024 * 1024


def applies(state, heads):
    """Whether `delta_decode_step` can step ``state`` (layers, slots, heads *
    key_head_dim, lanes) here: a TPU backend (or the interpreter), float32,
    whole 128-lane rows and key heads of whole sublane tiles."""
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return False
    return (state.ndim == 4 and state.dtype == jnp.float32
            and state.shape[3] % 128 == 0 and state.shape[2] % heads == 0
            and (state.shape[2] // heads) % 8 == 0)


def _kernel(slots_ref, layer_ref, kt_ref, qt_ref, v_ref, a_ref, b_ref, s_ref,
            o_ref, out_ref, *, heads, dk, scale):
    for h in range(heads):
        rows = slice(h * dk, (h + 1) * dk)
        s = s_ref[rows, :]                                # (dk, lanes)
        k = kt_ref[:, h:h + 1]                            # (dk, 1)
        q = qt_ref[:, h:h + 1]
        alpha = a_ref[h:h + 1, :]                         # (1, lanes)
        sk = jnp.sum(s * k, axis=0, keepdims=True)        # (1, lanes)
        sq = jnp.sum(s * q, axis=0, keepdims=True)
        u = b_ref[h:h + 1, :] * (v_ref[h:h + 1, :] - alpha * sk)
        kq = jnp.sum(k * q, axis=0, keepdims=True)        # (1, 1)
        o_ref[h:h + 1, :] = scale * (alpha * sq + u * kq)
        out_ref[rows, :] = alpha * s + k * u


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _step(q, k, v, alpha, beta, state, layer, slots, *, scale, interpret):
    b, heads, dk = k.shape
    lanes = state.shape[3]
    row = functools.partial(pl.BlockSpec, index_map=lambda r, *_: (r, 0, 0))
    slot = pl.BlockSpec((None, None, heads * dk, lanes),
                        lambda r, slots, layer: (layer[0], slots[r], 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, dk=dk, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,       # slots, the layer
            grid=(b,),
            in_specs=[row((None, dk, heads))] * 2
            + [row((None, heads, lanes))] * 3 + [slot],
            out_specs=[row((None, heads, lanes)), slot]),
        out_shape=[jax.ShapeDtypeStruct((b, heads, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state in (operand 7, counting the prefetched scalars) is the
        # state out; the decay and the write strength come a head's row
        # wide: a (1, 1) value times a head's rows is a broadcast over both
        # sublanes and lanes, which Mosaic does not lower
        input_output_aliases={7: 1},
        # padding rows share the spare slot: rows in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name="delta_decode_step",
    )(slots, layer.reshape(1), jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 1, 2),
      v, *(jnp.broadcast_to(x[..., None], v.shape) for x in (alpha, beta)),
      state)


def delta_decode_step(q, k, v, g, beta, state, layer, slots, scale=None):
    """One decode step of every row over its slot of ``state`` (layers,
    slots, heads * key_head_dim, lanes) float32, in place.  ``q`` and ``k``
    (b, heads, key_head_dim) of unit length, ``v`` (b, heads, lanes) (zeros
    past the value head), ``g`` and ``beta`` (b, heads), ``layer`` a scalar,
    ``slots`` (b,) int32.  Returns (o (b, heads, lanes), the state)."""
    f32 = jnp.float32
    if scale is None:
        scale = k.shape[-1] ** -0.5
    return _step(q.astype(f32), k.astype(f32), v.astype(f32),
                 jnp.exp(g.astype(f32)), beta.astype(f32), state,
                 jnp.asarray(layer, jnp.int32), slots.astype(jnp.int32),
                 scale=float(scale), interpret=_INTERPRET)
