"""What a Pallas kernel needs to run as part of an SPMD program.

Two facts of the installed JAX shape this module:

* **Mosaic kernels cannot be partitioned by GSPMD** ("wrap the call in a
  shard_map").  A kernel traced into a `jit` over a multi-device mesh — the
  `SPMDTrainer` step, a sub-mesh serving engine — therefore runs under
  `shard_map` on that mesh, each device on its own shard (`call_local`).
  The mesh is the one the caller scoped over the trace
  (`parallel.mesh.MeshContext`).
* **`shard_map(check_vma=True)` types every value by the mesh axes its
  per-device copies differ over.**  A `pallas_call`'s ``out_shape`` has to
  state that type by hand (`out_struct`); a kernel whose operands differ
  in it — tokens split over "data", a head split over "model" — is given
  one type on entry (`vary_alike`); and a `custom_vjp` rule hands each
  cotangent back in its primal's type (`reduce_like`).  Outside `shard_map`
  every set here is empty and these helpers are no-ops.
"""
from __future__ import annotations

import jax
from jax import lax
from jax.sharding import PartitionSpec as P


def vma_of(*operands):
    """Union of the operands' varying manual axes."""
    return frozenset().union(*(jax.typeof(o).vma for o in operands))


def out_struct(shape, dtype, *operands):
    """`pallas_call` out_shape entry that varies over the operands' axes."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma_of(*operands))


def vary_alike(*operands):
    """The operands, each cast to vary over every axis any of them varies
    over — the type the kernel's results have anyway.  Done where a kernel
    is entered, so that a scan carry derived from one operand matches what
    the body folds in from the others, and a `custom_vjp` sees primals of
    one type.  The casts cost nothing at run time."""
    want = vma_of(*operands)

    def cast(t):
        missing = tuple(sorted(want - jax.typeof(t).vma))
        return lax.pcast(t, missing, to="varying") if missing else t

    return tuple(cast(t) for t in operands)


def reduce_like(cot, primal):
    """A `custom_vjp` cotangent summed over the axes it varies over but
    its primal does not: the primal was one value shared by those devices,
    so its gradient is the sum of their partial gradients."""
    extra = tuple(sorted(vma_of(cot) - vma_of(primal)))
    return lax.psum(cot, extra) if extra else cot


def call_local(fn, args, batched, out_batched, interpreted=False):
    """``fn(*args)``, run once per device where the trace spans several.

    With no multi-device mesh scoped over the trace — or inside a
    `shard_map` already, whose body is per-device by construction — this is
    a plain call.  So it is for an ``interpreted`` kernel: that is plain
    HLO, which GSPMD partitions, and the Pallas interpreter cannot run under
    `shard_map(check_vma=True)` on this jax.  Otherwise ``fn`` runs under `shard_map` on the scoped
    mesh: arguments flagged in ``batched`` are split on their leading
    (batch) axis over the mesh's "data" axis when it divides every one of
    them, all else is replicated, and ``out_batched`` (a pytree of flags
    shaped like ``fn``'s result) says the same of the outputs.  A mesh
    without a "data" axis (a serving sub-mesh) runs the whole kernel on
    every device's replicated copy.
    """
    from ...parallel.mesh import get_mesh, shard_map

    def local(*a):
        return fn(*vary_alike(*a))

    mesh = get_mesh()
    if interpreted or mesh is None or mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return local(*args)
    dp = mesh.shape.get("data", 1)
    split = dp > 1 and all(a.shape[0] % dp == 0
                           for a, b in zip(args, batched) if b)

    def spec(is_batched):
        return P("data") if is_batched and split else P()

    return shard_map(
        local, mesh=mesh, in_specs=tuple(spec(b) for b in batched),
        out_specs=jax.tree_util.tree_map(spec, out_batched))(*args)
