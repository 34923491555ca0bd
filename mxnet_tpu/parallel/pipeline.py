"""Pipeline parallelism over a mesh axis (GPipe-style microbatching).

The reference's closest capability is pipelining-by-dataflow: layers pinned
to devices via `ctx_group`, overlap supplied by the dependency engine
(`example/model-parallel-lstm/lstm.py`, SURVEY §2.5 "PP").  That gives
overlap across a *single* step but no microbatching, so bubbles grow with
depth.

TPU-native design: the "pipe" mesh axis holds one stage per device slot.
Inside `shard_map`, every stage runs the same program (SPMD); activations
rotate stage-to-stage with `ppermute` over ICI.  Schedule: GPipe with M
microbatches — M forward rotations, then the loss stage's gradients rotate
backward through the same ring.  The whole schedule (forward ring, backward
ring, parameter grads) is ONE jitted program; XLA overlaps the `ppermute`s
with stage compute.

Because every stage must run the same traced computation, stages are
expressed as one `stage_fn(stage_params, x)` (same shapes on every stage) —
the classic homogeneous-pipeline restriction, matching transformer blocks.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .mesh import shard_map


class PipelineParallel:
    """GPipe pipeline of `num_stages` identical stages on mesh axis `axis`.

    Parameters
    ----------
    stage_fn : (params_pytree, x) -> y with y.shape == x.shape-compatible;
        runs as stage s with that stage's params.
    loss_fn : (y_last, label_microbatch) -> scalar loss (averaged later).
    mesh : Mesh whose `axis` has num_stages slots.
    num_microbatches : M; the global batch divides into M microbatches that
        stream through the ring.
    """

    def __init__(self, stage_fn, loss_fn, mesh, axis="pipe",
                 num_microbatches=None):
        if axis not in mesh.axis_names:
            raise MXNetError("mesh has no %r axis" % axis)
        self.stage_fn = stage_fn
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.axis = axis
        self.num_stages = mesh.shape[axis]
        self.num_microbatches = num_microbatches or self.num_stages

    def _forward_local(self, params, x_mb, labels_mb):
        """Runs inside shard_map: params are THIS stage's params (leading
        pipe axis already split away), x_mb/labels_mb are (M, mb, ...)."""
        ax = self.axis
        S = self.num_stages
        M = self.num_microbatches
        # shard_map keeps the split pipe axis as a leading length-1 dim
        params = jax.tree.map(lambda a: a[0], params)
        stage = jax.lax.axis_index(ax)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def loss_at_last(y, lbl):
            # only the last stage computes loss; others contribute 0
            return jnp.where(stage == S - 1,
                             self.loss_fn(y, lbl), 0.0)

        # GPipe: T = M + S - 1 ticks; at tick t, stage s processes
        # microbatch t - s (if in range).  `buf` is the activation entering
        # this stage this tick.
        T = M + S - 1
        zero = jnp.zeros_like(x_mb[0])
        # (1,)-shaped accumulator, not a scalar: older jax's shard_map
        # autodiff mis-specs a rank-0 scan carry inside manual axes
        # (_SpecError on float32[] under value_and_grad); a length-1 axis
        # sidesteps it with identical math
        total0 = jnp.zeros((1,), jnp.float32)
        # carries flow through ppermute/psum, so they are device-varying
        # over the pipe axis; the init must carry the same type
        zero = jax.lax.pcast(zero, ax, to="varying")
        total0 = jax.lax.pcast(total0, ax, to="varying")

        def tick(carry, t):
            buf, total = carry
            mb_idx = t - stage  # microbatch this stage works on
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 ingests a fresh microbatch; others take the rotated buf
            x_in = jnp.where(stage == 0,
                             x_mb[jnp.clip(t, 0, M - 1)], buf)
            y = self.stage_fn(params, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage: account loss for its (t - (S-1))th microbatch
            lbl = labels_mb[jnp.clip(mb_idx, 0, M - 1)]
            total = total + jnp.reshape(
                jnp.where(active, loss_at_last(y, lbl), 0.0), (1,))
            # rotate activations one stage forward
            buf = jax.lax.ppermute(y, ax, fwd_perm)
            return (buf, total), ()

        (buf, total), _ = jax.lax.scan(
            tick, (zero, total0), jnp.arange(T))
        # total is only nonzero on the last stage; share it
        total = jax.lax.psum(total[0], ax)
        return total / M

    def loss(self, params_stacked, x, labels):
        """Mean pipeline loss.  params_stacked: pytree with leading axis
        num_stages; x: (batch, ...); labels: (batch, ...)."""
        M = self.num_microbatches
        if x.shape[0] % M:
            raise MXNetError("batch %d not divisible by %d microbatches"
                             % (x.shape[0], M))
        x_mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
        l_mb = labels.reshape((M, labels.shape[0] // M) + labels.shape[1:])

        fn = shard_map(
            self._forward_local, mesh=self.mesh,
            in_specs=(P(self.axis), P(), P()),
            out_specs=P(),
        )
        return fn(params_stacked, x_mb, l_mb)

    def grad_step(self, params_stacked, x, labels, lr=None):
        """value_and_grad through the schedule (the backward rotations are
        the transposed ppermutes XLA derives).  Optionally SGD-update."""
        loss, grads = jax.value_and_grad(self.loss)(params_stacked, x, labels)
        if lr is None:
            return loss, grads
        new_params = jax.tree.map(lambda p, g: p - lr * g.astype(p.dtype),
                                  params_stacked, grads)
        return loss, new_params
