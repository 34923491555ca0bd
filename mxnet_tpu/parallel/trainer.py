"""SPMD fused trainer: the idiomatic TPU training path.

Where `DataParallelExecutorManager` mirrors the reference architecture
(per-device executors + kvstore reduce, `executor_manager.py:180-262` +
`kvstore_local.h`), this trainer is the TPU-native form of the same
computation: ONE jitted step over a `Mesh`, batch sharded on the "data" axis,
parameters replicated (or sharded on "model" for tensor parallelism), XLA
inserting the gradient all-reduce over ICI — the SPMD equivalent of
`kvstore='device'` push/pull with perfect comm/compute overlap (the XLA
latency-hiding scheduler replaces the reference's priority-queue trick,
`model.py:96-98`).

Forward+backward+optimizer-update fuse into a single XLA program with donated
buffers, so per-step HBM traffic is minimal — this is the bench.py path.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import os

from ..base import MXNetError
from ..executor import _build_graph_fn, _mirror_policy
from ..ndarray import NDArray
from ..optimizer import stochastic_round_bf16
from .. import random as _random
from .mesh import MeshContext


def _ce_head_params(symbol):
    """(weight_name, bias_name|None, num_hidden) of the symbol's
    FusedSoftmaxCE head, or None — the params MXNET_CE_SHARD shards over
    the "model" axis."""
    from ..symbol import _topo_order

    for node in _topo_order(symbol._heads):
        if node.is_variable or node.op.name != "FusedSoftmaxCE":
            continue
        wname = node.inputs[1][0].name
        bname = None
        if not node.params.get("no_bias"):
            bname = node.inputs[2][0].name
        return wname, bname, int(node.params["num_hidden"])
    return None


def _put_global(arr, sharding):
    """device_put that works in multi-process jobs: a LOCAL jax array
    cannot be copied onto non-addressable devices, so materialize host-side
    first (each process then provides its addressable shards; every process
    must pass the same global value)."""
    if jax.process_count() > 1 and isinstance(arr, jax.Array):
        arr = np.asarray(arr)
    return jax.device_put(arr, sharding)


def _wd_mult(name):
    """Reference `Optimizer.set_wd_mult` default: weight decay applies to
    *_weight/*_gamma only — biases/beta/BN stats are excluded
    (`optimizer.py:76-87`)."""
    return 1.0 if name.endswith(("weight", "gamma")) else 0.0


def _clip(g, clip):
    return jnp.clip(g, -clip, clip) if clip else g


def _sgd_update(params, grads, momenta, lr, momentum, wd, rescale,
                clip=None):
    new_p, new_m = {}, {}
    for k, p in params.items():
        g = _clip(grads[k] * rescale, clip) + wd * _wd_mult(k) * p
        if momentum:
            m = momentum * momenta[k] - lr * g
            new_m[k] = m
            new_p[k] = p + m
        else:
            new_m[k] = momenta[k]
            new_p[k] = p - lr * g
    return new_p, new_m


def _adam_update(params, grads, state, lr, wd, rescale, b1, b2, eps,
                 clip=None, v_dtype=None):
    """Fused Adam with the `optimizer.Adam` numerics (wd folded into the
    gradient, bias-corrected lr).  state: {"_t": count, k: (m, v)}.

    ``v_dtype`` (e.g. bfloat16) stores the second-moment table in reduced
    precision — the moment math stays float32, only the stored v rounds
    (stochastically, see `optimizer.stochastic_round_bf16`: RTNE would
    stall the EMA once updates drop below the bf16 ulp) — halving the
    biggest optimizer-state HBM stream (the embedding/head tables
    read+written every step)."""
    t = state["_t"] + 1
    coef1 = 1 - b1 ** t
    coef2 = 1 - b2 ** t
    lr_t = lr * jnp.sqrt(coef2) / coef1
    sr_bf16 = v_dtype is not None and jnp.dtype(v_dtype) == jnp.bfloat16
    if sr_bf16:
        # key is a pure function of the step count: reproducible, and
        # traced inside jit so no key threading through the step signature
        step_key = jax.random.fold_in(jax.random.PRNGKey(0x51ca57), t)
    new_state = {"_t": t}
    new_p = {}
    for i, (k, p) in enumerate(params.items()):
        g = _clip(grads[k] * rescale, clip) + wd * _wd_mult(k) * p
        m, v = state[k]
        m = b1 * m + (1 - b1) * g
        v = b2 * v.astype(jnp.float32) + (1 - b2) * jnp.square(g)
        if sr_bf16:
            v_store = stochastic_round_bf16(
                v, jax.random.fold_in(step_key, i))
        else:
            v_store = v.astype(v_dtype) if v_dtype else v
        new_state[k] = (m, v_store)
        new_p[k] = p - lr_t * m / (jnp.sqrt(v) + eps)
    return new_p, new_state


class SPMDTrainer:
    """One-program data-parallel trainer for a Symbol graph.

    Parameters
    ----------
    symbol : Symbol whose outputs are loss heads (SoftmaxOutput etc.).
    mesh : jax.sharding.Mesh with a "data" axis (make_mesh()).
    data_shapes : dict name -> global batch shape (like simple_bind kwargs).
    optimizer : 'sgd' (momentum/wd) or 'adam' (beta1/beta2/epsilon,
        `optimizer.Adam` numerics) — both fuse into the step program.
    """

    def __init__(self, symbol, mesh, data_shapes, initializer=None, lr=0.01,
                 momentum=0.9, wd=0.0001, dtype=np.float32,
                 param_sharding=None, optimizer="sgd", beta1=0.9,
                 beta2=0.999, epsilon=1e-8, clip_gradient=None,
                 adam_v_dtype=None, abstract=False):
        self.symbol = symbol
        self.mesh = mesh
        self.lr, self.momentum, self.wd = lr, momentum, wd
        if optimizer not in ("sgd", "ccsgd", "adam"):
            raise MXNetError(
                "SPMDTrainer fuses the optimizer; sgd and adam are "
                "supported (got %r)" % (optimizer,))
        self.optimizer = "sgd" if optimizer == "ccsgd" else optimizer
        self._adam_hp = (beta1, beta2, epsilon)
        # reduced-precision second-moment table (see _adam_update)
        self._adam_v_dtype = jnp.dtype(adam_v_dtype) if adam_v_dtype else None
        self.clip_gradient = clip_gradient
        # Mixed precision, the TPU way: master params/momenta/aux stay f32,
        # compute casts to `dtype` (bf16 on the MXU) inside the jitted step,
        # and vjp's cast-transpose returns f32 gradients for the f32 update.
        self._compute_dtype = jnp.dtype(dtype)
        arg_shapes, _, aux_shapes = symbol.infer_shape(**data_shapes)
        if arg_shapes is None:
            raise MXNetError("cannot infer shapes from %s" % (data_shapes,))
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.data_names = [n for n in self.arg_names if n in data_shapes]
        self.param_names = [n for n in self.arg_names if n not in data_shapes]
        shape_of = dict(zip(self.arg_names, arg_shapes))

        # init params on host (reference initializer protocol), then place
        # replicated over the mesh (or a custom per-param sharding for TP).
        # abstract=True skips BOTH: state becomes ShapeDtypeStructs
        # carrying the shardings, for AOT lowering/compiling the step
        # against an abstract TPU topology (jax.experimental.topologies)
        # with no live device — step()/run_steps() are unusable then.
        from ..initializer import Uniform
        from ..ndarray import zeros

        self.abstract = abstract
        initializer = initializer or Uniform(0.07)
        repl = NamedSharding(mesh, P())

        # MXNET_CE_SHARD=1: store the FusedSoftmaxCE head weight/bias (and
        # their optimizer moments, via _param_sharding below) sharded over
        # the "model" axis — the op itself picks up the scoped mesh at
        # trace time (ops/loss.py) and runs the vocab-sharded kernels, so
        # the V x d table never exists replicated on any chip
        if (os.environ.get("MXNET_CE_SHARD", "0") == "1"
                and "model" in mesh.axis_names
                and mesh.shape["model"] > 1):
            head = _ce_head_params(symbol)
            if head is not None and head[2] % mesh.shape["model"] == 0:
                wname, bname, _ = head
                param_sharding = dict(param_sharding or {})
                param_sharding.setdefault(
                    wname, NamedSharding(mesh, P("model", None)))
                if bname is not None:
                    param_sharding.setdefault(
                        bname, NamedSharding(mesh, P("model")))

        def place(value_or_shape, np_dtype, sh):
            if abstract:
                shape = value_or_shape if isinstance(value_or_shape, tuple) \
                    else value_or_shape.shape
                return jax.ShapeDtypeStruct(shape, np_dtype, sharding=sh)
            if isinstance(value_or_shape, tuple):
                value_or_shape = np.zeros(value_or_shape, np_dtype)
            return _put_global(value_or_shape, sh)

        self._param_sharding = {}
        params = {}
        for n in self.param_names:
            sh = (param_sharding or {}).get(n, repl)
            self._param_sharding[n] = sh
            if abstract:
                params[n] = place(tuple(shape_of[n]), np.float32, sh)
                continue
            host = zeros(shape_of[n], dtype=np.float32)
            initializer(n, host)
            params[n] = _put_global(host.data, sh)
        self.params = params
        if self.optimizer == "adam":
            vdt = np.dtype(self._adam_v_dtype) if self._adam_v_dtype \
                else np.float32
            self.momenta = {"_t": place((), np.float32, repl)}
            self.momenta.update({
                n: (place(tuple(v.shape), np.float32,
                          self._param_sharding[n]),
                    place(tuple(v.shape), vdt, self._param_sharding[n]))
                for n, v in params.items()
            })
        else:
            self.momenta = {
                n: place(tuple(v.shape), np.float32,
                         self._param_sharding[n])
                for n, v in params.items()
            }
        self.aux = {
            n: place(tuple(s), np.float32, repl)
            for n, s in zip(self.aux_names, aux_shapes)
        }
        if not abstract:
            for n in self.aux_names:  # aux init: means 0, vars 1
                if n.endswith("moving_var"):
                    self.aux[n] = _put_global(
                        np.ones(self.aux[n].shape, np.float32), repl)

        _raw_graph_fn, _, _, _ = _build_graph_fn(symbol)

        def graph_fn(args, aux_list, rng, is_train):
            # scope the mesh over the trace so mesh-aware ops (the
            # MXNET_CE_SHARD vocab-sharded head) can see it; pure python
            # context, zero cost in the compiled program
            with MeshContext(mesh):
                return _raw_graph_fn(args, aux_list, rng, is_train)
        # Rematerialization knobs (the reference's tunable mirroring plan,
        # `static_graph.cc:410-560`): MXNET_BACKWARD_MIRROR_POLICY selects
        # what survives fwd->bwd (dots / attn / nothing — see
        # executor._mirror_policy); MXNET_BACKWARD_MIRROR_STEP=k adds
        # segment remat inside _build_graph_fn.  Both trade free recompute
        # FLOPs for HBM, the scarce resource on TPU.
        self._mirror_policy = _mirror_policy()
        batch_sharding = NamedSharding(mesh, P("data"))
        self._batch_sharding = batch_sharding
        # stacked (nsteps, batch, ...) inputs for run_steps: steps axis
        # replicated, batch axis sharded over "data"
        self._stacked_sharding = NamedSharding(mesh, P(None, "data"))
        self._shape_of = shape_of
        self._base_key = _random.next_key()
        global_batch = shape_of[self.data_names[0]][0]
        rescale = 1.0 / global_batch

        cd = self._compute_dtype

        if self.optimizer == "adam":
            b1, b2, eps = self._adam_hp

            def opt_update(params, grads, state, lr):
                return _adam_update(params, grads, state, lr, self.wd,
                                    rescale, b1, b2, eps,
                                    clip=self.clip_gradient,
                                    v_dtype=self._adam_v_dtype)
        else:
            def opt_update(params, grads, state, lr):
                return _sgd_update(params, grads, state, lr, self.momentum,
                                   self.wd, rescale,
                                   clip=self.clip_gradient)

        def cast_arg(name, x):
            # labels stay in their own dtype (class ids > 256 are not exact
            # in bf16); everything else floating casts to the compute dtype
            if "label" in name or not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            return x.astype(cd)

        def step(params, momenta, aux, batch, rng, lr):
            def f(p):
                args = [
                    cast_arg(n, batch[n] if n in batch else p[n])
                    for n in self.arg_names
                ]
                aux_list = [aux[n] for n in self.aux_names]
                outs, new_aux = graph_fn(args, aux_list, rng, True)
                return outs, new_aux

            if self._mirror_policy is not None:
                f = jax.checkpoint(f, policy=self._mirror_policy)
            outs, vjp, new_aux = jax.vjp(f, params, has_aux=True)
            cot = tuple(jnp.ones_like(o) for o in outs)
            (grads,) = vjp(cot)
            with jax.named_scope("optimizer"):
                new_params, new_momenta = opt_update(params, grads, momenta,
                                                     lr)
            aux_out = dict(zip(self.aux_names, new_aux))
            return new_params, new_momenta, aux_out, outs

        # lr is a traced scalar argument, so schedules (set_lr) take effect
        # without recompiling the step program
        self._step = jax.jit(step, donate_argnums=(0, 1, 2))

        def multi_step(params, momenta, aux, batch, rng, lr, nsteps):
            """nsteps fused train steps in ONE XLA program (lax.scan), so
            dispatch/host latency is paid once per call instead of per step.
            `batch` leaves either have a leading (nsteps, ...) axis (fresh
            data each step) or are a single step's batch reused every step."""
            stacked = {
                n: v.ndim > len(shape_of.get(n, v.shape)) for n, v in batch.items()
            }

            def body(carry, i):
                params, momenta, aux = carry
                b = {n: (v[i] if stacked[n] else v) for n, v in batch.items()}
                rng_i = jax.random.fold_in(rng, i)

                def f(p):
                    args = [
                        cast_arg(n, b[n] if n in b else p[n])
                        for n in self.arg_names
                    ]
                    aux_list = [aux[n] for n in self.aux_names]
                    outs, new_aux = graph_fn(args, aux_list, rng_i, True)
                    return outs, new_aux

                if self._mirror_policy is not None:
                    f = jax.checkpoint(f, policy=self._mirror_policy)
                outs, vjp, new_aux = jax.vjp(f, params, has_aux=True)
                cot = tuple(jnp.ones_like(o) for o in outs)
                (grads,) = vjp(cot)
                with jax.named_scope("optimizer"):
                    new_params, new_momenta = opt_update(params, grads,
                                                         momenta, lr)
                aux_out = dict(zip(self.aux_names, new_aux))
                return (new_params, new_momenta, aux_out), ()

            # unroll=2 measured best for the ResNet bench
            # (docs/mfu_roofline.md); MXNET_MULTISTEP_UNROLL overrides for
            # workloads where the doubled loop body hurts scheduling
            unroll = int(os.environ.get("MXNET_MULTISTEP_UNROLL", "2"))
            (params, momenta, aux), _ = jax.lax.scan(
                body, (params, momenta, aux), jnp.arange(nsteps),
                unroll=max(unroll, 1))
            return params, momenta, aux

        self._multi_step = jax.jit(multi_step, donate_argnums=(0, 1, 2),
                                   static_argnums=(6,))

        def fwd(params, aux, batch, rng):
            args = [cast_arg(n, batch[n] if n in batch else params[n])
                    for n in self.arg_names]
            outs, _ = graph_fn(args, [aux[n] for n in self.aux_names],
                               rng, False)
            return outs

        self._fwd = jax.jit(fwd)
        self._nstep = 0

    def lower_step(self, batch_dtypes=None):
        """AOT-lower and compile the fused single-step program against
        this trainer's mesh WITHOUT touching a device (requires
        ``abstract=True``; the mesh may be built from
        `jax.experimental.topologies` abstract devices).  Returns the
        jax ``Compiled`` — `.as_text()` is the optimized target HLO and
        `.cost_analysis()` the compiler's own FLOP/byte model, which is
        how the perf campaign attributes traffic without a chip run.
        ``batch_dtypes`` overrides per-input dtypes (token ids
        are int32; default float32)."""
        if not self.abstract:
            raise MXNetError("lower_step needs SPMDTrainer(abstract=True)")
        batch_dtypes = batch_dtypes or {}
        batch = {
            n: jax.ShapeDtypeStruct(
                tuple(self._shape_of[n]),
                np.dtype(batch_dtypes.get(n, np.float32)),
                sharding=self._batch_sharding)
            for n in self.data_names
        }
        repl = NamedSharding(self.mesh, P())
        rng = jax.ShapeDtypeStruct((2,), np.uint32, sharding=repl)
        lr = jax.ShapeDtypeStruct((), np.float32, sharding=repl)
        return self._step.lower(self.params, self.momenta, self.aux,
                                batch, rng, lr).compile()

    def shard_batch(self, batch):
        """Host numpy/NDArray dict -> device arrays laid out over the data
        axis (the SPMD replacement for per-GPU slice copies)."""
        out = {}
        for n, v in batch.items():
            arr = v.data if isinstance(v, NDArray) else jnp.asarray(v)
            stacked = (n in self._shape_of
                       and arr.ndim > len(self._shape_of[n]))
            out[n] = _put_global(
                arr, self._stacked_sharding if stacked
                else self._batch_sharding)
        return out

    def set_lr(self, lr):
        """Change the learning rate (no recompile: lr is a traced scalar).
        Drive from an `lr_scheduler.FactorScheduler` etc. per epoch."""
        self.lr = float(lr)

    def _watch_retrace(self, site, dev_batch):
        """Feed the retrace watchdog this step's jit-cache key (shapes/
        dtypes of the batch leaves — params/momenta/aux are donated and
        never change shape).  A steady-state loop with the sharded CE
        head must show ZERO retraces here; the nightly gates on it."""
        from .. import telemetry

        if not telemetry.retrace_enabled():
            return
        names = sorted(dev_batch)
        sig = telemetry.arrays_signature([dev_batch[n] for n in names],
                                         names)
        telemetry.watch_jit(site, sig,
                            scope=telemetry.watch_scope(self.symbol))

    def step(self, batch):
        """One fused train step.  Returns the graph outputs.  In a
        profiler trace the step is a `train_step` span (with its number)
        on the host's line."""
        self._nstep += 1
        with jax.profiler.StepTraceAnnotation("train_step",
                                              step_num=self._nstep):
            rng = jax.random.fold_in(self._base_key, self._nstep)
            dev_batch = self.shard_batch(batch)
            self._watch_retrace("trainer.step", dev_batch)
            self.params, self.momenta, self.aux, outs = self._step(
                self.params, self.momenta, self.aux, dev_batch,
                rng, jnp.float32(self.lr)
            )
        return outs

    def run_steps(self, batch, nsteps):
        """nsteps fused steps in one dispatch (see multi_step).  `batch`
        leaves may carry a leading (nsteps, ...) axis for per-step data."""
        self._nstep += nsteps
        rng = jax.random.fold_in(self._base_key, self._nstep)
        self.params, self.momenta, self.aux = self._multi_step(
            self.params, self.momenta, self.aux, self.shard_batch(batch),
            rng, jnp.float32(self.lr), nsteps)

    def forward(self, batch):
        rng = jax.random.fold_in(self._base_key, 0)
        dev = self.shard_batch(batch)
        for n in self.data_names:  # labels are inert at inference
            if n not in dev:
                dev[n] = jax.device_put(
                    jnp.zeros(self._shape_of[n], jnp.float32),
                    self._batch_sharding)
        return self._fwd(self.params, self.aux, dev, rng)

    def get_params(self):
        """Host NDArray dicts (checkpoint path)."""
        arg = {n: NDArray(np.asarray(v)) for n, v in self.params.items()}
        aux = {n: NDArray(np.asarray(v)) for n, v in self.aux.items()}
        return arg, aux
