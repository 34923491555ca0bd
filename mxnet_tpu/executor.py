"""Executor: compiled forward/backward over a Symbol graph.

Reference: `include/mxnet/symbolic.h:316-384` (`Executor::Bind/Forward/
Backward/outputs`), `src/symbol/graph_executor.{h,cc}`, Python wrapper
`python/mxnet/executor.py`.

TPU-first redesign — what `GraphExecutor::Init` did once at Bind
(`graph_executor.cc:927-939`: backward pass, placement, memory planning,
cached engine ops) becomes: trace the DAG into a pure function and let XLA
compile it.

* Forward = one jitted call.  Training forward runs under `jax.vjp`, so the
  linearization residuals are produced by the same compiled program — the
  analogue of the reference pre-planning backward at bind time
  (`MakeBackwardPass`, `static_graph.cc:411-530`).
* Backward = the vjp function: XLA's autodiff replaces the explicit backward
  nodes, gradient-sum aggregation (`CreateGradSumNode`) and
  `DeclareBackwardDependency` pruning.
* `grad_req` keeps reference semantics: 'write' overwrites the bound grad
  array, 'add' accumulates (`kAddTo`), 'null' skips (`operator.h:23-36`).
* Memory: XLA's buffer assignment subsumes `GraphStorageAllocator`
  (inplace/colored reuse, `graph_memory_allocator.cc`); donation of input
  buffers gives the in-place update ceiling.
* Monitor callback (`symbolic.h:379-383`): eager interpretation path that
  walks the same DAG un-jitted and reports every internal entry.
"""
from __future__ import annotations

import os
import threading
import weakref

import numpy as np

import jax
import jax.numpy as jnp

from . import profiler
from . import random as _random
from . import telemetry
from .base import MXNetError, silence_cpu_donation_warning
from .context import Context
from .ndarray import NDArray
from .ops.registry import OpCtx
from .symbol import Symbol, _topo_order


def _mirror_segments(order):
    """Plan the MXNET_BACKWARD_MIRROR_STEP rematerialization regions.

    The reference keeps every MIRROR_STEP-th eligible node as a checkpoint
    boundary and recomputes the nodes in between during backward
    (`static_graph.cc:423-438`).  The XLA form of the same trade: group
    consecutive graph nodes into segments of ``step`` ops, wrap each
    segment in `jax.checkpoint` — segment boundaries are stored across
    fwd->bwd, interiors are recomputed (sqrt-checkpointing over the Symbol
    graph; for a transformer, step ≈ nodes-per-block gives per-layer
    remat).

    Per-node overrides via the reference's `force_mirroring` attr:
    ``"0"``/``"False"`` pins the node as a boundary (its outputs always
    stored); anything truthy keeps it inside a remat segment even where
    the step count would cut one.

    ``MXNET_BACKWARD_MIRROR_STEP=block`` segments on transformer-block
    NAME boundaries instead of a count: every run of ops whose names share
    a ``layer<k>_`` prefix becomes one remat segment (exactly per-layer
    remat for `models/transformer.py`, the bwd residual-stream fusion
    lever from the round-6 roofline), ops outside any layer prefix
    (embed, head, final LN) stay stored boundaries.  Per-node
    `force_mirroring` attrs are a count-mode feature and are ignored in
    block mode.

    Returns None when MXNET_BACKWARD_MIRROR_STEP is unset (or block mode
    finds no layer-prefixed nodes), else a list of (nodes, remat) runs
    covering `order` in topo sequence.
    """
    step_env = os.environ.get("MXNET_BACKWARD_MIRROR_STEP", "")
    if not step_env:
        return None
    if step_env.lower() == "block":
        import re

        groups = []  # (layer tag or None, [nodes])
        for node in order:
            if node.is_variable:
                continue
            m = re.match(r"(layer\d+)_", node.name or "")
            tag = m.group(1) if m else None
            if groups and groups[-1][0] == tag:
                groups[-1][1].append(node)
            else:
                groups.append((tag, [node]))
        if not any(tag is not None for tag, _ in groups):
            return None  # not a layer-structured graph: no-op
        return [(nodes, tag is not None) for tag, nodes in groups]
    step = max(int(step_env), 1)

    def boundary_attr(node):
        v = (node.attrs or {}).get("force_mirroring")
        if v is None:
            return None
        return str(v).lower() in ("0", "false")

    segments = []
    run, count = [], 0
    for node in order:
        if node.is_variable:
            # variables bind args straight from the caller — they carry no
            # compute and no op in the graph depends on being *inside* a
            # segment with them, so they must NOT cut op runs (each weight
            # variable precedes its op in topo order; flushing here would
            # cap every segment at ~1 op and nullify the memory trade)
            continue
        forced_boundary = boundary_attr(node)
        if forced_boundary:
            if run:
                segments.append((run, True))
                run, count = [], 0
            segments.append(([node], False))
            continue
        run.append(node)
        count += 1
        if count >= step and forced_boundary is None:
            segments.append((run, True))
            run, count = [], 0
    if run:
        segments.append((run, True))
    return segments


def _build_graph_fn(symbol: Symbol):
    """Trace plan: returns fn(arg_arrays, aux_arrays, rng, is_train) ->
    (outputs, new_aux).  Pure — jit/vjp/pjit compose over it.

    When MXNET_BACKWARD_MIRROR_STEP is set, node runs execute inside
    `jax.checkpoint` segments (see `_mirror_segments`)."""
    heads = symbol._heads
    order = _topo_order(heads)
    arg_names = symbol.list_arguments()
    arg_index = {n: i for i, n in enumerate(arg_names)}
    # aux slots per node, in the same global order as list_auxiliary_states()
    aux_slots = {}
    n_aux = 0
    for node in order:
        if not node.is_variable:
            k = len(node.op.list_aux(node.params))
            if k:
                aux_slots[id(node)] = (n_aux, n_aux + k)
                n_aux += k
    seq_of = {id(node): seq for seq, node in enumerate(order)}
    segments = _mirror_segments(order)

    def _run_nodes(nodes, env, new_aux, rng, is_train):
        for node in nodes:
            if node.is_variable:
                continue
            inputs = [env[(id(s), i)] for s, i in node.inputs]
            lo, hi = aux_slots.get(id(node), (0, 0))
            aux_in = new_aux[lo:hi]
            key = (
                jax.random.fold_in(rng, seq_of[id(node)])
                if getattr(node.op, "need_rng", False) and rng is not None
                else None
            )
            octx = OpCtx(is_train=is_train, rng=key)
            # the node's name on its operations, forward and backward
            # (`transpose(jvp(<node>))`), in HLO metadata and so in a
            # profiler trace's `tf_op`
            with jax.named_scope(node.name):
                outs, aux_up = node.op.apply(octx, node.params, inputs,
                                             aux_in)
            for i, o in enumerate(outs):
                env[(id(node), i)] = o
            for i, u in enumerate(aux_up):
                if u is not None:
                    new_aux[lo + i] = u

    def _plain_fn(arg_arrays, aux_arrays, rng, is_train):
        env = {}
        new_aux = list(aux_arrays)
        for node in order:
            if node.is_variable:
                env[(id(node), 0)] = arg_arrays[arg_index[node.name]]
            else:
                _run_nodes([node], env, new_aux, rng, is_train)
        outputs = tuple(env[(id(n), i)] for n, i in heads)
        return outputs, tuple(new_aux)

    if segments is None:
        fn = _plain_fn
    else:
        # static plan per segment: which env entries flow in (produced
        # before) and out (consumed after, or graph heads)
        head_keys = {(id(n), i) for n, i in heads}
        plans = []
        for nodes, remat in segments:
            in_keys = []
            local = set()
            for node in nodes:
                for s, i in node.inputs:
                    k = (id(s), i)
                    if k not in local and k not in in_keys:
                        in_keys.append(k)
                for i in range(len(node.op.list_outputs(node.params))):
                    local.add((id(node), i))
            plans.append((nodes, remat, in_keys, sorted(local)))
        # entries needed after each segment: consumed by later segments or
        # heads — only those are segment outputs (the checkpoint boundary)
        needed_later = [set() for _ in plans]
        running = set(head_keys)
        for idx in range(len(plans) - 1, -1, -1):
            nodes, _, in_keys, local = plans[idx]
            needed_later[idx] = {k for k in local if k in running}
            running |= set(in_keys)
        segment_plans = [
            (nodes, remat, in_keys, sorted(needed_later[idx]))
            for idx, (nodes, remat, in_keys, _) in enumerate(plans)
        ]

        def _seg_fn(arg_arrays, aux_arrays, rng, is_train):
            # variables bind upfront: no op runs before its inputs exist
            # in env, and variables never depend on ops
            env = {(id(node), 0): arg_arrays[arg_index[node.name]]
                   for node in order if node.is_variable}
            new_aux = list(aux_arrays)
            for nodes, remat, in_keys, out_keys in segment_plans:
                aux_ranges = [aux_slots[id(n)] for n in nodes
                              if id(n) in aux_slots]
                if not remat or not is_train:
                    _run_nodes(nodes, env, new_aux, rng, is_train)
                    continue

                def seg(in_vals, aux_vals, nodes=nodes, in_keys=in_keys,
                        out_keys=out_keys, aux_ranges=aux_ranges):
                    local_env = dict(zip(in_keys, in_vals))
                    local_aux = [None] * len(new_aux)  # only own slots used
                    for (lo, hi), vals in zip(aux_ranges, aux_vals):
                        local_aux[lo:hi] = vals
                    _run_nodes(nodes, local_env, local_aux, rng, is_train)
                    return ([local_env[k] for k in out_keys],
                            [local_aux[lo:hi] for lo, hi in aux_ranges])

                seg = jax.checkpoint(
                    seg, policy=jax.checkpoint_policies.nothing_saveable)
                outs, aux_outs = seg(
                    [env[k] for k in in_keys],
                    [new_aux[lo:hi] for lo, hi in aux_ranges])
                env.update(zip(out_keys, outs))
                for (lo, hi), vals in zip(aux_ranges, aux_outs):
                    new_aux[lo:hi] = vals
            outputs = tuple(env[(id(n), i)] for n, i in heads)
            return outputs, tuple(new_aux)

        fn = _seg_fn

    internal_entries = []
    for node in order:
        if node.is_variable:
            internal_entries.append((node.name, (id(node), 0)))
        else:
            for i, oname in enumerate(node.op.list_outputs(node.params)):
                internal_entries.append(("%s_%s" % (node.name, oname), (id(node), i)))

    def _walk_fn(arg_arrays, aux_arrays, rng, is_train):
        """Plain-walk variant exposing the full env — traceable, so the
        in-graph Monitor mode can jit one program that returns outputs,
        new aux AND per-entry stats (always un-segmented: a monitored
        step wants every internal entry live, which defeats remat
        anyway, exactly like the eager monitored path)."""
        env = {}
        new_aux = list(aux_arrays)
        for node in order:
            if node.is_variable:
                env[(id(node), 0)] = arg_arrays[arg_index[node.name]]
            else:
                _run_nodes([node], env, new_aux, rng, is_train)
        outputs = tuple(env[(id(n), i)] for n, i in heads)
        return outputs, tuple(new_aux), env

    return fn, order, internal_entries, _walk_fn


def _mirror_saveable(prim, *_, **__):
    """jax.checkpoint policy for MXNET_BACKWARD_DO_MIRROR: save MXU-heavy
    primitive results, rematerialize the rest (the reference's rule that
    Convolution/FullyConnected are never mirrored, `static_graph.cc:423-438`)."""
    return prim.name in ("dot_general", "conv_general_dilated")


def _mirror_policy():
    """Whole-graph rematerialization policy from the environment.

    The reference's mirroring plan is tunable per run and per node
    (`MXNET_BACKWARD_DO_MIRROR`, `MXNET_BACKWARD_MIRROR_STEP`, node attr
    `force_mirroring`; `static_graph.cc:410-560`).  The XLA counterpart is
    a `jax.checkpoint` policy choosing which fwd values survive to bwd:

    MXNET_BACKWARD_MIRROR_POLICY =
      ``dots``    save dot/conv results, remat elementwise/BN (the
                  round-2 MXNET_BACKWARD_DO_MIRROR=1 behavior; right for
                  conv nets, wrong for transformers where dot results are
                  most activations)
      ``attn``    save only attention-op outputs (`checkpoint_name` tag
                  "attn_out"), remat projections/FFN/LN — the transformer
                  memory policy
      ``streams`` save attention outputs AND activation-fn outputs
                  (tags "attn_out"/"act_out"): the round-6 bwd
                  residual-stream fusion — the LN/projection/gelu-input
                  streams the roofline flagged as re-read in backward are
                  recomputed from the two anchors instead of stored, at
                  +1 cheap VPU pass each (the FFN up-projection, the one
                  MXU-heavy input, stays anchored by "act_out")
      ``nothing`` save nothing inside the step, recompute the whole
                  forward in backward

    MXNET_BACKWARD_DO_MIRROR=1 with no POLICY keeps meaning ``dots``.
    Returns a jax.checkpoint policy or None (XLA's default).  Segment
    (step-k / per-block) remat is separate — see `_mirror_segments`.
    """
    pol = os.environ.get("MXNET_BACKWARD_MIRROR_POLICY", "").lower()
    if pol == "none":
        return None  # explicit 'none' wins over MXNET_BACKWARD_DO_MIRROR
    if not pol:
        if os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0").lower() in (
                "1", "true", "yes"):
            pol = "dots"
        else:
            return None
    if pol == "dots":
        return _mirror_saveable
    if pol in ("attn", "attn_out"):
        return jax.checkpoint_policies.save_only_these_names("attn_out")
    if pol == "streams":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "act_out")
    if pol == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    raise MXNetError(
        "MXNET_BACKWARD_MIRROR_POLICY must be one of none/dots/attn/"
        "streams/nothing, got %r" % pol)


class AotCache:
    """Keyed store of AOT-compiled executables (jit(...).lower().compile())
    with telemetry hit/compile accounting.

    The Predictor compiles one executable per instance; the serving engine
    (mxnet_tpu/serving) compiles one per (batch, seq) bucket and MUST hit
    this cache for every steady-state call — `<name>.compiles` advancing
    after warmup is the same signal the retrace watchdog diagnoses, made
    countable.  Thread-safe: replica engines build caches from worker
    threads.

    The cache object outlives any single engine: a respawned serving
    replica is constructed WITH its dead incarnation's AotCache (compiled
    executables are immutable — a failed call only consumes the donated
    buffers it was passed), so recovery warmup is pure hits and the
    zero-recompile invariant survives failover.  `compiles` exposes the
    local build count for exactly that gate."""

    def __init__(self, name="aot", signature=()):
        self._name = name
        self._cache = {}
        self._lock = threading.Lock()
        self._compiles = 0
        self._frozen = False
        # every key is scoped by this tuple (a sub-mesh serving replica
        # passes its mesh signature): executables partitioned for one
        # mesh shape are wrong — not just slow — on another, so two
        # engines with different signatures sharing this cache can
        # never alias each other's entries
        self._signature = tuple(signature or ())

    @property
    def signature(self):
        return self._signature

    def _scoped(self, key):
        return (key + self._signature) if self._signature else key

    @property
    def compiles(self):
        """Executables built BY this cache (== telemetry `<name>.compiles`
        when one cache owns the name).  The respawn path snapshots it
        around the replacement replica's warmup to assert recovery
        compiled nothing."""
        with self._lock:
            return self._compiles

    def get(self, key, build=None):
        """The executable for `key`, building (and counting a compile) via
        `build()` on first use.  `build=None` probes without compiling."""
        key = self._scoped(key)
        with self._lock:
            ent = self._cache.get(key)
        if ent is not None:
            telemetry.inc("%s.hits" % self._name)
            return ent
        if build is None:
            return None
        ent = build()
        with self._lock:
            winner = self._cache.setdefault(key, ent)
            frozen_miss = self._frozen and winner is ent
            if winner is ent:
                self._compiles += 1
        # two threads can race build() for the same key; only the insert
        # that won counts as a compile, so `<name>.compiles` stays exactly
        # the number of cached executables (the zero-recompile gates
        # compare against it)
        telemetry.inc("%s.compiles" % self._name
                      if winner is ent else "%s.hits" % self._name)
        if frozen_miss:
            # the declared-complete set grew: same bug class the retrace
            # watchdog diagnoses, made structural.  The compile still
            # proceeds (refusing would escalate a bucketing bug into an
            # engine death) but the gates fail loudly on the counter.
            telemetry.inc("%s.frozen_compiles" % self._name)
            telemetry.record_event("aot_frozen_compile", cache=self._name,
                                   key=str(key)[:200])
        return winner

    def keys(self):
        """Snapshot of the cached executable keys (introspection: the
        serving tests assert the warmup bucket set — e.g. that the
        speculative verify/draft shapes joined it before `freeze`)."""
        with self._lock:
            return sorted(self._cache)

    def freeze(self):
        """Declare the compiled set complete (the serving engine calls
        this after `warmup()`): any later build is counted in
        `<name>.frozen_compiles` and recorded as an `aot_frozen_compile`
        event — the steady-state "compiles nothing" assertion gets a
        witness at the cache itself, independent of the watchdog's
        signature tracking.  Idempotent; hits are unaffected."""
        with self._lock:
            self._frozen = True

    @property
    def frozen(self):
        with self._lock:
            return self._frozen

    def keys(self):
        with self._lock:
            return list(self._cache)

    def __len__(self):
        with self._lock:
            return len(self._cache)


def _as_list(arrays, names, what, allow_missing=False):
    if arrays is None:
        return None
    if isinstance(arrays, dict):
        missing = [n for n in names if n not in arrays]
        if missing and not allow_missing:
            raise MXNetError("%s missing entries for %s" % (what, missing))
        return [arrays.get(n) for n in names]
    arrays = list(arrays)
    if len(arrays) != len(names):
        raise MXNetError(
            "%s: expected %d arrays (%s), got %d"
            % (what, len(names), names, len(arrays))
        )
    return arrays


class _LazyOutputs:
    """List-like view of a pending training forward's outputs.  Accessing it
    materializes the forward; training loops that go forward→backward→metric
    never pay for a separate forward pass."""

    def __init__(self, exe):
        self._exe = exe

    def _mat(self):
        return self._exe.outputs

    def __len__(self):
        return len(self._mat())

    def __iter__(self):
        return iter(self._mat())

    def __getitem__(self, i):
        return self._mat()[i]


class Executor:
    """Bound computation (one Symbol + argument/gradient/aux arrays)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None):
        self._symbol = symbol
        self._ctx = Context(ctx) if ctx is not None else None
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()
        self.arg_arrays = _as_list(args, self._arg_names, "args")
        # a dict args_grad may omit entries: those args get no gradient,
        # like the reference's bind (grad_req forced to null below)
        self.grad_arrays = _as_list(args_grad, self._arg_names, "args_grad",
                                    allow_missing=isinstance(args_grad, dict))
        self.aux_arrays = _as_list(aux_states, self._aux_names, "aux_states") or []
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, dict):
            self._grad_req = {n: grad_req.get(n, "null") for n in self._arg_names}
        else:
            self._grad_req = dict(zip(self._arg_names, grad_req))
        if self.grad_arrays is not None:
            for n, g in zip(self._arg_names, self.grad_arrays):
                if g is None:
                    self._grad_req[n] = "null"
        # group2ctx (model-parallel ctx_group placement) is honored by the
        # sharded executor in parallel/; single-program binds run on ctx and
        # rely on XLA fusion. Recorded for introspection.
        self._group2ctx = group2ctx or {}

        fn, self._order, self._internal_entries, self._walk_fn = \
            _build_graph_fn(symbol)
        self._fn = fn
        self._jit_eval = jax.jit(lambda a, x, r: fn(a, x, r, False))
        self._jit_train = jax.jit(lambda a, x, r: fn(a, x, r, True))

        # Fused forward+backward program, compiled ONCE per executor: the
        # analogue of GraphExecutor pre-creating cached engine ops at Bind
        # (`graph_executor.cc:769-806`).  jax.vjp re-traces per call, so the
        # vjp is taken *inside* jit where it is traced once and cached; XLA
        # then shares activations between fwd and bwd in one program.
        #
        # MXNET_BACKWARD_DO_MIRROR (read at bind time, like the reference's
        # `static_graph.cc:410-560` mirroring plan): recompute cheap
        # activations in backward instead of storing them.  The reference
        # excludes Convolution/FullyConnected/BatchNorm outputs from
        # mirroring (`static_graph.cc:423-438`); the jax.checkpoint policy
        # below is the same trade — MXU-heavy primitive results are saved,
        # everything else is rematerialized.
        mirror_policy = _mirror_policy()

        def train_step(args, aux, rng, cots):
            f = lambda a: fn(a, aux, rng, True)
            if mirror_policy is not None:
                f = jax.checkpoint(f, policy=mirror_policy)
            outs, vjp_fn, new_aux = jax.vjp(f, args, has_aux=True)
            (grads,) = vjp_fn(cots)
            return outs, new_aux, grads

        self._train_step_fn = train_step  # un-jitted, for profiler.plan
        # on-device metric accumulation (set_step_stat_fn): stats ride the
        # SAME fused fwd+bwd program as extra outputs with a donated
        # device-resident carry — zero extra dispatches per step, one
        # blocking fetch per MXNET_METRIC_INTERVAL (pop_step_stats)
        self._step_stat_fn = None
        self._step_stat_n = 0
        self._stats_acc = None
        self._jit_stats = None   # (donate_program, keep_program)
        # The pending (aux, cot) buffers are DONATED: aux is rebound to the
        # returned new_aux right after the call and the default cotangents
        # are created per-call, so neither outlives the step.  The bound
        # args canNOT be donated here — the weights must survive the step
        # for the (separate) optimizer update; the path that donates them
        # is parallel.SPMDTrainer, whose step owns the update too.  A
        # non-donating variant serves user-supplied out_grads, whose
        # buffers the caller may reuse.
        silence_cpu_donation_warning()
        self._jit_train_step = jax.jit(train_step, donate_argnums=(1, 3))
        self._jit_train_step_keep = jax.jit(train_step)
        self._base_key = _random.next_key()
        self._step = 0
        self._pending = None  # (args, aux, rng) snapshot for lazy train fwd
        self._outputs = None
        self._monitor_cb = None
        self._monitor_mode = "eager"
        self._monitor_stat_fn = None
        self._monitor_active_fn = None
        self._mon_jits = {}  # is_train -> jitted monitored program
        self._device = self._ctx.jax_device() if self._ctx is not None else None
        # NDArrays verified resident on self._device: `_set_data` preserves
        # device placement, so one check per bound array suffices instead of
        # re-checking every array every step.  Keyed id -> weakref, with the
        # weakref target compared by identity on lookup: a dead or retargeted
        # weakref means CPython recycled the id for a different array, which
        # must be re-verified rather than trusted
        self._placed_refs = {}

    # -- dict views (python/mxnet/executor.py) -----------------------------
    @property
    def arg_dict(self):
        return dict(zip(self._arg_names, self.arg_arrays))

    @property
    def grad_dict(self):
        if self.grad_arrays is None:
            return {}
        return dict(zip(self._arg_names, self.grad_arrays))

    @property
    def aux_dict(self):
        return dict(zip(self._aux_names, self.aux_arrays))

    @property
    def outputs(self):
        """Outputs of the most recent forward (async handles, like the
        reference's `Executor::outputs` NDArrays)."""
        if self._outputs is None:
            if self._pending is not None:
                args, aux, rng = self._pending_live()
                self._watch_retrace("executor.forward[train]", args, aux)
                outs, new_aux = self._jit_train(args, aux, rng)
                profiler.record_dispatch("executor.forward")
                for nd, arr in zip(self.aux_arrays, new_aux):
                    nd._set_data(arr)
                self._outputs = [NDArray(o) for o in outs]
            else:
                raise MXNetError("call forward() first")
        return self._outputs

    def set_monitor_callback(self, callback, mode="eager", stat_fn=None,
                             active_fn=None):
        """Install a per-output monitor hook.

        mode='eager' (reference semantics): the next forward re-runs the
        graph un-jitted and calls ``callback(name, NDArray)`` per internal
        entry — O(n_outputs) python op dispatches plus whatever host
        fetches the callback's stat function performs.

        mode='ingraph': the stats are computed INSIDE one jitted program
        (``stat_fn``, a traceable array->scalar function; default
        |x|.sum()/size like the reference Monitor) and fetched as a single
        bundle — O(1) dispatches and ONE host transfer per monitored
        step; ``callback(name, float)`` receives the finished stat.

        ``active_fn`` (ingraph mode): zero-arg predicate consulted each
        forward — False skips the monitored program entirely, so a
        Monitor with interval N pays the stats program on 1-in-N steps,
        not every step."""
        if mode not in ("eager", "ingraph"):
            raise MXNetError("monitor mode must be 'eager' or 'ingraph', "
                             "got %r" % mode)
        self._monitor_cb = callback
        self._monitor_mode = mode
        self._monitor_active_fn = active_fn
        if stat_fn is not self._monitor_stat_fn:
            self._monitor_stat_fn = stat_fn
            self._mon_jits = {}

    # -- execution ---------------------------------------------------------
    def _gather(self, arrays):
        """Raw jax arrays of the bound NDArrays, resident on this
        executor's device.

        Device placement is verified ONCE per bound root array (cached in
        `_placed_refs`): `_set_data` keeps the old buffer's device on every
        write, so an array placed at first gather stays placed for the
        executor's lifetime.  Misplaced roots are moved and pinned; views
        read through their parent and are re-checked each time."""
        out = []
        placed = self._placed_refs
        for nd in arrays:
            if isinstance(nd, NDArray):
                ref = placed.get(id(nd))
                if ref is not None and ref() is nd:
                    out.append(nd.data)
                    continue
                arr = nd.data
                if self._device is not None and \
                        getattr(arr, "device", None) != self._device:
                    arr = jax.device_put(arr, self._device)
                    profiler.record_dispatch("executor.gather",
                                             kind="transfer")
                    if nd._parent is None:
                        nd._data = arr  # pin: future _set_data keeps device
                if nd._parent is None:
                    placed[id(nd)] = weakref.ref(nd)
                out.append(arr)
            else:
                out.append(jnp.asarray(nd))
        return out

    def _pending_live(self):
        """The `_pending` snapshot with donated buffers refreshed.

        The snapshot holds the raw weight/aux buffers gathered at
        forward(); a fused optimizer update between forward() and
        backward()/outputs donates the bound weights into `update_multi`,
        deleting those buffers.  Feeding them back to XLA is a crash, so a
        stale snapshot is re-gathered from the bound NDArrays — the replay
        then computes with the post-update values, i.e. the same
        recompute-with-current-weights semantics the eager `outputs` path
        has always had."""
        args, aux, rng = self._pending

        def stale(arrs):
            return any(getattr(a, "is_deleted", None) is not None
                       and a.is_deleted() for a in arrs)

        if stale(args) or stale(aux):
            args = self._gather(self.arg_arrays)
            aux = self._gather(self.aux_arrays)
            self._pending = (args, aux, rng)
        return args, aux, rng

    def forward(self, is_train=False, **kwargs):
        """Run forward.  kwargs copy new values into bound args by name,
        like `executor.py` forward(data=...)."""
        for k, v in kwargs.items():
            if k not in self._arg_names:
                raise MXNetError("forward: unknown argument %r" % k)
            dst = self.arg_arrays[self._arg_names.index(k)]
            if isinstance(v, NDArray):
                v.copyto(dst)
            else:
                dst[:] = v

        args = self._gather(self.arg_arrays)
        aux = self._gather(self.aux_arrays)
        self._step += 1
        rng = jax.random.fold_in(self._base_key, self._step)

        monitored = None
        if self._monitor_cb is not None:
            if self._monitor_mode == "ingraph":
                # interval gating: an inactive monitor (active_fn False)
                # costs nothing — the normal jit path below runs instead
                if self._monitor_active_fn is None \
                        or self._monitor_active_fn():
                    monitored = self._forward_monitored_ingraph(
                        args, aux, rng, is_train)
            else:
                self._forward_monitored(args, aux, rng, is_train)

        if is_train and self.grad_arrays is not None:
            # Lazy training forward: the actual compute happens in the fused
            # fwd+bwd program at backward() (training loops read outputs only
            # after backward, `model.py:244-245`).  Reading .outputs before
            # backward() triggers a separate forward (see outputs property).
            self._pending = (args, aux, rng)
            self._outputs = None
            return _LazyOutputs(self)
        if monitored is not None:
            # eval / non-lazy forward: the in-graph monitored program
            # already produced this step's outputs and aux — no second
            # forward dispatch.  (The lazy TRAINING path above cannot
            # reuse them: backward() recomputes in the fused fwd+bwd
            # program, so a monitored training step pays one extra
            # forward — still far cheaper than the eager monitor's O(n)
            # per-op python walk, and only on monitor-interval steps.)
            outs, new_aux = monitored
        else:
            self._watch_retrace("executor.forward[%s]"
                                % ("train" if is_train else "eval"),
                                args, aux)
            jit = self._jit_train if is_train else self._jit_eval
            outs, new_aux = jit(args, aux, rng)
            profiler.record_dispatch("executor.forward")
        self._pending = None
        if is_train:
            for nd, arr in zip(self.aux_arrays, new_aux):
                nd._set_data(arr)
        self._outputs = [NDArray(o) for o in outs]
        return self._outputs

    def _forward_monitored(self, args, aux, rng, is_train):
        """Eager interpretation for the monitor hook — reports every internal
        entry like `RunOps`'s per-op callback (`graph_executor.cc:835-849`)."""
        env_fn, order, entries = self._fn, self._order, self._internal_entries
        # re-run eagerly, capturing env by monkey-walking the same plan
        env = {}
        arg_index = {n: i for i, n in enumerate(self._arg_names)}
        aux_pos = 0
        aux_list = list(aux)
        seq = 0
        for node in order:
            if node.is_variable:
                env[(id(node), 0)] = args[arg_index[node.name]]
            else:
                inputs = [env[(id(s), i)] for s, i in node.inputs]
                k = len(node.op.list_aux(node.params))
                aux_in = aux_list[aux_pos:aux_pos + k]
                aux_pos += k
                key = (
                    jax.random.fold_in(rng, seq)
                    if getattr(node.op, "need_rng", False)
                    else None
                )
                with jax.named_scope(node.name):
                    outs, _ = node.op.apply(OpCtx(is_train, key),
                                            node.params, inputs, aux_in)
                for i, o in enumerate(outs):
                    env[(id(node), i)] = o
            seq += 1
        for name, key in entries:
            if key in env:
                self._monitor_cb(name, NDArray(env[key]))

    def _monitored_jit(self, is_train):
        """Jitted (outputs, new_aux, stats) program for the in-graph
        monitor mode: one dispatch computes every internal entry's stat
        alongside the normal forward."""
        fn = self._mon_jits.get(bool(is_train))
        if fn is None:
            stat = self._monitor_stat_fn
            if stat is None:
                def stat(x):  # reference Monitor's asum: |x|/size
                    xf = jnp.abs(x.astype(jnp.float32))
                    return jnp.sum(xf) / max(int(x.size), 1)
            entries = self._internal_entries
            walk = self._walk_fn

            def prog(args, aux, rng, _train=bool(is_train)):
                outs, new_aux, env = walk(args, aux, rng, _train)
                stats = jnp.stack(
                    [jnp.asarray(stat(env[k]), jnp.float32)
                     for _, k in entries])
                return outs, new_aux, stats

            fn = jax.jit(prog)
            self._mon_jits[bool(is_train)] = fn
        return fn

    def _forward_monitored_ingraph(self, args, aux, rng, is_train):
        """In-graph monitor: ONE jitted dispatch and ONE small host
        transfer for the whole stat bundle, vs the eager path's O(n)
        python op dispatches + O(n_outputs) blocking `asnumpy` fetches.
        Returns (outputs, new_aux) so the caller can reuse the forward."""
        fn = self._monitored_jit(is_train)
        self._watch_retrace("executor.forward_monitored[%s]"
                            % ("train" if is_train else "eval"), args, aux)
        outs, new_aux, stats = fn(args, aux, rng)
        profiler.record_dispatch("executor.forward_monitored")
        vals = np.asarray(stats)
        profiler.record_dispatch("executor.monitor_fetch", kind="transfer")
        cb = self._monitor_cb
        for (name, _), v in zip(self._internal_entries, vals):
            cb(name, float(v))
        return outs, new_aux

    def _watch_retrace(self, site, args, aux, cots=None, program=None):
        """Feed the retrace watchdog one jitted-call signature.  Scoped by
        the bound Symbol, so executors rebound at a new shape (reshape,
        bucketing) are recognized as recompiles of the SAME program while
        unrelated models stay independent."""
        if not telemetry.retrace_enabled():
            return
        sig = telemetry.arrays_signature(args, self._arg_names)
        sig += telemetry.arrays_signature(
            aux, ["aux:%s" % n for n in self._aux_names])
        if cots is not None:
            sig += telemetry.arrays_signature(
                cots, ["cot%d" % i for i in range(len(cots))])
        meta = {"program": program} if program else None
        telemetry.watch_jit(site, sig,
                            scope=telemetry.watch_scope(self._symbol),
                            meta=meta)

    def set_step_stat_fn(self, fn, n_stats=0):
        """Install (or clear, fn=None) a traceable per-step stat function
        ``fn(outputs, args) -> (n_stats,) float32`` that rides the fused
        fwd+bwd program as an extra output.  The program accumulates the
        vector into a donated device carry; nothing is fetched until
        `pop_step_stats` — the on-device metric path
        (docs/data_pipeline.md)."""
        self._step_stat_fn = fn
        self._step_stat_n = int(n_stats) if fn is not None else 0
        self._stats_acc = None
        self._jit_stats = None

    def pop_step_stats(self):
        """The accumulated stat carry (a device array — the caller owns
        the blocking fetch), resetting the accumulator.  None when nothing
        accumulated since the last pop."""
        acc, self._stats_acc = self._stats_acc, None
        return acc

    def _stats_programs(self):
        if self._jit_stats is None:
            base = self._train_step_fn
            stat_fn = self._step_stat_fn

            def train_step_stats(args, aux, rng, cots, acc):
                outs, new_aux, grads = base(args, aux, rng, cots)
                stats = jnp.asarray(stat_fn(outs, args), jnp.float32)
                return outs, new_aux, grads, acc + stats

            silence_cpu_donation_warning()
            self._jit_stats = (
                jax.jit(train_step_stats, donate_argnums=(1, 3, 4)),
                jax.jit(train_step_stats, donate_argnums=(4,)),
            )
        return self._jit_stats

    def _stats_carry(self):
        acc = self._stats_acc
        if acc is None:
            acc = jnp.zeros((self._step_stat_n,), jnp.float32)
            if self._device is not None:
                acc = jax.device_put(acc, self._device)
        return acc

    def _out_avals(self, args, aux, rng):
        key = tuple((tuple(a.shape), str(a.dtype)) for a in args)
        if not hasattr(self, "_aval_cache"):
            self._aval_cache = {}
        if key not in self._aval_cache:
            outs, _ = jax.eval_shape(
                lambda a, x, r: self._fn(a, x, r, True), args, aux, rng
            )
            self._aval_cache[key] = outs
        return self._aval_cache[key]

    def backward(self, out_grads=None):
        """Compute gradients into the bound grad arrays via the fused
        fwd+bwd program.

        Like the reference, `backward()` with no head gradients is only
        meaningful when the outputs are loss layers — their custom vjp ignores
        the incoming cotangent (`softmax_output-inl.h` Backward)."""
        if self.grad_arrays is None:
            raise MXNetError("bind with args_grad to use backward()")
        if self._pending is None:
            raise MXNetError("call forward(is_train=True) before backward()")
        args, aux, rng = self._pending_live()
        with_stats = False
        if out_grads is None:
            avals = self._out_avals(args, aux, rng)
            cot = tuple(jnp.ones(o.shape, o.dtype) for o in avals)
            donate = True
            # donating the same buffer twice — aux states bound to one
            # shared array, or an aux aliasing a bound arg — is an XLA
            # error; such binds take the non-donating program (the same
            # guard update_multi applies to its weight/state donation)
            seen = set(map(id, args))
            for a in aux:
                if id(a) in seen:
                    donate = False
                    break
                seen.add(id(a))
            with_stats = self._step_stat_fn is not None
            if with_stats:
                progs = self._stats_programs()
                step = progs[0] if donate else progs[1]
            else:
                step = self._jit_train_step if donate \
                    else self._jit_train_step_keep
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            cot = tuple(
                g.data if isinstance(g, NDArray) else jnp.asarray(g)
                for g in out_grads
            )
            # user-supplied cotangent buffers must survive the call; the
            # stat carry does not ride this path (training loops never
            # pass out_grads — custom loops keep host metrics)
            donate = False
            step = self._jit_train_step_keep
        # retrace watchdog: the fused train step is THE per-step program —
        # a shape drift (ragged last batch, rebind) or a fall-off-donation
        # here is the classic silent throughput cliff
        self._watch_retrace(
            "executor.train_step", args, aux, cots=cot,
            program=("donate" if donate else "keep") +
                    ("+stats" if with_stats else ""))
        if with_stats:
            outs, new_aux, grads, self._stats_acc = step(
                args, aux, rng, cot, self._stats_carry())
        else:
            outs, new_aux, grads = step(args, aux, rng, cot)
        profiler.record_dispatch("executor.train_step")
        self._pending = None  # aux was donated: forbid replay on stale aux
        self._outputs = [NDArray(o) for o in outs]
        for nd, arr in zip(self.aux_arrays, new_aux):
            nd._set_data(arr)
        for name, nd, g in zip(self._arg_names, self.grad_arrays, grads):
            req = self._grad_req.get(name, "write")
            if req == "null" or nd is None:
                continue
            if req == "add":
                nd._set_data(nd.data + g)
            else:
                nd._set_data(g)

    def debug_str(self, mode="auto"):
        """Execution-plan dump (`GraphExecutor::Print`,
        `graph_executor.cc:853-886`): per-node op/shape table with an
        analytic FLOPs/HBM-bytes roofline plus XLA's cost and memory
        analysis of the compiled program.  See `profiler.plan` for the
        structured form."""
        from . import profiler

        return str(profiler.plan(self, mode=mode))

    def copy_params_from(self, arg_params, aux_params=None, allow_extra_params=False):
        """Copy parameters by name (`executor.py` copy_params_from).

        Both args and aux get a PRIVATE buffer copy (not `copyto`'s
        pointer share): the fused train step donates its aux inputs and
        `Optimizer.update_multi` donates the bound weights, so neither may
        alias the caller's param dicts.  The copies run once at bind/init
        time, not per step."""
        for name, array in arg_params.items():
            if name in self._arg_names:
                dst = self.arg_arrays[self._arg_names.index(name)]
                if array.shape != dst.shape:
                    raise MXNetError("copyto shape mismatch %s vs %s"
                                     % (array.shape, dst.shape))
                dst._set_data(jnp.array(array.data, dtype=dst.dtype))
            elif not allow_extra_params:
                raise MXNetError("unknown argument %r" % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self._aux_names:
                    dst = self.aux_arrays[self._aux_names.index(name)]
                    if array.shape != dst.shape:
                        raise MXNetError("copyto shape mismatch %s vs %s"
                                         % (array.shape, dst.shape))
                    dst._set_data(jnp.array(array.data, dtype=dst.dtype))
                elif not allow_extra_params:
                    raise MXNetError("unknown aux state %r" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to new shapes.  The reference rebinds
        sharing memory (`graph_executor.h:48-55`); with XLA the compile cache
        keys on shapes, so this simply re-binds (buffers are reallocated)."""
        from .ndarray import zeros

        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("reshape: cannot infer new shapes")
        new_args = [
            zeros(s, ctx=self._ctx, dtype=a.dtype)
            for s, a in zip(arg_shapes, self.arg_arrays)
        ]
        new_grads = None
        if self.grad_arrays is not None:
            # grads must match the arg dtype (a bf16 bind used to get f32
            # grads here) and keep per-arg None for grad_req='null' args
            new_grads = [
                zeros(s, ctx=self._ctx, dtype=a.dtype) if g is not None
                else None
                for s, a, g in zip(arg_shapes, self.arg_arrays,
                                   self.grad_arrays)
            ]
        new_aux = [zeros(s, ctx=self._ctx, dtype=x.dtype)
                   for s, x in zip(aux_shapes, self.aux_arrays)]
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self._grad_req, new_aux,
                        group2ctx=self._group2ctx)
