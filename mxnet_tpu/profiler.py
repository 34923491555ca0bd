"""Profiling / tracing hooks.

The reference had no profiler — observability was `Monitor` tensor stats,
`Speedometer` samples/sec and `GraphExecutor::Print` (SURVEY §5.1).  On TPU
the runtime exposes real tracing: these helpers wrap `jax.profiler` so
training loops get xprof traces (op timeline, HBM, MXU utilization —
viewable in TensorBoard/xprof) and device memory profiles with the same
one-liner ergonomics as the reference's Monitor.

    with mx.profiler.trace("/tmp/xprof"):
        trainer.step(batch)

    with mx.profiler.annotate("data-augment"):
        batch = augmenter(batch)

    mx.profiler.save_device_memory_profile("mem.prof")
"""
from __future__ import annotations

import contextlib
import logging
import time

import jax
import numpy as np

from . import telemetry
from .base import MXNetError

_active_logdir = None

# ---------------------------------------------------------------------------
# Dispatch-count observability: the per-step number of XLA program entries
# and host<->device transfers the framework issues.  The engine layer of the
# reference existed to hide per-op dispatch latency; here the fused legacy
# training path (`model._update_params` + `Optimizer.update_multi` +
# `KVStore` bucketing) is asserted O(1) dispatches per step in CPU-only
# tier-1 tests via this hook, instead of only showing up as TPU wall-clock.
#
# Instrumentation points are the framework's own XLA chokepoints (executor
# jit entries, optimizer updates, kvstore reduces, NDArray host transfers),
# not a JAX-internal trace — the counter measures what the framework
# dispatches, which is exactly the quantity the fusion work optimizes.
# ---------------------------------------------------------------------------

_dispatch = None  # active DispatchCounts, or None when not counting


class DispatchCounts:
    """Tally of framework-level dispatches inside a `count_dispatches()`
    window: `jit_entries` (XLA program invocations), `host_transfers`
    (device_put / device->host fetches), and a per-site breakdown."""

    __slots__ = ("jit_entries", "host_transfers", "by_site")

    def __init__(self):
        self.jit_entries = 0
        self.host_transfers = 0
        self.by_site = {}

    @property
    def total(self):
        return self.jit_entries + self.host_transfers

    def as_dict(self):
        return {"jit_entries": self.jit_entries,
                "host_transfers": self.host_transfers,
                "by_site": dict(self.by_site)}

    def __repr__(self):
        return ("DispatchCounts(jit_entries=%d, host_transfers=%d, by_site=%r)"
                % (self.jit_entries, self.host_transfers, self.by_site))


def record_dispatch(site, kind="jit"):
    """Count one framework dispatch.  kind: 'jit' for an XLA program
    entry, 'transfer' for a host<->device copy.  Feeds both the scoped
    `count_dispatches()` window (when active) and the process-wide
    telemetry registry (always, unless MXNET_TELEMETRY=0), so the per-step
    JSONL stream carries dispatch counts without a counting context."""
    telemetry.inc("dispatch.jit_entries" if kind == "jit"
                  else "dispatch.host_transfers")
    telemetry.inc("dispatch.site.%s" % site)
    st = _dispatch
    if st is None:
        return
    if kind == "jit":
        st.jit_entries += 1
    else:
        st.host_transfers += 1
    st.by_site[site] = st.by_site.get(site, 0) + 1


@contextlib.contextmanager
def count_dispatches():
    """Count framework dispatches inside the block.

        with mx.profiler.count_dispatches() as d:
            mod.forward(batch); mod.backward(); mod.update()
        assert d.jit_entries <= 4   # O(1) in n_params on the fused path
    """
    global _dispatch
    if _dispatch is not None:
        raise MXNetError("count_dispatches already active")
    _dispatch = DispatchCounts()
    try:
        yield _dispatch
    finally:
        _dispatch = None


@contextlib.contextmanager
def trace(logdir, create_perfetto_link=False):
    """Trace everything in the block to an xprof logdir."""
    global _active_logdir
    if _active_logdir is not None:
        raise MXNetError("profiler.trace already active (%s)" % _active_logdir)
    jax.profiler.start_trace(logdir,
                             create_perfetto_link=create_perfetto_link)
    _active_logdir = logdir
    try:
        yield logdir
    finally:
        _active_logdir = None
        jax.profiler.stop_trace()


def annotate(name, **meta):
    """Named span on the host's line of the profiler's trace, on the same
    clock as the device's operations.  Costs a flag test when no trace is
    being taken.  ``meta`` (name=number) rides on the span's event as its
    stats; building it costs more than the span, so pass it only where
    `taking_trace()` holds."""
    return jax.profiler.TraceAnnotation(name, **meta)


def taking_trace():
    """Whether a profiler trace is being taken now (a flag test)."""
    return jax.profiler.TraceAnnotation.is_enabled()


def save_device_memory_profile(path, backend=None):
    """Snapshot of live device allocations (pprof format)."""
    jax.profiler.save_device_memory_profile(path, backend=backend)


def device_sync(tree):
    """Execution barrier for timing: returns once every array leaf of
    ``tree`` has been computed (non-array leaves are ignored).  JAX
    dispatch is asynchronous, so a timed window that does not end here
    measures the enqueue, not the device."""
    jax.block_until_ready(tree)


def timed_median(run, sync_tree_fn, reps=2, windows=3):
    """Median per-call seconds of ``run()`` over ``windows`` fixed-size
    windows, each closed by a `device_sync`.

    Robust against one-off stalls (a recompile, a preempted host thread):
    a polluted window lands above the median and is discarded.  (Do NOT
    time by differencing two window sizes to cancel a constant — a stall
    landing in the small window silently deflates the result; that once
    produced a fictitious 3.8x speedup.)  The per-window dispatch cost is
    not subtracted — size ``reps`` so each window's real work dwarfs it."""
    times = []
    for _ in range(windows):
        times.append(_timed_window(run, sync_tree_fn, reps))
    times.sort()
    return times[len(times) // 2] / reps


def _timed_window(run, sync_tree_fn, reps):
    t0 = time.perf_counter()
    for _ in range(reps):
        run()
    device_sync(sync_tree_fn())
    return time.perf_counter() - t0


class StepTimer:
    """Host-side per-step wall-clock stats: the `Speedometer` companion for
    loops that want numbers without a trace viewer.  `tic()` each step;
    `summary()` -> dict with mean/p50/p99 step ms and steps/sec."""

    def __init__(self, warmup=1):
        self.warmup = warmup
        self._times = []
        self._last = None

    def tic(self):
        now = time.perf_counter()
        if self._last is not None:
            dt = now - self._last
            self._times.append(dt)
            # the telemetry registry's "step.ms" histogram carries the same
            # number into the per-step JSONL stream
            telemetry.observe("step.ms", 1e3 * dt)
        self._last = now

    def summary(self):
        times = sorted(self._times[self.warmup:]) or [0.0]
        n = len(times)
        return {
            "steps": n,
            "mean_ms": 1e3 * sum(times) / n,
            "p50_ms": 1e3 * times[n // 2],
            "p99_ms": 1e3 * times[min(n - 1, int(n * 0.99))],
            "steps_per_sec": (n / sum(times)) if sum(times) else 0.0,
        }


# ---------------------------------------------------------------------------
# Execution-plan observability (`GraphExecutor::Print`,
# `src/symbol/graph_executor.cc:853-886`): per-node shapes + an itemized
# FLOPs/HBM-bytes roofline, plus XLA's own cost/memory analysis of the
# actual compiled program.
# ---------------------------------------------------------------------------

def _prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


def _node_cost(op_name, params, in_shapes, out_shapes, dsize):
    """Analytic (flops, hbm_bytes) for one graph node.

    flops follow the standard conventions (2*MACs for contractions); bytes
    are the minimum HBM traffic if nothing fuses — inputs read once, outputs
    written once.  XLA fusion makes the true per-op traffic lower; the
    aggregate truth lives in `ExecutionPlan.xla`.  Good enough to rank the
    movers, which is this tool's job."""
    ins = [s for s in in_shapes if s]
    outs = [s for s in out_shapes if s]
    in_elems = sum(_prod(s) for s in ins)
    out_elems = sum(_prod(s) for s in outs)
    bytes_ = (in_elems + out_elems) * dsize
    if op_name in ("Convolution", "Deconvolution"):
        k = params.get("kernel") or ()
        groups = int(params.get("num_group") or 1)
        # MACs = out_elems * (C_in/g * prod(kernel)); for Deconvolution the
        # same formula holds with its (bigger) output
        cin = ins[1][0] if op_name == "Deconvolution" else ins[1][1] * groups
        flops = 2 * out_elems * (cin // groups) * max(_prod(k), 1)
    elif op_name == "FullyConnected":
        flops = 2 * _prod(outs[0]) * _prod(ins[0][1:])
    elif op_name == "FusedSoftmaxCE":
        # one logit-tile matmul pass forward (N x D x V MACs) + softmax
        # math; the logits themselves never hit HBM, so bytes stay the
        # input/output default (inputs + the (N,) nll)
        n = ins[0][0]
        d = _prod(ins[0][1:])
        v = ins[1][0]
        flops = 2 * n * d * v + 5 * n * v
    elif op_name == "BatchNorm":
        flops = 10 * in_elems
    elif op_name in ("SoftmaxOutput", "softmax_cross_entropy", "Softmax",
                     "SoftmaxActivation", "log_softmax", "softmax"):
        flops = 5 * in_elems
    elif op_name == "Pooling":
        k = params.get("kernel") or (1, 1)
        flops = out_elems * max(_prod(k), 1)
    elif op_name == "LRN":
        flops = int(params.get("nsize") or 5) * 3 * in_elems
    elif op_name == "dot":
        a, b = ins[0], ins[1]
        flops = 2 * _prod(a) * (_prod(b) // max(a[-1], 1))
    else:
        flops = out_elems  # elementwise-ish default: 1 flop per output
    return int(flops), int(bytes_)


class PlanNode:
    __slots__ = ("name", "op", "in_shapes", "out_shapes", "flops", "bytes")

    def __init__(self, name, op, in_shapes, out_shapes, flops, bytes_):
        self.name, self.op = name, op
        self.in_shapes, self.out_shapes = in_shapes, out_shapes
        self.flops, self.bytes = flops, bytes_


class ExecutionPlan:
    """Itemized plan of one bound executor: per-node shapes + analytic
    flops/bytes, XLA aggregate cost & memory analysis, and the lowered HLO.

    `str(plan)` prints the reference-`Print`-style report; `plan.table()`
    returns the rows; `plan.hlo` is the lowered StableHLO text."""

    def __init__(self, nodes, xla, hlo, mode, n_params_bytes):
        self.nodes = nodes
        self.xla = xla  # dict: flops, bytes_accessed, peak_bytes, ...
        self.hlo = hlo
        self.mode = mode
        self.param_bytes = n_params_bytes
        self.total_flops = sum(n.flops for n in nodes)
        self.total_bytes = sum(n.bytes for n in nodes)

    def table(self, top=None, by="flops"):
        """Rows sorted by decreasing cost: (name, op, out_shapes, flops,
        bytes, flops_pct, bytes_pct)."""
        rows = sorted(self.nodes, key=lambda n: -getattr(n, by))
        if top:
            rows = rows[:top]
        out = []
        for n in rows:
            out.append({
                "name": n.name, "op": n.op, "out_shapes": n.out_shapes,
                "flops": n.flops, "bytes": n.bytes,
                "flops_pct": 100.0 * n.flops / max(self.total_flops, 1),
                "bytes_pct": 100.0 * n.bytes / max(self.total_bytes, 1),
            })
        return out

    def __str__(self):
        lines = ["Execution plan (%s)" % self.mode,
                 "%-34s %-16s %-24s %12s %12s" % (
                     "node", "op", "out_shapes", "GFLOPs", "MB")]
        for n in self.nodes:
            lines.append("%-34s %-16s %-24s %12.3f %12.2f" % (
                n.name[:34], n.op[:16],
                ",".join("x".join(map(str, s)) for s in n.out_shapes)[:24],
                n.flops / 1e9, n.bytes / 1e6))
        lines.append("-" * 100)
        lines.append("analytic totals: %.2f GFLOPs, %.1f MB unfused traffic, "
                     "params %.1f MB"
                     % (self.total_flops / 1e9, self.total_bytes / 1e6,
                        self.param_bytes / 1e6))
        if self.xla:
            lines.append("XLA compiled:    " + ", ".join(
                "%s=%.4g" % (k, v) for k, v in sorted(self.xla.items())))
        return "\n".join(lines)


def _xla_analysis(compiled):
    """Normalize compiled.cost_analysis()/memory_analysis() across jax
    versions into one flat dict."""
    out = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        for k in ("flops", "bytes accessed", "optimal_seconds"):
            if k in cost:
                out[k.replace(" ", "_")] = float(cost[k])
    except Exception:  # backend may not implement cost analysis
        pass
    try:
        mem = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                out[k] = float(v)
        if "temp_size_in_bytes" in out:
            out["peak_bytes_est"] = (
                out.get("argument_size_in_bytes", 0.0)
                + out.get("output_size_in_bytes", 0.0)
                + out["temp_size_in_bytes"])
    except Exception:
        pass
    return out


def plan(executor, mode="auto"):
    """Build the `ExecutionPlan` for a bound Executor — the analogue of
    `GraphExecutor::Print` plus XLA cost analysis.

    mode: 'eval' (inference forward), 'train' (training forward), or
    'train_step' (the fused fwd+bwd program backward() runs); 'auto' picks
    'train_step' when gradients are bound else 'eval'."""
    import jax.numpy as jnp

    from .symbol import _topo_order

    if mode == "auto":
        mode = "train_step" if executor.grad_arrays is not None else "eval"
    if mode not in ("eval", "train", "train_step"):
        raise MXNetError("plan: unknown mode %r" % mode)

    # -- per-node shapes: one forward walk with all arg shapes known -------
    arg_shapes = {n: tuple(a.shape)
                  for n, a in zip(executor._arg_names, executor.arg_arrays)}
    dsize = int(np.dtype(executor.arg_arrays[0].dtype).itemsize) \
        if executor.arg_arrays else 4
    order = executor._order
    entry_shape = {}
    nodes = []
    for node in order:
        if node.is_variable:
            entry_shape[(id(node), 0)] = arg_shapes.get(node.name)
            continue
        in_shapes = [entry_shape.get((id(s), i)) for s, i in node.inputs]
        _, outs, _ = node.op.infer_shape(node.params, in_shapes)
        for i, s in enumerate(outs):
            entry_shape[(id(node), i)] = tuple(s) if s else None
        out_shapes = [tuple(s) for s in outs if s]
        flops, bytes_ = _node_cost(node.op.name, node.params, in_shapes,
                                   out_shapes, dsize)
        nodes.append(PlanNode(node.name, node.op.name,
                              [s for s in in_shapes if s], out_shapes,
                              flops, bytes_))

    # -- lower + compile the program this executor actually runs -----------
    args = executor._gather(executor.arg_arrays)
    aux = executor._gather(executor.aux_arrays)
    rng = jax.random.PRNGKey(0)
    if mode == "train_step":
        avals = executor._out_avals(args, aux, rng)
        cots = tuple(jnp.ones(o.shape, o.dtype) for o in avals)
        # the per-node table stays the forward plan (what the user built);
        # the xla numbers describe the actual fused fwd+bwd program
        lowered = jax.jit(executor._train_step_fn).lower(args, aux, rng, cots)
    elif mode == "train":
        lowered = jax.jit(lambda a, x, r: executor._fn(a, x, r, True)).lower(
            args, aux, rng)
    else:
        lowered = jax.jit(lambda a, x, r: executor._fn(a, x, r, False)).lower(
            args, aux, rng)
    compiled = lowered.compile()
    xla = _xla_analysis(compiled)
    hlo = lowered.as_text()

    param_bytes = sum(
        _prod(a.shape) * np.dtype(a.dtype).itemsize
        for a in executor.arg_arrays)
    return ExecutionPlan(nodes, xla, hlo, mode, param_bytes)


# ---------------------------------------------------------------------------
# Optimized-HLO breakdown: per-instruction HBM bytes / FLOPs of the program
# XLA actually runs (post-fusion, post-layout).  This sees what the
# symbol-level plan cannot: materialized transposes/copies from layout
# assignment, fusion failures, f32 upcasts.  Feed it
# `jax.jit(f).lower(...).compile().as_text()`.
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}


def _shapes_in(text):
    """All array shapes mentioned in one HLO line -> [(dtype, dims)]."""
    import re

    out = []
    for m in re.finditer(r"\b(pred|[sufc]\d+|bf16)\[([\d,]*)\]", text):
        dims = [int(d) for d in m.group(2).split(",") if d] or [1]
        out.append((m.group(1), dims))
    return out


def _line_bytes(shapes):
    return sum(_DTYPE_BYTES.get(dt, 4) * _prod(dims) for dt, dims in shapes)


def _parse_window(line):
    """Parse `window={size=AxB stride=... pad=lo_hi x lo_hi lhs_dilate=...}`
    into per-dim dicts."""
    import re

    m = re.search(r"window=\{([^}]*)\}", line)
    if not m:
        return None
    fields = {}
    for kv in re.finditer(r"(\w+)=(-?[\w._\-]+(?:x-?[\w._\-]+)*)", m.group(1)):
        fields[kv.group(1)] = kv.group(2).split("x")
    if "size" not in fields:
        return None
    ndim = len(fields["size"])

    def per_dim(key, default):
        vals = fields.get(key)
        if not vals:
            return [default] * ndim
        return vals

    dims = []
    for d in range(ndim):
        pad = per_dim("pad", "0_0")[d]
        lo, _, hi = pad.partition("_")
        dims.append({
            "size": int(per_dim("size", "1")[d]),
            "stride": int(per_dim("stride", "1")[d]),
            "pad_lo": int(lo or 0),
            "lhs_dilate": int(per_dim("lhs_dilate", "1")[d]),
            "rhs_dilate": int(per_dim("rhs_dilate", "1")[d]),
        })
    return dims


def _conv_flops(line, out_dims, lhs_dims, rhs_dims):
    """Exact 2*MAC count for one HLO convolution, padding/dilation-aware.

    MACs = batch * out_features * in_features * prod_d(valid (out,k) index
    pairs in spatial dim d).  The naive out*prod(rhs) formula wildly
    overcounts gradient convs, whose windows are mostly padding."""
    import re

    if out_dims is None or rhs_dims is None or lhs_dims is None:
        return 0
    m = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", line)
    win = _parse_window(line)
    if not m or win is None:
        return 0
    lhs_l, rhs_l, out_l = m.groups()
    try:
        batch = out_dims[out_l.index("b")]
        o_feat = rhs_dims[rhs_l.index("o")]
        i_feat = rhs_dims[rhs_l.index("i")]
        out_sp = [out_dims[out_l.index(c)] for c in "0123456"[:len(win)]]
        lhs_sp = [lhs_dims[lhs_l.index(c)] for c in "0123456"[:len(win)]]
    except (ValueError, IndexError):
        return 0
    pairs = 1
    for d, w in enumerate(win):
        n, out_n = lhs_sp[d], out_sp[d]
        ld, rd = w["lhs_dilate"], w["rhs_dilate"]
        logical_n = (n - 1) * ld + 1 if n > 0 else 0
        cnt = 0
        for k in range(w["size"]):
            # input index for output position o: o*stride + k*rd - pad_lo;
            # valid if in [0, logical_n) and on the lhs_dilation grid
            base = k * rd - w["pad_lo"]
            # o in [0, out_n): idx = o*stride + base
            lo = max(0, -(base // w["stride"]) if base < 0 else 0)
            for o in range(out_n):
                idx = o * w["stride"] + base
                if 0 <= idx < logical_n and idx % ld == 0:
                    cnt += 1
        pairs *= cnt
    return 2 * batch * o_feat * i_feat * pairs


def _dot_flops(line, out_dims, lhs_dims):
    import re

    if out_dims is None or lhs_dims is None:
        return 0
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", line)
    if not m:
        return 0
    contract = 1
    for d in m.group(1).split(","):
        if d and int(d) < len(lhs_dims):
            contract *= lhs_dims[int(d)]
    return 2 * _prod(out_dims) * contract


_HLO_OPCODES = frozenset("""
abs add after-all all-gather all-gather-done all-gather-start all-reduce
all-reduce-done all-reduce-start all-to-all and async-done async-start
async-update atan2 batch-norm-grad batch-norm-inference batch-norm-training
bitcast bitcast-convert broadcast call ceil cholesky clamp clz
collective-broadcast collective-permute collective-permute-done
collective-permute-start compare complex concatenate conditional constant
convert convolution copy copy-done copy-start cosine custom-call divide
domain dot dynamic-reshape dynamic-slice dynamic-update-slice erf exponential
exponential-minus-one fft floor fusion gather get-dimension-size
get-tuple-element imag infeed iota is-finite log log-plus-one logistic map
maximum minimum multiply negate not optimization-barrier or outfeed pad
parameter partition-id popcnt power real recv recv-done reduce
reduce-precision reduce-scatter reduce-window remainder replica-id reshape
reverse rng rng-bit-generator rng-get-and-update-state round-nearest-afz
round-nearest-even rsqrt scatter select select-and-scatter send send-done
set-dimension-size shift-left shift-right-arithmetic shift-right-logical
sign sine slice sort sqrt stochastic-convert subtract tan tanh topk
transpose triangular-solve tuple while xor
""".split())

_opcode_candidate_re = None


def _parse_instruction(line):
    """(name, opcode, type_segment, rest) for one HLO instruction line, or
    None.  Robust to layout syntax containing parentheses — the opcode is
    located as the first known-opcode word followed by '(' after the '='."""
    import re

    global _opcode_candidate_re
    if _opcode_candidate_re is None:
        _opcode_candidate_re = re.compile(
            r"(?<![\w.%\-])([a-z][a-z0-9\-]*)\(")
    eq = line.find("= ")
    if eq < 0 or "%" not in line[:eq]:
        return None
    mname = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)", line)
    if not mname:
        return None
    for m in _opcode_candidate_re.finditer(line, eq):
        if m.group(1) in _HLO_OPCODES:
            return (mname.group(1), m.group(1), line[eq + 1:m.start()],
                    line[m.end():])
    return None


_param_decl_re = None


def hlo_breakdown(hlo_text, top=30):
    """Parse optimized HLO into {rows, by_op, by_src, total_bytes,
    total_flops}.

    Two passes.  Pass 1 splits the module into computations and builds a
    symbol table name -> result shapes (operands print without shapes in
    scheduled HLO, so consumers resolve through it; computation-header
    parameter declarations seed it for fusion bodies), then sums conv/dot
    FLOPs per computation with operand shapes resolved.  Pass 2 walks
    instructions of the directly-executed computations (entry, while
    bodies, regions — everything NOT named `fused_*`, whose internals are
    VMEM-resident) and charges HBM traffic per instruction: output bytes
    written + operand bytes read.  `*-start`/`*-done` async pairs are
    charged once (reads at start, writes at done).  Fusion calls inherit
    the called computation's conv/dot FLOPs.

    rows: top-N instructions by bytes.  by_op: per-opcode aggregate.
    by_src: per-source-op aggregate from `metadata op_name` (which model-
    level op the traffic belongs to — conv backward, BatchNorm, optimizer).
    """
    import re

    global _param_decl_re
    comp_re = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^=]*\)\s*->.*{$")
    meta_re = re.compile(r'op_name="([^"]*)"')
    calls_re = re.compile(r"calls=%?([\w.\-]+)")
    if _param_decl_re is None:
        _param_decl_re = re.compile(
            r"([\w.\-]+):\s*((?:pred|[sufc]\d+|bf16)\[[\d,]*\])")

    # -- pass 1a: computations + symbol table ------------------------------
    comps = {}        # name -> [(name, opcode, type_seg, rest, line)]
    shapes_of = {}    # instruction/param name -> [(dtype, dims), ...]
    cur = None
    for line in hlo_text.splitlines():
        s = line.strip()
        mc = comp_re.match(s)
        if mc:
            cur = mc.group(1)
            comps[cur] = []
            # header parameter declarations carry shapes
            for pm in _param_decl_re.finditer(s):
                shapes_of[pm.group(1)] = _shapes_in(pm.group(2))
            continue
        if s.startswith("}"):
            cur = None
            continue
        if cur is not None and "=" in s:
            parsed = _parse_instruction(s)
            if parsed:
                comps[cur].append(parsed)
                shapes_of[parsed[0]] = _shapes_in(parsed[2])

    def result_bytes(name):
        return _line_bytes(shapes_of.get(name, ()))

    def operand_names(rest):
        return [m.group(1) for m in re.finditer(r"%([\w.\-]+)", rest)
                if m.group(1) not in comps]

    def first_shape(name):
        sh = shapes_of.get(name)
        return sh[0][1] if sh else None

    def inst_flops(opcode, type_seg, rest):
        if opcode not in ("convolution", "dot"):
            return 0
        out_sh = _shapes_in(type_seg)
        out_dims = out_sh[0][1] if out_sh else None
        ops = operand_names(rest)
        if opcode == "convolution":
            lhs = first_shape(ops[0]) if ops else None
            rhs = first_shape(ops[1]) if len(ops) > 1 else None
            return _conv_flops(rest, out_dims, lhs, rhs)
        lhs = first_shape(ops[0]) if ops else None
        return _dot_flops(rest, out_dims, lhs)

    # -- pass 1b: per-computation conv/dot flops ---------------------------
    comp_flops = {}
    for cname, instrs in comps.items():
        comp_flops[cname] = sum(inst_flops(op, tseg, rest)
                                for _, op, tseg, rest in instrs)

    # -- pass 2: charge traffic in directly-executed computations ----------
    NO_TRAFFIC = ("parameter", "constant", "get-tuple-element", "tuple",
                  "bitcast", "after-all", "partition-id", "replica-id")
    rows, by_op, by_src = [], {}, {}
    for cname, instrs in comps.items():
        if "fused" in cname:
            continue
        for name, opcode, type_seg, rest in instrs:
            if opcode in NO_TRAFFIC:
                continue
            out_b = result_bytes(name)
            in_b = sum(result_bytes(o) for o in operand_names(rest))
            if opcode.endswith("-done"):
                b = out_b          # reads were charged at the -start
            elif opcode.endswith("-start"):
                b = in_b
            else:
                b = out_b + in_b
            if opcode == "fusion":
                mcall = calls_re.search(rest)
                f = comp_flops.get(mcall.group(1), 0) if mcall else 0
            else:
                f = inst_flops(opcode, type_seg, rest)
            line_txt = "%s = %s %s(%s" % (name, type_seg.strip(), opcode,
                                          rest[:120])
            rows.append({"name": name, "op": opcode, "bytes": b, "flops": f,
                         "line": line_txt[:200]})
            agg = by_op.setdefault(opcode,
                                   {"bytes": 0, "flops": 0, "count": 0})
            agg["bytes"] += b
            agg["flops"] += f
            agg["count"] += 1
            mm = meta_re.search(rest)
            src = mm.group(1).split("/")[-1] if mm else "(no metadata)"
            sagg = by_src.setdefault(src,
                                     {"bytes": 0, "flops": 0, "count": 0})
            sagg["bytes"] += b
            sagg["flops"] += f
            sagg["count"] += 1
    rows.sort(key=lambda r: -r["bytes"])
    return {
        "rows": rows[:top] if top else rows,
        "by_op": by_op,
        "by_src": by_src,
        "total_bytes": sum(a["bytes"] for a in by_op.values()),
        "total_flops": sum(a["flops"] for a in by_op.values()),
    }


def format_breakdown(bd, peak_flops=None, peak_gbps=None):
    """Human report for `hlo_breakdown` output."""
    lines = ["%-22s %8s %12s %12s" % ("opcode", "count", "GB", "GFLOPs")]
    for op, a in sorted(bd["by_op"].items(), key=lambda kv: -kv[1]["bytes"]):
        lines.append("%-22s %8d %12.3f %12.1f"
                     % (op, a["count"], a["bytes"] / 1e9, a["flops"] / 1e9))
    lines.append("total: %.3f GB moved, %.1f GFLOPs"
                 % (bd["total_bytes"] / 1e9, bd["total_flops"] / 1e9))
    if peak_flops and peak_gbps:
        t_comp = bd["total_flops"] / peak_flops
        t_mem = bd["total_bytes"] / (peak_gbps * 1e9)
        lines.append("roofline: compute %.2f ms vs memory %.2f ms -> %s-bound"
                     % (1e3 * t_comp, 1e3 * t_mem,
                        "compute" if t_comp > t_mem else "memory"))
    lines.append("top instructions by bytes:")
    for r in bd["rows"][:15]:
        lines.append("  %10.1f MB %-14s %s"
                     % (r["bytes"] / 1e6, r["op"], r["line"][:110]))
    return "\n".join(lines)
