"""Continuous-batching serving engine + multi-replica router.

Iteration-level scheduling (Orca, OSDI '22): the unit of work is ONE
decode step over whichever sequences are active, not one request.  A
request joins the running batch the step after its prefill and leaves the
step it finishes — no head-of-line blocking on the longest generation in
a batch, which is where request-level batching loses its throughput.

Zero steady-state recompiles: every program the engine launches is
AOT-compiled at `warmup()` for a small FIXED set of shapes —

* prefill buckets: (1, s) for s in ``MXNET_SERVE_PREFILL_BUCKETS``
  (prompts right-pad up to the smallest bucket that fits), and
* decode buckets: (b, 1) for b in ``MXNET_SERVE_BUCKETS`` (the active
  set pads up to the smallest bucket with rows pointed at the trash block).

Executables live in an `executor.AotCache` (`serve.aot.hits/compiles`
counters) and every launch feeds the PR-2 retrace watchdog
(`telemetry.watch_jit`, sites ``serving.prefill``/``serving.decode``), so
"no recompiles after warmup" is an asserted property
(tests/test_serving.py), not a hope.

The K/V cache is PAGED: a fixed block pool
(L, 2, n_blocks, block_size, E) DONATED through each compiled call, with
per-row int32 block tables and a host-side free-list allocator
(serving/paged.py).  Admission is free-block accounting — a sequence
holds blocks for its ACTUAL length, not for the cache depth.  Growth is
one block at a time; a denied growth preempts (blocks freed, request
requeued with its generated tokens — deterministic replay makes
preemption invisible in the output).  Prompts longer than the largest
prefill bucket stream through the pool in bucket-sized CHUNKS (one per
iteration once decoding — the Sarathi ttft-interference bound).

Paged blocks are SHAREABLE across requests (``MXNET_SERVE_PREFIX=0``
restores single-owner paging bit-for-bit): the allocator refcounts every
block and a block-aligned radix index (`serving/paged.PrefixCache`, the
RadixAttention idea at block granularity) maps full-block token runs to
the physical blocks already holding their K/V.  Admission looks up the
longest cached prefix, acquires those blocks, and prefills only the
uncached suffix — a fully-covered prompt skips prefill outright and
BOOTSTRAPS through one decode step of its last token.  A writer about
to touch a shared (or index-registered) block gets a private copy first
(copy-on-write: one tiny block-copy program compiled at warmup, the
AotCache stays frozen); a denied CoW allocation preempts typed, never
aliases.  Retired blocks no longer free eagerly: refcount-0 registered
blocks PARK in an LRU pool evicted only under allocation pressure, so a
hot system prompt survives across requests — lower ttft and strictly
more admitted concurrency at equal HBM under shared-prefix traffic
(``bench.py --serve --prefix`` measures the A/B).

Sampling runs inside the compiled step — greedy argmax, or per-request
temperature/top-k/top-p with a request-keyed position-folded RNG
(serving/sampling.py) when ``MXNET_SERVE_SAMPLING`` programs are built —
so the only per-step host traffic is the bucket of sampled token ids the
scheduler needs for EOS/retire decisions.

Failure model (docs/serving.md "Failure semantics"): partial failure is
the normal case, not an engine-killing event.  Every request carries an
optional deadline and resolves — with tokens or a typed `ServeError` —
at iteration granularity; admission control bounds the queue
(``MXNET_SERVE_QUEUE_MAX`` + ``MXNET_SERVE_OVERLOAD=shed|block|degrade``);
launch failures are classified by SCOPE (a poisoned request is
quarantined while the batch keeps decoding, a consumed donated cache is
rebuilt, only a dead device kills the scheduler); and a dead replica's
queued-but-not-admitted requests fail over to surviving replicas while
the `ReplicaRouter` respawns a replacement that re-warms from the SHARED
AOT cache — recovery compiles nothing.

DURABILITY (docs/serving.md "Durability"): replica death and planned
restarts are additionally output-invisible for ADMITTED requests.  The
router's request journal (serving/journal.py, ``MXNET_SERVE_JOURNAL``)
migrates a dead replica's in-flight requests to survivors through the
same `(prompt+generated)[:pos]` exact-replay resume the preemption path
already uses — deterministic request-keyed sampling makes the
continuation token-for-token identical at any temperature — and
`engine.drain`/`router.drain` turn that into zero-loss rolling restarts
(admission closes, in-flight work serves out, stragglers migrate, the
replacement warms off the shared AotCache and compiles nothing).
Anti-thrash preemption keeps sustained `block_exhaust` pressure from
degenerating into preempt/replay churn: a resumed sequence is exempt
from re-preemption until it advances ``MXNET_SERVE_MIN_PROGRESS``
tokens (a denied-but-protected row STALLS in place instead — no replay
burned), the oldest in-flight request is never preempted (livelock
breaker: someone always finishes), and a preemption storm
(``MXNET_SERVE_THRASH_TRIP`` preemptions with no completion) trips the
PR-8 degrade path until the pool drains.

MEMORY TIERING (docs/serving.md "Memory tiering & sessions",
``MXNET_SERVE_TIER``): the prefix cache gains a HOST-DRAM tier below
HBM (serving/tiers.py).  A parked block the LRU evicts is no longer
destroyed — its K/V spills device→host into a bounded
(``MXNET_SERVE_HOST_BLOCKS``) LRU pool and the radix node converts to
host residency, so the hot-prefix working set survives past device
memory.  Admission's prefix lookup returns a tier-aware plan: a match
landing on host-resident blocks becomes a *restore-then-acquire*
admission (`_Restore`) — fresh device blocks are allocated, the whole
host run packs into ONE async `jax.device_put` at admission, the
transfer OVERLAPS the current decode iteration (the
`io.DevicePrefetchIter` two-stage stage-ahead pattern), and next
iteration one bucketed pool-scatter program (compiled at warmup: the
AotCache stays frozen) lands the bytes and the sequence proceeds
exactly as a device hit —
so a host hit costs a PCIe copy instead of a prefill recompute, and
the miss path never waits behind a restore
(``MXNET_SERVE_RESTORE_AHEAD`` bounds concurrent restores; past it a
lookup simply takes its device-resident prefix).  Preempted requests
park their K/V the same way — their registered blocks spill under
pressure and the resume admission restores instead of replaying —
and ``submit(session=…)`` turns the tier into chat continuity: a
finished turn's full history is remembered under the session key,
a follow-up submit reattaches the cached blocks (device- or
host-resident) and prefills only the new turn's suffix.
``MXNET_SERVE_TIER=0`` (the default) restores PR-12
evict-and-recompute bit for bit.
"""
from __future__ import annotations

import os
import re
import threading
import time
from collections import OrderedDict, deque

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import chaos
from .. import profiler
from .. import telemetry
from .. import tracing
from ..base import MXNetError
from ..context import Context
from ..executor import AotCache
from ..parallel.mesh import MeshContext, mesh_signature, submeshes
from ..quant.codec import resolve as quant_resolve
from .handoff import HandoffLanding, HandoffTicket, disagg_enabled
from .journal import RequestJournal, journal_enabled
from .paged import BlockAllocator, PrefixCache, TRASH_BLOCK
from .sampling import sample_tokens
from .spec import make_drafter
from .tiers import HostBlockTier, check_cache_kind, pack_block_run
from .errors import (ServeError, ServeTimeout, ServeOverload,
                     ServeDeadlineExceeded, ServeCancelled,
                     ServeQuarantined, ServeBlocksExhausted,
                     ServeCacheInvalidated, ServeEngineDead,
                     ServeQuantError)


def _env_flag(name, default="1"):
    return os.environ.get(name, default).lower() not in ("0", "false", "no")


#: the leaf phases of the scheduler, whose host seconds each `iteration`
#: record carries as `phase_ms` (docs/observability.md "Request tracing")
SCHED_PHASES = ("sweep", "prefill", "admit", "grow", "pack", "launch",
                "fetch", "publish", "account", "wait")
_NO_PHASE_S = dict.fromkeys(SCHED_PHASES, 0.0)


class _Phase:
    """One phase of the scheduler, timed both ways: a `sched.<name>` span
    on the scheduler thread's line of the profiler's trace
    (docs/observability.md "In the profiler's trace"), on the device
    trace's clock by construction, and its `perf_counter` seconds added
    into ``acc``, the engine's table since the last `iteration` record
    (None where the span store is off: a flag test, no clock read).  The
    three decode bodies use the same name where they do the same thing.
    ``meta`` rides on the span as its stats."""

    __slots__ = ("_acc", "_name", "_ann", "_t")

    def __init__(self, acc, name, **meta):
        self._acc, self._name = acc, name
        self._ann = profiler.annotate("sched." + name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        if self._acc is not None:
            self._t = time.perf_counter()
        return self

    def split(self):
        """Add the seconds so far and go on timing from now: the part of
        the phase a record written inside it carries."""
        now = time.perf_counter()
        self._acc[self._name] += now - self._t
        self._t = now

    def __exit__(self, *exc):
        if self._acc is not None:
            self._acc[self._name] += time.perf_counter() - self._t
        return self._ann.__exit__(*exc)


class _EngineFatal(Exception):
    """A dead-device-scoped failure: the scheduler cannot carry on —
    step() must not swallow this as a per-request poison error."""


def _env_buckets(name, default):
    raw = os.environ.get(name, "")
    if not raw:
        return list(default)
    try:
        vals = sorted({int(x) for x in raw.replace(" ", "").split(",") if x})
    except ValueError:
        raise MXNetError("%s must be a comma-separated int list, got %r"
                         % (name, raw))
    if not vals or vals[0] < 1:
        raise MXNetError("%s needs positive bucket sizes, got %r"
                         % (name, raw))
    return vals


class ServeRequest:
    """One generation request: prompt in, tokens out, latency stamps.

    ``deadline_ms`` (optional) is the SLO contract: once
    ``t_submit + deadline_ms`` passes, the scheduler retires the request
    at its next iteration with `ServeDeadlineExceeded` — whether it is
    still queued or mid-decode — so an expired request never costs a
    dispatch.  ``cancel()`` retires the same way with `ServeCancelled`."""

    _ids = [0]
    _ids_lock = threading.Lock()

    def __init__(self, prompt, max_new_tokens, eos_id=None, deadline_ms=None,
                 temperature=0.0, top_k=0, top_p=1.0, seed=None,
                 session=None):
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise MXNetError("ServeRequest: empty prompt")
        # session continuity key (docs/serving.md "Memory tiering &
        # sessions"): the engine prepended the session's stored history
        # to `prompt` at submit, and will register prompt+generated
        # under this key at retire so the NEXT turn reattaches it
        self.session = session
        with self._ids_lock:
            self._ids[0] += 1
            self.id = self._ids[0]
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        # sampling contract: temperature <= 0 is greedy argmax (the
        # default); > 0 samples with optional top-k / nucleus filtering.
        # The RNG is request-keyed: `seed` (default: the request id, so
        # unseeded traffic still decodes deterministically per process)
        # folded with each token's absolute position — batch composition
        # and preemption are invisible to the draw sequence.
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        if self.temperature < 0:
            raise MXNetError("ServeRequest: temperature must be >= 0")
        if self.top_k < 0:
            raise MXNetError("ServeRequest: top_k must be >= 0")
        if not 0.0 < self.top_p <= 1.0:
            raise MXNetError("ServeRequest: top_p must be in (0, 1]")
        self.seed = (self.id if seed is None else int(seed)) & 0x7FFFFFFF
        self._resume = None       # paged preemption: (tokens, last, pos,
        #                           n_new) to re-prefill and continue from
        self.tokens = []          # generated ids (includes eos if hit)
        self.error = None
        self.t_submit = time.perf_counter()
        self.t_deadline = None if not deadline_ms \
            else self.t_submit + float(deadline_ms) / 1e3
        self.t_first = None       # first token sampled (end of prefill)
        self.t_done = None
        self._done = threading.Event()
        self._cancelled = False
        self._requeues = 0        # cache-loss retries already burned
        self._waker = None        # set by the owning engine at enqueue
        self._preempt_n_new = None  # n_new at the last preemption: a
        #                           resumed request is exempt from another
        #                           preemption until it advances
        #                           MXNET_SERVE_MIN_PROGRESS tokens past it
        self._migrated = False    # journal migration pending its replay
        self._no_handoff = False  # burned its one disagg handoff: a
        #                           replayed-from-handoff request decodes
        #                           wherever it lands (bounded churn —
        #                           roles are dispatch policy, not a
        #                           capability restriction)
        # streaming (docs/serving.md "Megastep decode & streaming"):
        # `stream()` iterators sleep on this condition; `_published` is
        # the scheduler's delivery high-water mark into `self.tokens`.
        # Exactly-once across preemption/migration is structural: every
        # resume path rebuilds context from (prompt+generated)[:pos]
        # and NEVER truncates or re-appends `tokens`, so indices below
        # the mark are final and new tokens only ever appear above it.
        self._stream_cond = threading.Condition()
        self._published = 0
        self._on_token = None     # optional submit(on_token=...) callback

    @property
    def done(self):
        return self._done.is_set()

    def expired(self, now=None):
        return self.t_deadline is not None and \
            (time.perf_counter() if now is None else now) > self.t_deadline

    def cancel(self):
        """Ask the scheduler to retire this request at its next iteration
        (`ServeCancelled`).  Idempotent; a no-op once finished."""
        self._cancelled = True
        waker = self._waker
        if waker is not None:
            waker()

    def result(self, timeout=None):
        """Block until finished; returns the generated token list.  Raises
        `ServeTimeout` if the wait expires, or the request's own typed
        `ServeError` if it failed."""
        if not self._done.wait(timeout):
            raise ServeTimeout("ServeRequest %d: timed out after %ss"
                               % (self.id, timeout))
        if self.error is not None:
            err = self.error
            cls = err.__class__ if isinstance(err, ServeError) else MXNetError
            msg = str(err)
            tag = "ServeRequest %d" % self.id
            raise cls(msg if tag in msg else "%s: %s" % (tag, msg))
        return list(self.tokens)

    def stream(self, timeout=None):
        """Iterate this request's generated tokens as the scheduler
        publishes them — one `int` per generated token, in order, each
        exactly once — instead of waiting for `result()` at retire.
        (Named `stream()` rather than `tokens()`: `self.tokens` is the
        generated-token LIST, the journal's durable record.)

        Tokens become visible after every scheduler iteration (every
        megastep with `MXNET_SERVE_MEGASTEP`, every decode/verify round
        without), so a consumer sees at most one iteration of latency.
        Preemption, quant-gate requeues and journal migration are
        invisible mid-stream: the resume replays context, not output,
        so the iterator never re-yields and never skips.  Ends when the
        request finishes; a failed request raises its typed error (after
        yielding everything that was delivered first).  ``timeout``
        bounds each WAIT for the next token (`ServeTimeout`), not the
        whole stream.  Multiple concurrent iterators each get the full
        stream; `result()` still works alongside.
        """
        cursor = 0
        while True:
            with self._stream_cond:
                while len(self.tokens) <= cursor and not self._done.is_set():
                    if not self._stream_cond.wait(timeout):
                        raise ServeTimeout(
                            "ServeRequest %d: stream timed out after %ss"
                            % (self.id, timeout))
                # snapshot under the condition: the scheduler appends
                # then notifies, so this view is never torn
                batch = list(self.tokens[cursor:])
            for t in batch:
                cursor += 1
                yield int(t)
            if self._done.is_set() and cursor >= len(self.tokens):
                if self.error is not None:
                    self.result(timeout=0.001)  # raises the typed error
                return

    def _publish(self):
        """Scheduler-side delivery point: wake `stream()` iterators and
        fire the `on_token` callback for tokens newly appended to
        `self.tokens`.  The high-water mark makes delivery exactly-once
        — a replayed/migrated request re-enters decode with its token
        list intact, so nothing below the mark is ever re-delivered."""
        n = len(self.tokens)
        if n <= self._published:
            return
        lo, self._published = self._published, n
        with self._stream_cond:
            self._stream_cond.notify_all()
        cb = self._on_token
        if cb is not None:
            for t in self.tokens[lo:n]:
                try:
                    cb(int(t))
                except Exception:  # a consumer bug must not kill the
                    pass           # scheduler thread

    # latency views (ms), None until the corresponding stamp exists
    @property
    def ttft_ms(self):
        return None if self.t_first is None else \
            1e3 * (self.t_first - self.t_submit)

    @property
    def latency_ms(self):
        return None if self.t_done is None else \
            1e3 * (self.t_done - self.t_submit)

    def _finish(self, error=None):
        if self._done.is_set():
            return
        # flush delivery first: the retiring step may have appended a
        # final token the trailing `_publish()` in the step loop has not
        # delivered yet — stream positions must match span positions
        # before the trace closes below
        self._publish()
        self.error = error
        self.t_done = time.perf_counter()
        self._done.set()
        with self._stream_cond:
            self._stream_cond.notify_all()  # unblock stream() waiters
        # every resolution (retire, shed, quarantine, cancel, deadline,
        # replica death) funnels through here exactly once: close the
        # request's trace and fold its phases into serve.attr.*
        tracing.on_finish(self)


class _Seq:
    """Scheduler state of one active sequence: `last` is the token that
    will be fed (and cached) at position `pos` on the next decode step.
    ``blocks`` is the host-side block list: entry t holds cache
    positions [t*bs, (t+1)*bs).
    ``ctx`` is the incrementally maintained list of the
    tokens cached at rows [0, pos) — prefix registration and preemption
    resume read it directly instead of re-concatenating prompt +
    generated every time (which would be quadratic over a long
    generation)."""

    __slots__ = ("req", "last", "pos", "n_new", "blocks", "ctx")

    def __init__(self, req, last, pos, blocks, ctx):
        self.req = req
        self.last = last
        self.pos = pos
        self.n_new = 1  # the prefill already sampled token #1
        self.blocks = blocks
        self.ctx = ctx


class _Prefill:
    """An admission mid-stream: ``tokens`` is everything the
    cache must hold before decode starts (the prompt — or, after a
    preemption, prompt + already-generated tokens), ``done`` how many of
    them are cached so far.  One bucket-sized chunk advances per
    scheduler iteration once the engine is decoding, so a long prompt
    never stalls active sequences for more than one chunk (the
    Sarathi-style piggyback); an idle engine streams chunks back to
    back."""

    __slots__ = ("req", "row", "tokens", "done", "blocks", "resume")

    def __init__(self, req, row, tokens, blocks, resume=None):
        self.req = req
        self.row = row
        self.tokens = tokens
        self.done = 0
        self.blocks = blocks
        self.resume = resume      # (last, pos, n_new) after preemption


class _Restore:
    """A tier-aware admission waiting on its host→device transfer: the
    prefix lookup matched ``done`` device-resident tokens plus
    ``nodes`` host-resident blocks, fresh device blocks were allocated
    for the host run (``dst``, the leading fresh blocks) and the whole
    run was packed into ONE padded array and dispatched with ONE async
    `jax.device_put` at admission (``staged``).  The transfer rides
    UNDER the current iteration's decode launch — the
    `DevicePrefetchIter` two-stage pattern — and `_advance_restores`
    completes it next iteration with one warmup-compiled bucketed pool
    write, after which the sequence proceeds exactly as if the whole
    run had been device-resident.  ``blocks`` is the full table (shared
    device prefix + every fresh block), held at ordinary refcounts so
    every failure path funnels through `_release_blocks` like any other
    holder.

    Two admissions racing over the SAME spilled prefix within one
    iteration each stage their own restore; the later `restore_landed`
    sees the node already device-resident and keeps its copy private —
    correct, at the cost of a duplicated transfer bounded by
    ``MXNET_SERVE_RESTORE_AHEAD`` (folding the second admission into
    the first's in-flight restore would save it, but degrading it to a
    recompute — the simple alternative — costs strictly more than the
    duplicate copy)."""

    __slots__ = ("req", "row", "tokens", "done", "blocks", "nodes",
                 "handles", "staged", "dst_d", "dst", "kb", "t_stage")

    def __init__(self, req, row, tokens, blocks, done, nodes, handles,
                 staged, dst_d, dst, kb, t_stage=None):
        self.req = req
        self.row = row
        self.tokens = tokens
        self.done = done          # device-matched tokens (valid rows)
        self.blocks = blocks
        self.nodes = nodes        # host-resident _PrefixNodes, in order
        self.handles = handles    # their host-tier handles
        self.staged = staged      # ONE staged (L, 2, kb, bs, E) array
        self.dst_d = dst_d        # (kb,) destination ids, trash-padded
        self.dst = dst            # real destination blocks, in order
        self.kb = kb              # the k-bucket the run padded up to
        # stamped by the caller BEFORE the host pack + device_put dispatch,
        # so serve.restore_wait_ms covers the whole stage -> land window
        self.t_stage = time.perf_counter() if t_stage is None else t_stage


class _SessionClaim:
    """Placeholder live entry between a session submit passing the
    liveness guard and its admission landing: never ``done``, so a
    racing second submit of the same session raises typed instead of
    both passing the guard and silently forking the history.  Resolves
    to the admitted request (`_session_record`) or back to ``prev``
    (`_session_unclaim` — the shed/raise path)."""

    __slots__ = ("prev", "id", "done")

    def __init__(self, prev):
        self.prev = prev
        self.id = 0 if prev is None else prev.id
        self.done = False


_OVERLOAD_POLICIES = ("shed", "block", "degrade")


class ServingEngine:
    """Single-replica continuous batcher over one device.

    model:  `TransformerKVModel` (the program builder).
    params: {name: array} transformer weights (device_put onto `ctx`;
            already-device-resident arrays are shared, not copied — the
            respawn path reuses the dead replica's placed params).
    ctx:    Context or jax device; default = first device.
    queue_max / overload / deadline_ms: admission control (env defaults
            ``MXNET_SERVE_QUEUE_MAX`` / ``MXNET_SERVE_OVERLOAD`` /
            ``MXNET_SERVE_DEADLINE_MS``).
    aot:    share a prebuilt `AotCache` (respawn: recovery compiles
            nothing the dead incarnation already compiled).
    """

    def __init__(self, model, params, ctx=None, max_batch=None,
                 decode_buckets=None, prefill_buckets=None,
                 max_new_tokens=None, eos_id=None, name="replica0",
                 queue_max=None, overload=None, deadline_ms=None, aot=None,
                 block_size=None, n_blocks=None, sampling=None, prefix=None,
                 prefix_pool=None, spec=None, spec_k=None,
                 spec_drafter=None, min_progress=None, thrash_trip=None,
                 tier=None, host_blocks=None, restore_ahead=None,
                 quant=None, kv_quant=None, megastep=None,
                 megastep_steps=None):
        model.check_params(params)
        self.model = model
        self.name = name
        # sub-mesh replica (docs/serving.md "Sharded replicas"): a Mesh
        # ctx shards the params AND the paged KV pool over the mesh via
        # NamedSharding/pjit, while every host-side structure — block
        # tables, allocator, prefix cache, scheduling, the router's view
        # — stays replica-global, so failover/respawn/journal/drain all
        # compose unchanged.  MXNET_SERVE_SHARDED=0 is the kill-switch:
        # a Mesh ctx degrades to its FIRST device, PR-19 single-device
        # behavior bit for bit.
        self._mesh = None
        self._mesh_axis = None
        if isinstance(ctx, Mesh):
            if _env_flag("MXNET_SERVE_SHARDED"):
                self._mesh = ctx
                ax = os.environ.get("MXNET_SERVE_SHARDED_AXIS", "model")
                self._mesh_axis = ax if ax in ctx.axis_names \
                    else ctx.axis_names[0]
            else:
                ctx = np.asarray(ctx.devices).reshape(-1)[0]
        if self._mesh is not None:
            self._refuse("mesh", "a Mesh ctx with MXNET_SERVE_SHARDED")
            # launch operands and token outputs are REPLICATED over the
            # mesh; _device doubles as that sharding so every existing
            # _put/device_put site stages mesh-consistently for free
            self._device = NamedSharding(self._mesh, PartitionSpec())
        elif ctx is None:
            self._device = jax.devices()[0]
        elif isinstance(ctx, Context):
            self._device = ctx.jax_device()
        else:
            self._device = ctx
        self.max_batch = int(os.environ.get("MXNET_SERVE_MAX_BATCH", "8")
                             if max_batch is None else max_batch)
        if self.max_batch < 1:
            raise MXNetError("ServingEngine: max_batch must be >= 1")
        # sorted + deduped regardless of source: submit() reads [-1] as the
        # largest bucket and _bucket_for first-fit-scans ascending.
        # Out-of-range values raise (a silently dropped bucket would make
        # occupancy/latency quietly differ from the configured intent).
        decode_src = decode_buckets or _env_buckets(
            "MXNET_SERVE_BUCKETS", _default_decode_buckets(self.max_batch))
        bad = sorted({int(b) for b in decode_src if b > self.max_batch})
        if bad:
            raise MXNetError(
                "ServingEngine: decode buckets %s exceed max_batch %d"
                % (bad, self.max_batch))
        self.decode_buckets = sorted({int(b) for b in decode_src}
                                     | {self.max_batch})
        prefill_src = prefill_buckets or _env_buckets(
            "MXNET_SERVE_PREFILL_BUCKETS",
            _default_prefill_buckets(model.seq_len))
        bad = sorted({int(s) for s in prefill_src if s > model.seq_len})
        if bad:
            raise MXNetError(
                "ServingEngine: prefill buckets %s exceed seq_len %d"
                % (bad, model.seq_len))
        self.prefill_buckets = sorted({int(s) for s in prefill_src})
        self.max_new_default = int(
            os.environ.get("MXNET_SERVE_MAX_NEW", "32")
            if max_new_tokens is None else max_new_tokens)
        if self.max_new_default < 1:
            raise MXNetError("ServingEngine: max_new_tokens must be >= 1")
        self.eos_id = eos_id
        # admission control (0 = unbounded queue, policy moot)
        self._queue_max = int(os.environ.get("MXNET_SERVE_QUEUE_MAX", "0")
                              if queue_max is None else queue_max)
        self._overload = str(os.environ.get("MXNET_SERVE_OVERLOAD", "shed")
                             if overload is None else overload).lower()
        if self._overload not in _OVERLOAD_POLICIES:
            raise MXNetError(
                "ServingEngine: overload policy %r not in %s"
                % (self._overload, _OVERLOAD_POLICIES))
        dl = float(os.environ.get("MXNET_SERVE_DEADLINE_MS", "0")
                   if deadline_ms is None else deadline_ms)
        self._deadline_ms_default = dl if dl > 0 else None
        self._launch_retries = max(1, int(os.environ.get(
            "MXNET_SERVE_LAUNCH_RETRIES", "3")))

        # sampling programs (MXNET_SERVE_SAMPLING=0 restores the PR-7
        # greedy-only program signatures)
        self._sampling = _env_flag("MXNET_SERVE_SAMPLING") \
            if sampling is None else bool(sampling)
        # post-training quantization (docs/serving.md "Quantization"):
        # MXNET_SERVE_QUANT=int8|fp8 quantizes the serving weights once
        # at load (scaled matmuls inside the same compiled programs);
        # MXNET_SERVE_KV_QUANT (default: int8 whenever weight quant is
        # on) stores the paged K/V pool int8 with per-row scales —
        # roughly 2-4x n_blocks at equal HBM.  =0 is bit-for-bit PR 13.
        self._quant = quant_resolve(
            os.environ.get("MXNET_SERVE_QUANT", "0") if quant is None
            else quant)
        kvq = os.environ.get("MXNET_SERVE_KV_QUANT", "") \
            if kv_quant is None else kv_quant
        if kvq in ("", None):
            # implicit default: int8 KV rides along with weight quant
            kvq = "int8" if self._quant is not None else "0"
        self._kv_quant = quant_resolve(kvq)
        if self._quant is not None:
            self._refuse("quant", "quant / MXNET_SERVE_QUANT")
        if self._kv_quant is not None:
            self._refuse("kv_quant", "kv_quant / MXNET_SERVE_KV_QUANT")
        self._quant_gate = (self._quant is not None
                            or self._kv_quant is not None)
        self._quant_logit_max = float(os.environ.get(
            "MXNET_SERVE_QUANT_LOGIT_MAX", "1e4"))
        self.model = model = model.with_quant(self._quant, self._kv_quant)
        if self._quant is not None:
            # quantize ONCE at load, host-side; a respawn passes the dead
            # incarnation's already-quantized device params straight
            # through (quantize_params is idempotent)
            params = model.quantize_params(params)
        jarr = jax.Array
        if self._mesh is not None:
            # the trainer's auto-param-sharding rules, applied at load:
            # tensor-parallel projections/head/expert banks, replicated
            # norms (decode.param_shardings).  Respawn passes already-
            # committed arrays — device_put onto the same sharding is a
            # no-op, so recovery moves no bytes, same as single-device.
            pshard = self.model.param_shardings(self._mesh,
                                                self._mesh_axis)
            self._kv_shard = self.model.kv_shardings(self._mesh,
                                                     self._mesh_axis)
            self._params = {k: jax.device_put(
                v if isinstance(v, jarr) else np.asarray(v),
                pshard.get(k, self._device))
                for k, v in params.items()}
        else:
            self._kv_shard = None
            self._params = {k: jax.device_put(
                v if isinstance(v, jarr) else np.asarray(v), self._device)
                for k, v in params.items()}
        # per-expert decode telemetry (serve.<name>.expert_load.<i>):
        # MoE programs return one extra (E,) counts row per launch,
        # drained LAZILY into a host accumulator so the gauge never
        # synchronizes an in-flight launch (megastep double-buffering)
        self._moe = bool(getattr(self.model, "moe_experts", 0))
        self._moe_pending = []
        self._moe_load = (np.zeros((self.model.moe_experts,), np.int64)
                          if self._moe else None)
        # (rows, hits) an expert folded since the last `iteration` record
        self._moe_since = (np.zeros((2, self.model.moe_experts), np.int64)
                           if self._moe else None)
        # `expert_load()` drains from the caller's thread while the
        # scheduler drains after every launch: a count is folded once
        self._moe_lock = threading.Lock()
        bs = int(os.environ.get("MXNET_SERVE_BLOCK_SIZE", "0")
                 if block_size is None else block_size)
        if bs < 0:
            raise MXNetError("ServingEngine: block_size must be >= 1")
        if bs == 0:
            # auto: the largest divisor of EVERY prefill bucket, capped
            # at 16 (the vLLM-ish default) — default buckets end at
            # seq_len itself, so e.g. seq_len=100 resolves to 4, not a
            # constructor error
            import math
            g = 0
            for s in self.prefill_buckets:
                g = math.gcd(g, s)
            bs = max(d for d in range(1, min(16, g) + 1) if g % d == 0)
        bad = [s for s in self.prefill_buckets if s % bs]
        if bad:
            raise MXNetError(
                "ServingEngine: block_size %d must divide every "
                "prefill bucket (violated by %s) — chunk starts and "
                "prefill scatters are block-aligned" % (bs, bad))
        self.block_size = bs
        # table width: enough entries to cover the full cache depth
        self._n_table = -(-model.seq_len // bs)
        nb = int(os.environ.get("MXNET_SERVE_N_BLOCKS", "0")
                 if n_blocks is None else n_blocks)
        if nb == 0:
            # default: max_batch rows at the full cache depth, and one
            # row's worth more that holds the trash block
            nb = (self.max_batch + 1) * self._n_table
        self.n_blocks = nb
        self._alloc = BlockAllocator(nb, bs)
        # per-sequence state that is not a run of token blocks
        # (docs/serving.md "A third kind of state"): a model that has one
        # (`state_slot_bytes`) gets a slot a batch row, and one more that
        # padding rows write to; the slot IS the row, taken at admission
        # and held until the sequence retires or is preempted.  The state
        # rides with the pool as one donated value; the programs take the
        # rows' slot indices beside their block tables.  Anyone else's
        # programs are what they were.
        self._state_slots = self.max_batch + 1 \
            if getattr(model, "state_slot_bytes", None) else 0
        self._cache = self._new_cache()
        # whether the decode programs attend with the paged Pallas
        # kernel: decided here, once, as their traces will (the pool,
        # the backend, this replica's mesh), for the `iteration`
        # record's `attn_kernel`
        self._attn_kernel = int(self._scoped(
            lambda: model.paged_decode_kernel(self._cache),
            "attn_kernel")())
        # and which prefill programs attend with theirs, a bucket at a
        # time, for the `prefill_chunk` span's
        self._prefill_kernel = {s: int(self._scoped(
            lambda s=s: model.paged_prefill_kernel(self._cache, s),
            "attn_kernel")()) for s in self.prefill_buckets}
        # whether the expert layers run as the Pallas kernel `expert_ffn`:
        # decided here, once, as the programs' traces will (the banks, the
        # backend, this replica's mesh; at the most rows a program routes),
        # for the `iteration` record's `expert_kernel`
        self._expert_kernel = int(self._moe and self._scoped(
            lambda: self._expert_kernel_applies(), "expert_kernel")())
        self._prefilling = {}  # row -> _Prefill (insertion-ordered)
        # cross-request prefix sharing (MXNET_SERVE_PREFIX=0 restores
        # single-owner paging bit-for-bit; MXNET_SERVE_PREFIX_POOL
        # caps the parked refcount-0 LRU pool, < 0 = bounded only by
        # allocation pressure)
        self._prefix_pool = int(
            os.environ.get("MXNET_SERVE_PREFIX_POOL", "-1")
            if prefix_pool is None else prefix_pool)
        prefix_on = _env_flag("MXNET_SERVE_PREFIX") if prefix is None \
            else bool(prefix)
        if "prefix" in getattr(model, "unsupported", ()):
            # a prefix hit skips the chunks that would build the state a
            # block does not hold: off unless asked for, refused if asked
            if prefix is None and "MXNET_SERVE_PREFIX" not in os.environ:
                prefix_on = False
            elif prefix_on:
                self._refuse("prefix", "prefix / MXNET_SERVE_PREFIX")
        # host-DRAM block tier (MXNET_SERVE_TIER, default OFF: =0 is
        # the PR-12 evict-and-recompute behavior bit-for-bit).  The
        # tier rides the prefix index — without it there is nothing
        # to spill — so prefix off forces tier off.
        tier_on = (_env_flag("MXNET_SERVE_TIER", "0") if tier is None
                   else bool(tier)) and prefix_on
        self._host_blocks = int(
            os.environ.get("MXNET_SERVE_HOST_BLOCKS", "256")
            if host_blocks is None else host_blocks)
        self._restore_ahead = int(
            os.environ.get("MXNET_SERVE_RESTORE_AHEAD", "2")
            if restore_ahead is None else restore_ahead)
        if tier_on:
            self._refuse("tier", "tier / MXNET_SERVE_TIER")
        self._tier = HostBlockTier(self._host_blocks) \
            if tier_on and self._host_blocks > 0 else None
        self._prefix = PrefixCache(
            bs, self._prefix_pool,
            spill_hook=self._spill_block if self._tier is not None
            else None,
            host_drop_hook=self._host_dropped if self._tier is not None
            else None) if prefix_on else None
        self._restoring = {}   # row -> _Restore (insertion-ordered)
        self._landing = {}     # row -> HandoffLanding (disagg)
        # speculative decoding (MXNET_SERVE_SPEC, default off: the
        # PR-10 single-token decode path is bit-for-bit untouched at 0)
        self._spec = _env_flag("MXNET_SERVE_SPEC", "0") if spec is None \
            else bool(spec)
        self._spec_k = int(os.environ.get("MXNET_SERVE_SPEC_K", "4")
                           if spec_k is None else spec_k)
        self._drafter_arg = spec_drafter
        self._drafter = None
        if self._spec:
            self._refuse("spec", "spec / MXNET_SERVE_SPEC")
            if self._spec_k < 1:
                raise MXNetError("ServingEngine: MXNET_SERVE_SPEC_K must "
                                 "be >= 1, got %d" % self._spec_k)
            self._drafter = make_drafter(
                os.environ.get("MXNET_SERVE_SPEC_DRAFTER", "ngram")
                if spec_drafter is None else spec_drafter)
            self._drafter.bind(self)
        # megastep decode (docs/serving.md "Megastep decode & streaming"):
        # MXNET_SERVE_MEGASTEP fuses m single-token decode launches into
        # ONE lax.scan launch with in-graph retirement, and the scheduler
        # runs its host sweep (retire/admission/journal) while the next
        # megastep is already in flight.  =0 (the default) is the PR-15
        # single-step loop bit-for-bit.
        mega_on = _env_flag("MXNET_SERVE_MEGASTEP", "0") if megastep \
            is None else bool(megastep)
        self._mega_m = 0
        if mega_on:
            self._refuse("megastep", "megastep / MXNET_SERVE_MEGASTEP")
            self._mega_m = int(
                os.environ.get("MXNET_SERVE_MEGASTEP_STEPS", "4")
                if megastep_steps is None else megastep_steps)
            if self._mega_m < 1:
                raise MXNetError(
                    "ServingEngine: MXNET_SERVE_MEGASTEP_STEPS must be "
                    ">= 1, got %d" % self._mega_m)
        # AotCache keys gain the mesh signature (executor._scoped): a
        # 2-shard and a 4-shard replica compile DIFFERENT partitioned
        # programs, so a shared cache must never cross their entries
        self._aot = aot if aot is not None else AotCache(
            "serve.aot", signature=mesh_signature(self._mesh))
        # gauges are namespaced per replica: engines share one process-wide
        # registry, and a global "serve.queue_depth" written by N scheduler
        # threads records whichever replica wrote last — neither any single
        # replica nor the aggregate
        self._gauge = "serve.%s." % self.name
        self._queue = deque()
        self._qlock = threading.Lock()
        self._qcond = threading.Condition(self._qlock)
        self._admitting = 0       # popped off _queue, prefill in flight
        self._iter = {}           # the iteration's counts, anew each step()
        # host seconds by phase since the last `iteration` record
        self._phase_s = dict.fromkeys(SCHED_PHASES, 0.0) \
            if tracing.enabled() else None
        self._active = {}         # row -> _Seq (insertion-ordered)
        self._free = list(range(self.max_batch))
        self._stopped = threading.Event()
        self._draining = False    # drain(): admission closed, queue serves out
        self._wake = threading.Event()  # set by submit(): work arrived
        self._thread = None
        self._dead = None         # scheduler-fatal error message, if any
        self._on_death = None     # router failover hook:
        #                           fn(engine, pending, inflight, msg)
        # disaggregated prefill/decode fleet (docs/serving.md
        # "Disaggregated prefill/decode"): the router assigns roles and
        # wires the hooks BEFORE warmup (a decode role decides which
        # restore buckets compile); role None = today's colocated
        # engine, bit for bit
        self.role = None          # None | "prefill" | "decode"
        self._handoff_sink = None      # router: fn(ticket) stages it on
        #                                a live decode replica or raises
        self._handoff_fallback = None  # router: fn(req) -> bool, the
        #                                journal exact-replay road
        self._handoff_inbox = deque()  # tickets received, not yet staged
        self._launch_fails = 0    # consecutive decode launch failures
        # anti-thrash preemption (docs/serving.md "Durability"): a resumed
        # sequence is exempt from re-preemption until it advances
        # min_progress tokens (0 = PR-9 preempt-on-every-denial), the
        # oldest in-flight request is never chosen as a victim, and
        # thrash_trip preemptions without a completion trip the PR-8
        # degrade path (0 = never trip)
        self._min_progress = int(
            os.environ.get("MXNET_SERVE_MIN_PROGRESS", "4")
            if min_progress is None else min_progress)
        self._thrash_trip = int(
            os.environ.get("MXNET_SERVE_THRASH_TRIP", "8")
            if thrash_trip is None else thrash_trip)
        self._stalled = set()     # rows sitting out THIS decode step
        self._preempts_since_retire = 0
        self._storm = False       # preemption storm: degrade admissions
        # session continuity (docs/serving.md "Memory tiering &
        # sessions"): key -> (full token history of the last COMPLETED
        # turn, last request).  LRU-capped; histories are host lists —
        # the K/V itself lives in the prefix index / host tier and is
        # reattached by the ordinary lookup at the follow-up submit.
        self._sessions = OrderedDict()
        self._session_cap = max(1, int(os.environ.get(
            "MXNET_SERVE_SESSION_CAP", "512")))
        # sessions are the one engine structure TWO threads touch: the
        # caller's submit (prompt expansion + live-turn record) and the
        # scheduler's retire (history store) — serialized here the way
        # _qlock serializes the queue
        self._slock = threading.Lock()
        self.last_beat = time.monotonic()  # scheduler heartbeat
        # bench accounting (host-side, touched only by the scheduler)
        self.stats = {"decode_steps": 0, "decode_rows": 0,
                      "decode_padded": 0, "prefills": 0, "completed": 0,
                      "tokens": 0, "prefill_chunks": 0, "preemptions": 0,
                      "alloc_denied": 0, "max_concurrent": 0,
                      "blocks_free_min": self._alloc.free_blocks,
                      # prefix caching (0s when disabled)
                      "prefix_hits": 0, "prefix_tokens": 0,
                      "prefix_lookup_tokens": 0, "prefix_bootstraps": 0,
                      "cow_copies": 0, "prefix_evictions": 0,
                      # speculative decoding (0s when disabled)
                      "verify_steps": 0, "spec_proposed": 0,
                      "spec_accepted": 0, "spec_rollbacks": 0,
                      "spec_junk_rounds": 0,
                      # durability (journal replay / drain / anti-thrash)
                      "replays": 0, "stalls": 0, "thrash_trips": 0,
                      # memory tiering + sessions (0s when disabled)
                      "spilled": 0, "restored": 0, "restored_tokens": 0,
                      "spill_fails": 0, "restore_fails": 0,
                      # disaggregated prefill/decode (0s when off)
                      "handoffs": 0, "handoffs_in": 0, "handoff_fails": 0,
                      "prefill_tokens": 0, "session_hits": 0,
                      "session_turns": 0,
                      # quantization (0s when disabled)
                      "quant_trips": 0, "scale_corrupts": 0,
                      # sparse experts (0s for a dense model): (row, expert)
                      # pairs the launches routed over all experts, and
                      # those of them that fell on experts held here
                      "moe_pairs_routed": 0, "moe_pairs_held": 0,
                      # decode-loop accounting behind the host_frac
                      # gauge: hidden_s spans launch-dispatch -> fetch-
                      # complete (host work inside it rides under the
                      # in-flight launch for free), host_s is the
                      # EXPOSED remainder the device pipeline is not
                      # covering — the thing double-buffering shrinks
                      "megasteps": 0, "megastep_tokens": 0,
                      "ingraph_retired": 0, "wall_s": 0.0,
                      "host_s": 0.0, "hidden_s": 0.0,
                      "fetch_wait_s": 0.0}

    def _new_cache(self):
        """The zeroed paged pool (also the rebuild's allocation), with the
        per-sequence state beside it where the model has one."""
        pool = self.model.init_block_pool(self.n_blocks, self.block_size,
                                          device=self._kv_device())
        if not self._state_slots:
            return pool
        return pool, self.model.init_state(self._state_slots,
                                           device=self._device)

    def _slots(self, rows, b):
        """The trailing launch operand of a model with per-sequence state:
        each row's slot, the spare slot for a bucket's padding rows.  ()
        for everyone else."""
        if not self._state_slots:
            return ()
        slots = np.full((b,), self._state_slots - 1, np.int32)
        slots[:len(rows)] = rows
        return (slots,)

    def _split_state(self, rest):
        """A program's operands after its block tables, as (the keyword its
        model takes the slots by, the sampling arrays)."""
        if not self._state_slots:
            return {}, rest
        return {"slots": rest[0]}, rest[1:]

    def _refuse(self, option, how):
        """Raise if the model lists ``option`` among those it cannot serve
        yet (`LatentMoEKVModel.unsupported`): by name, at construction,
        instead of mis-reading its pool later."""
        if option in getattr(self.model, "unsupported", ()):
            raise MXNetError(
                "ServingEngine: %s does not serve with %s yet (asked for by "
                "%s)" % (type(self.model).__name__, option, how))

    # -- program building --------------------------------------------------
    _SAMPLE_NAMES = ("temp", "top_k", "top_p", "seed")
    _PREFILL_NAMES = ("tokens", "start", "length", "tables")
    _DECODE_NAMES = ("token", "pos", "tables")

    def _tail_names(self, samp):
        """Watchdog names of a launch's operands after its block tables."""
        return ("slots",) * bool(self._state_slots) \
            + self._SAMPLE_NAMES[:len(samp)]

    def _sample_placeholders(self, b):
        """Per-row sampling arrays for lowering/watch signatures — empty
        when sampling programs are disabled (the PR-7 signatures)."""
        if not self._sampling:
            return ()
        return (np.zeros((b,), np.float32), np.zeros((b,), np.int32),
                np.ones((b,), np.float32), np.zeros((b,), np.uint32))

    def _quant_guard(self, logits, picked):
        """The in-graph quantization logit gate (docs/serving.md
        "Quantization"): with quant on, a row whose logits are
        nonfinite or implausibly large (`MXNET_SERVE_QUANT_LOGIT_MAX`)
        — corrupted per-block scales, the `scale_corrupt:P` chaos
        clause, or a genuine quantization blow-up — emits the sentinel
        token -1 instead of an unverifiable argmax.  The scheduler
        converts the sentinel into a typed requeue/quarantine
        (`_quant_trip_req`): NEVER a silent wrong token.  Quant off
        compiles no guard — the PR-13 tail bit for bit."""
        if not self._quant_gate:
            return picked
        bad = ~jnp.all(jnp.isfinite(logits), axis=-1) | \
            (jnp.max(jnp.abs(logits), axis=-1) > self._quant_logit_max)
        return jnp.where(bad, jnp.int32(-1), picked)

    def _pick(self, logits, samp, newpos):
        """The compiled program's token-selection tail.  ``newpos`` is
        the absolute position the chosen token will occupy — the RNG
        fold key, so chunked/unchunked prefill and preempt-resume draw
        identical sequences.  Greedy-only programs argmax (bit-for-bit
        the PR-7 tail)."""
        if not self._sampling:
            with jax.named_scope("sampler"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return self._quant_guard(logits, greedy)
        temp, top_k, top_p, seed = samp
        return self._quant_guard(
            logits, sample_tokens(logits, temp, top_k, top_p, seed,
                                  newpos))

    def _compiled_prefill(self, s_bucket):
        def build():
            def prog(params, pool, tokens, start, length, tables, *samp):
                tape = []
                slots, samp = self._split_state(samp)
                logits, pool = self.model.prefill_paged(
                    params, pool, tokens, start, length, tables,
                    moe_tape=tape, **slots)
                return (self._pick(logits, samp, start + length),
                        pool) + self._moe_out(tape)

            fn = self._jit(prog, (1,), ("repl", "cache")
                           + ("repl",) * self._moe,
                           "serve_prefill_s%d" % s_bucket)
            toks = self._put(np.zeros((1, s_bucket), np.int32))
            zero = self._put(np.zeros((1,), np.int32))
            one = self._put(np.ones((1,), np.int32))
            tables = self._put(np.zeros((1, self._n_table), np.int32))
            samp = tuple(self._put(a) for a in self._slots((), 1)
                         + self._sample_placeholders(1))
            return fn.lower(self._params, self._cache, toks, zero,
                            one, tables, *samp).compile()

        return self._aot.get(("prefill_paged", 1, s_bucket), build)

    def _compiled_decode(self, b_bucket):
        def build():
            def prog(params, pool, token, pos, tables, *samp):
                tape = []
                slots, samp = self._split_state(samp)
                logits, pool = self.model.decode_paged(
                    params, pool, token, pos, tables, moe_tape=tape,
                    **slots)
                return (self._pick(logits, samp, pos + 1),
                        pool) + self._moe_out(tape)

            fn = self._jit(prog, (1,), ("repl", "cache")
                           + ("repl",) * self._moe,
                           "serve_decode_b%d" % b_bucket)
            z = self._put(np.zeros((b_bucket,), np.int32))
            tables = self._put(np.zeros((b_bucket, self._n_table),
                                        np.int32))
            samp = tuple(self._put(a)
                         for a in self._slots((), b_bucket)
                         + self._sample_placeholders(b_bucket))
            return fn.lower(self._params, self._cache, z, z, tables,
                            *samp).compile()

        return self._aot.get(("decode_paged", b_bucket, 1), build)

    def _compiled_mega(self, b_bucket):
        """The m-step fused decode megastep (docs/serving.md "Megastep
        decode & streaming"): ONE launch scans ``self._mega_m`` copies
        of the single-token decode body with per-row active masks, so
        EOS / max_new_tokens / cache-depth retirement happens in-graph
        mid-scan.  Output is a (b, m) int32 token grid: >=0 real token,
        -1 quant trip (earlier emits stand), -2 dead row.  Sampling
        folds the carried position per scan step, so the grid is
        bit-identical to m sequential single-step launches."""
        m = self._mega_m

        def build():
            def prog(params, pool, token, pos, left, eos, tables, *samp):
                def pick(logits, newpos):
                    return self._pick(logits, samp, newpos)
                tape = []
                toks, pool = self.model.decode_megastep(
                    params, pool, token, pos, left, eos, tables, m, pick,
                    moe_tape=tape)
                return (toks, pool) + self._moe_out(tape)

            fn = self._jit(prog, (1,), ("repl", "cache")
                           + ("repl",) * self._moe,
                           "serve_mega_b%d" % b_bucket)
            z = self._put(np.zeros((b_bucket,), np.int32))
            tables = self._put(np.zeros((b_bucket, self._n_table),
                                        np.int32))
            samp = tuple(self._put(a)
                         for a in self._sample_placeholders(b_bucket))
            return fn.lower(self._params, self._cache, z, z, z, z,
                            tables, *samp).compile()

        return self._aot.get(("megastep", b_bucket, m), build)

    def _pick_cols(self, logits, samp, pos):
        """`_pick` over a (b, c, vocab) verify chunk: column j's token
        will occupy absolute position pos + j + 1 — the same RNG fold
        keys sequential decode would have used, which is exactly why a
        verified prefix is bit-identical to the non-speculative path."""
        b, c, v = logits.shape
        if not self._sampling:
            with jax.named_scope("sampler"):
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return self._quant_guard(logits, greedy)
        newpos = pos.astype(jnp.int32)[:, None] + 1 + \
            jnp.arange(c, dtype=jnp.int32)[None]
        temp, top_k, top_p, seed = (jnp.repeat(a, c, axis=0) for a in samp)
        flat = sample_tokens(logits.reshape(b * c, v), temp, top_k, top_p,
                             seed, newpos.reshape(-1))
        return self._quant_guard(logits, flat.reshape(b, c))

    def _compiled_verify(self, b_bucket):
        """The draft-verify step: ONE launch scores a whole draft run
        (`verify_paged`), picks the target's own token at every fed
        position, and counts in-graph how many leading drafts match.
        Output rows are [picked_0 .. picked_k, n_accepted] — a single
        (b, k+2) host fetch, the same per-step traffic discipline as
        plain decode."""
        c = self._spec_k + 1

        def build():
            def prog(params, pool, tokens, pos, length, tables, *samp):
                tape = []
                logits, pool = self.model.verify_paged(
                    params, pool, tokens, pos, length, tables,
                    moe_tape=tape)
                picked = self._pick_cols(logits, samp, pos)
                draft = tokens[:, 1:].astype(jnp.int32)
                match = (picked[:, :-1] == draft).astype(jnp.int32)
                acc = jnp.sum(jnp.cumprod(match, axis=1),
                              axis=1).astype(jnp.int32)
                return (jnp.concatenate([picked, acc[:, None]], axis=1),
                        pool) + self._moe_out(tape)

            fn = self._jit(prog, (1,), ("repl", "cache")
                           + ("repl",) * self._moe,
                           "serve_verify_b%d" % b_bucket)
            toks = self._put(np.zeros((b_bucket, c), np.int32))
            z = self._put(np.zeros((b_bucket,), np.int32))
            one = self._put(np.ones((b_bucket,), np.int32))
            tables = self._put(np.zeros((b_bucket, self._n_table),
                                        np.int32))
            samp = tuple(self._put(a)
                         for a in self._sample_placeholders(b_bucket))
            return fn.lower(self._params, self._cache, toks, z, one,
                            tables, *samp).compile()

        return self._aot.get(("verify", b_bucket, c), build)

    def _verify_watch_arrays(self, b):
        toks = np.zeros((b, self._spec_k + 1), np.int32)
        z = np.zeros((b,), np.int32)
        tables = np.zeros((b, self._n_table), np.int32)
        samp = self._sample_placeholders(b)
        return ((toks, z, z, tables) + samp,
                ("tokens", "pos", "length", "tables")
                + self._SAMPLE_NAMES[:len(samp)])

    def _compiled_cow(self):
        """The copy-on-write body: one block's rows copied pool→pool
        (every layer, K and V) with the pool donated — in-place on the
        device, zero host traffic.  ONE fixed shape regardless of
        buckets, compiled at warmup like everything else, so CoW adds
        nothing to steady state."""
        def build():
            def prog(pool, src, dst):
                return self.model.copy_block(pool, src, dst)

            fn = self._jit(prog, (0,), ("cache",), "serve_cow")
            z = self._put(np.zeros((1,), np.int32))
            return fn.lower(self._cache, z, z).compile()

        return self._aot.get(("cow", 1, 1), build)

    def _cow_watch_arrays(self):
        z = np.zeros((1,), np.int32)
        return (z, z), ("src", "dst")

    def _compiled_restore(self, kb):
        """The host-tier restore body: a whole staged run of K/V blocks
        scattered into the pool (every layer, K and V) with the pool
        donated — ONE launch per restored prefix, not one per block
        (per-block writes would pay k dispatches to replace the single
        prefill launch a recompute costs; the batched scatter keeps the
        restore cheaper than the recompute on dispatch-bound backends
        too).  Runs pad up to a few power-of-two k-buckets (padding
        entries scatter into the trash block), all compiled at warmup
        like `cow`, so the restore path adds nothing to steady state —
        its real cost is the PCIe transfer, which rode under the
        previous iteration's decode launch."""
        def build():
            def prog(pool, dst, data):
                return self.model.write_block(pool, dst, data)

            fn = self._jit(prog, (0,), ("cache",), "serve_restore_k%d" % kb)
            z = self._put(np.zeros((kb,), np.int32))
            d = self._put_run(self.model.block_run_placeholder(
                kb, self.block_size))
            return fn.lower(self._cache, z, d).compile()

        return self._aot.get(("tier_restore", kb, 1), build)

    def _restore_buckets(self):
        """Power-of-two restore run lengths up to the table width."""
        out, k = [], 1
        while k < self._n_table:
            out.append(k)
            k *= 2
        out.append(k)
        return out

    def _restore_bucket(self, n):
        for k in self._restore_buckets():
            if k >= n:
                return k
        raise MXNetError(
            "ServingEngine %s: restore run %d exceeds the table width %d"
            % (self.name, n, self._n_table))

    def _restore_watch_arrays(self, kb):
        ph = self.model.block_run_placeholder(kb, self.block_size)
        ph = ph if isinstance(ph, tuple) else (ph,)
        return ((np.zeros((kb,), np.int32),) + ph,
                ("dst", "data", "data_scale")[:1 + len(ph)])

    def _put(self, a):
        """Host→device staging for launch operands: the single device —
        or, on a sub-mesh replica, the REPLICATED mesh sharding
        (`self._device` doubles as it).  Lowering bakes committed-input
        shardings into the compiled executable's signature, so warmup
        placeholders and live operands must stage identically — which
        this one chokepoint (plus `_put_run` for block runs)
        guarantees."""
        return jax.device_put(a, self._device)

    def _put_run(self, data):
        """Stage a packed K/V block run (the restore / handoff payload,
        an array or the (int8 data, scales) pair): sharded exactly like
        the pool it scatters into on a sub-mesh replica — the run's
        trailing axis IS the pool's embed axis — replicated `_put`
        otherwise.  Used by both the live staging sites and
        `_compiled_restore`'s lowering placeholder, so the compiled
        scatter's committed-input sharding always matches."""
        if self._mesh is None:
            return self._put(data)
        psh, ssh = self._kv_shard
        if isinstance(data, tuple):
            return (jax.device_put(data[0], psh),
                    jax.device_put(data[1], ssh))
        return jax.device_put(data, psh)

    def _kv_device(self):
        """Placement for the K/V buffers: the (pool, scales) sharding
        pair on a sub-mesh replica — `init_block_pool` splits it — the
        plain device otherwise."""
        return self._device if self._mesh is None else self._kv_shard

    def _cache_sharding(self):
        """The sharding pytree of `self._cache` as the compiled
        programs see it (mesh mode only): the (pool, scales) pair under
        KV quant, the single pool sharding otherwise."""
        psh, ssh = self._kv_shard
        if self.model.kv_quant is not None:
            return (psh, ssh)
        return psh

    def _jit(self, prog, donate, outs, name):
        """`jax.jit` of ``prog`` under ``name`` — the profiler's "XLA
        Modules" line then reads `jit_serve_decode_b32(...)`, so a decode
        launch is told from a prefill chunk — with EXPLICIT output
        shardings on a sub-mesh replica: the donated cache comes back in
        its input sharding (anything else would defeat donation) and
        token/count outputs land replicated for the host's
        one-fetch-per-step discipline.  ``outs`` names each output:
        "repl" or "cache"."""
        prog = self._scoped(prog, name)
        if self._mesh is None:
            return jax.jit(prog, donate_argnums=donate)
        m = {"repl": self._device, "cache": self._cache_sharding()}
        sh = tuple(m[o] for o in outs)
        return jax.jit(prog, donate_argnums=donate,
                       out_shardings=sh if len(sh) > 1 else sh[0])

    def _scoped(self, prog, name):
        """``prog`` as the function to jit, called ``name`` (the jitted
        program's name in a profiler trace) — with this replica's mesh
        scoped over its trace, so the Pallas kernels — which GSPMD cannot
        partition — run per device under shard_map
        (ops/pallas_kernels/_spmd.py).  Single-device engines get
        ``prog`` itself back."""
        fn = prog
        if self._mesh is not None:
            def fn(*args):
                with MeshContext(self._mesh):
                    return prog(*args)

        fn.__name__ = fn.__qualname__ = name
        return fn

    def _expert_kernel_applies(self):
        """`ops.moe.expert_kernel_applies` for the first expert layer's
        banks, at the most rows a decode or prefill program routes; False
        for a model whose experts are not `ops.moe`'s (`TransformerKVModel`'s
        top-1 mixture, dispatched densely, names no ``moe_gate``)."""
        from ..ops.moe import expert_kernel_applies

        gates = sorted(k for k in self._params if k.endswith("moe_gate"))
        if not gates:
            return False
        banks = tuple(self._params[gates[0][:-4] + m]
                      for m in ("gate", "up", "down"))
        rows = max(max(self.decode_buckets), max(self.prefill_buckets))
        return expert_kernel_applies(rows, banks)

    def _moe_out(self, tape):
        """The MoE programs' extra output, ONE (2, E) array a launch: the
        per-expert routed-row counts summed over the tape's entries (one a
        layer; a megastep's one entry is already summed over its steps),
        and in how many of the entries each expert had a row at all (the
        expert matrices a launch has to read).  Dense models return () —
        their programs stay byte-identical to PR 19."""
        if not self._moe:
            return ()
        tape = jnp.stack(tape)
        return (jnp.stack([jnp.sum(tape, axis=0),
                           jnp.sum(tape > 0, axis=0, dtype=tape.dtype)]),)

    def _unpack(self, out):
        """Split a compiled launch's outputs into (tokens, new_cache),
        diverting a MoE program's counts row into the pending list
        WITHOUT synchronizing — `_drain_moe` folds all but the newest
        entry later, so megastep double-buffering keeps its overlap."""
        if self._moe:
            first, cache, counts = out
            self._moe_pending.append(counts)
            return first, cache
        return out

    def _drain_moe(self, keep_last=True):
        """Fold pending per-launch expert counts into the host
        accumulator and publish the `serve.<name>.expert_load.<i>`
        gauges.  ``keep_last`` leaves the newest pending — it may
        belong to a launch still in flight."""
        if not self._moe:
            return
        with self._moe_lock:
            pend = self._moe_pending
            n = len(pend) - 1 if keep_last else len(pend)
            if n <= 0:
                return
            folded = np.sum([np.asarray(a) for a in pend[:n]], axis=0)
            del pend[:n]
            self._moe_since += folded
            self._moe_load += folded[0]
            self.stats["moe_pairs_held"] += int(folded[0].sum())
            for i, v in enumerate(self._moe_load):
                telemetry.set_gauge(self._gauge + "expert_load.%s" % i,
                                    int(v))

    def _moe_record(self):
        """The `iteration` record's attributes for the expert counts folded
        since the last record (a chunk launched by an iteration that wrote
        none counts in the next): ``expert_rows`` (row-expert pairs that
        fell on held experts), ``expert_load_max`` (the fullest expert's
        rows), ``expert_hits`` ((layer, expert) pairs with a row at all:
        the expert matrices read) and ``expert_kernel`` (1 where the expert
        layers run as the Pallas kernel `expert_ffn`)."""
        if not self._moe:
            return {}
        rows, hits = self._moe_since
        out = {"expert_rows": int(rows.sum()),
               "expert_load_max": int(rows.max()),
               "expert_hits": int(hits.sum()),
               "expert_kernel": self._expert_kernel}
        self._moe_since[:] = 0
        return out

    def expert_load(self):
        """Cumulative per-expert routed-token counts as a host array
        (None for dense models).  Drains every pending launch —
        synchronizes, so it's a bench/test/report surface, not a
        scheduler-loop call."""
        if not self._moe:
            return None
        self._drain_moe(keep_last=False)
        with self._moe_lock:
            return self._moe_load.copy()

    def memory_footprint(self):
        """Device-memory accounting for params + K/V buffers:
        ``total_bytes`` (the whole replica) vs ``per_device_bytes``
        (the largest single device's share).  The nightly sharded
        gate's proof obligation reads off this: a config serves on the
        sub-mesh exactly when per_device_bytes fits one device's HBM
        even though total_bytes does not."""
        per = {}
        total = 0
        for a in jax.tree_util.tree_leaves((self._params, self._cache)):
            if not hasattr(a, "dtype"):
                continue
            total += int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
            for s in getattr(a, "addressable_shards", ()) or ():
                nb = int(np.prod(s.data.shape)) \
                    * np.dtype(s.data.dtype).itemsize
                d = getattr(s, "device", None)
                per[d] = per.get(d, 0) + nb
        return {"total_bytes": int(total),
                "per_device_bytes": int(max(per.values()) if per
                                        else total),
                "devices": len(per) if per else 1}

    def _prefill_watch_arrays(self, s):
        """(arrays, names) of a prefill launch at bucket ``s`` — the
        watchdog signature warmup seeds and live launches must match."""
        toks = np.zeros((1, s), np.int32)
        one = np.ones((1,), np.int32)
        samp = self._sample_placeholders(1)
        tables = np.zeros((1, self._n_table), np.int32)
        return ((toks, one, one, tables) + self._slots((), 1) + samp,
                self._PREFILL_NAMES + self._tail_names(samp))

    def _decode_watch_arrays(self, b):
        z = np.zeros((b,), np.int32)
        samp = self._sample_placeholders(b)
        tables = np.zeros((b, self._n_table), np.int32)
        return ((z, z, tables) + self._slots((), b) + samp,
                self._DECODE_NAMES + self._tail_names(samp))

    def _mega_watch_arrays(self, b):
        z = np.zeros((b,), np.int32)
        samp = self._sample_placeholders(b)
        tables = np.zeros((b, self._n_table), np.int32)
        return ((z, z, z, z, tables) + samp,
                ("token", "pos", "left", "eos", "tables")
                + self._SAMPLE_NAMES[:len(samp)])

    def warmup(self):
        """AOT-compile every bucket shape up front, and pre-seed the
        retrace watchdog with each bucket's call signature (the watchdog
        counts every post-warmup NEW signature as a recompile — the whole
        bucket set is warmup here, so only a shape that ESCAPED the
        bucketing fires an event).  After warmup, `serve.aot.compiles`
        advancing or a `serving.*` retrace event means exactly that bug.
        A respawned replica warms from the dead incarnation's shared
        AotCache, so recovery hits every key and compiles nothing.
        The cache is also FROZEN here: any later build additionally
        counts `serve.aot.frozen_compiles` — the zero-steady-state-
        compile gate, asserted at the cache itself.  Chunked prefill
        adds no shapes: every chunk is one of these prefill buckets."""
        for s in self.prefill_buckets:
            self._compiled_prefill(s)
            arrays, names = self._prefill_watch_arrays(s)
            self._watch("prefill", arrays, names, s, seed=True)
        for b in self.decode_buckets:
            self._compiled_decode(b)
            arrays, names = self._decode_watch_arrays(b)
            self._watch("decode", arrays, names, b, seed=True)
        if self._spec:
            # the verify (b, k+1) shapes — and the drafter's own
            # programs — JOIN the decode bucket set (plain decode stays
            # compiled: it is the no-usable-draft fallback round), all
            # compiled and watchdog-seeded here so `AotCache.freeze()`
            # still means "steady state compiles nothing" with
            # speculation on
            for b in self.decode_buckets:
                self._compiled_verify(b)
                arrays, names = self._verify_watch_arrays(b)
                self._watch("verify", arrays, names, b, seed=True)
                darrays, dnames = self._decode_watch_arrays(b)
                self._watch("draft", darrays, dnames, b, seed=True)
            self._drafter.warmup()
        if self._mega_m:
            # every (bucket, m) megastep shape joins the frozen set —
            # steady state with megastep on compiles nothing, same gate
            # as plain decode
            for b in self.decode_buckets:
                self._compiled_mega(b)
                arrays, names = self._mega_watch_arrays(b)
                self._watch("megastep", arrays, names, b, seed=True)
        if self._prefix is not None:
            self._compiled_cow()
            arrays, names = self._cow_watch_arrays()
            self._watch("cow", arrays, names, 1, seed=True)
        if self._tier is not None or self.role == "decode":
            # the restore writes join the frozen set too: a host hit in
            # steady state compiles nothing, it only transfers.  A
            # decode-role replica needs the same bucketed scatters for
            # handoff landings even without a host tier — the router
            # wires roles BEFORE warmup precisely so this gate sees them
            for kb in self._restore_buckets():
                self._compiled_restore(kb)
                arrays, names = self._restore_watch_arrays(kb)
                self._watch("restore", arrays, names, kb, seed=True)
        self._aot.freeze()
        return {"prefill": list(self.prefill_buckets),
                "decode": list(self.decode_buckets),
                "cache": "paged",
                "block_size": self.block_size, "n_blocks": self.n_blocks,
                "prefix": self._prefix is not None,
                "tier": None if self._tier is None else
                {"host_blocks": self._tier.capacity,
                 "restore_ahead": self._restore_ahead},
                "spec": None if not self._spec else
                {"k": self._spec_k, "drafter": self._drafter.name},
                "megastep": None if not self._mega_m else
                {"m": self._mega_m},
                "quant": None if not self._quant_gate else
                {"weights": None if self._quant is None
                 else self._quant.name,
                 "kv": None if self._kv_quant is None
                 else self._kv_quant.name}}

    def respawn(self, name=None):
        """A replacement engine for this (dead) replica: same device,
        geometry, name, and admission config; params SHARED (already on
        the device, no host round-trip); the compiled AOT set SHARED, so
        the replacement's `warmup()` re-seeds the watchdog but compiles
        nothing new; fresh K/V cache and row state.  ``name`` overrides
        the replica name — the autoscaler's scale-up templates a NEW
        replica off a live one, which must not collide with it in the
        per-replica gauges or the chaos step counters."""
        return ServingEngine(
            self.model, self._params,
            ctx=self._mesh if self._mesh is not None else self._device,
            max_batch=self.max_batch,
            decode_buckets=list(self.decode_buckets),
            prefill_buckets=list(self.prefill_buckets),
            max_new_tokens=self.max_new_default, eos_id=self.eos_id,
            name=self.name if name is None else name,
            queue_max=self._queue_max,
            overload=self._overload,
            deadline_ms=self._deadline_ms_default, aot=self._aot,
            block_size=self.block_size, n_blocks=self.n_blocks,
            sampling=self._sampling, prefix=self._prefix is not None,
            prefix_pool=self._prefix_pool, spec=self._spec,
            spec_k=self._spec_k,
            spec_drafter=self._drafter_arg if self._drafter_arg is not None
            else (self._drafter.name if self._drafter is not None
                  else None),
            min_progress=self._min_progress, thrash_trip=self._thrash_trip,
            tier=self._tier is not None, host_blocks=self._host_blocks,
            restore_ahead=self._restore_ahead,
            quant=self._quant if self._quant is not None else "0",
            kv_quant=self._kv_quant if self._kv_quant is not None
            else "0",
            megastep=bool(self._mega_m),
            megastep_steps=self._mega_m or None)

    # -- request intake ----------------------------------------------------
    def has_session(self, key):
        """Whether this engine holds session ``key``'s history (the
        router's affinity signal: a follow-up lands where the K/V
        likely still is — device-resident, or a host-tier restore)."""
        with self._slock:
            return key in self._sessions

    def _session_prompt(self, key, prompt):
        """Prepend session ``key``'s stored history to this turn's
        ``prompt`` (docs/serving.md "Memory tiering & sessions").  The
        expanded prompt flows through ordinary admission, so the prefix
        lookup reattaches the previous turns' cached blocks — device-
        or host-resident — and only the new suffix prefills.  A first
        turn (unknown key) passes through unchanged.  Submitting the
        next turn while the previous one is unresolved raises: the
        history it would build on does not exist yet, and silently
        using the older one would diverge the conversation.  (`_retire`
        stores the history BEFORE `_finish` sets done, so a prev.done
        observed here always sees its completed history.)

        Passing the guard CLAIMS the turn atomically (a `_SessionClaim`
        becomes the live entry under the lock), so two racing submits
        of the same session cannot both pass — the loser raises typed.
        The claim resolves in `submit`: `_session_record` on success,
        `_session_unclaim` when admission sheds/raises."""
        with self._slock:
            ent = self._sessions.get(key)
            if ent is None:
                return prompt
            hist, prev = ent
            if prev is not None and not prev.done:
                raise MXNetError(
                    "ServingEngine %s: session %r has an unresolved turn "
                    "(request %d) — wait for its result before submitting "
                    "the next turn" % (self.name, key, prev.id))
            self._sessions[key] = (hist, _SessionClaim(prev))
            self._sessions.move_to_end(key)
            hist = list(hist)
        return hist + [int(t) for t in np.asarray(prompt).reshape(-1)]

    def _session_record(self, key, req):
        """The claimed turn was ADMITTED: the request replaces the
        claim as the session's live entry (the liveness guard), under
        the LRU cap; history only advances at `_session_store`.
        Follow-up hits count HERE — at the landing, like prefix hits —
        so a shed submit can never inflate `session_hits`."""
        with self._slock:
            ent = self._sessions.get(key)
            hist = ent[0] if ent is not None else []
            self._sessions[key] = (hist, req)
            self._sessions.move_to_end(key)
            self.stats["session_turns"] += 1
            if hist:
                self.stats["session_hits"] += 1
            self._trim_sessions_locked()
        if hist:
            self._count("session_hits")

    def _session_unclaim(self, key):
        """Admission shed/raised after the claim: restore the previous
        resolved turn as the live entry — the conversation is exactly
        as it was, retryable."""
        with self._slock:
            ent = self._sessions.get(key)
            if ent is not None and isinstance(ent[1], _SessionClaim):
                self._sessions[key] = (ent[0], ent[1].prev)

    def _session_store(self, req):
        """A session turn completed: its FULL history (expanded prompt
        + every generated token) becomes the context the next turn
        builds on.  The K/V needs no copy — the full blocks are
        registered in the prefix index already, park at release, and
        spill to the host tier under pressure.  Runs on the scheduler
        thread, BEFORE `_finish` flips done (so the liveness guard can
        never admit a follow-up against a missing history)."""
        with self._slock:
            self._sessions[req.session] = (
                list(req.prompt) + [int(t) for t in req.tokens], req)
            self._sessions.move_to_end(req.session)
            self._trim_sessions_locked()

    def _trim_sessions_locked(self):
        """Enforce `MXNET_SERVE_SESSION_CAP` (caller holds `_slock`) —
        every insert path trims, so migrated turns retiring here count
        against the cap exactly like local submits."""
        while len(self._sessions) > self._session_cap:
            self._sessions.popitem(last=False)

    def submit(self, prompt, max_new_tokens=None, eos_id=None,
               deadline_ms=None, temperature=0.0, top_k=0, top_p=1.0,
               seed=None, session=None, on_token=None, _count_shed=True):
        if session is None:
            return self._submit(prompt, max_new_tokens, eos_id,
                                deadline_ms, temperature, top_k, top_p,
                                seed, None, on_token, _count_shed)
        prompt = self._session_prompt(session, prompt)  # claims the turn
        try:
            return self._submit(prompt, max_new_tokens, eos_id,
                                deadline_ms, temperature, top_k, top_p,
                                seed, session, on_token, _count_shed)
        except BaseException:
            # shed/rejected after the claim: the conversation reverts to
            # exactly its pre-submit state — retryable, never bricked
            self._session_unclaim(session)
            raise

    def _submit(self, prompt, max_new_tokens, eos_id, deadline_ms,
                temperature, top_k, top_p, seed, session, on_token,
                _count_shed):
        if max_new_tokens is None:
            max_new_tokens = self.max_new_default
        elif int(max_new_tokens) < 1:
            # every request samples at least its first token at prefill;
            # reject rather than silently substituting the default
            raise MXNetError("ServingEngine: max_new_tokens must be >= 1, "
                             "got %s" % max_new_tokens)
        if deadline_ms is None:
            deadline_ms = self._deadline_ms_default
        if temperature and not self._sampling:
            raise MXNetError(
                "ServingEngine: sampling programs are disabled "
                "(MXNET_SERVE_SAMPLING=0) — temperature > 0 unsupported")
        req = ServeRequest(prompt, max_new_tokens,
                           self.eos_id if eos_id is None else eos_id,
                           deadline_ms=deadline_ms,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p, seed=seed, session=session)
        req._on_token = on_token
        if len(req.prompt) >= self.model.seq_len:
            raise MXNetError(
                "ServingEngine: prompt length %d leaves no room to "
                "generate (seq_len %d)" % (len(req.prompt),
                                           self.model.seq_len))
        # a request whose WORST-CASE footprint exceeds the whole pool
        # can only ever end in a preemption livelock — reject typed
        # at the door (transient pressure is not this: it queues,
        # retries, or preempts+requeues instead)
        worst = min(len(req.prompt) + req.max_new_tokens,
                    self.model.seq_len)
        need = self._alloc.blocks_for(worst)
        if need > self._alloc.capacity:
            telemetry.inc("serve.blocks_rejected")
            raise ServeBlocksExhausted(
                "ServingEngine %s: request needs up to %d cache "
                "blocks but the pool only has %d usable "
                "(n_blocks=%d, block_size=%d)"
                % (self.name, need, self._alloc.capacity,
                   self.n_blocks, self.block_size))
        telemetry.inc("serve.sampled_requests" if req.temperature > 0
                      else "serve.greedy_requests")
        if self._queue_max > 0 and self._overload == "block":
            self._enqueue_blocking(req)
        else:
            self._enqueue(req, count_shed_global=_count_shed)
        if session is not None:
            # only an ADMITTED request becomes the session's live turn:
            # a shed/raise above leaves the session exactly as it was
            self._session_record(session, req)
        # counted at the submit door only: failover re-dispatch and chaos
        # floods reuse _enqueue but are not new offered requests (they
        # have serve.redispatched / serve.chaos_flooded of their own)
        telemetry.inc("serve.requests")
        return req

    def _count(self, what, n=1):
        telemetry.inc("serve.%s" % what, n)
        telemetry.inc(self._gauge + what, n)

    def _admission_shed(self, depth, count_global=True):
        """Overload decision for one enqueue at queue depth `depth`.
        Returns a degrade token-cap (or None) — raises `ServeOverload`
        when the request should shed.  Called under `_qlock`.

        ``count_global=False`` (the router's dispatch/redispatch paths,
        which retry other replicas) bumps only the per-replica shed
        counter: process-wide ``serve.shed`` counts REQUESTS finally
        rejected, not per-replica attempts."""
        if self._queue_max <= 0 or depth < self._queue_max:
            return None
        if self._overload == "degrade" and depth < 4 * self._queue_max:
            # cap generation length under pressure instead of shedding;
            # the 4x backstop bounds the queue even under a flood
            return max(1, self.max_new_default // 4)
        telemetry.inc(self._gauge + "shed")
        if count_global:
            telemetry.inc("serve.shed")
        raise ServeOverload(
            "ServingEngine %s: queue full (%d >= %d, policy %s)"
            % (self.name, depth, self._queue_max, self._overload))

    def _check_alive_locked(self):
        """Raise `ServeEngineDead` on a dead/stopped engine.  Must run
        under `_qlock` — the same lock `_die`/`stop` drain under, so a
        request can never slip in after the drain and hang."""
        if self._dead is not None:
            raise ServeEngineDead("ServingEngine %s: scheduler died: %s"
                                  % (self.name, self._dead))
        if self._draining:
            # rolling restart: this replica serves out its in-flight work
            # but admits nothing new — a router routes around it (checked
            # before `stopped`, which drain sets once the serve-out ends)
            raise ServeEngineDead("ServingEngine %s: draining for restart"
                                  % self.name)
        if self._stopped.is_set():
            raise ServeEngineDead("ServingEngine %s: engine stopped"
                                  % self.name)

    def _post_enqueue(self, req, depth):
        req._waker = self._wake.set
        self._wake.set()
        telemetry.set_gauge(self._gauge + "queue_depth", depth)
        # every road into the queue (submit, router dispatch, failover
        # redispatch, migration, handoff replay) passes through here: open
        # the trace (idempotent — a requeued request keeps its root and
        # its original t_submit) and flip the interval phase to queue_wait
        tracing.open_trace(req.id, self.name, t=req.t_submit)
        tracing.phase(req.id, "queue_wait", self.name, depth=depth)
        return req

    def _enqueue(self, req, count_shed_global=True):
        """Admission under the shed/degrade policies (also the router's
        failover re-dispatch path and the chaos flood — both must never
        block a scheduler thread)."""
        with self._qlock:
            self._check_alive_locked()
            cap = self._admission_shed(len(self._queue),
                                       count_global=count_shed_global)
            if cap is None and self._storm:
                # preemption storm (thrash detector): admit new work at
                # the PR-8 degrade cap — shorter answers shrink the
                # churning footprint instead of feeding the livelock
                cap = max(1, self.max_new_default // 4)
            if cap is not None and req.max_new_tokens > cap \
                    and req._resume is None and not req._migrated:
                # never degrade a resumed/migrated request: its output is
                # already promised (and partially delivered) — capping it
                # would truncate the exact-replay continuation
                req.max_new_tokens = cap
                self._count("degraded")
            self._queue.append(req)
            depth = len(self._queue)
        return self._post_enqueue(req, depth)

    def _enqueue_blocking(self, req):
        """`block` overload policy: wait for queue room, bounded by the
        request's own deadline (unbounded when it has none) and by
        `cancel()` — both resolve the wait typed instead of leaving the
        submitter blocked."""
        waited = False
        with self._qcond:
            while True:
                self._check_alive_locked()
                if req._cancelled:
                    self._count("cancelled")
                    raise ServeCancelled(
                        "ServeRequest %d: cancelled while blocked at "
                        "admission (%s queue full)" % (req.id, self.name))
                if req.expired():
                    self._count("expired")
                    raise ServeDeadlineExceeded(
                        "ServeRequest %d: deadline passed while blocked at "
                        "admission (%s queue full)" % (req.id, self.name))
                if len(self._queue) < self._queue_max:
                    self._queue.append(req)
                    depth = len(self._queue)
                    break
                waited = True
                self._qcond.wait(0.05)
        if waited:
            self._count("block_waits")
        return self._post_enqueue(req, depth)

    def depth(self):
        """Router load signal: queued + mid-admission + running requests.
        `_admitting` covers the window between the scheduler popping a
        request and its prefill landing in `_active` (or finishing) —
        without it a thread-driven `run_until_idle` could read depth 0
        and declare idle while a prefill is in flight.  `_prefilling`
        (chunked prefills mid-stream) and `_restoring` (host-tier
        restores staged but not landed) count the same way."""
        with self._qlock:
            return len(self._queue) + self._admitting + \
                len(self._active) + len(self._prefilling) + \
                len(self._restoring) + len(self._landing) + \
                len(self._handoff_inbox)

    # -- scheduling --------------------------------------------------------
    def _bucket_for(self, n, buckets):
        for b in buckets:
            if b >= n:
                return b
        # unreachable while submit()/__init__ enforce the bounds; raising
        # keeps the invariant self-checking instead of silently truncating
        raise MXNetError(
            "ServingEngine %s: no bucket >= %d in %s" % (self.name, n,
                                                         buckets))

    def _watch(self, site, arrays, names, bucket, seed=False):
        telemetry.watch_jit(
            "serving.%s" % site,
            telemetry.arrays_signature(arrays, names),
            scope=telemetry.watch_scope(self),
            meta={"bucket": bucket}, seed=seed)

    # -- failure scoping ---------------------------------------------------
    def _cache_lost(self):
        return self.model.cache_lost(self._cache)

    def _classify_failure(self, exc):
        """Scope of a failed compiled launch:

        * ``device`` — the accelerator itself is gone (or chaos says so):
          scheduler-fatal, the router fails over.
        * ``cache``  — the launch CONSUMED the donated K/V buffer before
          failing: every admitted sequence lost its context, but the
          engine rebuilds the cache and keeps serving its queue.
        * ``scoped`` — the donated buffer survived, so the fault is local
          to the triggering launch (a poisoned request at prefill, a
          transient error at decode)."""
        if isinstance(exc, chaos.ChaosEngineCrash):
            return "device"
        if self._cache_lost():
            return "cache"
        msg = str(exc).lower()
        # allocation pressure mentions the device in its message but the
        # device is healthy — scoped retry (an immediate respawn would
        # allocate ANOTHER full cache into the same pressure)
        if any(k in msg for k in ("resource_exhausted", "out of memory",
                                  "oom")):
            return "scoped"
        # \bdead\b: "dead device"/"backend is dead" yes, a transient
        # DEADLINE_EXCEEDED status no — that one takes the scoped retry
        if any(k in msg for k in ("device", "data_loss", "disconnected")) \
                or re.search(r"\bdead\b", msg):
            return "device"
        return "scoped"

    def _quarantine(self, req, msg):
        """Fail ONE poisoned request with a typed error; the batch keeps
        decoding and the scheduler stays up."""
        self._count("quarantined")
        telemetry.record_event("serve_quarantine", replica=self.name,
                               request=req.id, error=msg[:200])
        tracing.dump(self.name, "quarantine", request=req.id)
        req._finish(error=ServeQuarantined(msg[:500]))

    # -- quantization logit-gate trips (docs/serving.md "Quantization") ----
    def _scrub_quant(self, blocks):
        """Corrupted-scale hygiene: a tripped row's cached context may
        include SHARED prefix blocks whose scales are bad — detach them
        (and their subtrees) from the prefix index so no later lookup
        can re-acquire the corruption, and reclaim any that were parked.
        The retry's replay re-prefill then writes fresh blocks with
        fresh scales instead of re-reading the poisoned ones."""
        if self._prefix is None or not blocks:
            return
        freed = self._prefix.invalidate(blocks)
        if freed:
            self._alloc.reclaim(freed)
            self._count_evictions(len(freed))

    def _quant_trip_req(self, req, where):
        """A quantization logit gate tripped for ``req`` (the compiled
        program emitted the -1 sentinel): count, then requeue ONCE for
        a clean retry — the second trip quarantines typed
        `ServeQuantError`.  The one outcome this path can never have is
        a silently emitted wrong token."""
        self.stats["quant_trips"] += 1
        self._count("quant.trips")
        telemetry.record_event("serve_quant_trip", replica=self.name,
                               request=req.id, where=where)
        if req._requeues < 1:
            req._requeues += 1
            with self._qlock:
                self._queue.appendleft(req)
            tracing.phase(req.id, "queue_wait", self.name,
                          requeue="quant_trip")
        else:
            req._finish(error=ServeQuantError(
                "ServeRequest %d: quantization logit gate tripped (%s) — "
                "nonfinite or out-of-range logits under quantized "
                "weights/KV (corrupted scales?); the request was retried "
                "once and is quarantined rather than emitting unverified "
                "tokens" % (req.id, where)))

    def _vacate_row(self, row, seq, capture_resume=True):
        """Retire an active row for a later exact replay: leave the
        decode set, free the row, capture the uniform
        ``(ctx, last, pos, n_new)`` resume tuple, and release the
        blocks exactly once.  The ONE shared core of preemption
        (`_preempt`) and the quant-gate trip (`_quant_trip_seq`), so
        the replay formula and release ordering cannot drift between
        them."""
        del self._active[row]
        self._free.append(row)
        req = seq.req
        if capture_resume:
            req._resume = (list(seq.ctx), seq.last, seq.pos, seq.n_new)
            req._preempt_n_new = seq.n_new
        self._release_blocks(seq)
        return req

    def _quant_trip_seq(self, row, seq, where="decode"):
        """Gate trip on an ACTIVE row: leave the decode set, scrub the
        row's blocks from the prefix index, release them exactly once,
        and requeue with the exact-replay resume (tokens already
        emitted passed the gate — the replay continues after them with
        freshly quantized context)."""
        self._scrub_quant(seq.blocks)
        req = self._vacate_row(row, seq,
                               capture_resume=seq.req._requeues < 1)
        self._quant_trip_req(req, where)

    def _release_blocks(self, holder):
        """Drop a seq/prefill's block refs exactly once (every path a
        sequence leaves the cache by funnels through here).  Refcount-0
        blocks the prefix index registered PARK in its LRU pool instead
        of freeing — hot prefixes survive the request — everything else
        returns to the free list.  The leak check is `leaked_blocks()`
        returning 0 after a drain."""
        if holder.blocks is not None:
            self._drop_refs(holder.blocks)
            holder.blocks = None
            self._block_gauges()

    def _drop_refs(self, blocks):
        """release → park registered / reclaim unregistered, the single
        refcount-drop site (so a double drop raises in the allocator)."""
        for b in self._alloc.release(blocks):
            parked = None if self._prefix is None else self._prefix.park(b)
            if parked is None:
                self._alloc.reclaim([b])
            elif parked:
                # pool_cap overflow evicted the LRU tail
                self._alloc.reclaim(parked)
                self._count_evictions(len(parked))

    def _count_evictions(self, n):
        self.stats["prefix_evictions"] += n
        self._count("prefix_evictions", n)

    def _alloc_blocks(self, n):
        """`BlockAllocator.alloc` with eviction-under-pressure: when the
        free list alone cannot serve, parked prefix blocks are evicted
        LRU-first to make room.  None only when live blocks genuinely
        exhaust the pool (or chaos denies — a denial with enough free
        blocks is chaos, and deliberately does NOT burn the cache)."""
        got = self._alloc.alloc(n)
        if got is not None or self._prefix is None:
            return got
        if self._alloc.free_blocks >= n:
            return None  # chaos denial, not pressure: keep the cache
        evicted = self._prefix.evict(n - self._alloc.free_blocks)
        if not evicted:
            return None
        self._alloc.reclaim(evicted)
        self._count_evictions(len(evicted))
        return self._alloc.alloc(n)

    def leaked_blocks(self):
        """Blocks neither free, nor held by a live sequence, nor parked
        in the prefix pool — must be 0 after any drain."""
        parked = 0 if self._prefix is None else self._prefix.parked_count
        return self._alloc.capacity - self._alloc.free_blocks - \
            self._alloc.used_blocks - parked

    def leaked_host_blocks(self):
        """Host-tier blocks no prefix node references — must be 0
        whenever the scheduler is quiesced (every tier entry is owned
        by exactly one radix node; staged restores hold device copies,
        not handles)."""
        if self._tier is None:
            return 0
        return self._tier.used - self._prefix.host_count

    # -- host-DRAM tier (docs/serving.md "Memory tiering & sessions") ------
    def _spill_block(self, block, tokens, node):
        """`PrefixCache` eviction hook: copy the evicted block's K/V
        device→host into the tier so the prefix survives below HBM.
        Returns the host handle — or None (tier missing, `spill_fail`
        chaos, or a device read failure), upon which the cache detaches
        the node exactly as PR-12 did: spilling can only ever ADD a
        cheaper recovery path, never a correctness edge.  ``tokens`` is
        the node's full token path (the structured eviction metadata
        any observer gets); unused here beyond events because the node
        itself keys the index."""
        if self._tier is None:
            return None
        if chaos.enabled() and chaos.serve_spill_fail():
            self.stats["spill_fails"] += 1
            self._count("spill_fails")
            return None
        try:
            # the block is parked (refcount 0, full, registered): its
            # rows are stable between launches, and the scheduler owns
            # the pool here.  Dispatch the slice + an ASYNC device→host
            # copy and hand the in-flight array to the tier: a spill on
            # the admission road must never block on the launch queue
            # (a synchronous fetch here stalls every pressured admission
            # behind whatever decode work is in flight — measured as the
            # dominant tier cost before this went async).  `tier.get`
            # finalizes to numpy on first use, at least one admission
            # later, when the copy has long landed.
            data = self.model.slice_block(self._cache, block)
            for leaf in (data if isinstance(data, tuple) else (data,)):
                copy_async = getattr(leaf, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
        except Exception as e:  # noqa: BLE001 — degrade, never escalate
            self.stats["spill_fails"] += 1
            self._count("spill_fails")
            telemetry.record_event("serve_spill_failed", replica=self.name,
                                   block=int(block), error=str(e)[:200])
            return None
        handle, evicted = self._tier.put(data)
        for h in evicted:
            # the tier's own LRU pushed the oldest host blocks out: the
            # bottom tier really forgets — detach their index entries
            for orphan in self._prefix.drop_host(h):
                self._tier.free(orphan)
        self.stats["spilled"] += 1
        self._count("spilled")
        telemetry.set_gauge(self._gauge + "host_blocks_used",
                            self._tier.used)
        return handle

    def _host_dropped(self, handle):
        """`PrefixCache` host-drop hook: the index dropped its reference
        (node detach/orphan) — free the tier storage with it."""
        if self._tier is not None:
            self._tier.free(handle)
            telemetry.set_gauge(self._gauge + "host_blocks_used",
                                self._tier.used)

    def _drop_host_node(self, node):
        """Drop one host-resident node (and its host subtree) from both
        the index and the tier — the restore-failure degrade path: the
        retry must take the chunk-prefill replay road, not re-stage the
        same failing restore."""
        if node.tier != "host":
            return
        handle = node.block
        orphans = self._prefix.drop_host(handle)
        self._tier.free(handle)
        for h in orphans:
            self._tier.free(h)
        telemetry.set_gauge(self._gauge + "host_blocks_used",
                            self._tier.used)

    def _register_prefix(self, tokens, blocks, n_tokens):
        """Register a sequence's newly-FULL blocks in the prefix index
        (eager: a concurrent request can share them while the writer is
        still decoding — CoW guards the one block being written)."""
        if self._prefix is not None:
            self._prefix.insert(tokens, blocks,
                                int(n_tokens) // self.block_size)

    def _block_gauges(self, full=False):
        """Cheap pool gauges on every allocator touch; the per-block
        fill map behind `blocks_frag` only when ``full`` (once per
        scheduler iteration — it walks every held block, which is not
        free at large batch x depth), which then returns (blocks held by
        a sequence, blocks the prefix cache parks alone)."""
        free = self._alloc.free_blocks
        if free < self.stats["blocks_free_min"]:
            self.stats["blocks_free_min"] = free
        telemetry.set_gauge(self._gauge + "blocks_free", free)
        telemetry.set_gauge(self._gauge + "blocks_shared",
                            self._alloc.shared_blocks)
        if not full:
            return
        # used rows per PHYSICAL block: a block shared by k sequences
        # counts once (the sharers' fill of it is identical — it is
        # full), so `blocks_frag` stays meaningful under refcounts > 1;
        # the trash block never appears in any blocks list.  A seq at
        # `pos` has cached rows 0..pos-1 (its `last` token is only
        # written at `pos` by the NEXT decode step).
        bs = self.block_size
        filled = {}
        for holder, n in [(s.blocks, s.pos)
                          for s in self._active.values()] + \
                         [(p.blocks, p.done)
                          for p in self._prefilling.values()] + \
                         [(r.blocks, r.done)
                          for r in self._restoring.values()] + \
                         [(ld.blocks, ld.ticket.pos)
                          for ld in self._landing.values()]:
            if holder is None:
                continue
            for i, b in enumerate(holder):
                rows = min(bs, max(0, n - i * bs))
                if rows > filled.get(b, 0):
                    filled[b] = rows
        parked = 0 if self._prefix is None else self._prefix.parked_count
        used_tokens = sum(filled.values()) + parked * bs
        telemetry.set_gauge(self._gauge + "blocks_frag",
                            round(self._alloc.fragmentation(
                                used_tokens, cached_blocks=parked), 4))
        if self._prefix is not None:
            telemetry.set_gauge(self._gauge + "blocks_parked", parked)
            looked = self.stats["prefix_lookup_tokens"]
            if looked:
                telemetry.set_gauge(
                    self._gauge + "prefix_hit_rate",
                    round(self.stats["prefix_tokens"] / float(looked), 4))
        return len(filled), parked

    def _rebuild_cache(self, reason):
        """The donated K/V buffer was consumed by a failed launch: every
        ADMITTED sequence lost its context (typed failure), the cache is
        reallocated, and the engine keeps serving its queue — scoped
        failure, not an engine death.  The whole pool + every block
        table is rebuilt: the allocator resets, active
        sequences fail typed, and mid-prefill requests requeue for one
        retry against the fresh pool (their cached chunks died with it)."""
        err = ServeCacheInvalidated(
            "ServingEngine %s: K/V cache invalidated (%s)"
            % (self.name, reason[:300]))
        for row, seq in list(self._active.items()):
            seq.blocks = None  # the pool they pointed into is gone
            self._retire_error(row, seq, err)
        for row, pf in list(self._prefilling.items()):
            del self._prefilling[row]
            self._free.append(row)
            pf.blocks = None
            if pf.req._requeues < 1:
                pf.req._requeues += 1
                with self._qlock:
                    self._queue.appendleft(pf.req)
                tracing.phase(pf.req.id, "queue_wait", self.name,
                              requeue="cache_rebuild")
            else:
                self._quarantine(pf.req, "prefill lost to a cache "
                                 "rebuild twice: %s" % reason[:200])
        for row, rs in list(self._restoring.items()):
            # a staged restore's target blocks died with the pool;
            # same one-retry contract as a mid-stream prefill
            del self._restoring[row]
            self._free.append(row)
            rs.blocks = None
            if rs.req._requeues < 1:
                rs.req._requeues += 1
                with self._qlock:
                    self._queue.appendleft(rs.req)
                tracing.phase(rs.req.id, "queue_wait", self.name,
                              requeue="cache_rebuild")
            else:
                self._quarantine(rs.req, "restore lost to a cache "
                                 "rebuild twice: %s" % reason[:200])
        for row, ld in list(self._landing.items()):
            # a staged handoff landing's target blocks died with the
            # pool; the packed host bytes are useless without them —
            # fall back to the journal exact-replay road
            del self._landing[row]
            self._free.append(row)
            ld.blocks = None
            self._handoff_lost(ld.ticket.req,
                               "handoff landing lost to a cache "
                               "rebuild: %s" % reason[:200])
        if self._prefix is not None:
            self._prefix.clear()  # the pool its nodes point at is gone
        if self._tier is not None:
            # the index died with the pool and the host copies are
            # unreachable without it: clear the bottom tier too (one
            # sweep, not a hook per handle)
            self._tier.clear()
            telemetry.set_gauge(self._gauge + "host_blocks_used", 0)
        self._alloc.reset()
        self._cache = self._new_cache()
        if self._drafter is not None:
            self._drafter.on_cache_rebuild()
        self._block_gauges()
        self._count("cache_rebuilds")
        telemetry.record_event("serve_cache_rebuild", replica=self.name,
                               reason=reason[:200])
        tracing.dump(self.name, "cache_rebuild", detail=reason[:200])

    def _samp_device(self, reqs, b):
        """Per-row device sampling arrays for rows ``reqs`` padded to
        bucket ``b`` (padding rows: temperature 0 = greedy, output
        discarded), and how many rows sample (temperature > 0: the
        program runs its sampling body only where that is not 0).
        ((), 0) when sampling programs are disabled."""
        if not self._sampling:
            return (), 0
        temp = np.zeros((b,), np.float32)
        tk = np.zeros((b,), np.int32)
        tp = np.ones((b,), np.float32)
        seed = np.zeros((b,), np.uint32)
        for i, r in enumerate(reqs):
            temp[i] = r.temperature
            tk[i] = r.top_k
            tp[i] = r.top_p
            seed[i] = r.seed
        return (tuple(self._put(a) for a in (temp, tk, tp, seed)),
                int(np.count_nonzero(temp > 0)))

    # -- admission / chunked prefill ---------------------------------------
    def _admit_one(self, req):
        """Admit one queued request: look up the longest cached block-aligned
        prefix, acquire those shared blocks, allocate fresh blocks for
        the uncached suffix (+ the first decode write), then stream only
        the SUFFIX through the pool in bucket-sized chunks.  A prompt the
        index covers completely skips prefill outright: the sequence
        BOOTSTRAPS straight into the decode set, feeding its last token
        at its final position (the pre-decode CoW gives it a private
        copy of the shared block that write lands in).  A denied
        allocation — pool pressure past what evicting the parked prefix
        pool can free, or a `block_exhaust` chaos clause — is a typed
        requeue: the request goes BACK to the queue front and admission
        stops this iteration (free blocks can only appear when something
        retires); that is the ONLY case this returns False."""
        row = self._free.pop()
        tokens = req.prompt if req._resume is None else req._resume[0]
        if self._prefix is None:
            shared, host_nodes = [], []
        else:
            shared, host_nodes = self._prefix.lookup_plan(tokens)
            if host_nodes and (self._tier is None or
                               len(self._restoring) >=
                               self._restore_ahead):
                # no restore slot (or no tier): the miss path must never
                # wait behind a restore — admit on the device match
                # alone.  The matched host blocks stay put for a later
                # hit, MRU-touched so a hot prefix that keeps matching
                # while restore slots are busy cannot age out of the
                # host LRU unused.
                if self._tier is not None:
                    for node in host_nodes:
                        self._tier.touch(node.block)
                host_nodes = []
        matched = len(shared) * self.block_size
        # acquire BEFORE allocating: live refs pin the matched blocks so
        # the fresh allocation's eviction-under-pressure cannot reclaim
        # them out from under the table we are about to build
        self._alloc.acquire(shared)
        if self._prefix is not None:
            self._prefix.unpark(shared)
        fresh = self._alloc_blocks(
            self._alloc.blocks_for(len(tokens) + 1) - len(shared))
        if fresh is None:
            self._drop_refs(shared)
            self._free.append(row)
            self.stats["alloc_denied"] += 1
            self._count("alloc_denied")
            with self._qlock:
                self._queue.appendleft(req)
            return False
        # stage the host run's transfer (restore-then-acquire): the
        # whole run packs into ONE padded array and ONE async
        # device_put dispatched NOW, so the PCIe copy rides under this
        # iteration's decode launch; the write into the pool happens
        # next iteration (_advance_restores).  A handle the tier
        # evicted in the window truncates the run — contiguity is what
        # makes the table coverage valid.
        t_stage = time.perf_counter()  # restore stage START (pack + put)
        nodes, handles, arrs, dst = [], [], [], []
        for node in host_nodes:
            arr = self._tier.get(node.block)
            if arr is None:
                break
            nodes.append(node)
            handles.append(node.block)
            arrs.append(arr)
            dst.append(fresh[len(nodes) - 1])
        # hit accounting only for admissions that LAND: a denied-alloc
        # requeue retries the lookup every iteration, and a restore that
        # fails mid-flight requeues too — counting either at staging
        # would inflate hit_rate exactly when the pool (or the restore
        # path) is under pressure, so restore admissions count at
        # `_complete_restore` instead
        if self._prefix is not None and not nodes:
            self.stats["prefix_lookup_tokens"] += len(tokens)
            if matched:
                self._count_prefix_hit(matched)
        blocks = shared + fresh
        self._block_gauges()
        if req._migrated:
            # a journal-migrated request's exact-replay admission landed
            # on this survivor (counted once, at the landing)
            req._migrated = False
            self.stats["replays"] += 1
            self._count("replays")
        if nodes:
            kb = self._restore_bucket(len(nodes))
            data = pack_block_run(self.model, self.block_size, arrs, kb)
            dsts = np.full((kb,), TRASH_BLOCK, np.int32)
            dsts[:len(dst)] = dst
            self._restoring[row] = _Restore(req, row, list(tokens), blocks,
                                            matched, nodes, handles,
                                            self._put_run(data),
                                            self._put(dsts), dst, kb,
                                            t_stage=t_stage)
            tracing.phase(req.id, "restore_wait", self.name, t=t_stage,
                          blocks=len(nodes))
            return True
        self._enter_decode_or_prefill(req, row, list(tokens), blocks,
                                      matched)
        return True

    def _count_prefix_hit(self, matched_tokens):
        self.stats["prefix_hits"] += 1
        self.stats["prefix_tokens"] += matched_tokens
        self._count("prefix_hits")
        telemetry.inc("serve.prefix_tokens", matched_tokens)

    def _enter_decode_or_prefill(self, req, row, tokens, blocks, covered):
        """Route an admission whose cache rows ``[0, covered)`` are
        already valid (device prefix hit, or a completed host-tier
        restore): a full cover BOOTSTRAPS straight into the decode set,
        anything else streams its uncached suffix through chunked
        prefill.  The single entry point both the ordinary admission
        and `_advance_restores` funnel through, so resume bookkeeping,
        drafter seeding, and latency stamps cannot diverge between a
        device hit and a restored one."""
        if covered >= len(tokens):
            # full cover (len(tokens) is block-aligned): nothing to
            # prefill — admit straight to decode, feeding the last
            # cached token at its own position.  Fresh admissions have
            # sampled nothing yet (n_new 0, t_first stamps at the first
            # decode); a resumed preemption continues its own counters.
            self.stats["prefix_bootstraps"] += 1
            self._count("prefix_bootstraps")
            resumed = req._resume is not None
            if not resumed:
                last, pos, n_new = int(tokens[-1]), len(tokens) - 1, 0
                telemetry.observe(
                    "serve.queue_age_ms",
                    1e3 * (time.perf_counter() - req.t_submit))
            else:
                last, pos, n_new = req._resume[1:]
                req._resume = None
            if self._maybe_handoff(req, row, tokens, blocks,
                                   last, pos, n_new):
                return
            if resumed and self._drafter is not None and n_new:
                # seed the survivor's drafter with the replayed
                # generation: speculation recovers its accept rate on
                # the first post-resume round instead of re-learning
                self._drafter.on_resume(list(tokens) + [last])
            seq = _Seq(req, last, pos, blocks=blocks,
                       ctx=list(tokens[:pos]))
            seq.n_new = n_new
            tracing.phase(req.id, "decode", self.name, pos=pos,
                          bootstrap=True)
            self._active[row] = seq
            return
        # a resumed admission re-prefills context it already generated
        # once: that is SLO-attributed as `replay`, not `prefill`
        tracing.phase(req.id,
                      "replay" if req._resume is not None else "prefill",
                      self.name, covered=covered, total=len(tokens))
        pf = _Prefill(req, row, tokens, blocks,
                      resume=None if req._resume is None
                      else req._resume[1:])
        pf.done = covered  # the cached prefix needs no prefill
        self._prefilling[row] = pf
        self._advance_chunk(pf)

    def _drop_prefill(self, pf):
        """Remove a mid-stream prefill: row and blocks return to their
        pools; the caller resolves the request."""
        self._prefilling.pop(pf.row, None)
        self._free.append(pf.row)
        self._release_blocks(pf)

    def _advance_prefills(self):
        """Advance every mid-stream chunked prefill by ONE chunk (the
        Sarathi-style piggyback bound: a long prompt costs each decode
        iteration at most one chunk of ttft interference per prefilling
        request, instead of monopolizing the device until it lands)."""
        for pf in list(self._prefilling.values()):
            if pf.row in self._prefilling:
                self._advance_chunk(pf)

    # -- host-tier restore completion --------------------------------------
    def _drop_restore(self, rs):
        """Remove a staged restore: row and blocks return to their
        pools (the staged device arrays just drop — they were never
        part of the pool); the caller resolves the request."""
        self._restoring.pop(rs.row, None)
        self._free.append(rs.row)
        self._release_blocks(rs)

    def _advance_restores(self):
        """Land every restore staged in a PREVIOUS iteration: the async
        `device_put`s dispatched at admission rode under that
        iteration's decode launch (the DevicePrefetchIter overlap), so
        by now the bytes are on-device and each block costs one tiny
        warmup-compiled pool write.  Runs BEFORE `_advance_prefills`,
        so a restore that still has an uncached suffix advances its
        first prefill chunk in this same iteration."""
        for rs in list(self._restoring.values()):
            if rs.row in self._restoring:
                self._complete_restore(rs)

    def _complete_restore(self, rs):
        """Write one staged restore's blocks into the pool and route
        the admission onward.  Failure scoping mirrors `_advance_chunk`:
        device death is scheduler-fatal; a consumed pool rebuilds (which
        requeues every staged restore); a scoped fault DEGRADES to the
        chunk-prefill replay path — the involved host entries drop, the
        request requeues at the front, and its retry prefills the
        context the restore would have transferred.  Never a hang,
        never a leak in either tier."""
        req = rs.req
        ms = chaos.serve_restore_slow()
        if ms:
            time.sleep(ms / 1e3)
        try:
            compiled = self._compiled_restore(rs.kb)
            staged = rs.staged if isinstance(rs.staged, tuple) \
                else (rs.staged,)
            self._watch("restore", (rs.dst_d,) + staged,
                        ("dst", "data", "data_scale")[:1 + len(staged)],
                        rs.kb)
            if chaos.serve_launch_error():
                raise chaos.ChaosError(
                    "chaos: injected restore launch error")
            self._cache = compiled(self._cache, rs.dst_d, rs.staged)
        except Exception as e:
            kind = self._classify_failure(e)
            if kind == "device":
                self._drop_restore(rs)
                req._finish(error=ServeEngineDead(
                    "restore launch failed: %s" % str(e)[:400]))
                raise _EngineFatal("restore launch failed: %s" % e) from e
            if kind == "cache":
                self._rebuild_cache("restore launch failed: %s" % e)
                return
            self.stats["restore_fails"] += 1
            self._count("restore_fails")
            telemetry.record_event("serve_restore_failed",
                                   replica=self.name, request=req.id,
                                   error=str(e)[:200])
            self._drop_restore(rs)
            for node in rs.nodes:
                self._drop_host_node(node)
            with self._qlock:
                self._queue.appendleft(req)
            tracing.phase(req.id, "queue_wait", self.name,
                          requeue="restore_failed")
            return
        # landed: flip the nodes back to device residency (keeping the
        # host copies — re-evicting them is free), count, and proceed.
        # A node upgraded or dropped in the window leaves its restored
        # block as the sequence's private property: the bytes came from
        # the tier, the tree only decides future sharing.
        for node, handle, dstb in zip(rs.nodes, rs.handles, rs.dst):
            self._prefix.restore_landed(node, handle, dstb)
        n_host = len(rs.nodes)
        covered = rs.done + n_host * self.block_size
        # the deferred hit accounting: this restore admission LANDED
        self.stats["prefix_lookup_tokens"] += len(rs.tokens)
        self._count_prefix_hit(covered)
        self.stats["restored"] += n_host
        self._count("restored", n_host)
        self.stats["restored_tokens"] += n_host * self.block_size
        telemetry.observe("serve.restore_wait_ms",
                          1e3 * (time.perf_counter() - rs.t_stage))
        telemetry.set_gauge(self._gauge + "host_blocks_used",
                            self._tier.used)
        del self._restoring[rs.row]
        if self._drafter is not None and self._drafter.mirrors_pool:
            # the mirrored draft pool follows the restore: re-derive its
            # rows for the restored span by draft-prefilling the tokens
            # the target just got back as bytes (accept-rate hygiene,
            # never correctness)
            self._drafter_restore_span(rs.tokens, rs.blocks, rs.done,
                                       covered)
        self._enter_decode_or_prefill(req, rs.row, rs.tokens, rs.blocks,
                                      covered)
        self._block_gauges()

    def _drafter_restore_span(self, tokens, blocks, start, end):
        """Feed the restored (block-aligned) span to the drafter as
        ordinary prefill chunks over the warmup bucket shapes."""
        pos = start
        largest = self.prefill_buckets[-1]
        while pos < end:
            remaining = end - pos
            bucket = largest if remaining > largest else \
                self._bucket_for(remaining, self.prefill_buckets)
            chunk = min(remaining, bucket)
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :chunk] = tokens[pos:pos + chunk]
            table = np.full((1, self._n_table), TRASH_BLOCK, np.int32)
            table[0, :len(blocks)] = blocks
            self._drafter.on_restore_span(
                self._put(toks), self._put(np.array([pos], np.int32)),
                self._put(np.array([chunk], np.int32)), self._put(table))
            pos += chunk

    # -- disaggregated prefill/decode handoff ------------------------------
    # (docs/serving.md "Disaggregated prefill/decode")
    def _maybe_handoff(self, req, row, tokens, blocks, last, pos, n_new):
        """On a prefill-role replica, retire a prefill-complete sequence
        into a handoff instead of decode: pack the cached block run into
        ONE host array (`pack_block_run` — the tier-restore transfer
        shape), hand a `HandoffTicket` to the router's sink, and free
        the row and blocks HERE.  Returns True when the sequence was
        consumed (handed off, or failed over to journal replay) — the
        caller must not enter decode.  Colocated engines (role None)
        return False without touching anything: the `MXNET_SERVE_DISAGG=0`
        bit-for-bit contract lives on this first line."""
        if self._handoff_sink is None or self.role != "prefill" \
                or req._no_handoff or pos <= 0:
            return False
        t_pack = time.perf_counter()  # handoff stage START (pack + ship)
        ticket = None
        try:
            if chaos.enabled() and chaos.serve_handoff_fail():
                raise chaos.ChaosError(
                    "chaos: injected handoff transfer death")
            k = (pos + self.block_size - 1) // self.block_size
            arrs = []
            for b in blocks[:k]:
                data = self.model.slice_block(self._cache, b)
                for leaf in (data if isinstance(data, tuple)
                             else (data,)):
                    copy_async = getattr(leaf, "copy_to_host_async",
                                         None)
                    if copy_async is not None:
                        copy_async()
                arrs.append(data)
            # finalize to numpy AFTER all copies dispatched: each wait
            # overlaps the remaining transfers
            arrs = [tuple(np.asarray(x) for x in a)
                    if isinstance(a, tuple) else np.asarray(a)
                    for a in arrs]
            kb = self._restore_bucket(k)
            packed = pack_block_run(self.model, self.block_size, arrs,
                                    kb)
            ticket = HandoffTicket(req, list(tokens[:pos]), last, pos,
                                   n_new, packed, k, kb, self.name,
                                   t_start=t_pack)
            ctx = tracing.context(req.id)
            if ctx is not None:
                # the ticket carries (trace id, root span id) across the
                # role boundary; the decode side adopts it at receive
                ticket.trace, ticket.parent = ctx
        except Exception as e:  # noqa: BLE001 — degrade to replay
            self._free.append(row)
            self._drop_refs(blocks)
            self._block_gauges()
            self._handoff_lost(req, "handoff pack failed: %s" % e)
            return True
        # the source is done with the sequence whatever happens next:
        # the bytes are on host and the resume tuple is in the ticket
        self._free.append(row)
        self._drop_refs(blocks)
        self._block_gauges()
        # handoff_wait opens at PACK start: the wait the SLO attribution
        # charges covers pack + transfer + landing, matching the fixed
        # serve.handoff_wait_ms stage-time measurement
        tracing.phase(req.id, "handoff_wait", self.name, t=t_pack,
                      blocks=ticket.k, nbytes=ticket.nbytes)
        tracing.add_span(req.id, "handoff_pack", self.name, t_pack,
                         time.perf_counter(), blocks=ticket.k,
                         nbytes=ticket.nbytes)
        try:
            self._handoff_sink(ticket)
        except Exception as e:  # noqa: BLE001 — no live decode target
            self._handoff_lost(req, "handoff dispatch failed: %s" % e)
            return True
        self.stats["handoffs"] += 1
        self._count("handoffs")
        telemetry.inc("serve.handoff_bytes", ticket.nbytes)
        return True

    def _handoff_lost(self, req, msg):
        """A handoff died (pack, dispatch, chaos, target death, cache
        rebuild under a staged landing): count the typed failure and
        requeue the request onto the router's journal exact-replay road.
        Only when even that road is closed does the request fail typed —
        never hung, and never duplicated (replay regenerates only tokens
        streaming never published)."""
        self.stats["handoff_fails"] += 1
        self._count("handoff_fails")
        telemetry.record_event("serve_handoff_fail", replica=self.name,
                               request=req.id, error=str(msg)[:200])
        tracing.dump(self.name, "handoff_fail", request=req.id,
                     error=str(msg)[:200])
        ok = False
        if self._handoff_fallback is not None:
            try:
                ok = self._handoff_fallback(req)
            except Exception:  # noqa: BLE001 — fall through to typed
                ok = False
        if not ok and not req.done:
            req._finish(error=ServeEngineDead(
                "handoff failed with no replay road: %s" % str(msg)[:300]))

    def receive_handoff(self, ticket):
        """Router-facing: accept one handoff ticket onto this DECODE
        replica's inbox (any thread).  Raises `ServeEngineDead` when
        this replica is dead, draining, or stopped — the drain fence
        the router's redirect logic relies on: a handoff must never
        race admission-close on a draining target."""
        # adopt the carried trace context BEFORE queueing: spans this
        # replica records parent under the root the prefill side opened
        tracing.adopt(ticket.trace, ticket.parent, replica=self.name)
        with self._qlock:
            self._check_alive_locked()
            self._handoff_inbox.append(ticket)
        self._wake.set()

    def _stage_handoffs(self):
        """Stage received tickets (scheduler thread): claim a row,
        allocate fresh target blocks, and dispatch the packed run's
        async ``device_put`` so the PCIe copy rides under this
        iteration's decode launch — `_advance_landings` completes it
        next iteration, exactly the `_Restore` two-stage overlap.  A
        denied allocation leaves the ticket queued (blocks can only
        appear when something retires)."""
        while self._free:
            with self._qlock:
                if not self._handoff_inbox:
                    return
                ticket = self._handoff_inbox.popleft()
            req = ticket.req
            if req.done:
                continue
            row = self._free.pop()
            fresh = self._alloc_blocks(
                self._alloc.blocks_for(ticket.pos + 1))
            if fresh is None:
                self._free.append(row)
                self.stats["alloc_denied"] += 1
                self._count("alloc_denied")
                with self._qlock:
                    self._handoff_inbox.appendleft(ticket)
                return
            dsts = np.full((ticket.kb,), TRASH_BLOCK, np.int32)
            dsts[:ticket.k] = fresh[:ticket.k]
            self._landing[row] = HandoffLanding(
                ticket, row, fresh, self._put_run(ticket.data),
                self._put(dsts))
            self._block_gauges()

    def _drop_landing(self, ld):
        """Remove a staged landing: row and blocks return to their
        pools; the caller resolves the request."""
        self._landing.pop(ld.row, None)
        self._free.append(ld.row)
        self._release_blocks(ld)

    def _advance_landings(self):
        """Land every handoff staged in a PREVIOUS iteration (the
        `_advance_restores` twin — the staged ``device_put`` rode under
        that iteration's decode launch)."""
        for ld in list(self._landing.values()):
            if ld.row in self._landing:
                self._complete_landing(ld)

    def _complete_landing(self, ld):
        """Scatter one staged handoff's blocks into the pool with the
        warmup-compiled bucketed ``write_block`` (AotCache stays
        frozen), register the context in this replica's OWN prefix
        index, and enter decode at the ticket's resume tuple.  Failure
        scoping mirrors `_complete_restore`: device death is
        scheduler-fatal; a consumed pool rebuilds; a scoped fault drops
        the staged bytes and falls back to journal exact-replay."""
        t = ld.ticket
        req = t.req
        t_land = time.perf_counter()
        try:
            compiled = self._compiled_restore(t.kb)
            staged = ld.staged if isinstance(ld.staged, tuple) \
                else (ld.staged,)
            self._watch("restore", (ld.dst_d,) + staged,
                        ("dst", "data", "data_scale")[:1 + len(staged)],
                        t.kb)
            if chaos.serve_launch_error():
                raise chaos.ChaosError(
                    "chaos: injected handoff landing launch error")
            self._cache = compiled(self._cache, ld.dst_d, ld.staged)
        except Exception as e:
            kind = self._classify_failure(e)
            if kind == "device":
                self._drop_landing(ld)
                req._finish(error=ServeEngineDead(
                    "handoff landing failed: %s" % str(e)[:400]))
                raise _EngineFatal(
                    "handoff landing failed: %s" % e) from e
            if kind == "cache":
                self._rebuild_cache("handoff landing failed: %s" % e)
                return
            self._drop_landing(ld)
            self._handoff_lost(req, "handoff landing failed: %s" % e)
            return
        # landed: the context's FULL blocks publish in this replica's
        # prefix index (follow-up session turns share them here — the
        # tier entry lives where decode happens)
        self._register_prefix(t.ctx, ld.blocks, t.pos)
        self.stats["handoffs_in"] += 1
        self._count("handoffs_in")
        now = time.perf_counter()
        telemetry.observe("serve.handoff_wait_ms",
                          1e3 * (now - t.t_start))
        tracing.add_span(req.id, "handoff_land", self.name, t_land, now,
                         blocks=t.k, src=t.src)
        tracing.phase(req.id, "decode", self.name, pos=t.pos,
                      handoff=t.src)
        del self._landing[ld.row]
        if self._drafter is not None and t.n_new:
            # the handed-off generation seeds the drafter store, same
            # as any resume: full accept rate on the first round
            self._drafter.on_resume(list(t.ctx) + [t.last])
        seq = _Seq(req, t.last, t.pos, blocks=ld.blocks,
                   ctx=list(t.ctx))
        seq.n_new = t.n_new
        self._active[ld.row] = seq
        self._block_gauges()

    def _pending_work(self):
        """Admitted-but-not-decoding work still owed to callers:
        mid-stream prefills, staged restores, staged handoff landings,
        and received-but-unstaged tickets.  The scheduler's idle test —
        every `_step` variant counts these before sleeping."""
        return len(self._prefilling) + len(self._restoring) \
            + len(self._landing) + len(self._handoff_inbox)

    def decode_depth(self):
        """Decode-side load for the router's least-loaded handoff
        targeting: active rows plus handoffs already owed to this
        replica (staged or inboxed)."""
        with self._qlock:
            return len(self._active) + len(self._landing) \
                + len(self._handoff_inbox)

    def prefill_backlog(self):
        """Prompt tokens queued or mid-stream on this replica — the
        ttft-ordered dispatch key for prefill-role replicas (queue
        depth alone starves short prompts behind storms).  Snapshot
        reads of prefill progress are tolerated: this is a load signal,
        not an invariant."""
        with self._qlock:
            t = sum(len(r.prompt) for r in self._queue)
            for pf in list(self._prefilling.values()):
                t += max(0, len(pf.tokens) - pf.done)
        return t

    def _advance_chunk(self, pf):
        """Launch one prefill chunk; the final chunk moves the sequence
        to the active set.  Failure scoping:
        setup/scoped faults quarantine the request, cache loss rebuilds
        the pool (requeueing every mid-prefill request, this one
        included), device death is scheduler-fatal."""
        req = pf.req
        total = len(pf.tokens)
        remaining = total - pf.done
        largest = self.prefill_buckets[-1]
        bucket = largest if remaining > largest else \
            self._bucket_for(remaining, self.prefill_buckets)
        chunk = min(remaining, bucket)
        t_chunk = time.perf_counter()
        try:
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :chunk] = pf.tokens[pf.done:pf.done + chunk]
            table = np.full((1, self._n_table), TRASH_BLOCK, np.int32)
            table[0, :len(pf.blocks)] = pf.blocks
            toks_d = self._put(toks)
            start_d = self._put(np.array([pf.done], np.int32))
            length_d = self._put(np.array([chunk], np.int32))
            table_d = self._put(table)
            samp, sampled = self._samp_device([req], 1)
            tail = tuple(self._put(a)
                         for a in self._slots((pf.row,), 1)) + samp
            self._watch("prefill",
                        (toks_d, start_d, length_d, table_d) + tail,
                        self._PREFILL_NAMES + self._tail_names(samp),
                        bucket)
            compiled = self._compiled_prefill(bucket)
            if chaos.serve_launch_error():
                raise chaos.ChaosError("chaos: injected prefill launch "
                                       "error")
        except Exception as e:
            self._drop_prefill(pf)
            self._quarantine(req, "prefill setup failed: %s" % e)
            return
        try:
            tok, self._cache = self._unpack(compiled(
                self._params, self._cache, toks_d, start_d, length_d,
                table_d, *tail))
        except Exception as e:
            kind = self._classify_failure(e)
            if kind == "device":
                self._drop_prefill(pf)
                req._finish(error=ServeEngineDead(
                    "prefill launch failed: %s" % str(e)[:400]))
                raise _EngineFatal("prefill launch failed: %s" % e) from e
            if kind == "cache":
                self._rebuild_cache("prefill launch failed: %s" % e)
                return
            self._drop_prefill(pf)
            self._quarantine(req, "prefill launch failed: %s" % e)
            return
        if self._drafter is not None:
            # the draft cache prefills in lockstep over the SAME chunk
            # arrays and block table — positions the draft never cached
            # would otherwise cost accept rate on every token after them
            self._drafter.on_prefill_chunk(toks_d, start_d, length_d,
                                           table_d)
        if self._state_slots and not pf.done:
            # a sequence's first chunk starts its slot's state from nothing
            self._count(getattr(self.model, "state_reset_counter",
                                "state_resets"))
        pf.done += chunk
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += chunk  # the suffix-only witness
        telemetry.inc("serve.prefill_chunks")
        tracing.add_span(req.id, "prefill_chunk", self.name, t_chunk,
                         time.perf_counter(), start=pf.done - chunk,
                         tokens=chunk, sampled=sampled,
                         attn_kernel=self._prefill_kernel[bucket])
        # publish the chunk's newly-FULL blocks (a block whose bucket
        # tail is padding garbage stays private: `done` counts only real
        # tokens, so it rounds down past any partially-written block)
        self._register_prefix(pf.tokens, pf.blocks, pf.done)
        if pf.done < total:
            return
        # prefill complete: the row becomes an active decode sequence
        del self._prefilling[pf.row]
        blocks, pf.blocks = pf.blocks, None
        self.stats["prefills"] += 1
        telemetry.inc("serve.prefills")
        if pf.resume is None:
            # fresh admissions only: a preempt-resume re-prefill would
            # log its pre-preemption DECODE time as queue wait
            telemetry.observe("serve.queue_age_ms",
                              1e3 * (time.perf_counter() - req.t_submit))
        if pf.resume is not None:
            # preempt-resume: the cache rows are rebuilt; generation
            # continues from the token the preemption interrupted (no
            # re-sampling — the interrupted draw never happened)
            last, pos, n_new = pf.resume
            req._resume = None
            if self._maybe_handoff(req, pf.row, pf.tokens, blocks,
                                   last, pos, n_new):
                return
            seq = _Seq(req, last, pos, blocks=blocks, ctx=pf.tokens)
            seq.n_new = n_new
            if self._drafter is not None and n_new:
                # replayed generation seeds the drafter store (migration
                # and preempt-resume alike): full accept rate immediately
                self._drafter.on_resume(list(pf.tokens) + [last])
            tracing.phase(req.id, "decode", self.name, pos=pos,
                          resumed=True)
            self._active[pf.row] = seq
            return
        first = int(np.asarray(tok)[0])
        if first < 0:
            # quantization logit gate on the prompt's final chunk (no
            # token emitted yet): scrub the blocks it read — a shared
            # prefix with corrupted scales must not be re-acquired by
            # the retry — release them, and requeue once
            self._free.append(pf.row)
            self._scrub_quant(blocks)
            self._drop_refs(blocks)
            self._block_gauges()
            self._quant_trip_req(req, "prefill")
            return
        req.t_first = time.perf_counter()
        req.tokens.append(first)
        self.stats["tokens"] += 1
        telemetry.inc("serve.tokens")
        seq = _Seq(req, first, total, blocks=blocks, ctx=pf.tokens)
        if self._seq_finished(seq, first):
            self._retire(pf.row, seq, enter=False)
        elif not self._maybe_handoff(req, pf.row, pf.tokens, blocks,
                                     first, total, 1):
            tracing.phase(req.id, "decode", self.name, pos=total)
            self._active[pf.row] = seq
        # the first token publishes from the SOURCE exactly once —
        # streaming's positional high-water mark; the decode side
        # resumes at n_new=1 and appends from position 1 on
        req._publish()

    def _grow_active(self):
        """Before a decode step, every active row must EXCLUSIVELY own
        the block its write position lands in.

        * Growth: a row whose write position crossed into an unallocated
          block allocates it (one block at a time).
        * Copy-on-write: a row about to write into a block that is
          shared (refcount > 1) or registered in the prefix index gets a
          private copy first — fresh block allocated, cached rows copied
          in-graph (`copy_block`, compiled at warmup), table repointed,
          shared ref dropped — so the cached original keeps serving its
          other readers untouched.  Writing in place would alias: the
          one thing this path must never do.

        A denied allocation (growth or CoW) PREEMPTS the sequence:
        blocks free, the request requeues at the front carrying its
        generated tokens, and a later re-prefill (which may itself hit
        the prefix cache) rebuilds its context — greedy decoding and the
        position-keyed sampler both replay identically, so preemption is
        invisible in the output."""
        # speculation writes a whole span per step (the fed token plus k
        # drafts, clipped at the cache end), so every block the span
        # lands in — not just one — must exist and be exclusively owned
        span = self._spec_k + 1 if self._spec else 1
        if self._mega_m:
            # a megastep writes up to m positions before the host sees
            # any of them, so the whole m-span must be covered up front
            span = max(span, self._mega_m)
        self._stalled.clear()
        oldest = self._oldest_inflight()
        for row, seq in list(self._active.items()):
            if row not in self._active:
                continue  # a CoW cache-loss rebuild retired the rest
            last_write = min(seq.pos + span, self.model.seq_len) - 1
            need = last_write // self.block_size + 1
            if need > len(seq.blocks):
                got = self._grow_alloc(row, seq, need - len(seq.blocks),
                                       oldest)
                if got is None:
                    continue  # preempted or stalled out of this step
                seq.blocks.extend(got)
                self._block_gauges()
            for idx in range(seq.pos // self.block_size, need):
                if row not in self._active or row in self._stalled:
                    break  # a scoped CoW failure preempted this row (or
                    #        a denied CoW alloc stalled it)
                wb = seq.blocks[idx]
                if self._alloc.exclusive(wb) and \
                        (self._prefix is None
                         or not self._prefix.contains(wb)):
                    continue  # sole unregistered owner: write in place
                got = self._grow_alloc(row, seq, 1, oldest)
                if got is None:
                    break
                if not self._cow(seq, idx, got[0]):
                    return  # cache rebuilt (or fatal raised)

    # -- anti-thrash preemption policy -------------------------------------
    def _oldest_inflight(self):
        """Request id of the oldest admitted request (active or
        mid-prefill) — the one the anti-thrash policy never preempts, so
        under sustained pressure at least one request always runs to
        completion (the livelock breaker)."""
        reqs = [s.req for s in self._active.values()] + \
               [p.req for p in self._prefilling.values()]
        if not reqs:
            return None
        return min(reqs, key=lambda r: (r.t_submit, r.id)).id

    def _protected(self, seq, oldest):
        """Whether the anti-thrash policy exempts ``seq`` from
        preemption: the oldest in-flight request always, and a resumed
        sequence until it has advanced `MXNET_SERVE_MIN_PROGRESS` tokens
        past its last preemption point (so preempt-replay cycles are
        guaranteed net progress instead of churn).  0 disables both —
        the PR-9 preempt-on-every-denial behavior."""
        if self._min_progress <= 0:
            return False
        if seq.req.id == oldest:
            return True
        base = seq.req._preempt_n_new
        return base is not None and seq.n_new - base < self._min_progress

    def _grow_alloc(self, row, seq, n, oldest):
        """Allocate ``n`` blocks for an active row's growth or CoW under
        the anti-thrash policy.  Returns the blocks, or None after
        either preempting the row (unprotected — the PR-9 path) or
        STALLING it: a protected row whose allocation is denied keeps
        its blocks and context and simply sits out this decode step,
        retrying next iteration — a replay-free wait.  Real pressure
        against a protected row first preempts a younger, unprotected
        victim to free room (never the oldest); with no victim to
        yield, protection defers to the self-preempt rather than
        deadlock a sole sequence."""
        got = self._alloc_blocks(n)
        if got is not None:
            return got
        if not self._protected(seq, oldest):
            self._preempt(row, seq)
            return None
        if not self._alloc.can_serve(n):
            # real exhaustion (eviction already ran inside _alloc_blocks)
            if self._preempt_victim(row, oldest):
                got = self._alloc_blocks(n)
                if got is not None:
                    return got
            else:
                self._preempt(row, seq)
                return None
        # chaos denial with free blocks on hand, or the freed room was
        # denied again: wait in place instead of burning a replay
        self._stall(row)
        return None

    def _preempt_victim(self, protect_row, oldest):
        """Free pool room for a protected row by preempting the
        cheapest younger holder: a fresh mid-chunked-prefill admission
        first (nothing sampled yet, and its partial context is already
        in the prefix index, so the retry is mostly a lookup), then the
        youngest unprotected active sequence.  Never the oldest
        in-flight request.  Returns True when a victim yielded."""
        for pf in reversed(list(self._prefilling.values())):
            r = pf.req
            if r.id == oldest or r._preempt_n_new is not None:
                continue  # resumed prefills are protected like seqs
            self._preempt_prefill(pf)
            return True
        cands = [(row, s) for row, s in self._active.items()
                 if row != protect_row
                 and not self._protected(s, oldest)]
        if not cands:
            return False
        row, seq = max(cands, key=lambda rs: (rs[1].req.t_submit,
                                              rs[1].req.id))
        self._preempt(row, seq)
        return True

    def _preempt_prefill(self, pf):
        """Preempt a mid-chunked-prefill admission (victim path): its
        partially-cached context is released EXACTLY ONCE
        (`_release_blocks` nulls ``pf.blocks``, so no later sweep or
        drop can double-free) and the request requeues at the front.  A
        fresh admission (no sampled tokens) replays its prompt from
        scratch; one that was already resuming still carries
        ``req._resume``, so its re-admission replays the same context —
        output-invisible either way."""
        del self._prefilling[pf.row]
        self._free.append(pf.row)
        req = pf.req
        req._preempt_n_new = pf.resume[2] if pf.resume is not None else 0
        self._release_blocks(pf)
        self.stats["preemptions"] += 1
        self._count("preempted")
        self._note_preempt()
        telemetry.record_event("serve_preempt", replica=self.name,
                               request=req.id, pos=pf.done, prefill=True)
        with self._qlock:
            self._queue.appendleft(req)
        tracing.phase(req.id, "queue_wait", self.name, requeue="preempt",
                      pos=pf.done)

    def _stall(self, row):
        """Sit ``row`` out of this iteration's decode launch: blocks and
        cached context stay put, the allocation retries next step."""
        self._stalled.add(row)
        self.stats["stalls"] += 1
        self._count("stalled")

    def _note_preempt(self):
        """Preemption-storm detector: `MXNET_SERVE_THRASH_TRIP`
        preemptions with no completed request in between trips the PR-8
        degrade path (new admissions capped at max_new_default/4) until
        something completes — pressure drains instead of thrashing."""
        self._preempts_since_retire += 1
        if self._thrash_trip > 0 and not self._storm and \
                self._preempts_since_retire >= self._thrash_trip:
            self._storm = True
            self.stats["thrash_trips"] += 1
            self._count("thrash_trips")
            telemetry.record_event(
                "serve_thrash_trip", replica=self.name,
                preempts=self._preempts_since_retire)

    def _cow(self, seq, idx, dst):
        """Copy block ``seq.blocks[idx]`` into ``dst`` and repoint the
        table.  Returns False when the launch consumed the pool (cache
        rebuild ran — every table is void); device death raises."""
        src = seq.blocks[idx]
        try:
            arrays = (self._put(np.array([src], np.int32)),
                      self._put(np.array([dst], np.int32)))
            self._watch("cow", arrays, ("src", "dst"), 1)
            compiled = self._compiled_cow()
            self._cache = compiled(self._cache, *arrays)
        except Exception as e:
            kind = self._classify_failure(e)
            if kind == "device":
                raise _EngineFatal("cow copy failed: %s" % e) from e
            if kind == "cache":
                self._drop_refs([dst])
                self._rebuild_cache("cow copy failed: %s" % e)
                return False
            # scoped: the pool survived — safest exit is a preemption
            # (replay rebuilds the context; never write the shared block)
            self._drop_refs([dst])
            self._preempt_seq_row(seq)
            return True
        if self._drafter is not None:
            # mirror the copy in the draft pool: the draft rows live at
            # the same (block, offset) coordinates (accept-rate hygiene
            # only — a stale draft block cannot corrupt output)
            self._drafter.on_cow(*arrays)
        seq.blocks[idx] = dst
        self._drop_refs([src])
        self.stats["cow_copies"] += 1
        self._count("cow_copies")
        self._block_gauges()
        return True

    def _preempt_seq_row(self, seq):
        for row, s in list(self._active.items()):
            if s is seq:
                self._preempt(row, seq)
                return

    def _preempt(self, row, seq):
        # the cache holds rows 0..pos-1: exactly the fed tokens `ctx`
        # tracks (a bootstrap admission has fed pos of its prompt and
        # generated nothing; after prefill + k decodes it is prompt +
        # generated[:-1] — the incremental list covers both)
        req = self._vacate_row(row, seq)
        self.stats["preemptions"] += 1
        self._count("preempted")
        self._note_preempt()
        telemetry.record_event("serve_preempt", replica=self.name,
                               request=req.id, pos=seq.pos)
        with self._qlock:
            self._queue.appendleft(req)
        tracing.phase(req.id, "queue_wait", self.name, requeue="preempt",
                      pos=seq.pos)

    def _seq_finished(self, seq, token):
        if seq.req.eos_id is not None and token == seq.req.eos_id:
            return True
        if seq.n_new >= seq.req.max_new_tokens:
            return True
        # `last` is fed (and cached) at `pos` on the next decode, so the
        # last decodable position is seq_len - 1: the token IT samples
        # needs no cache row because generation stops there
        if seq.pos >= self.model.seq_len:
            return True
        return False

    def _retire(self, row, seq, enter=True):
        if enter:
            del self._active[row]
        self._free.append(row)
        if self._drafter is not None and seq.ctx is not None:
            # learning drafters index completed generations (the REST-
            # style store): deterministic decoding makes a finished
            # stream an exact oracle for the next identical request
            self._drafter.on_retire(seq.ctx + [seq.last])
        self._release_blocks(seq)
        if seq.req.session is not None:
            # the turn's full history becomes the session context the
            # next submit(session=...) reattaches; its registered blocks
            # just parked (and will spill under pressure), so the
            # follow-up is a prefix hit — device or host — not a replay
            self._session_store(seq.req)
        seq.req._finish()
        self.stats["completed"] += 1
        # a completion proves the pool drains: reset the storm detector
        self._preempts_since_retire = 0
        self._storm = False
        telemetry.inc("serve.completed")
        telemetry.observe("serve.latency_ms", seq.req.latency_ms)
        if seq.req.ttft_ms is not None:
            telemetry.observe("serve.ttft_ms", seq.req.ttft_ms)

    def _retire_error(self, row, seq, err):
        del self._active[row]
        self._free.append(row)
        self._release_blocks(seq)
        seq.req._finish(error=err)

    def _finish_dropped(self, req, now=None):
        """Resolve a cancelled/expired request with its typed error (the
        single construction site for both — `_sweep` and the admit pop
        share it)."""
        if req._cancelled:
            self._count("cancelled")
            req._finish(error=ServeCancelled(
                "ServeRequest %d: cancelled" % req.id))
        else:
            now = time.perf_counter() if now is None else now
            self._count("expired")
            req._finish(error=ServeDeadlineExceeded(
                "ServeRequest %d: deadline exceeded after %.0f ms"
                % (req.id, 1e3 * (now - req.t_submit))))

    def _sweep(self):
        """Retire expired/cancelled requests at iteration granularity:
        queued ones never reach a prefill, active ones leave the next
        decode batch — shedding costs no extra dispatches."""
        now = time.perf_counter()
        dropped = []
        with self._qlock:
            if any(r._cancelled or r.expired(now) for r in self._queue):
                keep = deque()
                for r in self._queue:
                    if r._cancelled or r.expired(now):
                        dropped.append(r)
                    else:
                        keep.append(r)
                self._queue = keep
                self._qcond.notify_all()
        for row, seq in list(self._active.items()):
            r = seq.req
            if r._cancelled or r.expired(now):
                dropped.append(r)
                del self._active[row]
                self._free.append(row)
                self._release_blocks(seq)
        for pf in list(self._prefilling.values()):
            r = pf.req
            if r._cancelled or r.expired(now):
                dropped.append(r)
                self._drop_prefill(pf)
        for rs in list(self._restoring.values()):
            # a deadline expiring mid-restore (restore_slow pressure)
            # resolves typed like any other holder — the staged arrays
            # simply drop
            r = rs.req
            if r._cancelled or r.expired(now):
                dropped.append(r)
                self._drop_restore(rs)
        for ld in list(self._landing.values()):
            r = ld.ticket.req
            if r._cancelled or r.expired(now):
                dropped.append(r)
                self._drop_landing(ld)
        with self._qlock:
            if any(t.req._cancelled or t.req.expired(now)
                   for t in self._handoff_inbox):
                keep = deque()
                for t in self._handoff_inbox:
                    if t.req._cancelled or t.req.expired(now):
                        dropped.append(t.req)
                    else:
                        keep.append(t)
                self._handoff_inbox = keep
        for r in dropped:
            self._finish_dropped(r, now)

    def _corrupt_scales(self, u):
        """`scale_corrupt:P` chaos: overwrite one HELD block's per-row
        quantization scales with NaN in the device scale array — the
        deterministic stand-in for scale-memory corruption (bit rot, a
        torn spill, a bad restore).  Every launch that subsequently
        reads the block dequantizes NaN K/V, so its logits go nonfinite
        and the in-graph guard MUST convert the step into a typed
        requeue/quarantine — the clause exists to prove "never silent
        wrong tokens" is structural.  Runs as a tiny eager scatter
        between launches (not a serving program: the frozen AotCache
        and the retrace watchdog are about the SERVING shapes, and the
        clause is chaos-only)."""
        held = sorted(self._alloc._ref)
        if not held:
            return
        blk = held[int(u * len(held)) % len(held)]
        pool, scales = self._cache
        idx = jnp.asarray(blk, jnp.int32)
        self._cache = (pool, scales.at[:, :, idx].set(jnp.nan))
        self.stats["scale_corrupts"] += 1
        self._count("quant.scale_corrupts")
        telemetry.record_event("serve_scale_corrupt", replica=self.name,
                               block=int(blk))

    def _inject_flood(self):
        """`queue_flood:rate` chaos: synthetic one-token requests pushed
        through the SAME admission control as real traffic (shed floods
        count in `serve.shed`)."""
        n = chaos.serve_queue_flood()
        for _ in range(n):
            req = ServeRequest([1], 1,
                               deadline_ms=self._deadline_ms_default)
            telemetry.inc("serve.chaos_flooded")
            try:
                self._enqueue(req)
            except ServeError:
                pass  # shed: exactly the pressure the clause probes

    def step(self):
        """One scheduler iteration.  Dispatches to the PR-15 single-step
        body (`_step`) or the double-buffered megastep body
        (`_step_mega`), wrapped in the decode-loop wall/host accounting
        behind the `serve.<name>.host_frac` gauge.  host_frac is the
        EXPOSED host fraction: wall time outside any launch-dispatch ->
        fetch-complete span — host work the in-flight launch was NOT
        hiding.  Single-step fetches right after dispatch, so its whole
        sweep is exposed; the double-buffered megastep runs the sweep
        inside the span, so the gauge collapses toward the walk/launch
        residue.  Only iterations that actually launched accumulate (an
        idle or admission-only iteration has no decode loop to
        attribute), and each leaves one replica-scoped `iteration` record
        in the span store (docs/observability.md "Request tracing"), with
        the host seconds of every phase since the last record (an
        iteration that launched nothing, and the loop's waits, carry over
        into the next).  The whole body is a `sched.iteration` span in a
        profiler trace, which carries the record's ``t0`` as `t0_ns`.
        Returns the number of sequences still active (0 = idle)."""
        t0 = time.perf_counter()
        h0 = self.stats["hidden_s"]
        c0 = self.stats["prefill_chunks"]
        r0 = self.stats["decode_rows"] + self.stats["prefill_tokens"]
        # the anchor costs more than the span: built only while a trace
        # is being taken
        anchor = {"t0_ns": int(t0 * 1e9)} if profiler.taking_trace() \
            else {}
        # the span around the leaves, itself timed by no table
        with _Phase(None, "iteration", **anchor):
            with self._phase("account"):
                # fold settled expert-load rows (all but the newest — it
                # may still be in flight) into the per-expert gauges
                self._drain_moe()
            self._iter = {}
            if self._mega_m and not self._spec:
                n = self._step_mega()
            else:
                n = self._step()
            with self._phase("account") as account:
                if self._moe:
                    self.stats["moe_pairs_routed"] += \
                        self.model.moe_pairs_per_row \
                        * (self.stats["decode_rows"]
                           + self.stats["prefill_tokens"] - r0)
                dh = self.stats["hidden_s"] - h0
                if dh > 0:
                    self._record_iteration(t0, dh, c0, account)
        return n

    def _record_iteration(self, t0, dh, c0, account):
        """The host_frac accounting of an iteration that launched, and its
        `iteration` record, written inside its ``account`` phase."""
        # a single-step iteration has fetched its launch's tokens, so
        # every count pending is on the host's side of it
        self._drain_moe(keep_last=bool(self._mega_m))
        self._iter.update(self._moe_record())
        t1 = time.perf_counter()
        wall = t1 - t0
        self.stats["wall_s"] += wall
        self.stats["host_s"] += max(0.0, wall - dh)
        telemetry.set_gauge(
            self._gauge + "host_frac",
            round(self.stats["host_s"] / self.stats["wall_s"], 4))
        acc = self._phase_s
        if acc is not None:
            # the phase's part so far rides in this record, the rest in
            # the next
            account.split()
            self._iter["phase_ms"] = {k: round(1e3 * v, 3)
                                      for k, v in acc.items()}
            acc.update(_NO_PHASE_S)
        # the replica-scoped iteration record: the counts as taken where
        # the work happened, in this iteration
        tracing.add_span(
            0, "iteration", self.name, t0, t1,
            chunks=self.stats["prefill_chunks"] - c0, **self._iter)

    def _phase(self, name):
        """A leaf phase (one of `SCHED_PHASES`) of this engine."""
        return _Phase(self._phase_s, name)

    def _grow(self):
        """Cover the active rows' next positions with blocks, then the
        iteration's full pool gauges."""
        with self._phase("grow"):
            self._grow_active()
            self._iter["blocks_live"], self._iter["blocks_parked"] = \
                self._block_gauges(full=True)
            if self._state_slots:
                # a slot is live from admission to retirement
                live = self.max_batch - len(self._free)
                self._iter["state_slots_live"] = live
                telemetry.set_gauge(self._gauge + "state_slots_live", live)

    def _advance_staged(self):
        """Chunk dispatch.  Restores staged last iteration land BEFORE
        new prefill chunks and admissions: their transfers already
        overlapped the previous decode launch (handoff landings ride
        the same two-stage overlap)."""
        with self._phase("prefill"):
            self._advance_restores()
            self._advance_landings()
            self._advance_prefills()
            self._stage_handoffs()

    def _admit(self):
        """Admit queued requests while a row is free."""
        with self._phase("admit"):
            while self._free:
                with self._qlock:
                    req = self._queue.popleft() if self._queue else None
                    if req is not None:
                        self._admitting += 1
                        self._qcond.notify_all()
                if req is None:
                    break
                try:
                    if req._cancelled or req.expired():
                        # arrived expired between sweeps
                        self._finish_dropped(req)
                        continue
                    if self._admit_one(req) is False:
                        break  # block pool can't admit more this iteration
                finally:
                    with self._qlock:
                        self._admitting -= 1
            with self._qlock:
                self._iter["queued"] = len(self._queue)
            telemetry.set_gauge(self._gauge + "queue_depth",
                                self._iter["queued"])

    def _step(self):
        """One single-step scheduler iteration: sweep deadlines/
        cancellations, admit while there is room, then one decode step
        over the active set."""
        self.last_beat = time.monotonic()
        if chaos.enabled():
            self._inject_flood()
            if self._kv_quant is not None:
                u = chaos.serve_scale_corrupt()
                if u is not None:
                    self._corrupt_scales(u)
            if self._prefix is not None and chaos.serve_prefix_evict():
                # `prefix_evict:P` chaos: shove the LRU parked block out
                # as if allocation pressure claimed it — hot-prefix loss
                # must only cost a re-prefill, never correctness
                evicted = self._prefix.evict(1)
                if evicted:
                    self._alloc.reclaim(evicted)
                    self._count_evictions(len(evicted))
        with self._phase("sweep"):
            self._sweep()
        self._advance_staged()
        self._admit()
        self._grow()
        n = len(self._active)
        if n > self.stats["max_concurrent"]:
            self.stats["max_concurrent"] = n
        telemetry.set_gauge(self._gauge + "active", n)
        if n == 0:
            # mid-stream chunked prefills, staged restores and staged
            # handoffs still count as work: the scheduler keeps stepping
            # until they land
            return self._pending_work()
        if chaos.enabled():
            if chaos.serve_engine_crash(self.name):
                raise chaos.ChaosEngineCrash(
                    "chaos: engine_crash killed replica %s" % self.name)
            ms = chaos.serve_decode_slow()
            if ms:
                time.sleep(ms / 1e3)
        if self._spec:
            return self._decode_spec()
        return self._decode_plain()

    def _decode_plain(self):
        """One single-token decode launch over the active set (the
        PR-10 iteration body; also the speculative mode's fallback when
        no row has a usable draft — a verify launch that can only
        accept zero drafts would pay the k+1-wide program for the same
        one token per row this computes)."""
        rows = [s for s in self._active if s not in self._stalled]
        n = len(rows)
        if n == 0:
            # every active row is stalled on a denied allocation: nothing
            # to launch — back off briefly so the retry loop doesn't spin
            # the host while it waits for room (or a deadline) to resolve
            time.sleep(0.001)
            return len(self._active) + self._pending_work()
        b = self._bucket_for(n, self.decode_buckets)
        seqs = [self._active[s] for s in rows]
        with self._phase("pack"):
            token = np.zeros((b,), np.int32)
            pos = np.zeros((b,), np.int32)
            tables = np.full((b, self._n_table), TRASH_BLOCK, np.int32)
            for i, seq in enumerate(seqs):
                token[i] = seq.last
                pos[i] = seq.pos
                tables[i, :len(seq.blocks)] = seq.blocks
            samp, sampled = self._samp_device([s.req for s in seqs], b)
            args = tuple(self._put(a) for a in (token, pos, tables)
                         + self._slots(rows, b)) + samp
            self._watch("decode", args,
                        self._DECODE_NAMES + self._tail_names(samp), b)
            compiled = self._compiled_decode(b)
        # table entries the rows' attention walks: their live blocks
        ctx = int(np.sum(pos[:n] // self.block_size) + n)
        self._iter.update(rows=n, bucket=b, attn_kernel=self._attn_kernel,
                          ctx_blocks=ctx, sampled_rows=sampled)
        if getattr(self.model, "ring_layers", 0):
            # a model whose window layers keep no blocks: the blocks its
            # full layers' attention reads, all of them
            self._iter["full_ctx_blocks"] = ctx * self.model.full_layers
        t_launch = time.perf_counter()
        try:
            if chaos.serve_launch_error():
                raise chaos.ChaosError("chaos: injected decode launch error")
            with self._phase("launch"):
                nxt, self._cache = self._unpack(
                    compiled(self._params, self._cache, *args))
        except Exception as e:
            # scoped/transient: the donated cache survived — retry the
            # same decode next iteration, escalate after N consecutive
            self._handle_launch_failure(e, "decode")
            return len(self._active) + self._pending_work()
        self._launch_fails = 0
        t_fetch = time.perf_counter()
        with self._phase("fetch"):
            nxt = np.asarray(nxt)  # the one per-step host fetch (b ints)
        now = time.perf_counter()
        self.stats["fetch_wait_s"] += now - t_fetch
        self.stats["hidden_s"] += now - t_launch
        self.stats["decode_steps"] += 1
        self.stats["decode_rows"] += n
        self.stats["decode_padded"] += b - n
        self.stats["tokens"] += n
        telemetry.inc("serve.decode_steps")
        telemetry.inc("serve.tokens", n)
        telemetry.inc("serve.decode_padded", b - n)
        telemetry.set_gauge(self._gauge + "batch_occupancy", n / float(b))
        with self._phase("publish"):
            for i, (row, seq) in enumerate(zip(rows, seqs)):
                t = int(nxt[i])
                if t < 0:
                    # quantization logit gate: never emit the flagged token
                    self._quant_trip_seq(row, seq)
                    continue
                finished = self._advance_one(seq, t)
                if not finished and self._drafter is not None \
                        and seq.ctx is not None:
                    # adaptive-fallback rounds still feed the drafter's
                    # store: a staggered twin drafts off this row's stream
                    self._drafter.observe(seq.ctx + [seq.last], 1)
                if finished:
                    self._retire(row, seq)
                seq.req._publish()
        return len(self._active) + self._pending_work()

    def _step_mega(self):
        """One double-buffered megastep iteration (docs/serving.md
        "Megastep decode & streaming"): the m-step launch is dispatched
        FIRST, the host sweep (retire/admission/block accounting/
        journal) runs while it is in flight, and only then does the
        iteration block on the (b, m) token grid — the
        `DevicePrefetchIter` two-stage overlap applied to `_sweep`.
        Safe because the device stream is serial (a prefill queued
        during the overlap window executes after the megastep's writes,
        so a freed-and-reassigned block is rewritten by its new owner
        before any read) and because `_finish_mega` identity-checks
        each row against `_active` (a row swept or preempted mid-
        flight just drops its in-flight tokens; replay resumes from
        the pre-megastep journal position)."""
        self.last_beat = time.monotonic()
        if chaos.enabled():
            self._inject_flood()
            if self._kv_quant is not None:
                u = chaos.serve_scale_corrupt()
                if u is not None:
                    self._corrupt_scales(u)
            if self._prefix is not None and chaos.serve_prefix_evict():
                evicted = self._prefix.evict(1)
                if evicted:
                    self._alloc.reclaim(evicted)
                    self._count_evictions(len(evicted))
        inflight = None
        if self._active:
            # grow BEFORE launch: the megastep writes up to m positions
            # before the host sees any of them, so the whole span must
            # be covered (and exclusively owned) up front
            self._grow()
            inflight = self._launch_mega()
        # -- overlap window: host work the device no longer waits on --
        t_sweep = time.perf_counter()
        with self._phase("sweep"):
            self._sweep()
        self._advance_staged()
        self._admit()
        n = len(self._active)
        if n > self.stats["max_concurrent"]:
            self.stats["max_concurrent"] = n
        telemetry.set_gauge(self._gauge + "active", n)
        if chaos.enabled() and inflight is not None:
            if chaos.serve_engine_crash(self.name):
                # the mid-megastep crash: the launch is in flight, its
                # tokens are not yet journaled — replay must resume
                # from the last PROCESSED position without re-streaming
                raise chaos.ChaosEngineCrash(
                    "chaos: engine_crash killed replica %s" % self.name)
            ms = chaos.serve_decode_slow()
            if ms:
                time.sleep(ms / 1e3)
        if inflight is not None:
            # the replica-scoped host-sweep span: the PR-16 host_frac
            # bookkeeping's overlap window, visible per iteration
            tracing.add_span(0, "host_sweep", self.name, t_sweep,
                             time.perf_counter())
            self._finish_mega(inflight)
        elif self._active:
            # every active row is stalled on a denied allocation —
            # back off briefly so the retry loop doesn't spin the host
            time.sleep(0.001)
        return len(self._active) + self._pending_work()

    def _launch_mega(self):
        """Dispatch ONE m-step megastep over the non-stalled active
        rows and return the in-flight handle WITHOUT blocking —
        `_finish_mega` fetches after the host sweep has already run
        under the launch.  Returns None when nothing launched (all
        rows stalled, or the launch failed and took the retry
        ladder)."""
        rows = [s for s in self._active if s not in self._stalled]
        nrows = len(rows)
        if nrows == 0:
            return None
        b = self._bucket_for(nrows, self.decode_buckets)
        seqs = [self._active[s] for s in rows]
        with self._phase("pack"):
            token = np.zeros((b,), np.int32)
            pos = np.zeros((b,), np.int32)
            left = np.zeros((b,), np.int32)
            eos = np.full((b,), -1, np.int32)
            tables = np.full((b, self._n_table), TRASH_BLOCK, np.int32)
            for i, seq in enumerate(seqs):
                token[i] = seq.last
                pos[i] = seq.pos
                left[i] = max(0, seq.req.max_new_tokens - seq.n_new)
                if seq.req.eos_id is not None:
                    eos[i] = int(seq.req.eos_id)
                tables[i, :len(seq.blocks)] = seq.blocks
            samp, sampled = self._samp_device([s.req for s in seqs], b)
            args = (self._put(token), self._put(pos), self._put(left),
                    self._put(eos), self._put(tables)) + samp
            self._watch("megastep", args,
                        ("token", "pos", "left", "eos", "tables")
                        + self._SAMPLE_NAMES[:len(samp)], b)
            compiled = self._compiled_mega(b)
        self._iter.update(rows=nrows, bucket=b,
                          attn_kernel=self._attn_kernel,
                          sampled_rows=sampled)
        t_launch = time.perf_counter()
        try:
            if chaos.serve_launch_error():
                raise chaos.ChaosError(
                    "chaos: injected megastep launch error")
            with self._phase("launch"):
                out, self._cache = self._unpack(
                    compiled(self._params, self._cache, *args))
        except Exception as e:
            self._handle_launch_failure(e, "megastep")
            return None
        self._launch_fails = 0
        return (rows, seqs, out, nrows, b, t_launch)

    def _finish_mega(self, inflight):
        """Fetch a megastep's (b, m) token grid and walk it row-major
        through `_advance_one` — the SAME single bookkeeping site the
        plain and speculative loops use, so stopping, ctx order and
        prefix registration cannot diverge.  Grid sentinels: >=0 real
        token, -1 quant trip at that step (earlier emits stand, the
        trip scrubs/requeues exactly as a single-step trip would),
        -2 dead (the row retired at an earlier step — or was launched
        already-finished)."""
        rows, seqs, out, nrows, b, t_launch = inflight
        t_fetch = time.perf_counter()
        with self._phase("fetch"):
            out = np.asarray(out)  # the one per-megastep host fetch
        now = time.perf_counter()
        self.stats["fetch_wait_s"] += now - t_fetch
        # the launch->fetch span: every host cycle spent inside it
        # (the whole overlap window) rode under the in-flight megastep
        self.stats["hidden_s"] += now - t_launch
        tracing.add_span(0, "megastep", self.name, t_launch, now,
                         rows=nrows, bucket=b, m=self._mega_m)
        m = self._mega_m
        self.stats["megasteps"] += 1
        self.stats["decode_rows"] += nrows
        self.stats["decode_padded"] += b - nrows
        telemetry.inc("serve.megasteps")
        telemetry.inc("serve.decode_padded", b - nrows)
        telemetry.set_gauge(self._gauge + "batch_occupancy",
                            nrows / float(b))
        emitted = retired = 0
        with self._phase("publish"):
            for i, (row, seq) in enumerate(zip(rows, seqs)):
                if self._active.get(row) is not seq:
                    # swept, preempted or vacated while in flight: its
                    # in-flight tokens drop on the floor; the journal still
                    # holds the pre-megastep position, so replay neither
                    # loses nor duplicates anything
                    continue
                adv = 0
                finished = tripped = False
                for j in range(m):
                    t = int(out[i, j])
                    if t == -2:
                        break
                    if t < 0:
                        tripped = True
                        break
                    finished = self._advance_one(seq, t)
                    adv += 1
                    if finished:
                        break
                emitted += adv
                if tripped:
                    # quantization logit gate: never emit the flagged token
                    self._quant_trip_seq(row, seq, "megastep")
                elif finished:
                    retired += 1  # retirement decided in-graph, mid-scan
                    self._retire(row, seq)
                elif adv and self._drafter is not None \
                        and seq.ctx is not None:
                    self._drafter.observe(seq.ctx + [seq.last], adv)
                seq.req._publish()
        self.stats["tokens"] += emitted
        self.stats["megastep_tokens"] += emitted
        self.stats["ingraph_retired"] += retired
        telemetry.inc("serve.tokens", emitted)
        telemetry.inc("serve.megastep_tokens", emitted)
        if retired:
            telemetry.inc("serve.ingraph_retired", retired)

    def _decode_mega(self):
        """Synchronous megastep round: launch + immediate fetch — the
        speculative mode's no-usable-draft fallback when megastep is
        also on.  Spec verify rounds and megasteps share
        `_advance_one` and the block-span bookkeeping
        (`_grow_active` covers max(k+1, m)), so the two interleave
        without diverging from either oracle."""
        inflight = self._launch_mega()
        if inflight is None:
            if self._active:
                time.sleep(0.001)
            return len(self._active) + self._pending_work()
        self._finish_mega(inflight)
        return len(self._active) + self._pending_work()

    def _advance_one(self, seq, t):
        """Advance one sequence by ONE emitted token ``t`` — the single
        bookkeeping site both the plain decode loop and the speculative
        accept loop run, so stopping, truncation, ctx order and prefix
        registration cannot diverge between them.  Returns True when
        the sequence finished with this token."""
        if seq.req.t_first is None:
            # a prefix-bootstrap admission skipped prefill: THIS is its
            # first token (ttft = pure cache-hit latency)
            seq.req.t_first = time.perf_counter()
        seq.req.tokens.append(t)
        if seq.ctx is not None:
            seq.ctx.append(seq.last)  # the token cached at the old pos
        seq.last = t
        seq.pos += 1
        seq.n_new += 1
        if self._prefix is not None and seq.pos % self.block_size == 0:
            # the block behind `pos` just filled with real rows: publish
            # it (eagerly — concurrent requests share it while this one
            # keeps decoding; CoW guards the writer)
            self._register_prefix(seq.ctx, seq.blocks, seq.pos)
        return self._seq_finished(seq, t)

    def _handle_launch_failure(self, e, what):
        """The decode/verify launch failure ladder, shared so the two
        iteration modes cannot drift: device death raises
        `_EngineFatal`, a consumed cache rebuilds (returns True), a
        scoped/transient fault counts toward the consecutive-failure
        escalation and retries next iteration (returns False)."""
        kind = self._classify_failure(e)
        if kind == "device":
            raise _EngineFatal("%s launch failed: %s" % (what, e)) from e
        if kind == "cache":
            self._rebuild_cache("%s launch failed: %s" % (what, e))
            return True
        self._launch_fails += 1
        self._count("launch_errors")
        if self._launch_fails >= self._launch_retries:
            raise _EngineFatal(
                "%s launch failed %d consecutive times (last: %s)"
                % (what, self._launch_fails, e)) from e
        return False

    # -- speculative decode (draft -> verify -> accept/rollback) -----------
    def _rewind_blocks(self, seq):
        """Release the speculative tail past the ACCEPTED frontier: the
        row keeps exactly the blocks covering its cached rows 0..pos-1,
        everything beyond holds rejected-draft garbage and goes back
        through `_drop_refs` — the same exactly-one-ref drop site every
        other release uses.  That routing is the whole safety argument:
        a tail block another request shares (refcount > 1) loses only
        THIS row's reference, and a tail block the prefix index
        registered parks instead of returning to the free list, so a
        rewind can never free or alias a block someone else still
        reads.  The floor at `blocks_for(pos)` means accepted context
        is never rewound, shared prefix blocks included."""
        keep = max(1, self._alloc.blocks_for(seq.pos))
        if len(seq.blocks) <= keep:
            return
        tail = seq.blocks[keep:]
        del seq.blocks[keep:]
        self._drop_refs(tail)
        self.stats["spec_rollbacks"] += len(tail)
        self._count("spec.rollbacks", len(tail))
        self._block_gauges()

    def _decode_spec(self):
        """One draft-verify-accept iteration over the active set (the
        MXNET_SERVE_SPEC replacement for the single-token decode step).

        The drafter proposes k tokens per row; ONE verify launch feeds
        [last, d_1..d_k] at positions pos..pos+k, scatters their K/V
        through the block tables (the span `_grow_active` secured), and
        returns the target's own pick at every position plus the count
        of leading drafts that match those picks.  Accepted tokens are
        then consumed host-side ONE AT A TIME through the exact
        bookkeeping the sequential path uses — ctx/pos/n_new advance,
        blocks register on fill, `_seq_finished` checks EOS/max_new/
        depth per token — so stopping, truncation and prefix
        registration are bit-identical to non-speculative decode.
        Rejected positions hold garbage K/V the next round overwrites
        before attending; their tail blocks rewind via `_drop_refs`."""
        rows = [r for r in self._active if r not in self._stalled]
        n = len(rows)
        if n == 0:
            time.sleep(0.001)  # all rows stalled: retry next iteration
            return len(self._active) + self._pending_work()
        b = self._bucket_for(n, self.decode_buckets)
        k = self._spec_k
        c = k + 1
        seqs = [self._active[r] for r in rows]
        with self._phase("pack"):
            token = np.zeros((b, c), np.int32)
            pos = np.zeros((b,), np.int32)
            length = np.ones((b,), np.int32)
            tables = np.full((b, self._n_table), TRASH_BLOCK, np.int32)
            for i, seq in enumerate(seqs):
                token[i, 0] = seq.last
                pos[i] = seq.pos
                length[i] = min(c, self.model.seq_len - seq.pos)
                tables[i, :len(seq.blocks)] = seq.blocks
            pos_d = self._put(pos)
            tables_d = self._put(tables)
            samp, sampled = self._samp_device([s.req for s in seqs], b)
            tok0 = token[:, 0].copy()
            dev = (self._put(tok0), pos_d, tables_d) \
                if self._drafter.needs_device else None
            drafts = self._drafter.propose(seqs, k, b,
                                           host=(tok0, pos, tables),
                                           dev=dev, samp=samp)
            usable = True
            if isinstance(drafts, tuple):
                drafts, confident = drafts
                usable = np.asarray(confident)[:n].any()
        if not usable:
            # adaptive speculation: with no usable draft anywhere in
            # the batch a verify could only advance one token per
            # row — run the (cheaper) plain round instead; with
            # megastep on the fallback fuses m steps (the megastep
            # x speculation interlock: both paths run _advance_one
            # and share the max(k+1, m) block-span bookkeeping)
            if self._mega_m:
                return self._decode_mega()
            return self._decode_plain()
        with self._phase("pack"):
            if chaos.enabled() and chaos.serve_draft_junk():
                # `draft_junk:P`: deterministically corrupt the round's
                # proposals — parity must hold, only the accept rate drops
                drafts = (np.asarray(drafts, np.int64) + 1
                          + np.arange(k, dtype=np.int64)[None]) \
                    % self.model.vocab_size
                self.stats["spec_junk_rounds"] += 1
                telemetry.inc("serve.chaos_draft_junk")
            token[:, 1:] = np.asarray(drafts, np.int32)[:b]
            token_d = self._put(token)
            length_d = self._put(length)
            args = (token_d, pos_d, length_d, tables_d) + samp
            self._watch("verify", args,
                        ("tokens", "pos", "length", "tables")
                        + self._SAMPLE_NAMES[:len(samp)], b)
            compiled = self._compiled_verify(b)
        # a verify launch attends with `verify_attention`, never the kernel
        self._iter.update(rows=n, bucket=b, attn_kernel=0,
                          sampled_rows=sampled)
        t_launch = time.perf_counter()
        try:
            if chaos.serve_launch_error():
                raise chaos.ChaosError("chaos: injected verify launch "
                                       "error")
            with self._phase("launch"):
                out, self._cache = self._unpack(
                    compiled(self._params, self._cache, *args))
        except Exception as e:
            self._handle_launch_failure(e, "verify")
            return len(self._active) + self._pending_work()
        self._launch_fails = 0
        t_fetch = time.perf_counter()
        with self._phase("fetch"):
            out = np.asarray(out)  # (b, k+2): picks then n_accepted
        now = time.perf_counter()
        self.stats["fetch_wait_s"] += now - t_fetch
        self.stats["hidden_s"] += now - t_launch
        tracing.add_span(0, "spec_round", self.name, t_launch, now,
                         rows=n, bucket=b, k=k)
        self.stats["verify_steps"] += 1
        self.stats["decode_rows"] += n
        self.stats["decode_padded"] += b - n
        telemetry.inc("serve.verify_steps")
        telemetry.inc("serve.decode_padded", b - n)
        telemetry.set_gauge(self._gauge + "batch_occupancy", n / float(b))
        emitted_total = 0
        seqs_n_new = [s.n_new for s in seqs]
        with self._phase("publish"):
            for i, (row, seq) in enumerate(zip(rows, seqs)):
                # drafts past this row's in-range span can never be emitted
                # (their K/V went to the trash block); clamp acceptance so
                # the host loop below cannot walk into them
                n_acc = min(int(out[i, c]), int(length[i]) - 1)
                self.stats["spec_proposed"] += k
                self._count("spec.proposed", k)
                finished = False
                tripped = False
                acc_emitted = 0
                for j in range(n_acc + 1):
                    t = int(out[i, j])
                    if t < 0:
                        # quantization logit gate: tokens accepted BEFORE
                        # the flagged position passed it (identical context
                        # to sequential decode); the trip retires the row
                        # into the exact-replay requeue from right here
                        tripped = True
                        break
                    emitted_total += 1
                    if j < n_acc:
                        acc_emitted += 1
                    if self._advance_one(seq, t):
                        finished = True
                        break
                # a trip discards the tail past the flagged position — the
                # accept counters (and the accept_rate gauge the chaos runs
                # watch) only count drafts that actually reached the output
                n_counted = acc_emitted if tripped else n_acc
                self.stats["spec_accepted"] += n_counted
                if n_counted:
                    self._count("spec.accepted", n_counted)
                if tripped:
                    self._quant_trip_seq(row, seq, "verify")
                elif finished:
                    self._retire(row, seq)
                else:
                    if seq.n_new > seqs_n_new[i]:
                        # let a learning drafter see this row's fresh tokens
                        # now (a concurrent twin drafts off them next round)
                        self._drafter.observe(seq.ctx + [seq.last],
                                              seq.n_new - seqs_n_new[i])
                    self._rewind_blocks(seq)
                seq.req._publish()
        self.stats["tokens"] += emitted_total
        telemetry.inc("serve.tokens", emitted_total)
        if self.stats["spec_proposed"]:
            telemetry.set_gauge(
                self._gauge + "spec_accept_rate",
                round(self.stats["spec_accepted"]
                      / float(self.stats["spec_proposed"]), 4))
        return len(self._active) + self._pending_work()

    # -- worker loop -------------------------------------------------------
    def start(self):
        """Run the scheduler on a background thread (idempotent)."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stopped.clear()
        self._thread = threading.Thread(
            target=self._loop, name="serve-%s" % self.name, daemon=True)
        self._thread.start()
        return self

    def _loop(self):
        while not self._stopped.is_set():
            try:
                n = self.step()
            except Exception as e:  # noqa: BLE001
                # per-request poison and cache loss are absorbed inside
                # step(); anything that escapes is device-scoped — die
                # loudly, hand queued requests to the router's failover
                telemetry.inc("serve.engine_failures")
                self._die(str(e)[:500])
                return
            self.last_beat = time.monotonic()
            if n == 0:
                # idle: wait for a submit instead of spinning step() (and
                # its gauge writes) at 1 kHz per replica.  Clear FIRST and
                # then re-check the queue, so a submit landing in between
                # leaves the event set and wait() returns immediately.
                self._wake.clear()
                with self._qlock:
                    queued = bool(self._queue) or \
                        bool(self._handoff_inbox)
                if not queued and not self._stopped.is_set():
                    # outside any iteration: the engine has no work
                    with self._phase("wait"):
                        self._wake.wait(0.05)

    def _die(self, msg):
        """Scheduler death: release every admitted request's cache state
        (the blocks died with the device anyway; releasing keeps the
        accounting honest), mark dead, and hand BOTH the in-flight
        (admitted) and the queued-but-not-admitted requests to the
        router's failover hook.  A journal-owning router migrates the
        in-flight ones to survivors via exact replay; without a journal
        (or without a router) they fail typed — their K/V context alone
        is unrecoverable — exactly the PR-11 contract."""
        err = ServeEngineDead("ServingEngine %s: scheduler died: %s"
                              % (self.name, msg))
        # postmortem FIRST, while the rings still hold the death's lead-up
        # (the failover hook below may enqueue onto survivors and write
        # fresh spans into the stream)
        tracing.dump(self.name, "scheduler_death", error=msg[:200])
        inflight = self._sweep_inflight()
        with self._qlock:
            # mark dead and drain atomically: _enqueue checks _dead under
            # this lock, so everything it enqueued is in `pending` and
            # everything after it raises
            self._dead = msg
            pending = list(self._queue)
            self._queue.clear()
            self._qcond.notify_all()
        handler = self._on_death
        if handler is not None:
            try:
                handler(self, pending, inflight, msg)
                return
            except Exception:  # failover must never strand requests
                pass
        for req in inflight + pending:
            req._finish(error=err)

    def _sweep_inflight(self):
        """Remove every admitted sequence and mid-stream prefill, release
        their cache state (rows freed, block refs dropped exactly once),
        and return their requests UNRESOLVED — the shared walk under
        `_die` (hook migrates or fails them) and `drain` (router
        migrates the stragglers), so the release accounting cannot
        diverge between the two exits."""
        inflight = []
        for row, seq in list(self._active.items()):
            del self._active[row]
            self._free.append(row)
            self._release_blocks(seq)
            inflight.append(seq.req)
        for pf in list(self._prefilling.values()):
            del self._prefilling[pf.row]
            self._free.append(pf.row)
            self._release_blocks(pf)
            inflight.append(pf.req)
        for rs in list(self._restoring.values()):
            del self._restoring[rs.row]
            self._free.append(rs.row)
            self._release_blocks(rs)
            inflight.append(rs.req)
        for ld in list(self._landing.values()):
            # a staged handoff landing dies with this replica: the
            # request rejoins the failover walk and migrates (journal
            # exact-replay) like any other in-flight sequence — the
            # target-death-mid-transfer road
            del self._landing[ld.row]
            self._free.append(ld.row)
            self._release_blocks(ld)
            inflight.append(ld.ticket.req)
        with self._qlock:
            while self._handoff_inbox:
                inflight.append(self._handoff_inbox.popleft().req)
        return inflight

    def _join_thread(self):
        """Stop and join the scheduler thread (after which the caller
        owns every piece of scheduler state)."""
        self._stopped.set()
        self._wake.set()
        with self._qcond:
            self._qcond.notify_all()  # unblock `block`-policy submitters
        t = self._thread
        if t is not None:
            t.join(timeout=30)
            if t.is_alive():
                # a wedged device launch: keep the ref so a later start()
                # cannot spawn a second scheduler over the same cache and
                # row state, and fail loudly
                raise MXNetError(
                    "ServingEngine %s: scheduler thread did not stop "
                    "within 30s (wedged launch?)" % self.name)
            self._thread = None

    def stop(self):
        self._join_thread()
        # every-request-resolves contract: anything still queued or
        # admitted when the scheduler stopped gets a typed error instead
        # of a result() that hangs forever (drained under the same lock
        # _enqueue's stopped-check reads, so no request slips in after)
        err = ServeEngineDead("ServingEngine %s: engine stopped"
                              % self.name)
        with self._qlock:
            stranded = list(self._queue)
            self._queue.clear()
        # post-join the caller owns the scheduler state: reuse the same
        # sweep `_die`/`drain` use so release accounting cannot diverge
        for req in self._sweep_inflight():
            req._finish(error=err)
        for req in stranded:
            req._finish(error=err)

    def drain(self, deadline_ms=None):
        """Graceful drain (rolling-restart half of the durability story):
        close admission — new `submit`s raise typed `ServeEngineDead`
        and a router routes around this replica — keep serving the work
        already here until it finishes or ``deadline_ms`` expires
        (default ``MXNET_SERVE_DRAIN_MS``; 0/None = wait for idle), then
        stop the scheduler and return the STRAGGLERS: every request
        still in flight, unfinished, each reconstructible through the
        journal's exact-replay formula.  `ReplicaRouter.drain` migrates
        them to survivors; a standalone caller may resubmit or fail
        them.  In-flight stragglers come first (they carry progress),
        then the still-queued tail."""
        if deadline_ms is None:
            dl = float(os.environ.get("MXNET_SERVE_DRAIN_MS", "0"))
            deadline_ms = dl if dl > 0 else None
        with self._qcond:
            self._draining = True
            self._qcond.notify_all()  # blocked submitters resolve typed
        self._wake.set()
        telemetry.record_event("serve_drain_begin", replica=self.name,
                               depth=self.depth())
        t0 = time.monotonic()
        budget_s = None if deadline_ms is None else float(deadline_ms) / 1e3
        while not self._stopped.is_set():
            with self._qlock:   # _die publishes _dead under _qlock
                if self._dead is not None:
                    break
            if self._thread is not None and self._thread.is_alive():
                if self.depth() == 0:
                    break
                time.sleep(0.005)
            else:
                try:
                    n = self.step()
                except Exception as e:  # noqa: BLE001 — same as _loop
                    telemetry.inc("serve.engine_failures")
                    self._die(str(e)[:500])
                    break
                if n == 0:
                    with self._qlock:
                        if not self._queue:
                            break
            if budget_s is not None and time.monotonic() - t0 > budget_s:
                break
        # quiesce the scheduler so the straggler walk owns the state
        self._join_thread()
        stragglers = self._sweep_inflight()
        with self._qlock:
            stragglers.extend(self._queue)
            self._queue.clear()
            self._qcond.notify_all()
        self._count("drained")
        telemetry.record_event("serve_drain", replica=self.name,
                               stragglers=len(stragglers),
                               waited_ms=round(1e3 * (time.monotonic()
                                                      - t0), 1))
        return stragglers

    def run_until_idle(self, timeout=None):
        """Drive the scheduler until the queue and active set drain;
        returns steps taken.  Steps synchronously when no worker thread
        owns the engine, polls for drain when one does, and returns
        immediately on a dead engine (its queue was drained/redispatched
        at death — that depth will never drain by stepping)."""
        t0 = time.perf_counter()
        steps = 0
        while True:
            with self._qlock:   # _die publishes _dead under _qlock
                dead = self._dead
            if dead is not None:
                return steps
            thread_driven = self._thread is not None and \
                self._thread.is_alive()
            if thread_driven:
                if self.depth() == 0:
                    return steps
                time.sleep(0.005)
            else:
                with self._qlock:
                    queued = len(self._queue)
                if self.step() == 0 and queued == 0:
                    with self._qlock:
                        if not self._queue:
                            return steps
                steps += 1
            if timeout is not None and time.perf_counter() - t0 > timeout:
                raise ServeTimeout(
                    "run_until_idle: timed out after %.1fs "
                    "(%d steps, depth %d)" % (timeout, steps, self.depth()))


def _default_decode_buckets(max_batch):
    """Powers of two up to max_batch (+ max_batch itself)."""
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return sorted(set(out))


def _default_prefill_buckets(seq_len):
    """Powers of two from 16 up to seq_len (+ seq_len itself)."""
    out, s = [], 16
    while s < seq_len:
        out.append(s)
        s *= 2
    out.append(seq_len)
    return sorted(set(out))


class ReplicaRouter:
    """Least-depth dispatch over per-device engine replicas, with health
    monitoring, failover, and respawn.

    A replica is one device holding full params — OR a sub-mesh of
    ``devices_per_replica`` devices over which one engine shards its
    params and paged KV pool via NamedSharding/pjit (docs/serving.md
    "Sharded replicas"): models bigger than one chip serve as ONE
    replica here, and every failover/respawn/journal/drain mechanism
    below composes unchanged because the router only ever sees the
    engine, never the mesh.  `from_mesh` builds one engine per device
    (row-major over the first axis), or one engine per consecutive
    ``devices_per_replica``-device sub-mesh.

    Partial failure is the normal case: when a replica's scheduler dies,
    its queued-but-not-admitted requests re-dispatch to survivors, its
    ADMITTED in-flight requests MIGRATE to survivors through the request
    journal's exact-replay path (``MXNET_SERVE_JOURNAL=0`` restores the
    PR-11 fail-typed contract), and a background monitor respawns a
    replacement on the same device behind a capped-exponential-backoff
    circuit breaker (the PR-3 `parallel/dist.py` pattern).  The
    replacement warms from the dead incarnation's SHARED AotCache, so
    failover compiles nothing — `serve.aot.compiles` stays at its warmup
    value (asserted by the chaos acceptance test).  ``respawn=False``
    (or ``MXNET_SERVE_RESPAWN=0``) disables respawn; failover
    re-dispatch still runs.  `drain` is the planned-restart counterpart:
    one replica serves out its work, stragglers migrate the same way,
    and the replacement compiles nothing — a rolling restart of N
    replicas loses zero requests.
    """

    _MONITOR_PERIOD = 0.2
    _BREAKER_RESET_S = 10.0   # healthy-for-this-long clears the breaker

    def __init__(self, engines, respawn=None, journal=None, disagg=None,
                 prefill_replicas=None):
        if not engines:
            raise MXNetError("ReplicaRouter: need at least one engine")
        self.engines = list(engines)
        self._lock = threading.Lock()
        if respawn is None:
            respawn = os.environ.get("MXNET_SERVE_RESPAWN", "1").lower() \
                not in ("0", "false", "no")
        self._respawn = bool(respawn)
        if journal is None:
            journal = journal_enabled()
        self.journal = RequestJournal() if journal else None
        self._stopped = False
        self._monitor = None
        self._mon_stop = threading.Event()
        self._breaker = {}   # replica name -> (fails, next_try monotonic)
        # disaggregated prefill/decode (docs/serving.md "Disaggregated
        # prefill/decode"): the first MXNET_SERVE_PREFILL_REPLICAS
        # engines specialize to prefill, the rest to decode.  Off (the
        # default) assigns no roles at all — bit-for-bit colocated.
        if disagg is None:
            disagg = disagg_enabled()
        self._disagg = bool(disagg) and len(self.engines) >= 2
        n = len(self.engines)
        if self._disagg:
            p = int(os.environ.get("MXNET_SERVE_PREFILL_REPLICAS", "0")
                    if prefill_replicas is None else prefill_replicas)
            if p <= 0:
                p = max(1, n // 4)
            if p >= n:
                raise MXNetError(
                    "ReplicaRouter: MXNET_SERVE_PREFILL_REPLICAS=%d "
                    "leaves no decode replica among %d" % (p, n))
            self._n_prefill = p
        for i, e in enumerate(self.engines):
            self._wire(e, self._role_for(i))

    def _role_for(self, i):
        if not self._disagg:
            return None
        return "prefill" if i < self._n_prefill else "decode"

    def _wire(self, engine, role):
        """Attach one engine to this router: death hook, role, and (for
        role-bearing replicas) the handoff sink and the journal-replay
        fallback.  MUST run before the engine's `warmup()` — a decode
        role decides which restore buckets join the frozen AOT set."""
        engine._on_death = self._handle_death
        if role is not None:
            check_cache_kind(engine.model, "prefill/decode handoff tickets")
        engine.role = role
        if role is not None:
            engine._handoff_sink = self._dispatch_handoff
            engine._handoff_fallback = \
                lambda req, _e=engine: self._handoff_replay(req, source=_e)
        telemetry.set_gauge("serve.%s.role" % engine.name,
                            {"prefill": 1, "decode": 2}.get(role, 0))

    @classmethod
    def from_mesh(cls, model, params, mesh=None, n_replicas=None,
                  devices_per_replica=None, respawn=None, journal=None,
                  disagg=None, prefill_replicas=None, **kw):
        devices = (list(np.asarray(mesh.devices).reshape(-1))
                   if mesh is not None else jax.devices())
        k = int(os.environ.get("MXNET_SERVE_SHARDED_DEVICES", "1")
                if devices_per_replica is None else devices_per_replica)
        if k > 1:
            # sub-mesh replicas: consecutive k-device groups, each ONE
            # sharded engine (a remainder that can't fill a group is
            # dropped — parallel.mesh.submeshes)
            ctxs = submeshes(devices, k)
        else:
            ctxs = devices
        if n_replicas is not None:
            ctxs = ctxs[:int(n_replicas)]
        engines = [ServingEngine(model, params, ctx=c,
                                 name="replica%d" % i, **kw)
                   for i, c in enumerate(ctxs)]
        return cls(engines, respawn=respawn, journal=journal,
                   disagg=disagg, prefill_replicas=prefill_replicas)

    def warmup(self):
        return [e.warmup() for e in self.engines]

    # -- failover ----------------------------------------------------------
    def _live_engines(self, exclude=None):
        with self._lock:
            engines = list(self.engines)
        return [e for e in engines
                if e is not exclude and e._dead is None
                and not e._stopped.is_set() and not e._draining]

    def _handle_death(self, engine, pending, inflight, msg):
        """Engine death hook (runs on the dying scheduler's thread):
        MIGRATE its admitted in-flight requests to survivors via the
        journal's exact-replay path (fail-typed without a journal — the
        PR-11 contract), and re-dispatch its queued-but-not-admitted
        requests.  Resolution is guaranteed PER REQUEST: a surprise
        mid-list must not abort the loop — `_die`'s fallback would then
        fail the whole list typed, including requests already
        successfully moved to healthy survivors."""
        try:
            telemetry.inc("serve.failovers")
            telemetry.inc("serve.%s.failover" % engine.name)
            telemetry.record_event("serve_failover", replica=engine.name,
                                   pending=len(pending),
                                   inflight=len(inflight), error=msg[:200])
        except Exception:  # accounting must not abort failover
            pass
        err = ServeEngineDead("ServingEngine %s: scheduler died: %s"
                              % (engine.name, msg))
        for req in inflight:
            try:
                if not self._migrate(req, exclude=engine):
                    req._finish(error=err)
            except Exception:
                req._finish(error=err)
        err = ServeEngineDead(
            "ServingEngine %s: scheduler died: %s (no live replica to "
            "fail over to)" % (engine.name, msg))
        for req in pending:
            try:
                if not self._redispatch(req, exclude=engine):
                    req._finish(error=err)
            except Exception:
                req._finish(error=err)

    def _migrate(self, req, exclude=None):
        """Move an ADMITTED in-flight request off a dead/draining
        replica with token-for-token exactness: the journal rebuilds the
        uniform ``(prompt+generated)[:pos]`` resume state, the request
        (same object — deadline age and latency stamps never reset)
        enqueues on the least-loaded survivor, and the survivor's
        ordinary resume admission chunk-prefills the replayed context
        and re-enters decode at the same position with the same
        request-keyed RNG.  Returns the engine that took it (truthy; a
        request already resolved in the window returns True), or False
        when nothing can take it (no journal, no survivor, or
        every survivor shed) — callers that only branch keep working,
        and `drain` uses the target to move session entries WITH their
        live turn."""
        if self.journal is None:
            return False  # PR-11: in-flight context dies with the replica
        if req.done:
            return True   # resolved in the window: nothing to move
        state = self.journal.replay_state(req)
        survivors = self._live_engines(exclude=exclude)
        if not survivors:
            return False
        if state is not None:
            req._resume = state
            req._migrated = True
        for eng in sorted(survivors, key=lambda e: e.depth()):
            try:
                eng._enqueue(req, count_shed_global=False)
            except ServeError:
                continue  # died or shed in the window: try the next
            self.journal.migrations += 1
            telemetry.inc("serve.migrated")
            telemetry.record_event(
                "serve_migrate", request=req.id, target=eng.name,
                pos=0 if state is None else state[2],
                generated=len(req.tokens))
            return eng
        req._migrated = False
        req._resume = None if state is not None else req._resume
        return False

    def _redispatch(self, req, exclude=None):
        """Move an un-admitted request (same object: deadline and latency
        stamps ride along) to the least-loaded survivor."""
        for eng in sorted(self._live_engines(exclude=exclude),
                          key=lambda e: e.depth()):
            try:
                eng._enqueue(req, count_shed_global=False)
            except ServeError:
                continue  # died or shed in the window: try the next
            telemetry.inc("serve.redispatched")
            return True
        return False

    # -- disaggregated handoff routing -------------------------------------
    def _dispatch_handoff(self, ticket):
        """Stage one prefill→decode ticket on the least-loaded LIVE
        decode replica (runs on the source's scheduler thread).
        `_live_engines` already fences out dead, stopped AND DRAINING
        replicas — a handoff must redirect to a survivor rather than
        race a draining target's admission-close — and the target's
        `receive_handoff` re-checks under its own lock for the window
        in between.  Raises `ServeEngineDead` when no decode replica
        can take it; the source then falls back to journal replay."""
        last = None
        targets = [e for e in self._live_engines()
                   if e.role == "decode"]
        for eng in sorted(targets, key=lambda e: e.decode_depth()):
            try:
                eng.receive_handoff(ticket)
            except ServeError as e:
                last = e
                continue  # died/started draining in the window
            telemetry.record_event(
                "serve_handoff", request=ticket.req.id, source=ticket.src,
                target=eng.name, blocks=ticket.k, nbytes=ticket.nbytes)
            return True
        raise ServeEngineDead(
            "ReplicaRouter: no live decode replica for handoff (%s)"
            % last)

    def _handoff_replay(self, req, source=None):
        """The failed-handoff fallback: requeue ``req`` onto journal
        exact-replay on any survivor (the same road engine death takes).
        ``_no_handoff`` pins the retry to ordinary decode — a replay
        that handed off again could ping-pong forever.  The last resort
        retries WITHOUT excluding the source: roles are routing policy,
        and a prefill replica that must decode one stray request beats
        failing it."""
        req._no_handoff = True
        if req.done:
            return True
        ok = False
        if self._migrate(req, exclude=source):
            ok = True
        elif not req.tokens and self._redispatch(req, exclude=source):
            ok = True
        elif source is not None and \
                (self._migrate(req) or
                 (not req.tokens and self._redispatch(req))):
            ok = True
        if ok:
            telemetry.inc("serve.replays_from_handoff")
            if self.journal is not None:
                self.journal.handoff_replays += 1
        return ok

    def _monitor_loop(self):
        """Replica health: export heartbeat-age gauges, and respawn dead
        replicas behind a capped-exp-backoff circuit breaker."""
        while not self._mon_stop.wait(self._MONITOR_PERIOD):
            with self._lock:
                engines = list(self.engines)
            now = time.monotonic()
            for e in engines:
                telemetry.set_gauge("serve.%s.beat_age_s" % e.name,
                                    round(now - e.last_beat, 3))
                if e._dead is None:
                    # replacement stayed healthy past the reset window:
                    # clear its breaker so independent rare faults over a
                    # long process lifetime don't escalate recovery
                    # latency toward the permanent backoff cap
                    fails, next_try = self._breaker.get(e.name, (0, 0.0))
                    if fails and now - next_try > self._BREAKER_RESET_S:
                        self._breaker.pop(e.name, None)
                if e._dead is None or not self._respawn or self._stopped:
                    continue
                fails, next_try = self._breaker.get(e.name, (0, 0.0))
                if now < next_try:
                    continue
                # breaker advances whether or not the respawn works: a
                # replica that dies instantly again retries with backoff
                self._breaker[e.name] = (
                    fails + 1, now + min(0.05 * (2 ** fails), 5.0))
                try:
                    fresh = e.respawn()
                    # role (and its warmup bucket set) carries over —
                    # wired BEFORE warmup, like first construction
                    self._wire(fresh, e.role)
                    compiled_before = fresh._aot.compiles
                    fresh.warmup()
                    if fresh._aot.compiles != compiled_before:
                        # the zero-recompile invariant of recovery: warmup
                        # off the shared AOT set must be pure cache hits
                        telemetry.record_event(
                            "serve_respawn_compiled", replica=e.name,
                            n=fresh._aot.compiles - compiled_before)
                    fresh.start()
                except Exception as ex:  # noqa: BLE001
                    telemetry.record_event("serve_respawn_failed",
                                           replica=e.name,
                                           error=str(ex)[:200])
                    continue
                with self._lock:
                    try:
                        self.engines[self.engines.index(e)] = fresh
                    except ValueError:   # raced with a concurrent swap
                        fresh.stop()
                        continue
                telemetry.inc("serve.respawns")
                telemetry.record_event("serve_respawn", replica=e.name,
                                       attempt=fails + 1)

    # -- dispatch ----------------------------------------------------------
    def submit(self, prompt, **kw):
        if self._stopped:
            raise ServeEngineDead("ReplicaRouter: router stopped")
        with self._lock:   # monitor/drain swap replicas under _lock
            fleet = len(self.engines)
        telemetry.set_gauge("serve.replicas", fleet)
        last_err = None
        session = kw.get("session")
        # two rounds: a replica dying (or respawning) between the snapshot
        # and the submit re-routes instead of failing the request
        for _ in range(2):
            live = self._live_engines()
            if not live:
                break
            shed = 0
            # session affinity: a follow-up turn must land on a replica
            # holding the session's history — its K/V is device- or
            # host-resident there, and any other replica would SILENTLY
            # restart the conversation.  With holders alive the
            # candidate set is the holders ONLY (ties break
            # least-depth): a holder that sheds fails the submit typed
            # rather than forking the history onto a stranger.  With no
            # live holder (first turn, or the holder died — session
            # state is engine-local and dies with its replica) the turn
            # routes least-depth as a fresh conversation.
            order = sorted(live, key=lambda e: e.depth())
            if self._disagg:
                # two-stage dispatch: every fresh request enters through
                # a PREFILL replica, ordered by prompt-token backlog
                # (the ttft signal — queue depth alone starves short
                # prompts behind a storm); the handoff picks the decode
                # replica later, at least-decode-depth
                pre = [e for e in live if e.role == "prefill"]
                if pre:
                    order = sorted(pre,
                                   key=lambda e: e.prefill_backlog())
                telemetry.set_gauge(
                    "serve.prefill_depth",
                    sum(e.depth() for e in pre))
                telemetry.set_gauge(
                    "serve.decode_depth",
                    sum(e.decode_depth() for e in live
                        if e.role == "decode"))
            if session is not None:
                holders = [e for e in live if e.has_session(session)]
                if holders:
                    # disagg: prefer DECODE-role holders — `_retire`
                    # stores the session history on the replica that
                    # decoded the previous turn, and the prefill source
                    # keeps only an unresolved claim; landing the
                    # follow-up on the decode holder reattaches its
                    # cached blocks instead of forking the history
                    dec = [e for e in holders if e.role == "decode"]
                    order = sorted(dec or holders,
                                   key=lambda e: e.depth())
            for eng in order:
                try:
                    req = eng.submit(prompt, _count_shed=False, **kw)
                    if self.journal is not None:
                        # the handle the caller gets back IS the journal
                        # entry: it survives the replica it landed on
                        telemetry.set_gauge("serve.journal_depth",
                                            self.journal.record(req))
                    return req
                except ServeOverload as e:
                    last_err = e
                    shed += 1
                except ServeEngineDead as e:
                    last_err = e  # died in the window: try the next
                except MXNetError as e:
                    if eng._dead is None:
                        raise  # a bad request, not a dead replica
                    last_err = e
            if shed == len(order):
                # the request is definitively rejected only here — the
                # per-replica attempts above counted serve.<name>.shed
                # (for a session turn, "all" means all HOLDERS: shedding
                # onto a history-less replica is not an option)
                telemetry.inc("serve.shed")
                raise ServeOverload(
                    "ReplicaRouter: all %d live candidate replicas shed "
                    "(%s)" % (shed, last_err))
        raise ServeEngineDead(
            "ReplicaRouter: no live replica among %d (%s)"
            % (fleet, last_err))

    def _resolve_engine(self, replica):
        """An engine by object, index, or replica name."""
        with self._lock:
            engines = list(self.engines)
        if isinstance(replica, ServingEngine):
            if replica in engines:
                return replica
            raise MXNetError("ReplicaRouter: engine %s is not (or no "
                             "longer) one of this router's replicas"
                             % replica.name)
        if isinstance(replica, int):
            if not 0 <= replica < len(engines):
                raise MXNetError(
                    "ReplicaRouter: replica index %d out of range "
                    "(have %d replicas)" % (replica, len(engines)))
            return engines[replica]
        for e in engines:
            if e.name == replica:
                return e
        raise MXNetError("ReplicaRouter: no replica named %r (have %s)"
                         % (replica, [e.name for e in engines]))

    def drain(self, replica, deadline_ms=None, respawn=True):
        """Gracefully restart ONE replica (the rolling-restart
        primitive): close its admission, let its in-flight work finish
        within ``deadline_ms``, MIGRATE the stragglers to survivors
        through the journal's exact-replay path, stop it, and (by
        default) swap in a respawned replacement warmed from the shared
        AotCache — so draining every replica in turn restarts the fleet
        with zero failed requests and zero new compiles.  Returns the
        replacement engine (None with ``respawn=False``)."""
        eng = self._resolve_engine(replica)
        stragglers = eng.drain(deadline_ms=deadline_ms)  # counts drained
        err = ServeEngineDead(
            "ServingEngine %s: drained for restart with no live replica "
            "to migrate to" % eng.name)
        moved = {}   # id(req) -> engine the straggler migrated to
        for req in stragglers:
            if req.done:
                continue
            try:
                target = self._migrate(req, exclude=eng)
                if target:
                    if isinstance(target, ServingEngine):
                        moved[id(req)] = target
                    continue
                # no journal (or no survivor): a straggler with no
                # generated tokens needs no replay — the PR-8 redispatch
                # keeps it alive losslessly; only in-flight progress that
                # cannot be replayed has to fail typed
                if not req.tokens and self._redispatch(req, exclude=eng):
                    continue
                req._finish(error=err)
            except Exception:
                req._finish(error=err)
        fresh = None
        if respawn and not self._stopped:
            try:
                fresh = eng.respawn()
                self._wire(fresh, eng.role)  # role before warmup
                fresh.warmup()  # pure AotCache hits: the restart compiles 0
            except Exception as ex:  # noqa: BLE001
                # don't strand the fleet a replica short: mark the drained
                # engine dead so the monitor's breaker-backed respawn path
                # retries, exactly like a crashed replica
                eng._dead = "drain respawn failed: %s" % str(ex)[:300]
                telemetry.record_event("serve_respawn_failed",
                                       replica=eng.name,
                                       error=str(ex)[:200])
                fresh = None
            if fresh is not None:
                with self._lock:
                    try:
                        self.engines[self.engines.index(eng)] = fresh
                    except ValueError:  # raced with a concurrent swap
                        fresh.stop()
                        fresh = None
                if fresh is not None and self._monitor is not None \
                        and self._monitor.is_alive():
                    fresh.start()
        # session histories move WITH the drain (PR-13 affinity made the
        # engines holders-only: an entry left on the stopped engine would
        # orphan the conversation — the follow-up turn would silently
        # restart it on a stranger).  Runs after the swap so a live
        # straggler's entry follows ITS new engine and everything else
        # lands on the replacement (or the least-loaded survivor).
        self._migrate_sessions(eng, moved, dest=fresh)
        return fresh

    def _migrate_sessions(self, eng, moved, dest=None):
        """Move ``eng``'s session store to the rest of the fleet (the
        engine is stopped: its scheduler no longer mutates the store).
        A session whose live turn migrated as a straggler follows that
        turn's engine — `_session_store` advances the history there at
        retire, and the unresolved-turn guard keeps protecting it.
        Every other entry (resolved turn, claim, first-turn record)
        lands on ``dest`` (the drain replacement) or the least-loaded
        live survivor.  Returns how many entries moved."""
        with eng._slock:
            sessions = list(eng._sessions.items())
            eng._sessions.clear()
        if not sessions:
            return 0
        live = self._live_engines(exclude=eng)
        n = 0
        for key, (hist, ent) in sessions:
            if isinstance(ent, _SessionClaim):
                # an un-admitted claim: the previous resolved turn is
                # the state the conversation retries from
                ent = ent.prev
            target = None
            if isinstance(ent, ServeRequest) and not ent.done:
                target = moved.get(id(ent))
            if target is None:
                target = dest
            if target is None and live:
                target = min(live, key=lambda e: e.depth())
            if target is None:
                continue   # nowhere to go: the history dies with eng
            with target._slock:
                if key in target._sessions:
                    continue   # the target's own copy wins
                target._sessions[key] = (hist, ent)
                target._sessions.move_to_end(key)
                target._trim_sessions_locked()
            n += 1
        if n:
            telemetry.inc("serve.sessions_migrated", n)
            telemetry.record_event("serve_sessions_migrated",
                                   replica=eng.name, n=n)
        return n

    def _next_name(self):
        """A fresh replicaN name (caller holds ``_lock``)."""
        names = {e.name for e in self.engines}
        idx = len(self.engines)
        while "replica%d" % idx in names:
            idx += 1
        return "replica%d" % idx

    def add_replica(self, role=None, name=None, template=None):
        """Grow the fleet by one replica — the autoscaler's scale-up
        primitive.  The new engine is templated off a live replica:
        params SHARED (already device-resident) and the frozen AotCache
        SHARED, so its warmup is pure cache hits.  That zero-compile
        property is ASSERTED — a scale-up that would compile raises
        instead of stalling steady state, the same contract respawn
        holds.  Under MXNET_SERVE_DISAGG ``role`` picks the pool
        (default decode).  Returns the started engine."""
        if self._stopped:
            raise MXNetError("ReplicaRouter: router stopped")
        with self._lock:
            if template is None:
                for e in self.engines:
                    if e._dead is None and not e._stopped.is_set() \
                            and not e._draining:
                        template = e
                        break
            if template is None:
                raise MXNetError("ReplicaRouter: no live replica to "
                                 "template a scale-up from")
            if name is None:
                name = self._next_name()
        if self._disagg and role is None:
            role = "decode"
        fresh = template.respawn(name=name)
        self._wire(fresh, role if self._disagg else None)
        before = fresh._aot.compiles
        fresh.warmup()
        compiled = fresh._aot.compiles - before
        if compiled:
            telemetry.record_event("serve_respawn_compiled",
                                   replica=name, n=compiled)
            fresh.stop()
            raise MXNetError(
                "ReplicaRouter.add_replica: scale-up warmup compiled %d "
                "new program(s) — growth off the shared frozen AotCache "
                "must be compile-free" % compiled)
        with self._lock:
            self.engines.append(fresh)
            if self._disagg and role == "prefill":
                self._n_prefill += 1
            fleet = len(self.engines)
        fresh.start()
        telemetry.set_gauge("serve.replicas", fleet)
        return fresh

    def remove_replica(self, replica=None, deadline_ms=None, role=None):
        """Shrink the fleet by one replica — the autoscaler's scale-down
        primitive: graceful `drain` (admission closes typed, in-flight
        work serves out, stragglers AND session histories migrate to
        survivors), then the stopped engine leaves the fleet.  With no
        ``replica`` given the least-loaded live one (of ``role``, when
        set) is chosen.  Refuses to remove the last replica — or the
        last of its role under MXNET_SERVE_DISAGG.  Returns the removed
        engine's name."""
        with self._lock:
            engines = list(self.engines)
        if replica is None:
            pool = [e for e in engines if e._dead is None
                    and not e._stopped.is_set() and not e._draining]
            if role is not None:
                pool = [e for e in pool if e.role == role]
            if not pool:
                raise MXNetError(
                    "ReplicaRouter: no removable replica%s"
                    % (" with role %r" % role if role else ""))
            eng = min(pool, key=lambda e: e.depth())
        else:
            eng = self._resolve_engine(replica)
        with self._lock:
            if self._disagg:
                peers = [e for e in self.engines
                         if e is not eng and e.role == eng.role]
            else:
                peers = [e for e in self.engines if e is not eng]
            if not peers:
                raise MXNetError(
                    "ReplicaRouter: refusing to remove %s — it is the "
                    "last %sreplica" % (eng.name, "%s " % eng.role
                                        if eng.role else ""))
        self.drain(eng, deadline_ms=deadline_ms, respawn=False)
        with self._lock:
            try:
                self.engines.remove(eng)
            except ValueError:
                pass   # raced with a concurrent removal
            if self._disagg and eng.role == "prefill":
                self._n_prefill = max(1, self._n_prefill - 1)
            fleet = len(self.engines)
        telemetry.set_gauge("serve.replicas", fleet)
        return eng.name

    def start(self):
        self._stopped = False
        with self._lock:   # monitor/drain swap replicas under _lock
            engines = list(self.engines)
        for e in engines:
            e.start()
        if self._monitor is None or not self._monitor.is_alive():
            self._mon_stop.clear()
            self._monitor = threading.Thread(
                target=self._monitor_loop, name="serve-router-monitor",
                daemon=True)
            self._monitor.start()
        return self

    def stop(self):
        # refuse new submits first, then stop the monitor (no respawn may
        # race the drain), then stop EVERY engine before raising: aborting
        # on the first failure would leave the remaining schedulers
        # running (and, from a finally block, mask the error that actually
        # failed the run)
        self._stopped = True
        self._mon_stop.set()
        m = self._monitor
        if m is not None:
            m.join(timeout=10)
            self._monitor = None
        errs = []
        with self._lock:
            engines = list(self.engines)
        for e in engines:
            try:
                e.stop()
            except MXNetError as err:
                errs.append(str(err))
        if errs:
            raise MXNetError(
                "ReplicaRouter: %d engine(s) failed to stop: %s"
                % (len(errs), "; ".join(errs)))

    def run_until_idle(self, timeout=None):
        """Synchronous drain of every replica (tests; bench uses start()).
        ``timeout`` bounds the WHOLE drain — a replica whose worker thread
        died cannot eat the budget waiting on a depth that will never
        drain (its queue was redispatched/failed at death, and the shared
        deadline raises `ServeTimeout` instead of hanging)."""
        t0 = time.perf_counter()
        steps = []
        with self._lock:
            engines = list(self.engines)
        for e in engines:
            remaining = None if timeout is None else \
                max(0.0, timeout - (time.perf_counter() - t0))
            if timeout is not None and remaining <= 0 and e.depth() > 0:
                raise ServeTimeout(
                    "ReplicaRouter.run_until_idle: timed out after %.1fs "
                    "with %s still holding %d request(s)"
                    % (timeout, e.name, e.depth()))
            steps.append(e.run_until_idle(timeout=remaining))
        return steps

    def depth(self):
        with self._lock:
            engines = list(self.engines)
        return sum(e.depth() for e in engines)
