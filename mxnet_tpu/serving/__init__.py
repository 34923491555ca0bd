"""Continuous-batching TPU serving engine.

The `Predictor` (predictor.py) is the faithful `MXPredCreate` analogue:
one AOT-compiled launch per request, one shape, host-blocking.  This
package is the production path on top of it (ROADMAP item 1):

* `decode.TransformerKVModel` — prefill + single-token KV-cache decode
  functions for `models/transformer.py` graphs (same parameter names, so
  training checkpoints serve directly), over the paged block pool
  (`prefill_paged`/`decode_paged`).
* `paged.BlockAllocator` — refcounted host-side free list over the
  fixed device block pool (the vLLM PagedAttention idea): sequences
  hold blocks for their actual length, so HBM admits by footprint, not
  worst case; refcounts let blocks be SHARED across requests.
* `paged.PrefixCache` — block-aligned radix index over cached K/V
  prefixes (RadixAttention at block granularity): admission reuses the
  longest cached full-block prefix instead of re-prefilling it, with
  copy-on-write for writers and an LRU pool of retired prefix blocks
  evicted only under allocation pressure (`MXNET_SERVE_PREFIX=0`
  restores single-owner paging bit-for-bit).
* `sampling.sample_tokens` — in-graph temperature/top-k/top-p sampling
  with a request-keyed, position-folded RNG (deterministic, batch-
  composition-invariant; temperature 0 = greedy argmax).
* `engine.ServingEngine` — request queue + iteration-level continuous
  batcher (Orca, OSDI '22): sequences admit/retire at step granularity,
  padded and bucketed onto a small fixed set of pre-AOT-compiled
  (batch, seq) shapes so steady state has zero recompiles (asserted via
  the telemetry retrace watchdog; chunked prefill streams long prompts
  through the same bucket shapes).  Per-request deadlines, cancellation,
  and a bounded queue with configurable overload policy
  (``MXNET_SERVE_OVERLOAD=shed|block|degrade``) make it SLO-grade.
* `spec.Drafter` / `NgramDrafter` / `ModelDrafter` — speculative
  decoding (`MXNET_SERVE_SPEC`): a drafter proposes k tokens per row,
  one batched verify launch scores them against the target over the
  same paged blocks, and accepted prefixes advance rows 1..k+1 tokens
  per iteration at exact output parity (the position-folded sampler
  makes the accept rule deterministic at any temperature).
* `engine.ReplicaRouter` — least-depth dispatch over per-device engine
  replicas (the mesh scale-out path) with heartbeat monitoring, failover
  of a dead replica's queued requests to survivors, and background
  respawn off the shared AOT cache (recovery compiles nothing).
* `tiers.HostBlockTier` — the host-DRAM block tier under the paged
  pool (`MXNET_SERVE_TIER`): prefix blocks the LRU evicts SPILL
  device→host instead of being destroyed, the radix index becomes
  tier-aware (a lookup landing on host-resident blocks returns a
  restore-then-acquire plan), and restores ride async `jax.device_put`
  transfers overlapped with the current decode iteration — a host hit
  costs a PCIe copy instead of a prefill recompute.  Preempted
  requests resume by restore when their spilled blocks survive, and
  `submit(session=…)` turns the tier into chat continuity: a finished
  turn's blocks reattach to the follow-up, which prefills only the
  new suffix.
* `journal.RequestJournal` — router-owned durability ledger
  (`MXNET_SERVE_JOURNAL`): a dead or draining replica's ADMITTED
  in-flight requests migrate to survivors via the exact-replay
  `(prompt+generated)[:pos]` resume formula — token-for-token identical
  continuation at any temperature — and `ReplicaRouter.drain` turns
  that into zero-loss rolling restarts.  Anti-thrash preemption
  (`MXNET_SERVE_MIN_PROGRESS`, oldest-request protection, a
  preemption-storm detector tripping the degrade path) guarantees net
  forward progress under sustained block-pool pressure.
* quantization (mxnet_tpu/quant, ``MXNET_SERVE_QUANT=int8|fp8``) —
  serving weights quantize once at load (scaled matmuls inside the
  same compiled programs) and the paged K/V pool stores int8 rows
  with per-row scales (``MXNET_SERVE_KV_QUANT``, on by default with
  weight quant) — roughly 2-4x ``n_blocks`` at equal HBM, spilled/
  restored through the host tier in the quantized dtype, guarded by
  an in-graph logit gate that fails typed (`ServeQuantError`) on
  corrupted scales instead of emitting silent wrong tokens.
* `handoff` / disaggregated serving (``MXNET_SERVE_DISAGG``) — the
  Splitwise/DistServe split: `ReplicaRouter` specializes the fleet
  into prefill and decode roles; prefill replicas run chunked prefill
  only and retire finished prompts into a `HandoffTicket` (the packed
  K/V block run + the uniform resume tuple), decode replicas land the
  ticket through the warmup-compiled restore scatter and megastep-
  decode it — a long-prompt storm queues on the prefill side while
  decode inter-token p99 stays flat.  A dead transfer or target falls
  back to the journal's exact-replay road; ``=0`` (default) is the
  colocated fleet bit for bit.
* `gateway.ServeGateway` (``MXNET_SERVE_GATEWAY``) — stdlib-asyncio
  HTTP/SSE front door over the router: per-token streaming rides the
  engine's `on_token` push path (ttfb ≈ engine ttft), HTTP sessions map
  onto session affinity, and backpressure is end-to-end — a bounded
  connection budget sheds with typed status codes from the error
  taxonomy, per-connection send buffers cancel slow consumers at a
  watermark (releasing their blocks), and client disconnects cancel
  the in-flight request.  ``=0`` (default) builds nothing.
* `autoscale.AutoScaler` (``MXNET_SERVE_AUTOSCALE``) — gauge-driven
  elasticity over the same fleet primitives: sustained per-replica
  queue depth (or shed activity) past a hysteresis window grows the
  fleet off the SHARED frozen `AotCache` (asserted compile-free);
  sustained idleness drains a replica, migrating stragglers AND
  session histories to survivors.  Under ``MXNET_SERVE_DISAGG`` the
  prefill/decode pools scale independently.
* `errors` — the typed failure taxonomy every request resolves to.

See docs/serving.md.
"""
from .autoscale import AutoScaler, autoscale_enabled
from .decode import TransformerKVModel
from .latent import LatentMoEKVModel
from .shortconv import ShortConvMoEKVModel
from .window import HybridWindowMoEKVModel
from .gateway import ServeGateway, gateway_enabled, http_status
from .engine import ServeRequest, ServingEngine, ReplicaRouter
from .handoff import HandoffTicket, disagg_enabled
from .journal import RequestJournal, journal_enabled
from .paged import BlockAllocator, PrefixCache, TRASH_BLOCK
from .sampling import sample_tokens
from .tiers import HostBlockTier, pack_block_run
from .spec import Drafter, NgramDrafter, ModelDrafter, make_drafter
from .errors import (ServeError, ServeTimeout, ServeOverload,
                     ServeDeadlineExceeded, ServeCancelled,
                     ServeQuarantined, ServeBlocksExhausted,
                     ServeCacheInvalidated, ServeEngineDead,
                     ServeQuantError)

__all__ = ["TransformerKVModel", "LatentMoEKVModel", "ShortConvMoEKVModel",
           "HybridWindowMoEKVModel",
           "ServeRequest", "ServingEngine",
           "ReplicaRouter", "HandoffTicket", "disagg_enabled",
           "ServeGateway", "gateway_enabled", "http_status",
           "AutoScaler", "autoscale_enabled",
           "RequestJournal", "journal_enabled",
           "BlockAllocator", "PrefixCache", "TRASH_BLOCK", "HostBlockTier",
           "pack_block_run", "sample_tokens", "Drafter", "NgramDrafter", "ModelDrafter",
           "make_drafter", "ServeError", "ServeTimeout", "ServeOverload",
           "ServeDeadlineExceeded", "ServeCancelled", "ServeQuarantined",
           "ServeBlocksExhausted", "ServeCacheInvalidated",
           "ServeEngineDead", "ServeQuantError"]
