"""Speculative decoding: pluggable drafters for draft-verify serving.

Plain continuous batching advances every row by exactly ONE target-model
token per scheduler iteration — the decode floor.  Speculative decoding
(Leviathan et al. 2023) breaks it: a cheap DRAFTER proposes k tokens per
row, ONE batched verify launch scores all of them against the target
model (`TransformerKVModel.verify_paged` — a k+1-token "prefill" over
the same paged blocks), and the engine keeps the longest prefix of
proposals the target itself would have picked, plus the target's own
next token.  Each iteration therefore advances a row by 1..k+1 tokens.

Exactness is free in this engine, not probabilistic: sampling is
request-keyed and position-folded (serving/sampling.py), so the target's
pick at position P is a deterministic function of (seed, context) — the
verify launch computes the SAME picks sequential decode would have made
at every accepted position, for any temperature.  The accept rule is
simply "draft j survives iff it equals the target's own pick at its
position"; at T=0 that is bit-identical greedy, at T>0 it is
deterministic rejection sampling against the request's own RNG stream.
Draft quality only moves the ACCEPT RATE, never the output — a drafter
can be wrong, stale, or actively corrupted (`draft_junk` chaos) and the
engine still emits parity tokens, just closer to one per step.

Two drafters ship:

* `NgramDrafter` — zero-cost prompt-lookup (Saxena 2023): each row's
  proposals are the continuation of the most recent earlier occurrence
  of its trailing n-gram in ``prompt + generated``.  No device state,
  no launches; one verify launch per iteration total.  Wins on
  repetitive traffic (code, extraction, chat echoes).
* `ModelDrafter` — a small draft model (any `TransformerKVModel`
  geometry; by default the target's own config + weights, the
  serve-bench self-draft configuration) running its own paged K/V pool
  over the SAME block ids as the target: the engine's block tables,
  growth, CoW repoints, preemption and prefix sharing all apply to the
  draft cache for free, because draft rows live at the same
  (block, offset) coordinates.  All k draft steps run inside ONE
  compiled `lax.scan` launch, so a speculation round costs 2 launches
  (draft + verify) against the k+1 a non-speculative engine would
  spend — the dispatch-bound win — while the verify's batched k+1-token
  pass is the HBM-bound win on real accelerators.

Draft state is deliberately NEVER correctness-critical: a draft launch
failure, a consumed draft pool, or junk K/V in a reused block degrades
proposals (and the accept rate) but cannot corrupt output — verify
always re-derives truth from the target.  `ModelDrafter` therefore
self-heals (pool rebuild + junk proposals) instead of escalating,
except for an injected device death which must still kill the scheduler.

The engine wires the lifecycle (docs/serving.md "Speculative decoding"):
`bind` at construction, `warmup` inside `ServingEngine.warmup()` (draft
programs join the frozen AotCache bucket set), `on_prefill_chunk` after
every target prefill chunk (the draft cache prefills in lockstep),
`on_cow` after a target copy-on-write (same src/dst block pair), and
`on_cache_rebuild` when the target pool is rebuilt.

Megastep interlock: with `MXNET_SERVE_MEGASTEP` on too, speculation
keeps the iteration (it already amortizes launches k+1-wide and its
accept bookkeeping is host-sequential by design); the fused megastep
replaces the plain single-token program as the fallback when no row
has a usable draft, so cold batches still advance m tokens per launch.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from .. import chaos
from .. import telemetry
from ..base import MXNetError

__all__ = ["Drafter", "NgramDrafter", "ModelDrafter", "make_drafter"]


class Drafter:
    """Interface a `ServingEngine` speculation round drives.

    ``propose`` is the only required method: (bucket, k) int32 draft
    tokens for the active rows (padding rows past ``len(seqs)`` are
    don't-cares).  The paged-lifecycle hooks default to no-ops — only a
    drafter with device state (ModelDrafter) needs them."""

    name = "drafter"
    # whether propose() wants the device-resident (token, pos, tables)
    # triple — False lets the engine skip staging it (a host drafter
    # costs zero device traffic per round)
    needs_device = False
    # whether the drafter mirrors the target's paged pool and wants
    # `on_restore_span` after a host-tier restore — False lets the
    # engine skip building the span's chunk arrays entirely
    mirrors_pool = False

    def __init__(self):
        self._engine = None
        self.launches = 0   # compiled draft launches (bench accounting)

    def bind(self, engine):
        """Attach to (or re-attach to, on respawn) an engine: allocate
        any device state against its device/geometry."""
        self._engine = engine

    def warmup(self):
        """Compile every draft program (called inside engine.warmup(),
        BEFORE `AotCache.freeze()` — draft shapes join the bucket set)."""

    def propose(self, seqs, k, bucket, host, dev, samp):
        """Proposals for the active ``seqs``: a (bucket, k) int32 array,
        or a ((bucket, k) array, (bucket,) bool mask) pair where the
        mask marks rows with a REAL draft (False = filler the drafter
        already expects to be rejected).  When NO row has a real draft
        the engine skips the verify launch and runs a plain decode
        round instead — adaptive speculation, so a cold batch never
        pays the k+1-wide program to advance one token per row.

        host: (token0, pos, tables) numpy arrays at the bucket shape —
              token0 (b,) is each row's next fed token, pos (b,) its
              position, tables (b, m) its block table.
        dev:  the same three arrays already on the engine's device (the
              verify launch shares them; a device drafter reuses them
              instead of re-staging).
        samp: the engine's per-row device sampling arrays (() when
              sampling programs are off) — a model drafter samples its
              proposals with the SAME request-keyed position-folded RNG
              the target uses, so a perfect draft matches at any
              temperature."""
        raise NotImplementedError

    def on_prefill_chunk(self, toks_d, start_d, length_d, table_d):
        """A target prefill chunk landed with these (device) arrays."""

    def on_cow(self, src_d, dst_d):
        """The target copied block src -> dst (copy-on-write)."""

    def on_restore_span(self, toks_d, start_d, length_d, table_d):
        """A host-tier restore landed this (block-aligned) span of the
        target pool without running prefill — the mirrored draft pool
        has no K/V for it.  Only the TARGET's K/V could be spilled (a
        draft cache is derived state, never worth a host copy), so a
        pool-mirroring drafter re-derives its rows by prefilling the
        restored tokens through its OWN model — accept-rate hygiene
        exactly like `on_prefill_chunk`, and like all draft state never
        correctness-critical: the default no-op just costs accept rate
        on the restored span until decode overwrites past it."""

    def on_cache_rebuild(self):
        """The target pool was rebuilt: every cached draft row is void."""

    def on_retire(self, hist):
        """A request completed with full token history ``hist`` (prompt
        + generated) — a learning drafter may index it."""

    def observe(self, hist, new):
        """A live row extended its history: the last ``new`` tokens of
        ``hist`` were just emitted.  Lets a learning drafter index
        generations mid-flight (a concurrent twin of a slow request can
        then draft off its progress instead of waiting for a retire)."""

    def on_resume(self, hist):
        """A preempted — or journal-MIGRATED — request re-entered decode
        with replayed context ``hist`` (everything its cache now holds,
        plus the pending feed token).  Speculation state is never
        carried across a migration: a device drafter's mirrored pool
        refilled in lockstep with the replay prefill chunks, and a
        learning drafter may index the replayed generation here so its
        accept rate recovers on the first post-resume round instead of
        re-learning token by token.  Default: no-op — draft state is
        never correctness-critical, so forgetting everything is always
        safe."""


class NgramDrafter(Drafter):
    """Model-free n-gram drafting: prompt-lookup (Saxena 2023) plus a
    REST-style generation store (He et al. 2024, retrieval-based
    speculation, shrunk to one replica's own recent completions).

    Proposals for a row are the continuation of its trailing n-gram
    (n from ``max_n`` down to ``min_n``), looked up first in the
    GENERATION STORE — a bounded FIFO index over the token streams of
    requests this replica already finished, which is exact for
    repeated/templated traffic because greedy decoding (and the
    request-keyed sampler under a fixed seed) is deterministic — and
    then in the row's OWN ``prompt + generated`` history (repetition,
    extraction, code echoes).  No match falls back to repeating the
    last token: a junk proposal the verify simply rejects.

    Zero device state, zero launches — speculation costs exactly ONE
    verify launch per iteration, which is what makes this drafter the
    dispatch-bound default."""

    name = "ngram"

    # longest continuation one store entry keeps (covers any sane k)
    _CONT = 16

    def __init__(self, max_n=3, min_n=1, min_local_n=2, store_cap=65536):
        super().__init__()
        if int(max_n) < int(min_n) or int(min_n) < 1:
            raise MXNetError("NgramDrafter: need max_n >= min_n >= 1")
        self.max_n = int(max_n)
        self.min_n = int(min_n)
        # store hits are real generations — trustworthy at any n — but
        # a LOCAL match needs >= min_local_n tokens before it means
        # repetition rather than coincidence: on non-repetitive text a
        # unigram self-match is noise, and proposing off it would drag
        # every cold batch through the k+1-wide verify for nothing
        self.min_local_n = max(int(min_local_n), int(min_n))
        self.store_cap = int(store_cap)
        from collections import OrderedDict
        self._store = OrderedDict()   # ngram tuple -> continuation tuple

    def _index(self, hist, start):
        """Index every n-gram whose continuation starts at >= ``start``
        (0 re-indexes everything — the retire path, which also refreshes
        continuations truncated while the generation was in flight)."""
        if self.store_cap <= 0:
            return
        hist = [int(t) for t in hist]
        for n in range(self.min_n, self.max_n + 1):
            for i in range(max(n, int(start)), len(hist)):
                cont = tuple(hist[i:i + self._CONT])
                if not cont:
                    break
                key = tuple(hist[i - n:i])
                self._store[key] = cont
                self._store.move_to_end(key)
        while len(self._store) > self.store_cap:
            self._store.popitem(last=False)

    def on_retire(self, hist):
        self._index(hist, 0)

    def observe(self, hist, new):
        self._index(hist, len(hist) - int(new))

    def on_resume(self, hist):
        # a replayed (preempted or migrated-in) generation seeds the
        # store wholesale: deterministic decoding makes it an exact
        # oracle for its own continuation, so the first post-resume
        # speculation round already drafts at full accept rate
        self._index(hist, 0)

    def _lookup(self, hist, k):
        """(k proposals, confident) — ``confident`` means the match is
        at least ``min_local_n`` tokens long (a shorter store hit, or
        the repeat-last-token filler, still proposes to satisfy the
        fixed shape, but does not by itself justify paying the verify
        launch: on non-repetitive text a unigram match is coincidence,
        and the engine's adaptive fallback should keep a cold batch on
        the plain decode program)."""
        n_hist = len(hist)
        for n in range(min(self.max_n, n_hist), self.min_n - 1, -1):
            pat = hist[-n:]
            hit = self._store.get(tuple(pat))
            if hit is not None:
                cont = list(hit[:k])
                return (cont + [hist[-1]] * (k - len(cont)),
                        n >= self.min_local_n)
            if n < self.min_local_n:
                continue
            # most recent earlier occurrence in the row's own history
            # (recency wins: generation drifts, the newest continuation
            # is the best bet)
            for j in range(n_hist - n - 1, -1, -1):
                if hist[j:j + n] == pat:
                    cont = hist[j + n:j + n + k]
                    if cont:
                        return cont + [hist[-1]] * (k - len(cont)), True
        return [hist[-1]] * k, False

    def propose(self, seqs, k, bucket, host, dev, samp):
        out = np.zeros((bucket, k), np.int32)
        mask = np.zeros((bucket,), bool)
        for r, seq in enumerate(seqs):
            hist = (seq.ctx or []) + [seq.last]
            out[r], mask[r] = self._lookup(hist, k)
        return out, mask


class ModelDrafter(Drafter):
    """Draft-model drafting over a mirrored paged K/V pool.

    ``model``/``params`` default to the bound engine's own target model
    and (device-resident) weights — the self-draft configuration the
    serve bench uses to measure the mechanism at a 100% ceiling accept
    rate; production passes a distilled draft checkpoint with the same
    vocabulary (any num_layers/num_heads/num_embed geometry works: the
    draft pool carries its own (L_d, 2, n_blocks, block_size, E_d)
    shape, only the BLOCK IDS are shared with the target).

    One compiled program per decode bucket runs the whole k-step draft
    autoregression as a `lax.scan` (feed token -> write draft K/V ->
    attend -> pick -> feed the pick), carrying the donated pool.  The
    scan runs k+1 steps and discards the last pick: the extra step
    writes draft K/V for proposal k itself, so after a fully-accepted
    round (pos advances k+1) the draft cache has no hole and the next
    round needs no catch-up feed.  Rejected-draft rows are garbage the
    next round overwrites position by position BEFORE attending them —
    the same overwrite-then-attend order the verify scatter uses."""

    name = "model"
    needs_device = True
    mirrors_pool = True

    def __init__(self, model=None, params=None):
        super().__init__()
        self.model = model
        self.params = params
        self._pool = None
        self._dparams = None

    def bind(self, engine):
        super().bind(engine)
        if self.model is None:
            self.model = engine.model
        else:
            # the mirrored draft pool must quantize IDENTICALLY to the
            # target's (same specs, same per-row scale discipline): a
            # draft reading f32 K/V while the target reads int8 would
            # diverge for quantization reasons alone, polluting the
            # accept-rate signal — the accounting stays honest only
            # when both sides see the same arithmetic
            self.model = self.model.with_quant(engine.model.quant,
                                               engine.model.kv_quant)
        if self.model.vocab_size != engine.model.vocab_size:
            raise MXNetError(
                "ModelDrafter: draft vocab %d != target vocab %d"
                % (self.model.vocab_size, engine.model.vocab_size))
        params = self.params if self.params is not None else engine._params
        self.model.check_params(params)
        if self.model.quant is not None:
            # idempotent: the self-draft path shares the engine's
            # already-quantized device params
            params = self.model.quantize_params(params)
        jarr = jax.Array
        self._dparams = {k: v if isinstance(v, jarr)
                         else engine._put(np.asarray(v))
                         for k, v in params.items()}
        self._init_pool()

    def _init_pool(self):
        e = self._engine
        self._pool = self.model.init_block_pool(e.n_blocks, e.block_size,
                                                device=e._device)

    def _pool_lost(self):
        return self.model.cache_lost(self._pool)

    # -- compiled programs (keys live in the engine's frozen AotCache) ----
    def _compiled_propose(self, b):
        e = self._engine
        k = e._spec_k

        def build():
            def prog(params, pool, token, pos, tables, *samp):
                def step(carry, j):
                    pool, tok = carry
                    logits, pool = self.model.decode_paged(
                        params, pool, tok, pos + j, tables)
                    nxt = e._pick(logits, samp, pos + j + 1)
                    return (pool, nxt), nxt

                (pool, _), toks = jax.lax.scan(
                    step, (pool, token), jnp.arange(k + 1, dtype=jnp.int32))
                # (k+1, b) -> (b, k): the last pick is never proposed,
                # its step only writes proposal k's own draft K/V
                return toks[:k].T, pool

            fn = jax.jit(e._scoped(prog, "serve_draft_propose_b%d" % b),
                         donate_argnums=(1,))
            z = e._put(np.zeros((b,), np.int32))
            tables = e._put(np.zeros((b, e._n_table), np.int32))
            samp = tuple(e._put(a) for a in e._sample_placeholders(b))
            return fn.lower(self._dparams, self._pool, z, z, tables,
                            *samp).compile()

        return e._aot.get(("draft_propose", b, k + 1), build)

    def _compiled_prefill(self, s):
        e = self._engine

        def build():
            def prog(params, pool, tokens, start, length, tables):
                _, pool = self.model.prefill_paged(
                    params, pool, tokens, start, length, tables)
                return pool

            fn = jax.jit(e._scoped(prog, "serve_draft_prefill_s%d" % s),
                         donate_argnums=(1,))
            toks = e._put(np.zeros((1, s), np.int32))
            zero = e._put(np.zeros((1,), np.int32))
            one = e._put(np.ones((1,), np.int32))
            tables = e._put(np.zeros((1, e._n_table), np.int32))
            return fn.lower(self._dparams, self._pool, toks, zero, one,
                            tables).compile()

        return e._aot.get(("draft_prefill", 1, s), build)

    def _compiled_cow(self):
        e = self._engine

        def build():
            def serve_draft_cow(pool, src, dst):
                return self.model.copy_block(pool, src, dst)

            fn = jax.jit(serve_draft_cow, donate_argnums=(0,))
            z = e._put(np.zeros((1,), np.int32))
            return fn.lower(self._pool, z, z).compile()

        return e._aot.get(("draft_cow", 1, 1), build)

    def warmup(self):
        e = self._engine
        for s in e.prefill_buckets:
            self._compiled_prefill(s)
        for b in e.decode_buckets:
            self._compiled_propose(b)
        if e._prefix is not None:
            self._compiled_cow()

    # -- degradation: draft state is never correctness-critical ----------
    def _degrade(self, site, exc):
        """A failed draft launch costs accept rate, not correctness: log,
        heal a consumed pool, carry on.  An injected device death still
        escalates — the scheduler must die for failover to run."""
        if isinstance(exc, chaos.ChaosEngineCrash):
            raise exc
        telemetry.inc("serve.draft_degraded")
        telemetry.record_event("serve_draft_degraded", site=site,
                               error=str(exc)[:200])
        if self._pool_lost():
            self._init_pool()

    def propose(self, seqs, k, bucket, host, dev, samp):
        token_d, pos_d, tables_d = dev
        try:
            compiled = self._compiled_propose(bucket)
            self._engine._watch(
                "draft", (token_d, pos_d, tables_d) + samp,
                ("token", "pos", "tables")
                + self._engine._SAMPLE_NAMES[:len(samp)], bucket)
            out, self._pool = compiled(self._dparams, self._pool, token_d,
                                       pos_d, tables_d, *samp)
            self.launches += 1
            return np.asarray(out)
        except Exception as exc:  # noqa: BLE001
            self._degrade("propose", exc)
            # junk proposals: the verify rejects them and the round
            # degenerates to one (correct) token per row
            return np.repeat(host[0][:, None], k, axis=1)

    def on_prefill_chunk(self, toks_d, start_d, length_d, table_d):
        try:
            compiled = self._compiled_prefill(int(toks_d.shape[1]))
            self._pool = compiled(self._dparams, self._pool, toks_d,
                                  start_d, length_d, table_d)
            self.launches += 1
        except Exception as exc:  # noqa: BLE001
            self._degrade("prefill", exc)

    def on_cow(self, src_d, dst_d):
        try:
            self._pool = self._compiled_cow()(self._pool, src_d, dst_d)
        except Exception as exc:  # noqa: BLE001
            self._degrade("cow", exc)

    def on_restore_span(self, toks_d, start_d, length_d, table_d):
        # the draft pool follows a host-tier restore by PREFILLING the
        # restored tokens through the draft model (the target restored
        # bytes; the draft re-derives its own) — same chunk arrays,
        # same compiled prefill buckets as `on_prefill_chunk`
        self.on_prefill_chunk(toks_d, start_d, length_d, table_d)

    def on_cache_rebuild(self):
        self._init_pool()


def make_drafter(kind, **kw):
    """Drafter factory for the ``MXNET_SERVE_SPEC_DRAFTER`` names."""
    if isinstance(kind, Drafter):
        return kind
    if kind == "ngram":
        return NgramDrafter(**kw)
    if kind == "model":
        return ModelDrafter(**kw)
    raise MXNetError("make_drafter: unknown drafter %r "
                     "(expected 'ngram' or 'model')" % (kind,))
