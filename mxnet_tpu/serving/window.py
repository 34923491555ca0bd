"""Prefill/decode programs of the MiMo-V2 block: window attention layers with
a learned sink beside full grouped-query attention layers, both with q/k
heads wider than their v heads and rotary positions on part of each head; a
SwiGLU where the layer has no experts and a share of a sparse expert layer
(sigmoid router over all the experts, no shared expert) where it has, RMSNorm,
no biases, an untied head.

`HybridWindowMoEKVModel` stands beside `shortconv.ShortConvMoEKVModel` behind
the protocol `ServingEngine` calls.  The block, for a row ``x`` at position
``t`` in layer ``i``:

    h = x + A_i(norm(x));   y = h + F_i(norm(h))

* ``A`` projects q (``num_heads`` heads of ``head_dim``), k (``K_i`` heads
  of ``head_dim``) and v (``K_i`` heads of ``v_head_dim``); rotates the first
  ``rotary_dim`` dims of each q and k head (rotate-half; the full layers' and
  the window layers' own theta); query head ``h`` reads K/V head ``h //
  (num_heads / K_i)``; scores ``q k / sqrt(head_dim)``.  A full layer's
  softmax runs over positions ``j <= t``; a window layer's over ``t - W < j
  <= t`` with the head's learned sink ``b_h`` in the denominator, ``p_j =
  exp(s_j) / (exp(b_h) + sum exp(s))``, a logit that takes mass and adds no
  value.  The output, times ``value_scale``, goes through ``W_o``.
* ``F``: `ops.moe.swiglu` in a dense layer, else `ops.moe.expert_layer`
  over the held range of the experts, their weights renormalised.

The equations are written out in `benchmark/reference/mimo_v2.py`, the plain
float32 reference the tests and the benchmark hold these programs to.

**Two lifetimes of K/V** (`cache_kind` "kv_pair_state"; docs/serving.md
"Window layers: a ring in the state slot").  The full layers' K and V live in
the paged pool, each at its own width: the pair of ``(full layers, 1,
n_blocks, block_size, kv_heads * head_dim)`` for K (after its rotation) and
``(..., kv_heads * v_head_dim)`` for V, block 0 the trash block
(`pallas_kernels.paged_attention.pair`).  A window layer keeps the last ``W``
positions' K and V a sequence, whatever its length: the pair of rings
``(window layers, slots, W, window_kv_heads * head_dim)`` for K and ``(...,
window_kv_heads * v_head_dim)`` for V, position ``p`` at index ``p mod W``
(two arrays: lanes sliced out of one would make the TPU compiler lay the
whole of it out anew in every layer), which the engine makes once
(`init_state`: a slot a batch row, a spare one for padding rows) and hands
to both programs beside the pool, as the pair ``(pool, ring)``, with each
row's slot index (``slots``).  A decode step writes its position and
attends over the ring; a chunk attends banded over the ring's ``W`` and its
own rows, then leaves its last ``W`` positions in the ring.  A position
below 0 is masked wherever it is read: a sequence's first chunk reads
nothing of its slot, whoever held the slot before.

What does not know the ring is refused by name at construction
(`unsupported`), as `ShortConvMoEKVModel` refuses it.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..ops import moe
from ..ops.attention import (paged_chunk_attention,
                             paged_chunk_kernel_applies,
                             paged_decode_attention,
                             paged_decode_kernel_applies)
from ..ops.lm_parts import proj, rms_norm, rope, rope_inv_freq
from .decode import TransformerKVModel
from .shortconv import ShortConvMoEKVModel

#: `hybrid_layer_pattern`'s values: 0 a full layer, 1 a window layer
FULL, WINDOW = 0, 1


class HybridWindowMoEKVModel:
    """Program builder for one geometry of the block above.

    ``layer_pattern`` gives each layer's kind (0 full, 1 window) and
    ``moe_layers`` whether it has experts (0 dense, 1 experts); ``seq_len``
    is the deepest context this engine's tables reach (a limit of the
    instance, not of the model).  ``experts_held`` is the half-open range of
    the ``n_routed_experts`` whose weights this chip holds: the router scores
    all of them, the held ones are computed (`ops.moe`)."""

    cache_kind = "kv_pair_state"
    #: engine options that do not know the ring yet (the engine refuses
    #: each at construction; block runs, and so the handoff, refuse the
    #: cache kind: `tiers.check_cache_kind`)
    unsupported = frozenset({"prefix", "quant", "kv_quant", "megastep",
                             "spec", "tier", "mesh"})
    #: the engine's counter of chunks that start a sequence's slot anew
    state_reset_counter = "ring_resets"

    def __init__(self, vocab_size, seq_len, layer_pattern, moe_layers,
                 hidden_size, num_heads, num_kv_heads, window_kv_heads,
                 head_dim, v_head_dim, rotary_dim, window, intermediate_size,
                 moe_intermediate_size, n_routed_experts, experts_held,
                 num_experts_per_tok, routed_scaling_factor=1.0,
                 rope_theta=1e7, window_rope_theta=1e4, value_scale=1.0,
                 eps=1e-5, dtype=np.float32):
        kinds = tuple(int(k) for k in layer_pattern)
        moe_layers = tuple(bool(m) for m in moe_layers)
        if set(kinds) - {FULL, WINDOW} or FULL not in kinds \
                or len(moe_layers) != len(kinds):
            raise MXNetError(
                "HybridWindowMoEKVModel: layer_pattern must be 0 (full) and 1 "
                "(window) with a full layer among them, and moe_layers as "
                "long; got %s and %s" % (kinds, moe_layers))
        for kv in (num_kv_heads, window_kv_heads):
            if num_heads % kv:
                raise MXNetError("HybridWindowMoEKVModel: %d heads over %d "
                                 "K/V heads do not divide" % (num_heads, kv))
        if rotary_dim % 2 or not 0 < rotary_dim <= head_dim:
            raise MXNetError("HybridWindowMoEKVModel: the rotary width %d "
                             "must be even and within the head's %d"
                             % (rotary_dim, head_dim))
        lo, hi = (int(e) for e in experts_held)
        if not 0 <= lo < hi <= n_routed_experts:
            raise MXNetError("HybridWindowMoEKVModel: experts_held %r is not "
                             "a range of the %d experts"
                             % (experts_held, n_routed_experts))
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self.layer_pattern = kinds
        self.moe_layers = moe_layers
        self.num_layers = len(kinds)
        self.hidden = int(hidden_size)
        self.num_heads = int(num_heads)
        self.kv_heads = int(num_kv_heads)
        self.window_kv_heads = int(window_kv_heads)
        self.head_dim, self.v_head_dim = int(head_dim), int(v_head_dim)
        self.rotary_dim = int(rotary_dim)
        self.window = int(window)
        self.dense_ffn = int(intermediate_size)
        self.expert_ffn = int(moe_intermediate_size)
        self.n_routed = int(n_routed_experts)
        self.experts_held = (lo, hi)
        self.top_k = int(num_experts_per_tok)
        self.routed_scale = float(routed_scaling_factor)
        self.value_scale = float(value_scale)
        self.eps = float(eps)
        self.dtype = np.dtype(dtype)
        self.inv_freq = {FULL: rope_inv_freq(self.rotary_dim,
                                             float(rope_theta)),
                         WINDOW: rope_inv_freq(self.rotary_dim,
                                               float(window_rope_theta))}
        self.full_layers = kinds.count(FULL)
        self.ring_layers = kinds.count(WINDOW)
        # the engine's MoE seam: one count a held expert a launch
        self.moe_experts = hi - lo
        self.quant = self.kv_quant = None

    def _kv(self, kind):
        return self.kv_heads if kind == FULL else self.window_kv_heads

    @property
    def k_width(self):
        """A full layer's cached K row: its K/V heads at the q/k width."""
        return self.kv_heads * self.head_dim

    @property
    def v_width(self):
        """A full layer's cached V row: its K/V heads at the v width."""
        return self.kv_heads * self.v_head_dim

    @property
    def ring_width(self):
        """A window layer's ring rows of one position: K's and V's."""
        return self.window_kv_heads * (self.head_dim + self.v_head_dim)

    @property
    def moe_pairs_per_row(self):
        """(row, expert) pairs a real row routes in one launch, over all
        the experts: ``top_k`` in every expert layer."""
        return self.top_k * sum(self.moe_layers)

    # -- parameters --------------------------------------------------------
    def param_shapes(self):
        d, h = self.hidden, self.num_heads
        shapes = {"embed_weight": (self.vocab_size, d),
                  "final_ln_gamma": (d,),
                  "pred_weight": (self.vocab_size, d)}
        for i, kind in enumerate(self.layer_pattern):
            p = "layer%d_" % i
            kv = self._kv(kind)
            shapes.update({
                p + "ln1_gamma": (d,),
                p + "q_weight": (h * self.head_dim, d),
                p + "k_weight": (kv * self.head_dim, d),
                p + "v_weight": (kv * self.v_head_dim, d),
                p + "attn_out_weight": (d, h * self.v_head_dim),
                p + "ln2_gamma": (d,)})
            if kind == WINDOW:
                # a logit a query head
                shapes[p + "attn_sink_bias"] = (h,)
            if not self.moe_layers[i]:
                f = self.dense_ffn
                shapes.update({p + "ffn_gate_weight": (f, d),
                               p + "ffn_up_weight": (f, d),
                               p + "ffn_down_weight": (d, f)})
                continue
            f, n = self.expert_ffn, self.moe_experts
            shapes.update({
                p + "moe_router_weight": (self.n_routed, d),
                p + "moe_router_bias": (self.n_routed,),
                # the held experts' banks, (in, out) on the last two axes
                p + "moe_gate": (n, d, f), p + "moe_up": (n, d, f),
                p + "moe_down": (n, f, d)})
        return shapes

    # tests' random parameters, and the quantisation the engine has refused
    # by name before it asks: LFM2's
    init_params = ShortConvMoEKVModel.init_params
    with_quant = ShortConvMoEKVModel.with_quant

    def check_params(self, params):
        missing = [n for n in self.param_shapes() if n not in params]
        if missing:
            raise MXNetError("HybridWindowMoEKVModel: params missing %s"
                             % missing)

    # -- the pool and the ring ---------------------------------------------
    cache_lost = staticmethod(TransformerKVModel.cache_lost)
    _zeros = ShortConvMoEKVModel._zeros

    def block_bytes(self, block_size, shards=1):
        """Device bytes of one block of the pool: K and V of every FULL
        layer, each at its width (a window layer caches nothing a
        token)."""
        if shards != 1:
            raise MXNetError("HybridWindowMoEKVModel: the pool is not "
                             "sharded over a mesh yet")
        return self.full_layers * int(block_size) \
            * (self.k_width + self.v_width) * self.dtype.itemsize

    def state_slot_bytes(self):
        """Device bytes of one sequence's ring: ``W`` positions of K and V
        in every window layer."""
        return self.ring_layers * self.window * self.ring_width \
            * self.dtype.itemsize

    def init_block_pool(self, n_blocks, block_size, device=None):
        """The zeroed pair of K's ``(full_layers, 1, n_blocks, block_size,
        k_width)`` and V's ``(..., v_width)``, made on the device (also the
        rebuild's allocation)."""
        shape = (self.full_layers, 1, int(n_blocks), int(block_size))
        return (self._zeros(shape + (self.k_width,), device),
                self._zeros(shape + (self.v_width,), device))

    def init_state(self, n_slots, device=None):
        """The zeroed pair of K's rings ``(ring_layers, n_slots, W,
        window_kv_heads * head_dim)`` and V's ``(..., window_kv_heads *
        v_head_dim)``; the engine asks for a slot a batch row and one
        more."""
        rows = (self.ring_layers, int(n_slots), self.window)
        kw = self.window_kv_heads
        return (self._zeros(rows + (kw * self.head_dim,), device),
                self._zeros(rows + (kw * self.v_head_dim,), device))

    def paged_decode_kernel(self, cache):
        """Whether `decode_paged` over ``cache``, traced here, attends in
        its full layers with the Pallas kernel `paged_decode_attn`."""
        return paged_decode_kernel_applies(cache[0], self.num_heads,
                                           self.kv_heads)

    def paged_prefill_kernel(self, cache, c):
        """Whether `prefill_paged` of a chunk of ``c`` tokens attends with
        the Pallas kernel `paged_prefill_attn`: never, a pair of widths is
        walked by `ops.attention.paged_chunk_stream`."""
        pool = cache[0]
        return paged_chunk_kernel_applies(
            pool, self.num_heads, self.kv_heads, c,
            -(-self.seq_len // pool[0].shape[3]))

    # -- the block's parts -------------------------------------------------
    _embed = ShortConvMoEKVModel._embed

    @jax.named_scope("lm_head")
    def _head(self, params, x):
        return proj(rms_norm(x, params["final_ln_gamma"], self.eps),
                    params["pred_weight"])

    def _qkv(self, params, p, kind, u, positions):
        """q (n, heads, head_dim), k (n, K, head_dim) after their rotation
        and v (n, K * v_head_dim) of rows ``u`` (n, d) at ``positions``
        (n,)."""
        n, hd = u.shape[0], self.head_dim
        with jax.named_scope("qkv_proj"):
            q = proj(u, params[p + "q_weight"]).reshape(n, -1, hd)
            k = proj(u, params[p + "k_weight"]).reshape(n, -1, hd)
            v = proj(u, params[p + "v_weight"])
        with jax.named_scope("rope"):
            q = rope(q, positions[:, None], self.inv_freq[kind],
                     rotary=self.rotary_dim)
            k = rope(k, positions[:, None], self.inv_freq[kind],
                     rotary=self.rotary_dim)
        return q, k, v

    @jax.named_scope("kv_scatter")
    def _scatter_kv(self, pool, layer, blk, off, k, v):
        # rows by (block, offset): a whole-block update makes the TPU
        # compiler copy the pool (`ShortConvMoEKVModel._scatter_kv`)
        k_pool, v_pool = pool
        return (k_pool.at[layer, 0, blk, off].set(k.astype(k_pool.dtype)),
                v_pool.at[layer, 0, blk, off].set(v.astype(v_pool.dtype)))

    @jax.named_scope("attn_out")
    def _attn_out(self, params, p, attn):
        attn = (attn.astype(jnp.float32) * self.value_scale).astype(
            attn.dtype)
        return proj(attn, params[p + "attn_out_weight"])

    @staticmethod
    def _own(positions):
        """Whether a ring row said to hold ``positions`` holds the
        sequence's own: nothing lies below position 0, whatever the slot's
        last holder left there."""
        return positions >= 0

    @staticmethod
    def _sink_softmax(s, valid, sink):
        """The probabilities of scores ``s`` (..., keys) float32 masked by
        ``valid``, with the heads' sink logits (broadcast to (..., 1)) in
        the denominator and nowhere else."""
        s = jnp.where(valid, s, -jnp.inf)
        m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), sink)
        e = jnp.exp(s - m)
        return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - m))

    def _ffn(self, params, p, i, u, valid, tape):
        if not self.moe_layers[i]:
            with jax.named_scope("ffn"):
                return moe.swiglu(u, params[p + "ffn_gate_weight"],
                                  params[p + "ffn_up_weight"],
                                  params[p + "ffn_down_weight"])
        y, counts = moe.expert_layer(
            u, params[p + "moe_router_weight"], params[p + "moe_router_bias"],
            tuple(params[p + "moe_" + n] for n in ("gate", "up", "down")),
            top_k=self.top_k, scale=self.routed_scale,
            experts_held=self.experts_held, valid=valid)
        if tape is not None:
            tape.append(counts)
        return y

    def _window_chunk(self, params, p, q, k, v, kb, vb, start):
        """A window layer's attention for a chunk: queries ``q`` (b, c,
        heads, hd) at ``start + i``, the chunk's own k (b, c, Kw, hd) and v
        (b, c, Kw * vd), ``kb`` and ``vb`` the ring's ``W`` rows of K and V
        in order of position (``start - W`` .. ``start - 1``).  Banded: a
        block of ``qb = min(c, W)`` queries scores the ``qb + W`` keys that
        can lie in its windows.  Returns (b, c, heads * vd) float32."""
        b, c, h, hd = q.shape
        w, kw, vd = self.window, self.window_kv_heads, self.v_head_dim
        g = h // kw
        kb = kb.reshape(b, w, kw, hd)
        vb = vb.reshape(b, w, kw, vd)
        keys = jnp.concatenate([kb, k.astype(kb.dtype)], axis=1)
        vals = jnp.concatenate([vb, v.reshape(b, c, kw, vd).astype(
            vb.dtype)], axis=1)                        # (b, W + c, Kw, vd)
        # key x sits at position start - W + x; below 0 it is nothing
        x = jnp.arange(w + c, dtype=jnp.int32)
        real = self._own(start[:, None] - w + x[None])          # (b, W + c)
        vals = jnp.where(real[:, :, None, None], vals.astype(jnp.float32),
                         0.0)
        qb = min(c, w)
        nb = -(-c // qb)
        span = qb + w
        if nb * qb > c:
            # queries past the chunk, and keys past them: masked by the band
            pad = nb * qb - c
            q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
            keys = jnp.pad(keys, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vals = jnp.pad(vals, ((0, 0), (0, pad), (0, 0), (0, 0)))
            real = jnp.pad(real, ((0, 0), (0, pad)))
        slab = np.arange(nb)[:, None] * qb + np.arange(span)[None]
        ks = keys[:, slab]                             # (b, nb, span, Kw, hd)
        vs = vals[:, slab]
        qs = q.reshape(b, nb, qb, kw, g, hd)
        s = jnp.einsum("bnqkgd,bnskd->bnkgqs", qs, ks,
                       preferred_element_type=jnp.float32) \
            / float(hd) ** 0.5
        # query i of block a (position start + a * qb + i) sees key y of
        # its slab (x = a * qb + y) where i < y <= i + W
        i = np.arange(qb)[:, None]
        y = np.arange(span)[None]
        band = jnp.asarray((y > i) & (y <= i + w))
        valid = band[None, None] & real[:, slab][:, :, None, :]
        valid = valid[:, :, None, None]                 # (b, nb, 1, 1, qb, s)
        sink = params[p + "attn_sink_bias"].astype(jnp.float32).reshape(
            1, 1, kw, g, 1, 1)
        o = jnp.einsum("bnkgqs,bnskd->bnqkgd",
                       self._sink_softmax(s, valid, sink), vs)
        # (b, nb, qb, Kw, g, vd) -> (b, c, heads * vd), head-major
        return o.reshape(b, nb * qb, h * vd)[:, :c]

    @staticmethod
    def _slot_rows(ring, layer, slots):
        """Rows ``slots`` of a layer's rings, (b, W, width): a slice a row
        (a chunk's launch has one), which the TPU compiler reads in place
        where a gather by index made it copy every layer's rings first."""
        zero = jnp.int32(0)
        return jnp.concatenate([
            jax.lax.dynamic_slice(ring, (jnp.int32(layer), slots[r], zero,
                                         zero), (1, 1) + ring.shape[2:])[0]
            for r in range(slots.shape[0])])

    @staticmethod
    def _set_slot_rows(ring, layer, slots, rows):
        """``ring`` with rows ``slots`` of layer ``layer`` set to ``rows``,
        a row at a time in place (`_slot_rows`)."""
        for r in range(slots.shape[0]):
            ring = jax.lax.dynamic_update_slice(
                ring, rows[r][None, None],
                (jnp.int32(layer), slots[r], jnp.int32(0), jnp.int32(0)))
        return ring

    # -- the engine's two programs -----------------------------------------
    def prefill_paged(self, params, cache, tokens, start, length, tables,
                      moe_tape=None, slots=None):
        """One chunked-prefill step; the contract is
        `TransformerKVModel.prefill_paged`'s (tokens (b, c), c a multiple of
        the block size; start (b,) block-aligned; length (b,) real tokens in
        this chunk; tables (b, m)) with ``cache`` the pair (pool, ring) and
        ``slots`` (b,) each row's slot in the ring.  Returns (logits of each
        row's last real token, (pool, ring))."""
        pool, (ring_k, ring_v) = cache
        b, c = tokens.shape
        bs, m = pool[0].shape[3], tables.shape[1]
        w = self.window
        start = start.astype(jnp.int32)
        length = length.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        slots = slots.astype(jnp.int32)
        offs = jnp.arange(c, dtype=jnp.int32)[None]
        positions = (start[:, None] + offs).reshape(-1)
        valid = (offs < length[:, None]).reshape(-1)
        # where each of the chunk's rows is cached, as `ShortConvMoEKVModel`
        # addresses its own; past the table's width (a short last chunk's
        # padding) rows go to the trash block
        ent = positions.reshape(b, c) // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1), axis=1)
        blk = jnp.where(ent < m, blk, 0).reshape(-1)
        off = positions % bs
        ridx = jnp.arange(w, dtype=jnp.int32)[None]
        # the ring's rows in order of position: start - W + r at index
        # (start + r) mod W
        order = (start[:, None] + ridx) % w
        # what index r holds after the chunk: the last position up to the
        # chunk's last real one that is r mod W; from the chunk where that
        # lies in it, else what the ring held
        end = (start + length - 1)[:, None]
        last_r = end - (end - ridx) % w
        from_chunk = (last_r >= start[:, None])[:, :, None]
        src = jnp.clip(last_r - start[:, None], 0, c - 1)[:, :, None]
        x = self._embed(params, tokens)                       # (b, c, d)
        fi = wi = 0
        for i, kind in enumerate(self.layer_pattern):
            p = "layer%d_" % i
            u = rms_norm(x, params[p + "ln1_gamma"], self.eps)
            q, k, v = self._qkv(params, p, kind, u.reshape(b * c, -1),
                                positions)
            if kind == FULL:
                pool = self._scatter_kv(pool, fi, blk, off,
                                        k.reshape(b * c, -1), v)
                attn = paged_chunk_attention(
                    q.reshape(b, c, -1), pool, fi, tables, start,
                    self.num_heads, kv_heads=self.kv_heads)
                fi += 1
            else:
                with jax.named_scope("window_state"):
                    rows_k = self._slot_rows(ring_k, wi, slots)
                    rows_v = self._slot_rows(ring_v, wi, slots)
                    kb, vb = (jnp.take_along_axis(r, order[:, :, None],
                                                  axis=1)
                              for r in (rows_k, rows_v))
                with jax.named_scope("window_attention"):
                    attn = self._window_chunk(
                        params, p, q.reshape(b, c, self.num_heads, -1),
                        k.reshape(b, c, self.window_kv_heads, -1), v,
                        kb, vb, start).astype(x.dtype)
                with jax.named_scope("window_state"):
                    def left(ring, rows, new):
                        return self._set_slot_rows(ring, wi, slots, jnp.where(
                            from_chunk, jnp.take_along_axis(
                                new.astype(ring.dtype), src, axis=1), rows))

                    ring_k = left(ring_k, rows_k, k.reshape(b, c, -1))
                    ring_v = left(ring_v, rows_v, v.reshape(b, c, -1))
                wi += 1
            x = x + self._attn_out(params, p, attn)
            u = rms_norm(x, params[p + "ln2_gamma"], self.eps)
            x = x + self._ffn(params, p, i, u.reshape(b * c, -1), valid,
                              moe_tape).reshape(b, c, -1)
        last = jnp.take_along_axis(x, (length - 1)[:, None, None],
                                   axis=1)[:, 0]
        return self._head(params, last), (pool, (ring_k, ring_v))

    def decode_paged(self, params, cache, token, pos, tables, moe_tape=None,
                     slots=None):
        """One generation step (the contract is
        `TransformerKVModel.decode_paged`'s: padding rows are all-trash with
        pos 0; theirs is the spare slot).  Returns (logits (b, vocab),
        (pool, ring))."""
        pool, (ring_k, ring_v) = cache
        b = token.shape[0]
        bs, m = pool[0].shape[3], tables.shape[1]
        w, hd = self.window, self.head_dim
        kw, vd = self.window_kv_heads, self.v_head_dim
        g = self.num_heads // kw
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        slots = slots.astype(jnp.int32)
        ent = pos // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1)[:, None],
                                  axis=1)[:, 0]
        blk = jnp.where(ent < m, blk, 0)
        off = pos % bs
        # ring index r holds position pos - ((pos - r) mod W)
        ridx = jnp.arange(w, dtype=jnp.int32)[None]
        seen = self._own(pos[:, None] - (pos[:, None] - ridx) % w)

        # the index this step writes
        mine = (ridx == (pos % w)[:, None])[:, :, None]
        # a real row's first block is never the trash block
        valid = tables[:, 0] != 0
        x = self._embed(params, token)                        # (b, d)
        fi = wi = 0
        for i, kind in enumerate(self.layer_pattern):
            p = "layer%d_" % i
            u = rms_norm(x, params[p + "ln1_gamma"], self.eps)
            q, k, v = self._qkv(params, p, kind, u, pos)
            if kind == FULL:
                pool = self._scatter_kv(pool, fi, blk, off, k.reshape(b, -1),
                                        v)
                attn = paged_decode_attention(
                    q.reshape(b, -1), pool, fi, tables, pos, self.num_heads,
                    kv_heads=self.kv_heads)
                fi += 1
            else:
                with jax.named_scope("window_state"):
                    # the rows gathered before the step's write, and the
                    # write put into them: read after the scatter, the
                    # rings would be copied whole
                    nk = k.reshape(b, -1).astype(ring_k.dtype)
                    nv = v.astype(ring_v.dtype)
                    rows_k = jnp.where(mine, nk[:, None], ring_k[wi, slots])
                    rows_v = jnp.where(mine, nv[:, None], ring_v[wi, slots])
                    ring_k = ring_k.at[wi, slots, pos % w].set(nk)
                    ring_v = ring_v.at[wi, slots, pos % w].set(nv)
                with jax.named_scope("window_attention"):
                    kr = rows_k.reshape(b, w, kw, hd)
                    vr = jnp.where(seen[:, :, None, None],
                                   rows_v.reshape(b, w, kw, vd).astype(
                                       jnp.float32), 0.0)
                    s = jnp.einsum("bkgd,bwkd->bkgw",
                                   q.reshape(b, kw, g, hd), kr,
                                   preferred_element_type=jnp.float32) \
                        / float(hd) ** 0.5
                    sink = params[p + "attn_sink_bias"].astype(
                        jnp.float32).reshape(1, kw, g, 1)
                    attn = jnp.einsum(
                        "bkgw,bwkd->bkgd",
                        self._sink_softmax(s, seen[:, None, None], sink),
                        vr).reshape(b, -1).astype(x.dtype)
                wi += 1
            x = x + self._attn_out(params, p, attn)
            u = rms_norm(x, params[p + "ln2_gamma"], self.eps)
            x = x + self._ffn(params, p, i, u, valid, moe_tape)
        return self._head(params, x), (pool, (ring_k, ring_v))
