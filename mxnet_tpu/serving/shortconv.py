"""Prefill/decode programs of the LFM2-MoE block: gated short convolutions
and grouped-query attention interleaved by `layer_types`, a SwiGLU in the
first ``num_dense_layers`` layers and a sparse expert layer (no shared
expert) after them, RMSNorm, no biases, a head tied to the embedding.

`ShortConvMoEKVModel` stands beside `decode.TransformerKVModel` and
`latent.LatentMoEKVModel` behind the protocol `ServingEngine` calls.  The
block, for a row ``x``:

    h = x + Op_i(norm(x));   y = h + FFN_i(norm(h))

* ``Op`` of a "conv" layer: ``[B, C, z] = u W_in^T``; ``s_t = B_t * z_t``;
  ``c_t = sum_j w_j * s_(t-(L-1)+j)`` over the ``L`` taps (depthwise, causal,
  ``s`` zero before the sequence starts); ``Op(u)_t = (C_t * c_t) W_out^T``.
* ``Op`` of a "full_attention" layer: q, k, v projections, RMSNorm over each
  head of q and k, RoPE (rotate-half) on both, causal softmax with query
  head ``h`` reading K/V head ``h // (heads // kv_heads)``, ``W_o``.
* FFN: `ops.moe.swiglu`, or `ops.moe.expert_layer` with every expert held
  here, no shared expert and the published ``1e-6`` in the renormalisation.

The equations are written out in `benchmark/reference/lfm2_moe.py`, the
plain float32 reference the tests and the benchmark hold these programs to.

**Two kinds of state** (`cache_kind` "kv_pair_state"; docs/serving.md "A
third kind of state").  The attention layers' K and V live in a paged pool
``(attention layers, 2, n_blocks, block_size, kv_heads * head)``, block 0 the
trash block, K after its norm and RoPE: `TransformerKVModel`'s layout at the
K/V heads' width, over the attention layers alone.  A conv layer keeps the
last ``L - 1`` values of ``s`` a sequence, whatever its length: the state
``(conv layers, slots, L - 1, hidden)``, which the engine makes once
(`init_state`: a slot a batch row and a spare one for padding rows) and
hands to both programs beside the pool, as the pair ``(pool, state)``, with
each row's slot index (``slots``).  A chunk that starts at position 0 reads
zeros in its slot's place (a new sequence starts from nothing, whoever held
the slot before); every chunk and every decode step leaves the values its
successor needs.

What does not know the state yet is refused by name at construction
(`unsupported`): a prefix hit would skip the chunks that build it, a
speculative rewind would have to take it back, the host tier and the
handoff move block runs only (`tiers.check_cache_kind`), the megastep and a
sharded mesh carry the pool alone, and the pool is not quantised.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..ops import moe
from ..ops.attention import (chunk_attention, gather_paged_kv,
                             paged_decode_attention,
                             paged_decode_kernel_applies)
from ..ops.lm_parts import proj, rms_norm, rope, rope_inv_freq
from .decode import TransformerKVModel

_KINDS = ("conv", "full_attention")


class ShortConvMoEKVModel:
    """Program builder for one geometry of the block above.

    ``layer_types`` names each layer's operator ("conv" or
    "full_attention"); ``seq_len`` is the deepest context this engine's
    tables reach (a limit of the instance, not of the model).  The head is
    the embedding: `param_shapes` names no ``pred_weight``, and a
    parameter dict that brings one is served with it.
    """

    cache_kind = "kv_pair_state"
    #: engine options that do not know the per-sequence state yet (the
    #: engine refuses each at construction; block runs, and so the
    #: handoff, refuse the cache kind: `tiers.check_cache_kind`)
    unsupported = frozenset({"prefix", "quant", "kv_quant", "megastep",
                             "spec", "tier", "mesh"})

    def __init__(self, vocab_size, seq_len, layer_types, hidden_size,
                 num_heads, num_kv_heads, conv_kernel, intermediate_size,
                 moe_intermediate_size, num_experts, num_experts_per_tok,
                 num_dense_layers=2, routed_scaling_factor=1.0, eps=1e-5,
                 rope_theta=1e6, dtype=np.float32):
        layer_types = tuple(layer_types)
        bad = sorted(set(layer_types) - set(_KINDS))
        if bad or "full_attention" not in layer_types:
            raise MXNetError("ShortConvMoEKVModel: layer_types must be of "
                             "%s with an attention layer among them, got %s"
                             % (_KINDS, bad or layer_types))
        if hidden_size % num_heads or num_heads % num_kv_heads:
            raise MXNetError("ShortConvMoEKVModel: hidden %d, %d heads and "
                             "%d K/V heads do not divide"
                             % (hidden_size, num_heads, num_kv_heads))
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self.layer_types = layer_types
        self.num_layers = len(layer_types)
        self.hidden = int(hidden_size)
        self.num_heads, self.kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = self.hidden // self.num_heads
        if self.head_dim % 2:
            raise MXNetError("ShortConvMoEKVModel: the head size must be "
                             "even")
        self.conv_kernel = int(conv_kernel)
        self.dense_ffn = int(intermediate_size)
        self.expert_ffn = int(moe_intermediate_size)
        self.n_experts = int(num_experts)
        self.top_k = int(num_experts_per_tok)
        self.first_dense = int(num_dense_layers)
        self.routed_scale = float(routed_scaling_factor)
        self.eps = float(eps)
        self.dtype = np.dtype(dtype)
        self.inv_freq = rope_inv_freq(self.head_dim, float(rope_theta))
        self.attn_layers = layer_types.count("full_attention")
        self.conv_layers = layer_types.count("conv")
        # the engine's MoE seam: one count an expert a launch
        self.moe_experts = self.n_experts
        self.quant = self.kv_quant = None

    @property
    def kv_width(self):
        """A cached K (or V) row: the K/V heads side by side."""
        return self.kv_heads * self.head_dim

    @property
    def moe_pairs_per_row(self):
        """(row, expert) pairs a real row routes in one launch: ``top_k``
        in every expert layer, all of them held here."""
        return self.top_k * max(0, self.num_layers - self.first_dense)

    # -- parameters --------------------------------------------------------
    def param_shapes(self):
        d, hd = self.hidden, self.head_dim
        shapes = {"embed_weight": (self.vocab_size, d),
                  "final_ln_gamma": (d,)}
        for i, kind in enumerate(self.layer_types):
            p = "layer%d_" % i
            shapes[p + "ln1_gamma"] = (d,)
            if kind == "conv":
                shapes.update({
                    p + "conv_in_weight": (3 * d, d),
                    # the taps, oldest first: (channel, tap)
                    p + "conv_weight": (d, self.conv_kernel),
                    p + "conv_out_weight": (d, d)})
            else:
                shapes.update({
                    p + "q_weight": (self.num_heads * hd, d),
                    p + "k_weight": (self.kv_width, d),
                    p + "v_weight": (self.kv_width, d),
                    p + "q_ln_gamma": (hd,), p + "k_ln_gamma": (hd,),
                    p + "attn_out_weight": (d, self.num_heads * hd)})
            shapes[p + "ln2_gamma"] = (d,)
            if i < self.first_dense:
                f = self.dense_ffn
                shapes.update({p + "ffn_gate_weight": (f, d),
                               p + "ffn_up_weight": (f, d),
                               p + "ffn_down_weight": (d, f)})
                continue
            f, n = self.expert_ffn, self.n_experts
            shapes.update({
                p + "moe_router_weight": (n, d),
                p + "moe_router_bias": (n,),
                # the experts' banks, (in, out) on the last two axes
                p + "moe_gate": (n, d, f), p + "moe_up": (n, d, f),
                p + "moe_down": (n, f, d)})
        return shapes

    def init_params(self, rng=None, scale=0.02):
        """Random parameters (tests; gains near 1, all else N(0, scale))."""
        rng = rng or np.random.RandomState(0)
        return {name: ((1.0 if name.endswith("_gamma") else 0.0)
                       + rng.randn(*shape) * scale).astype(self.dtype)
                for name, shape in self.param_shapes().items()}

    def check_params(self, params):
        missing = [n for n in self.param_shapes() if n not in params]
        if missing:
            raise MXNetError("ShortConvMoEKVModel: params missing %s"
                             % missing)

    def with_quant(self, quant, kv_quant):
        # the engine has refused either by name before it asks
        return self

    # -- the pool and the state --------------------------------------------
    cache_lost = staticmethod(TransformerKVModel.cache_lost)

    def block_bytes(self, block_size, shards=1):
        """Device bytes of one block of the pool: K and V of every
        ATTENTION layer (a conv layer caches nothing a token)."""
        if shards != 1:
            raise MXNetError("ShortConvMoEKVModel: the pool is not sharded "
                             "over a mesh yet")
        return self.attn_layers * 2 * int(block_size) * self.kv_width \
            * self.dtype.itemsize

    def state_slot_bytes(self):
        """Device bytes of one sequence's state: the last ``L - 1`` values
        of ``s`` in every conv layer."""
        return self.conv_layers * (self.conv_kernel - 1) * self.hidden \
            * self.dtype.itemsize

    def _zeros(self, shape, device):
        if device is None:
            return jnp.zeros(shape, self.dtype)
        return jax.jit(lambda: jnp.zeros(shape, self.dtype),
                       out_shardings=jax.sharding.SingleDeviceSharding(
                           device))()

    def init_block_pool(self, n_blocks, block_size, device=None):
        """Zeroed K/V pool ``(attn_layers, 2, n_blocks, block_size,
        kv_width)``, made on the device (also the rebuild's allocation)."""
        return self._zeros((self.attn_layers, 2, int(n_blocks),
                            int(block_size), self.kv_width), device)

    def init_state(self, n_slots, device=None):
        """Zeroed per-sequence state ``(conv_layers, n_slots, L - 1,
        hidden)``; the engine asks for a slot a batch row and one more."""
        return self._zeros((self.conv_layers, int(n_slots),
                            self.conv_kernel - 1, self.hidden), device)

    def paged_decode_kernel(self, cache):
        """Whether `decode_paged` over ``cache``, traced here, attends with
        the Pallas kernel `paged_decode_attn`."""
        return paged_decode_kernel_applies(cache[0], self.num_heads,
                                           self.kv_heads)

    # -- the block's parts -------------------------------------------------
    @jax.named_scope("embed")
    def _embed(self, params, tokens):
        return jnp.take(params["embed_weight"], tokens.astype(jnp.int32),
                        axis=0)

    @jax.named_scope("lm_head")
    def _head(self, params, x):
        # tied unless the parameters bring a head of their own
        w = params.get("pred_weight", params["embed_weight"])
        return proj(rms_norm(x, params["final_ln_gamma"], self.eps), w)

    def _conv(self, params, p, u, before):
        """The gated short convolution of rows ``u`` (b, c, d) that follow
        ``before`` (b, L - 1, d), the sequence's last values of ``s``.
        Returns (Op(u) (b, c, d), ``before`` and the rows' ``s`` in one run
        (b, L - 1 + c, d))."""
        with jax.named_scope("short_conv"):
            c, d = u.shape[1], self.hidden
            bcz = proj(u, params[p + "conv_in_weight"])
            s = bcz[..., :d] * bcz[..., 2 * d:]              # B * z
            run = jnp.concatenate([before.astype(s.dtype), s], axis=1)
            w = params[p + "conv_weight"].astype(jnp.float32)
            taps = sum(run[:, j:j + c].astype(jnp.float32) * w[:, j]
                       for j in range(self.conv_kernel))
            gated = bcz[..., d:2 * d] * taps.astype(u.dtype)  # C * c
            return proj(gated, params[p + "conv_out_weight"]), run

    def _qkv(self, params, p, u, positions):
        """q (n, heads * hd), k and v (n, kv_width) of rows ``u`` (n, d) at
        ``positions`` (n,): k as it is cached, after its norm and RoPE."""
        n, hd = u.shape[0], self.head_dim
        with jax.named_scope("qkv_proj"):
            q = proj(u, params[p + "q_weight"]).reshape(n, -1, hd)
            k = proj(u, params[p + "k_weight"]).reshape(n, -1, hd)
            v = proj(u, params[p + "v_weight"])
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, params[p + "q_ln_gamma"], self.eps)
            k = rms_norm(k, params[p + "k_ln_gamma"], self.eps)
        with jax.named_scope("rope"):
            q = rope(q, positions[:, None], self.inv_freq)
            k = rope(k, positions[:, None], self.inv_freq)
        return q.reshape(n, -1), k.reshape(n, -1), v

    @jax.named_scope("kv_scatter")
    def _scatter_kv(self, pool, layer, blk, off, k, v):
        # rows by (block, offset), as `LatentMoEKVModel` writes its own: a
        # whole-block update makes the TPU compiler copy the pool
        pool = pool.at[layer, 0, blk, off].set(k.astype(pool.dtype))
        return pool.at[layer, 1, blk, off].set(v.astype(pool.dtype))

    @jax.named_scope("attn_out")
    def _attn_out(self, params, p, attn):
        return proj(attn, params[p + "attn_out_weight"])

    def _ffn(self, params, p, i, u, valid, tape):
        if i < self.first_dense:
            with jax.named_scope("ffn"):
                return moe.swiglu(u, params[p + "ffn_gate_weight"],
                                  params[p + "ffn_up_weight"],
                                  params[p + "ffn_down_weight"])
        y, counts = moe.expert_layer(
            u, params[p + "moe_router_weight"], params[p + "moe_router_bias"],
            tuple(params[p + "moe_" + n] for n in ("gate", "up", "down")),
            top_k=self.top_k, scale=self.routed_scale,
            experts_held=(0, self.n_experts), valid=valid, eps=1e-6)
        if tape is not None:
            tape.append(counts)
        return y

    # -- the engine's two programs -----------------------------------------
    def prefill_paged(self, params, cache, tokens, start, length, tables,
                      moe_tape=None, slots=None):
        """One chunked-prefill step; the contract is
        `TransformerKVModel.prefill_paged`'s (tokens (b, c), c a multiple of
        the block size; start (b,) block-aligned; length (b,) real tokens in
        this chunk; tables (b, m)) with ``cache`` the pair (pool, state) and
        ``slots`` (b,) each row's slot in the state.  Returns (logits of
        each row's last real token, (pool, state))."""
        pool, state = cache
        b, c = tokens.shape
        bs, m = pool.shape[3], tables.shape[1]
        start = start.astype(jnp.int32)
        length = length.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        slots = slots.astype(jnp.int32)
        offs = jnp.arange(c, dtype=jnp.int32)[None]
        positions = (start[:, None] + offs).reshape(-1)
        valid = (offs < length[:, None]).reshape(-1)
        # where each of the chunk's rows is cached, as `LatentMoEKVModel`
        # addresses its own; past the table's width (a short last chunk's
        # padding) rows go to the trash block
        ent = positions.reshape(b, c) // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1), axis=1)
        blk = jnp.where(ent < m, blk, 0).reshape(-1)
        off = positions % bs
        # the values the next chunk, or decode, starts from: the L - 1
        # before position start + length
        keep = length[:, None, None] + jnp.arange(
            self.conv_kernel - 1, dtype=jnp.int32)[None, :, None]
        x = self._embed(params, tokens)                       # (b, c, d)
        ai = ci = 0
        for i, kind in enumerate(self.layer_types):
            p = "layer%d_" % i
            u = rms_norm(x, params[p + "ln1_gamma"], self.eps)
            if kind == "conv":
                with jax.named_scope("conv_state"):
                    # a sequence's first chunk starts from nothing,
                    # whatever the slot's last holder left in it
                    before = jnp.where((start == 0)[:, None, None], 0,
                                       state[ci, slots])
                op, run = self._conv(params, p, u, before)
                with jax.named_scope("conv_state"):
                    state = state.at[ci, slots].set(
                        jnp.take_along_axis(run, keep, axis=1).astype(
                            state.dtype))
                ci += 1
            else:
                q, k, v = self._qkv(params, p, u.reshape(b * c, -1),
                                    positions)
                pool = self._scatter_kv(pool, ai, blk, off, k, v)
                attn = chunk_attention(
                    q.reshape(b, c, -1),
                    gather_paged_kv(pool, ai, 0, tables),
                    gather_paged_kv(pool, ai, 1, tables), start,
                    self.num_heads, kv_heads=self.kv_heads)
                op = self._attn_out(params, p, attn)
                ai += 1
            x = x + op
            u = rms_norm(x, params[p + "ln2_gamma"], self.eps)
            x = x + self._ffn(params, p, i, u.reshape(b * c, -1), valid,
                              moe_tape).reshape(b, c, -1)
        last = jnp.take_along_axis(x, (length - 1)[:, None, None],
                                   axis=1)[:, 0]
        return self._head(params, last), (pool, state)

    def decode_paged(self, params, cache, token, pos, tables, moe_tape=None,
                     slots=None):
        """One generation step (the contract is
        `TransformerKVModel.decode_paged`'s: padding rows are all-trash with
        pos 0; theirs is the spare slot).  Returns (logits (b, vocab),
        (pool, state))."""
        pool, state = cache
        bs, m = pool.shape[3], tables.shape[1]
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        slots = slots.astype(jnp.int32)
        ent = pos // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1)[:, None],
                                  axis=1)[:, 0]
        blk = jnp.where(ent < m, blk, 0)
        off = pos % bs
        # a real row's first block is never the trash block
        valid = tables[:, 0] != 0
        x = self._embed(params, token)                        # (b, d)
        ai = ci = 0
        for i, kind in enumerate(self.layer_types):
            p = "layer%d_" % i
            u = rms_norm(x, params[p + "ln1_gamma"], self.eps)
            if kind == "conv":
                with jax.named_scope("conv_state"):
                    before = state[ci, slots]
                op, run = self._conv(params, p, u[:, None], before)
                with jax.named_scope("conv_state"):
                    state = state.at[ci, slots].set(
                        run[:, 1:].astype(state.dtype))
                op = op[:, 0]
                ci += 1
            else:
                q, k, v = self._qkv(params, p, u, pos)
                pool = self._scatter_kv(pool, ai, blk, off, k, v)
                attn = paged_decode_attention(q, pool, ai, tables, pos,
                                              self.num_heads,
                                              kv_heads=self.kv_heads)
                op = self._attn_out(params, p, attn)
                ai += 1
            x = x + op
            u = rms_norm(x, params[p + "ln2_gamma"], self.eps)
            x = x + self._ffn(params, p, i, u, valid, moe_tape)
        return self._head(params, x), (pool, state)
