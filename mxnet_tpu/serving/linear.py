"""Prefill/decode programs of the OLMo hybrid block: gated delta-rule linear
attention layers (Gated DeltaNet) beside full multi-head attention layers,
interleaved by `layer_types`, each sublayer normed after it (OLMo 3's
reordered norm), a SwiGLU in every layer, RMSNorm, no biases, an untied head.

`HybridDeltaKVModel` stands beside `shortconv.ShortConvMoEKVModel` and
`window.HybridWindowMoEKVModel` behind the protocol `ServingEngine` calls.
The block, for a row ``x``:

    h = x + n_op(Op_i(x));   y = h + n_ffn(SwiGLU(h))

* ``Op`` of a "linear_attention" layer: ``[q, k, v] = SiLU(conv(x [W_q, W_k,
  W_v]^T))``, a depthwise causal convolution of ``conv_kernel`` taps over
  the three projections' channels (zeros before the sequence starts); per
  head ``q`` and ``k`` scaled to unit length; ``beta = 2 sigmoid(x W_b^T)``
  (in (0, 2): the write may reflect, a negative eigenvalue); ``g =
  -exp(a_log) softplus(x W_a^T + dt_bias)``; the gated delta rule over a
  ``(key_head_dim, value_head_dim)`` float32 state a head
  (`ops.delta_rule`); ``Op(x) = (n_head(o) * SiLU(x W_g^T)) W_o^T`` with
  ``n_head`` an RMSNorm over each head's ``value_head_dim`` with one gain
  the heads share.
* ``Op`` of a "full_attention" layer: ``q = n_q(x W_q^T)`` and ``k = n_k(x
  W_k^T)``, RMSNorms over the whole projection; ``v = x W_v^T``; causal
  softmax at ``1/sqrt(head_dim)``, query head ``h`` reading K/V head ``h //
  (heads / kv_heads)``; no rotary positions; ``W_o``.

The equations are written out in `benchmark/reference/olmo_hybrid.py`, the
plain float32 reference the tests and the benchmark hold these programs to.

**Two kinds of state** (`cache_kind` "kv_pair_state"; docs/serving.md "A
third kind of state").  The full layers' K and V live in a paged pool
``(full layers, 2, n_blocks, block_size, kv_heads * head_dim)``, block 0 the
trash block, K after its norm: `TransformerKVModel`'s layout over the full
layers alone.  A linear layer keeps, a sequence, whatever its length, the
last ``conv_kernel - 1`` rows of its projections (the convolution's tail)
and the delta rule's state ``S``: the pair ``(conv tails (linear layers,
slots * (conv_kernel - 1), conv channels), S (linear layers, slots, heads *
key_head_dim, lanes) float32)``.  ``lanes`` is ``value_head_dim`` rounded
up to whole 128-lane tiles (256 for heads of 192), the lanes past it zero:
the TPU lays a row out at whole tiles anyway, and at 192 declared lanes its
compiler copies the whole state to move a slot.  A decode step steps each
row's slot of ``S`` in place (`ops.delta_rule.gated_delta_slots`: on a TPU
the kernel `delta_decode_step`); a chunk reads its row's slot as a slice and
writes it back over itself; the conv tails move as whole rows.  Which the engine makes once
(`init_state`: a slot a batch row and a spare one for padding rows) and
hands to both programs beside the pool, as ``(pool, state)``, with each
row's slot index (``slots``).  Both programs donate it and write each row's
slot in place.  A chunk that starts at position 0 reads zeros in its slot's
place, whoever held the slot before.  A chunk's positions past the row's
length write with ``beta = 0`` and ``g = 0``, which leave ``S`` as the last
real position left it, and the tails kept are those before the row's
length: a short last chunk's padding leaves the state untouched.

What does not know the state is refused by name at construction
(`unsupported`), as `ShortConvMoEKVModel` refuses it.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..ops import moe
from ..ops.attention import (paged_chunk_attention,
                             paged_chunk_kernel_applies, paged_chunk_stream,
                             paged_decode_attention)
from ..ops.delta_rule import (chunk_gated_delta, gated_delta_slots,
                              l2_normalize)
from ..ops.lm_parts import proj, rms_norm
from .decode import TransformerKVModel
from .shortconv import ShortConvMoEKVModel
from .window import HybridWindowMoEKVModel

_KINDS = ("linear_attention", "full_attention")


class HybridDeltaKVModel:
    """Program builder for one geometry of the block above.

    ``layer_types`` names each layer's operator; ``seq_len`` is the deepest
    context this engine's tables reach (a limit of the instance, not of the
    model); ``chunk_size`` is the delta rule's sub-chunk in prefill."""

    cache_kind = "kv_pair_state"
    #: engine options that do not know the per-sequence state yet (the
    #: engine refuses each at construction; block runs, and so the
    #: handoff, refuse the cache kind: `tiers.check_cache_kind`)
    unsupported = frozenset({"prefix", "quant", "kv_quant", "megastep",
                             "spec", "tier", "mesh"})

    def __init__(self, vocab_size, seq_len, layer_types, hidden_size,
                 num_heads, num_kv_heads, linear_heads, key_head_dim,
                 value_head_dim, conv_kernel, intermediate_size,
                 chunk_size=64, eps=1e-6, dtype=np.float32):
        layer_types = tuple(layer_types)
        bad = sorted(set(layer_types) - set(_KINDS))
        if bad or "full_attention" not in layer_types:
            raise MXNetError("HybridDeltaKVModel: layer_types must be of %s "
                             "with a full layer among them, got %s"
                             % (_KINDS, bad or layer_types))
        if hidden_size % num_heads or num_heads % num_kv_heads:
            raise MXNetError("HybridDeltaKVModel: hidden %d, %d heads and %d "
                             "K/V heads do not divide"
                             % (hidden_size, num_heads, num_kv_heads))
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self.layer_types = layer_types
        self.num_layers = len(layer_types)
        self.hidden = int(hidden_size)
        self.num_heads, self.kv_heads = int(num_heads), int(num_kv_heads)
        self.head_dim = self.hidden // self.num_heads
        self.linear_heads = int(linear_heads)
        self.key_dim, self.value_dim = int(key_head_dim), int(value_head_dim)
        self.conv_kernel = int(conv_kernel)
        self.ffn = int(intermediate_size)
        self.chunk = int(chunk_size)
        self.eps = float(eps)
        self.dtype = np.dtype(dtype)
        # a row of S at whole 128-lane tiles
        self.state_lanes = -(-self.value_dim // 128) * 128
        self.full_layers = layer_types.count("full_attention")
        self.linear_layers = layer_types.count("linear_attention")
        self.quant = self.kv_quant = None

    @property
    def kv_width(self):
        """A cached K (or V) row of a full layer: its K/V heads side by
        side."""
        return self.kv_heads * self.head_dim

    @property
    def conv_channels(self):
        """The channels a linear layer's convolution runs over: q's, k's and
        v's, in that order."""
        h = self.linear_heads
        return h * (2 * self.key_dim + self.value_dim)

    # -- parameters --------------------------------------------------------
    def param_shapes(self):
        d, f = self.hidden, self.ffn
        h, dk, dv = self.linear_heads, self.key_dim, self.value_dim
        shapes = {"embed_weight": (self.vocab_size, d),
                  "final_ln_gamma": (d,),
                  "pred_weight": (self.vocab_size, d)}
        for i, kind in enumerate(self.layer_types):
            p = "layer%d_" % i
            if kind == "linear_attention":
                shapes.update({
                    p + "q_weight": (h * dk, d), p + "k_weight": (h * dk, d),
                    p + "v_weight": (h * dv, d),
                    # the taps, oldest first: (channel, tap)
                    p + "conv_weight": (self.conv_channels,
                                        self.conv_kernel),
                    p + "a_weight": (h, d), p + "b_weight": (h, d),
                    p + "a_log": (h,), p + "dt_bias": (h,),
                    p + "gate_weight": (h * dv, d),
                    p + "o_norm_gamma": (dv,),
                    p + "attn_out_weight": (d, h * dv)})
            else:
                e, kv = self.num_heads * self.head_dim, self.kv_width
                shapes.update({
                    p + "q_weight": (e, d), p + "k_weight": (kv, d),
                    p + "v_weight": (kv, d), p + "q_norm_gamma": (e,),
                    p + "k_norm_gamma": (kv,),
                    p + "attn_out_weight": (d, e)})
            shapes.update({p + "post_op_ln_gamma": (d,),
                           p + "ffn_gate_weight": (f, d),
                           p + "ffn_up_weight": (f, d),
                           p + "ffn_down_weight": (d, f),
                           p + "post_ffn_ln_gamma": (d,)})
        return shapes

    def init_params(self, rng=None, scale=0.02):
        """Random parameters (tests): gains near 1, all else N(0, scale),
        but the decays' as the published models start them: ``A = exp(a_log)``
        uniform in [1, 16] and ``dt_bias`` the inverse softplus of a ``dt``
        log-uniform in [0.001, 0.1]."""
        rng = rng or np.random.RandomState(0)
        out = {}
        for name, shape in self.param_shapes().items():
            if name.endswith("a_log"):
                w = np.log(rng.uniform(1.0, 16.0, shape))
            elif name.endswith("dt_bias"):
                dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
                w = dt + np.log(-np.expm1(-dt))
            else:
                w = (1.0 if name.endswith("_gamma") else 0.0) \
                    + rng.randn(*shape) * scale
            out[name] = w.astype(self.dtype)
        return out

    def check_params(self, params):
        missing = [n for n in self.param_shapes() if n not in params]
        if missing:
            raise MXNetError("HybridDeltaKVModel: params missing %s"
                             % missing)

    # the engine has refused quantisation by name before it asks
    with_quant = ShortConvMoEKVModel.with_quant

    # -- the pool and the state --------------------------------------------
    cache_lost = staticmethod(TransformerKVModel.cache_lost)
    _zeros = ShortConvMoEKVModel._zeros

    def block_bytes(self, block_size, shards=1):
        """Device bytes of one block of the pool: K and V of every FULL
        layer (a linear layer caches nothing a token)."""
        if shards != 1:
            raise MXNetError("HybridDeltaKVModel: the pool is not sharded "
                             "over a mesh yet")
        return self.full_layers * 2 * int(block_size) * self.kv_width \
            * self.dtype.itemsize

    def state_slot_bytes(self):
        """Device bytes of one sequence's state: in every linear layer, the
        convolution's tail in the model's dtype and ``S`` in float32 at
        whole lane tiles (`state_lanes`)."""
        tail = (self.conv_kernel - 1) * self.conv_channels \
            * self.dtype.itemsize
        s = self.linear_heads * self.key_dim * self.state_lanes * 4
        return self.linear_layers * (tail + s)

    def init_block_pool(self, n_blocks, block_size, device=None):
        """Zeroed K/V pool ``(full_layers, 2, n_blocks, block_size,
        kv_width)``, made on the device (also the rebuild's allocation)."""
        return self._zeros((self.full_layers, 2, int(n_blocks),
                            int(block_size), self.kv_width), device)

    def init_state(self, n_slots, device=None):
        """The zeroed pair of conv tails ``(linear_layers, n_slots *
        (conv_kernel - 1), conv_channels)`` (slot ``i`` the ``i``-th run of
        rows) and states ``(linear_layers, n_slots, heads * key_head_dim,
        state_lanes)`` float32; the engine asks for a slot a batch row and
        one more."""
        n = int(n_slots)
        s = (self.linear_layers, n, self.linear_heads * self.key_dim,
             self.state_lanes)
        if device is None:
            ssm = jnp.zeros(s, jnp.float32)
        else:
            ssm = jax.jit(lambda: jnp.zeros(s, jnp.float32),
                          out_shardings=jax.sharding.SingleDeviceSharding(
                              device))()
        return (self._zeros((self.linear_layers, n * (self.conv_kernel - 1),
                             self.conv_channels), device), ssm)

    # whether the full layers attend with the Pallas kernels: the pool is
    # LFM2's layout over the full layers
    paged_decode_kernel = ShortConvMoEKVModel.paged_decode_kernel
    paged_prefill_kernel = ShortConvMoEKVModel.paged_prefill_kernel

    # -- the block's parts -------------------------------------------------
    _embed = ShortConvMoEKVModel._embed
    _head = HybridWindowMoEKVModel._head      # the untied head

    def _ffn(self, params, p, x):
        with jax.named_scope("ffn"):
            y = moe.swiglu(x, params[p + "ffn_gate_weight"],
                           params[p + "ffn_up_weight"],
                           params[p + "ffn_down_weight"])
        return x + rms_norm(y, params[p + "post_ffn_ln_gamma"], self.eps)

    def _taps(self, params, p, run, c):
        """SiLU of the depthwise causal convolution of ``run`` (b, L - 1 +
        c, channels) at its last ``c`` rows, float32."""
        w = params[p + "conv_weight"].astype(jnp.float32)
        return jax.nn.silu(sum(run[:, j:j + c].astype(jnp.float32) * w[:, j]
                               for j in range(self.conv_kernel)))

    def _gate(self, params, p, x):
        """The output gate ``SiLU(x W_g^T)``, float32."""
        return jax.nn.silu(proj(x, params[p + "gate_weight"]).astype(
            jnp.float32))

    def _rule_inputs(self, params, p, x, before, valid):
        """What a linear layer hands the delta rule for rows ``x`` (b, c, d)
        that follow the conv tail ``before`` (b, L - 1, channels); ``valid``
        (b, c) marks the real positions.  Returns (q, k (b, c, heads, dk)
        of unit length, v (b, c, heads, state_lanes), g, beta (b, c,
        heads), ``before`` and the rows' projections in one run (b, L - 1 +
        c, channels)): past a row's length, g and beta are 0, positions that
        leave the state alone."""
        b, c, _ = x.shape
        h, dk, dv = self.linear_heads, self.key_dim, self.value_dim
        f32 = jnp.float32
        with jax.named_scope("qkv_proj"):
            mixed = jnp.concatenate([proj(x, params[p + n + "_weight"])
                                     for n in ("q", "k", "v")], axis=-1)
            # the decay and the write strength from float32 sums
            a = jnp.dot(x, params[p + "a_weight"].T,
                        preferred_element_type=f32)
            beta = 2.0 * jax.nn.sigmoid(jnp.dot(
                x, params[p + "b_weight"].T, preferred_element_type=f32))
        with jax.named_scope("short_conv"):
            run = jnp.concatenate([before.astype(mixed.dtype), mixed], axis=1)
            y = self._taps(params, p, run, c)
        q = l2_normalize(y[..., :h * dk].reshape(b, c, h, dk))
        k = l2_normalize(y[..., h * dk:2 * h * dk].reshape(b, c, h, dk))
        # v, and so the state's rows, at whole lane tiles (`init_state`):
        # the lanes past dv write zeros
        v = jnp.pad(y[..., 2 * h * dk:].reshape(b, c, h, dv),
                    ((0, 0),) * 3 + ((0, self.state_lanes - dv),))
        g = -jnp.exp(params[p + "a_log"].astype(f32)) * jax.nn.softplus(
            a + params[p + "dt_bias"].astype(f32))
        beta = jnp.where(valid[..., None], beta, 0.0)
        g = jnp.where(valid[..., None], g, 0.0)
        return q, k, v, g, beta, run

    def _rule_out(self, params, p, x, o):
        """A linear layer's ``Op`` from the rule's output ``o`` (b, c,
        heads, state_lanes): the head norm, the gate, ``W_o``."""
        b, c, _ = x.shape
        with jax.named_scope("attn_out"):
            o = rms_norm(o[..., :self.value_dim], params[p + "o_norm_gamma"],
                         self.eps)
            o = (o.reshape(b, c, -1) * self._gate(params, p, x)).astype(
                x.dtype)
            return proj(o, params[p + "attn_out_weight"])

    def _full_qkv(self, params, p, u):
        """q (n, heads * hd), k and v (n, kv_width) of rows ``u`` (n, d): k
        as it is cached, after its norm."""
        with jax.named_scope("qkv_proj"):
            q = proj(u, params[p + "q_weight"])
            k = proj(u, params[p + "k_weight"])
            v = proj(u, params[p + "v_weight"])
        with jax.named_scope("qk_norm"):
            q, k = self._qk_norm(params, p, q, k)
        return q, k, v

    def _qk_norm(self, params, p, q, k):
        """OLMo's norms over the whole q and k projections."""
        return (rms_norm(q, params[p + "q_norm_gamma"], self.eps),
                rms_norm(k, params[p + "k_norm_gamma"], self.eps))

    _scatter_kv = ShortConvMoEKVModel._scatter_kv

    @staticmethod
    def _conv_rows(conv, layer, slots, rows):
        """The ``rows`` conv rows of each of ``slots`` in layer ``layer`` of
        ``conv`` (layers, slots * rows, channels), (b, rows, channels), and
        their indices: one gather of whole rows."""
        idx = (slots[:, None] * rows
               + jnp.arange(rows, dtype=jnp.int32)[None]).reshape(-1)
        return conv[layer, idx].reshape(slots.shape[0], rows, -1), idx

    @staticmethod
    def _set_conv_rows(conv, layer, idx, new):
        """``conv`` with the rows ``idx`` of layer ``layer`` set to ``new``
        (b, rows, channels), in place."""
        return conv.at[layer, idx].set(
            new.reshape(idx.shape[0], -1).astype(conv.dtype))

    def _slot_state(self, ssm, layer, slot):
        """One slot's ``S`` of layer ``layer``, (heads, dk, state_lanes):
        a slice of ``ssm``, read in place."""
        zero = jnp.int32(0)
        s = jax.lax.dynamic_slice(ssm, (jnp.int32(layer), slot, zero, zero),
                                  (1, 1) + ssm.shape[2:])
        return s.reshape(self.linear_heads, self.key_dim, -1)

    @staticmethod
    def _set_slot_state(ssm, layer, slot, s):
        """``ssm`` with one slot of layer ``layer`` set to ``s``, in
        place."""
        zero = jnp.int32(0)
        return jax.lax.dynamic_update_slice(
            ssm, s.reshape((1, 1) + ssm.shape[2:]),
            (jnp.int32(layer), slot, zero, zero))

    # -- the engine's two programs -----------------------------------------
    def prefill_paged(self, params, cache, tokens, start, length, tables,
                      moe_tape=None, slots=None):
        """One chunked-prefill step; the contract is
        `TransformerKVModel.prefill_paged`'s (tokens (b, c), c a multiple of
        the block size; start (b,) block-aligned; length (b,) real tokens in
        this chunk; tables (b, m)) with ``cache`` the pair (pool, state) and
        ``slots`` (b,) each row's slot in the state.  Returns (logits of
        each row's last real token, (pool, state))."""
        pool, (conv, ssm) = cache
        b, c = tokens.shape
        bs, m = pool.shape[3], tables.shape[1]
        start = start.astype(jnp.int32)
        length = length.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        slots = slots.astype(jnp.int32)
        offs = jnp.arange(c, dtype=jnp.int32)[None]
        positions = (start[:, None] + offs).reshape(-1)
        valid = offs < length[:, None]                            # (b, c)
        # where each of the chunk's rows is cached; past the table's width
        # (a short last chunk's padding) rows go to the trash block
        ent = positions.reshape(b, c) // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1), axis=1)
        blk = jnp.where(ent < m, blk, 0).reshape(-1)
        off = positions % bs
        # the conv rows the next chunk, or decode, starts from: the L - 1
        # before position start + length
        taps = self.conv_kernel - 1
        keep = length[:, None, None] + jnp.arange(
            taps, dtype=jnp.int32)[None, :, None]
        fresh = start == 0
        x = self._embed(params, tokens)                       # (b, c, d)
        fi = li = 0
        for i, kind in enumerate(self.layer_types):
            p = "layer%d_" % i
            if kind == "linear_attention":
                with jax.named_scope("linear_state"):
                    # a sequence's first chunk starts from nothing,
                    # whatever the slot's last holder left in it
                    before, at_c = self._conv_rows(conv, li, slots, taps)
                    before = jnp.where(fresh[:, None, None], 0, before)
                    s0 = jnp.where(fresh[:, None, None, None], 0.0,
                                   jnp.stack([self._slot_state(ssm, li,
                                                               slots[r])
                                              for r in range(b)]))
                q, k, v, g, beta, run = self._rule_inputs(params, p, x,
                                                          before, valid)
                o, s1 = chunk_gated_delta(q, k, v, g, beta, s0,
                                          chunk=self.chunk)
                op = self._rule_out(params, p, x, o)
                with jax.named_scope("linear_state"):
                    conv = self._set_conv_rows(
                        conv, li, at_c,
                        jnp.take_along_axis(run, keep, axis=1))
                    for r in range(b):
                        ssm = self._set_slot_state(ssm, li, slots[r], s1[r])
                li += 1
            else:
                q, k, v = self._full_qkv(params, p, x.reshape(b * c, -1))
                pool = self._scatter_kv(pool, fi, blk, off, k, v)
                q = q.reshape(b, c, -1)
                if paged_chunk_kernel_applies(pool, self.num_heads,
                                              self.kv_heads, c, m):
                    attn = paged_chunk_attention(
                        q, pool, fi, tables, start, self.num_heads,
                        kv_heads=self.kv_heads)
                else:
                    # a table too deep for the kernel's VMEM: walked a
                    # step at a time, never gathered whole
                    attn = paged_chunk_stream(
                        q, pool, fi, tables, start, self.num_heads,
                        kv_heads=self.kv_heads)
                with jax.named_scope("attn_out"):
                    op = proj(attn, params[p + "attn_out_weight"])
                fi += 1
            x = x + rms_norm(op, params[p + "post_op_ln_gamma"], self.eps)
            x = self._ffn(params, p, x)
        last = jnp.take_along_axis(x, (length - 1)[:, None, None],
                                   axis=1)[:, 0]
        return self._head(params, last), (pool, (conv, ssm))

    def decode_paged(self, params, cache, token, pos, tables, moe_tape=None,
                     slots=None):
        """One generation step (the contract is
        `TransformerKVModel.decode_paged`'s: padding rows are all-trash with
        pos 0; theirs is the spare slot).  Returns (logits (b, vocab),
        (pool, state))."""
        pool, (conv, ssm) = cache
        b = token.shape[0]
        bs, m = pool.shape[3], tables.shape[1]
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        slots = slots.astype(jnp.int32)
        ent = pos // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1)[:, None],
                                  axis=1)[:, 0]
        blk = jnp.where(ent < m, blk, 0)
        off = pos % bs
        x = self._embed(params, token)[:, None]               # (b, 1, d)
        every = jnp.ones((b, 1), bool)
        fi = li = 0
        for i, kind in enumerate(self.layer_types):
            p = "layer%d_" % i
            if kind == "linear_attention":
                with jax.named_scope("linear_state"):
                    # the rows read before the layer writes them: read
                    # after the scatter, the tails would be copied whole
                    before, at_c = self._conv_rows(conv, li, slots,
                                                   self.conv_kernel - 1)
                q, k, v, g, beta, run = self._rule_inputs(params, p, x,
                                                          before, every)
                # each row's slot stepped in place
                o, ssm = gated_delta_slots(q[:, 0], k[:, 0], v[:, 0],
                                           g[:, 0], beta[:, 0], ssm, li,
                                           slots)
                op = self._rule_out(params, p, x, o[:, None])
                with jax.named_scope("linear_state"):
                    conv = self._set_conv_rows(conv, li, at_c, run[:, 1:])
                li += 1
            else:
                q, k, v = self._full_qkv(params, p, x[:, 0])
                pool = self._scatter_kv(pool, fi, blk, off, k, v)
                attn = paged_decode_attention(q, pool, fi, tables, pos,
                                              self.num_heads,
                                              kv_heads=self.kv_heads)
                with jax.named_scope("attn_out"):
                    op = proj(attn, params[p + "attn_out_weight"])[:, None]
                fi += 1
            x = x + rms_norm(op, params[p + "post_op_ln_gamma"], self.eps)
            x = self._ffn(params, p, x)
        return self._head(params, x[:, 0]), (pool, (conv, ssm))
