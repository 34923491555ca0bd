"""Prefill/decode programs of the DeepSeek-V3 block (Kimi-K2 is one): latent
(MLA) attention over a paged latent cache and a sparse expert layer of which
this chip holds a share.

`LatentMoEKVModel` stands beside `decode.TransformerKVModel` behind the
protocol `ServingEngine` calls (`prefill_paged`, `decode_paged`, the pool
methods, `check_params`, ...), so the engine's default path serves it
unchanged: paged pool, chunked prefill, buckets, the in-graph sampler.  The
block, for a row ``x`` (RMSNorm, no biases anywhere):

    h = x + MLA(norm(x));   y = h + FFN(norm(h))

FFN is a SwiGLU in the first ``first_dense`` layers and `ops.moe`'s expert
layer after them; `ops.latent_attention` has the attention's two forms and
the rotary positions.  The equations are written out in
`benchmark/reference/kimi_k2.py`, the plain float32 reference the tests and
the benchmark hold these programs to.

**The second cache kind** (`cache_kind` "latent").  A block holds one row a
token, ``c_kv`` after its norm beside ``k_pe`` after RoPE, shared by all
heads: the pool is ``(layers, n_blocks, block_size, width)`` with no K/V axis,
block 0 the trash block; ``width`` is ``kv_lora_rank + qk_rope_head_dim``
rounded up to whole 128-lane tiles (576 -> 640: the TPU lays the last axis
out in tiles of 128 either way, and the decode kernel's copies must be whole
tiles), the spare lanes zero.  The engine reaches it only through this
class's methods; `block_bytes` answers for its size.  What does not know the layout yet refuses it by name: int8 KV,
megastep, speculation (`verify_paged`), the host tier, handoff,
a sharded mesh (`unsupported`; the engine raises at construction).

**The share** (`experts_held`).  The router scores all ``n_routed_experts``
and takes ``top_k``; this chip adds the terms of the experts ``lo <= e < hi``
it holds, and the shared expert, and what the others would add is left out
(docs/serving.md).  ``vocab_size`` is the chip's slice of the vocabulary.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..ops import moe
from ..ops.lm_parts import proj as _proj, rms_norm, rope
from ..ops.latent_attention import (latent_decode_attention,
                                    latent_decode_kernel_applies,
                                    latent_prefill_attention,
                                    latent_prefill_kernel_applies,
                                    rope_factor, softmax_scale,
                                    yarn_inv_freq)
from .decode import TransformerKVModel


class LatentMoEKVModel:
    """Program builder for one geometry of the block above.

    ``seq_len`` is the deepest context this engine's tables reach (a limit
    of the instance, not of the model).  ``experts_held`` is the half-open
    range of routed experts whose weights live here; ``vocab_size`` the
    rows of the vocabulary held here.
    """

    cache_kind = "latent"
    #: engine options that do not know the latent pool yet (the engine
    #: refuses each at construction; block runs, and so the handoff, refuse
    #: the cache kind: `tiers.check_cache_kind`)
    unsupported = frozenset({"quant", "kv_quant", "megastep", "spec", "tier",
                             "mesh"})

    def __init__(self, vocab_size, seq_len, num_layers, hidden_size,
                 num_heads, q_lora_rank, kv_lora_rank, qk_nope_head_dim,
                 qk_rope_head_dim, v_head_dim, intermediate_size,
                 moe_intermediate_size, n_routed_experts, experts_held,
                 num_experts_per_tok, first_dense=1,
                 routed_scaling_factor=1.0, eps=1e-5, rope_theta=10000.0,
                 rope_scaling=None, dtype=np.float32):
        lo, hi = (int(e) for e in experts_held)
        if not 0 <= lo < hi <= n_routed_experts:
            raise MXNetError("LatentMoEKVModel: experts_held %r is not a "
                             "range of the %d routed experts"
                             % (experts_held, n_routed_experts))
        if qk_rope_head_dim % 2:
            raise MXNetError("LatentMoEKVModel: qk_rope_head_dim must be "
                             "even")
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self.num_layers = int(num_layers)
        self.hidden = int(hidden_size)
        self.num_heads = int(num_heads)
        self.q_rank, self.kv_rank = int(q_lora_rank), int(kv_lora_rank)
        self.nope, self.rope_dim = int(qk_nope_head_dim), \
            int(qk_rope_head_dim)
        self.v_dim = int(v_head_dim)
        self.dense_ffn = int(intermediate_size)
        self.expert_ffn = int(moe_intermediate_size)
        self.n_routed = int(n_routed_experts)
        self.experts_held = (lo, hi)
        self.top_k = int(num_experts_per_tok)
        self.first_dense = int(first_dense)
        self.routed_scale = float(routed_scaling_factor)
        self.eps = float(eps)
        self.dtype = np.dtype(dtype)
        self.inv_freq = yarn_inv_freq(self.rope_dim, float(rope_theta),
                                      rope_scaling)
        self.rope_factor = rope_factor(rope_scaling)
        self.scale = softmax_scale(self.nope + self.rope_dim, rope_scaling)
        # the engine's MoE seam: one count a held expert a launch
        self.moe_experts = hi - lo
        self.quant = self.kv_quant = None

    @property
    def latent_width(self):
        return self.kv_rank + self.rope_dim

    @property
    def pool_width(self):
        """The pool's last axis: `latent_width` in whole 128-lane tiles."""
        return -(-self.latent_width // 128) * 128

    @property
    def moe_pairs_per_row(self):
        """(row, expert) pairs a real row routes in one launch, over ALL
        the routed experts: ``top_k`` in every expert layer."""
        return self.top_k * (self.num_layers - self.first_dense)

    # -- parameters --------------------------------------------------------
    def param_shapes(self):
        d, h = self.hidden, self.num_heads
        held = self.moe_experts
        shapes = {"embed_weight": (self.vocab_size, d),
                  "final_ln_gamma": (d,),
                  "pred_weight": (self.vocab_size, d)}
        for i in range(self.num_layers):
            p = "layer%d_" % i
            shapes.update({
                p + "ln1_gamma": (d,),
                p + "q_a_weight": (self.q_rank, d),
                p + "q_a_ln_gamma": (self.q_rank,),
                p + "q_b_weight": (h * (self.nope + self.rope_dim),
                                   self.q_rank),
                p + "kv_a_weight": (self.latent_width, d),
                p + "kv_a_ln_gamma": (self.kv_rank,),
                p + "kv_b_weight": (h * (self.nope + self.v_dim),
                                    self.kv_rank),
                p + "attn_out_weight": (d, h * self.v_dim),
                p + "ln2_gamma": (d,)})
            if i < self.first_dense:
                f = self.dense_ffn
                shapes.update({p + "ffn_gate_weight": (f, d),
                               p + "ffn_up_weight": (f, d),
                               p + "ffn_down_weight": (d, f)})
                continue
            f = self.expert_ffn
            shapes.update({
                p + "moe_router_weight": (self.n_routed, d),
                p + "moe_router_bias": (self.n_routed,),
                # the held experts' banks, (in, out) on the last two axes
                p + "moe_gate": (held, d, f), p + "moe_up": (held, d, f),
                p + "moe_down": (held, f, d),
                p + "shared_gate_weight": (f, d),
                p + "shared_up_weight": (f, d),
                p + "shared_down_weight": (d, f)})
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
            raise MXNetError("LatentMoEKVModel: params missing %s" % missing)

    def with_quant(self, quant, kv_quant):
        # the engine has refused either by name before it asks
        return self

    # -- the latent pool ---------------------------------------------------
    cache_lost = staticmethod(TransformerKVModel.cache_lost)

    def block_bytes(self, block_size, shards=1):
        """Device bytes of one block of the pool, every layer."""
        if shards != 1:
            raise MXNetError("LatentMoEKVModel: the latent pool is not "
                             "sharded over a mesh yet")
        return self.num_layers * int(block_size) * self.pool_width \
            * self.dtype.itemsize

    def init_block_pool(self, n_blocks, block_size, device=None):
        """Zeroed latent pool ``(num_layers, n_blocks, block_size,
        pool_width)``, made on the device (also the pool-rebuild
        allocation)."""
        shape = (self.num_layers, int(n_blocks), int(block_size),
                 self.pool_width)
        if device is None:
            return jnp.zeros(shape, self.dtype)
        return jax.jit(lambda: jnp.zeros(shape, self.dtype),
                       out_shardings=jax.sharding.SingleDeviceSharding(
                           device))()

    def block_run_placeholder(self, k, block_size):
        return np.zeros((self.num_layers, int(k), int(block_size),
                         self.pool_width), self.dtype)

    def slice_block(self, cache, block):
        return cache[:, block]

    def copy_block(self, pool, src, dst):
        """Copy one block's rows, every layer, from ``src`` to ``dst`` (both
        (1,) int32): the copy-on-write body."""
        return pool.at[:, dst.astype(jnp.int32)].set(
            pool[:, src.astype(jnp.int32)])

    def write_block(self, pool, dst, data):
        return pool.at[:, dst.astype(jnp.int32)].set(data.astype(pool.dtype))

    def paged_decode_kernel(self, cache):
        """Whether `decode_paged` over ``cache``, traced here, attends with
        the Pallas kernel `latent_decode_attn`."""
        return latent_decode_kernel_applies(cache, self.kv_rank)

    def paged_prefill_kernel(self, cache, c):
        """Whether `prefill_paged` of a chunk of ``c`` tokens over
        ``cache``, traced here, attends with the Pallas kernel
        `latent_prefill_attn`."""
        return latent_prefill_kernel_applies(cache, self.kv_rank, c)

    # -- the block's parts -------------------------------------------------
    @jax.named_scope("embed")
    def _embed(self, params, tokens):
        return jnp.take(params["embed_weight"], tokens.astype(jnp.int32),
                        axis=0)

    @jax.named_scope("lm_head")
    def _head(self, params, x):
        return _proj(rms_norm(x, params["final_ln_gamma"], self.eps),
                     params["pred_weight"])

    @jax.named_scope("mla_q_proj")
    def _queries(self, params, p, u, positions):
        """(q_nope (n, heads, nope), rotated q_pe (n, heads, rope))."""
        c_q = rms_norm(_proj(u, params[p + "q_a_weight"]),
                       params[p + "q_a_ln_gamma"], self.eps)
        q = _proj(c_q, params[p + "q_b_weight"]).reshape(
            u.shape[0], self.num_heads, self.nope + self.rope_dim)
        q_pe = rope(q[..., self.nope:], positions[:, None], self.inv_freq,
                    self.rope_factor)
        return q[..., :self.nope], q_pe

    @jax.named_scope("mla_kv_proj")
    def _latent(self, params, p, u, positions):
        """The rows to cache, (n, pool_width): normed ``c_kv``, rotated
        ``k_pe`` beside it, zeros in the spare lanes."""
        kv = _proj(u, params[p + "kv_a_weight"])
        c_kv = rms_norm(kv[:, :self.kv_rank], params[p + "kv_a_ln_gamma"],
                        self.eps)
        k_pe = rope(kv[:, self.kv_rank:], positions, self.inv_freq,
                    self.rope_factor)
        return self._to_pool_width(jnp.concatenate([c_kv, k_pe], axis=-1))

    def _to_pool_width(self, rows):
        spare = self.pool_width - self.latent_width
        return jnp.pad(rows, ((0, 0),) * (rows.ndim - 1) + ((0, spare),))

    def _kv_b(self, params, p):
        """``W_kvb`` as (heads, nope + v, kv_rank)."""
        return params[p + "kv_b_weight"].reshape(
            self.num_heads, self.nope + self.v_dim, self.kv_rank)

    @jax.named_scope("attn_out")
    def _attn_out(self, params, p, attn):
        return _proj(attn, params[p + "attn_out_weight"])

    def _ffn(self, params, p, i, u, valid, tape):
        if i < self.first_dense:
            with jax.named_scope("ffn"):
                return moe.swiglu(u, params[p + "ffn_gate_weight"],
                                  params[p + "ffn_up_weight"],
                                  params[p + "ffn_down_weight"])
        y, counts = moe.expert_layer(
            u, params[p + "moe_router_weight"], params[p + "moe_router_bias"],
            tuple(params[p + "moe_" + n] for n in ("gate", "up", "down")),
            tuple(params[p + "shared_%s_weight" % n]
                  for n in ("gate", "up", "down")),
            top_k=self.top_k, scale=self.routed_scale,
            experts_held=self.experts_held, valid=valid)
        if tape is not None:
            tape.append(counts)
        return y

    # -- the engine's two programs -----------------------------------------
    def prefill_paged(self, params, pool, tokens, start, length, tables,
                      moe_tape=None):
        """One chunked-prefill step over the latent pool; the contract is
        `TransformerKVModel.prefill_paged`'s (tokens (b, c), c a multiple of
        the block size; start (b,) block-aligned; length (b,) real tokens in
        this chunk; tables (b, m)).  Returns (logits of each row's last real
        token, pool)."""
        b, c = tokens.shape
        bs, m = pool.shape[2], tables.shape[1]
        start = start.astype(jnp.int32)
        length = length.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        offs = jnp.arange(c, dtype=jnp.int32)[None]
        positions = (start[:, None] + offs).reshape(-1)
        valid = (offs < length[:, None]).reshape(-1)
        # where each of the chunk's rows is cached: (block, offset) by its
        # position, as `decode_paged` addresses its one row.  (A whole-block
        # scatter of a one-block chunk is a dynamic-update-slice to the TPU
        # compiler, which then lays the WHOLE pool out to suit the update
        # and copies it: 7 GB of temporaries at 8 layers.)  Past the table's
        # width (a short last chunk's padding) rows go to the trash block
        ent = positions.reshape(b, c) // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1), axis=1)
        blk = jnp.where(ent < m, blk, 0).reshape(-1)
        off = positions % bs
        x = self._embed(params, tokens).reshape(b * c, self.hidden)
        for i in range(self.num_layers):
            p = "layer%d_" % i
            u = rms_norm(x, params[p + "ln1_gamma"], self.eps)
            q_nope, q_pe = self._queries(params, p, u, positions)
            lat = self._latent(params, p, u, positions)
            with jax.named_scope("latent_scatter"):
                pool = pool.at[i, blk, off].set(lat.astype(pool.dtype))
            attn = latent_prefill_attention(
                q_nope.reshape(b, c, self.num_heads, -1),
                q_pe.reshape(b, c, self.num_heads, -1), pool, i, tables,
                start, params[p + "kv_b_weight"], rank=self.kv_rank,
                v_dim=self.v_dim, scale=self.scale)
            x = x + self._attn_out(params, p, attn.reshape(b * c, -1))
            u = rms_norm(x, params[p + "ln2_gamma"], self.eps)
            x = x + self._ffn(params, p, i, u, valid, moe_tape)
        last = jnp.take_along_axis(x.reshape(b, c, -1),
                                   (length - 1)[:, None, None], axis=1)[:, 0]
        return self._head(params, last), pool

    def decode_paged(self, params, pool, token, pos, tables, moe_tape=None):
        """One generation step over the latent pool, absorbed (the contract
        is `TransformerKVModel.decode_paged`'s: padding rows are all-trash
        with pos 0).  Returns (logits (b, vocab), pool)."""
        bs, m = pool.shape[2], tables.shape[1]
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        ent = pos // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1)[:, None],
                                  axis=1)[:, 0]
        blk = jnp.where(ent < m, blk, 0)
        off = pos % bs
        # a real row's first block is never the trash block
        valid = tables[:, 0] != 0
        x = self._embed(params, token)
        for i in range(self.num_layers):
            p = "layer%d_" % i
            u = rms_norm(x, params[p + "ln1_gamma"], self.eps)
            q_nope, q_pe = self._queries(params, p, u, pos)
            lat = self._latent(params, p, u, pos)
            with jax.named_scope("latent_scatter"):
                pool = pool.at[i, blk, off].set(lat.astype(pool.dtype))
            w = self._kv_b(params, p)
            with jax.named_scope("mla_q_proj"):
                # absorb W_kvb^K: each head's query in the latent space
                # (float32 sums inside the matmul unit, rounded once to
                # the activations' dtype: asking for a float32 result of
                # this batched form is what XLA:CPU cannot run in bf16)
                q_abs = jnp.einsum("bhd,hdr->bhr", q_nope, w[:, :self.nope])
                q_abs = self._to_pool_width(
                    jnp.concatenate([q_abs, q_pe], axis=-1))
            o = latent_decode_attention(q_abs, pool, i, tables, pos,
                                        self.kv_rank, self.scale)
            with jax.named_scope("attn_out"):
                attn = jnp.einsum("bhr,hor->bho", o, w[:, self.nope:])
            x = x + self._attn_out(params, p, attn.reshape(x.shape[0], -1))
            u = rms_norm(x, params[p + "ln2_gamma"], self.eps)
            x = x + self._ffn(params, p, i, u, valid, moe_tape)
        return self._head(params, x), pool
