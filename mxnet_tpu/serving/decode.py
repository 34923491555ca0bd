"""KV-cache prefill/decode functions for `models/transformer.py` graphs.

The training/Predictor path runs the full-sequence graph: every forward
recomputes attention over all S positions.  Autoregressive serving wants
two different programs:

* **prefill** (`prefill_paged`) — one pass over a bucket-sized chunk of
  the (padded) prompt that writes the chunk's per-layer K/V projections
  into a persistent cache and returns the logits of the LAST real token
  (the first sampling decision).  Attention runs `chunk_attention` over
  the cached prefix and the chunk itself: the training causal mask once
  the chunk starts at 0.
* **decode** (`decode_paged`) — one token per sequence per step: reads
  the K/V cache via `ops.attention.paged_decode_attention` (O(S) per
  token instead of the full graph's O(S^2)) and scatter-writes the new
  K/V row in place.

Both are pure functions over a `{name: array}` parameter dict using the
SAME names `get_transformer_lm` mints (embed_weight, pos_embed_weight,
layer<i>_{q,k,v,attn_out,ffn1,ffn2}_weight/_bias, layer<i>_ln{1,2}_gamma/
_beta, final_ln_gamma/_beta, pred_weight/_bias), so a FeedForward
checkpoint serves without conversion and the parity test
(tests/test_serving.py) can bind one set of weights to both programs.

Cache layout: ONE block pool of shape (num_layers, 2, n_blocks,
block_size, embed) (2 = K then V; block 0 is the trash block,
serving/paged.py).  Keeping every layer in a single buffer lets the
engine donate it through each prefill/decode call (in-place update, no
per-step reallocation), and a sequence reaches its rows through its
(b, m) int32 block table, so admit/retire is host-side bookkeeping: no
data moves when a sequence enters or leaves the batch.  Row b attends
to positions 0..pos[b] of its own table.

QUANTIZATION (docs/serving.md "Quantization", mxnet_tpu/quant):

* ``quant`` (weights, ``MXNET_SERVE_QUANT=int8|fp8``) — the matmul
  weights (per-layer projections, the embedding, the pred head) are
  quantized ONCE at load (`quantize_params`: symmetric per-output-
  channel, scales stored under ``<name>_qscale``) and every program
  runs *scaled matmuls*: ``y = (x @ W_q.T) * scale`` — mathematically
  dequantize-then-matmul, but the f32 weight never materializes, so
  HBM streams 1-byte rows into the same f32-accumulating dot.
* ``kv_quant`` (paged KV, ``MXNET_SERVE_KV_QUANT``, int8 by default
  whenever weight quant is on) — the block pool becomes the PAIR
  ``(int8 pool (L, 2, n_blocks, bs, E), f32 scales (L, 2, n_blocks,
  bs))``: quantize-on-write at every scatter (prefill chunks, decode
  rows, verify spans, `copy_block`, `write_block`), dequantize at
  every gather, one scale per cached token row so incremental writes
  never re-scale earlier rows.  Scales are indexed by block, so
  prefix sharing, copy-on-write, host-tier spill and restore all
  carry them beside the data for free.

Both default OFF; a model without quant specs builds byte-identical
programs to PR 13.
"""
from __future__ import annotations

import copy

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..base import MXNetError
from ..ops.attention import (gather_paged_kv, paged_decode_attention,
                             paged_decode_kernel_applies, decode_attention,
                             chunk_attention, paged_chunk_attention,
                             paged_chunk_kernel_applies, verify_attention)
from ..ops.pallas_kernels.layer_norm import layer_norm
from ..quant.codec import quantize, quantize_rows, resolve as quant_resolve


class TransformerKVModel:
    """Prefill/decode program builder for one transformer-LM geometry.

    Mirrors `get_transformer_lm(vocab_size, seq_len, num_layers, num_heads,
    num_embed, num_ffn_hidden, use_bias)` — `seq_len` is the maximum
    context (cache depth S_max).  `attn_layout` does not appear: the
    parameter set is identical for 'bsd'/'bhsd' (only internal reshapes
    differ), so checkpoints from either layout serve here.
    """

    def __init__(self, vocab_size, seq_len, num_layers=2, num_heads=4,
                 num_embed=128, num_ffn_hidden=None, use_bias=True,
                 eps=1e-5, dtype=np.float32, quant=None, kv_quant=None,
                 moe_experts=0):
        if num_embed % num_heads != 0:
            raise MXNetError("num_embed must be divisible by num_heads")
        self.vocab_size = int(vocab_size)
        self.seq_len = int(seq_len)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.num_embed = int(num_embed)
        self.num_ffn_hidden = int(num_ffn_hidden or 4 * num_embed)
        self.use_bias = bool(use_bias)
        # moe_experts > 0 replaces every layer's dense FFN with a top-1
        # routed mixture of expert FFNs (`_ffn`): the Switch-style
        # serving counterpart of parallel/moe.py, dispatched densely
        # (no capacity drops) so parity and batch-invariance hold
        self.moe_experts = int(moe_experts or 0)
        self.eps = float(eps)
        self.dtype = np.dtype(dtype)
        # post-training quantization specs (None = full precision, the
        # PR-13 programs bit for bit); see the module docstring
        self.quant = quant_resolve(quant)
        self.kv_quant = quant_resolve(kv_quant)

    #: what a block of the paged pool holds: a K row and a V row of model
    #: width a token (`serving/latent.py` has the other kind)
    cache_kind = "kv_pair"

    @property
    def moe_pairs_per_row(self):
        """(row, expert) pairs a row routes in one launch: one a layer."""
        return self.num_layers if self.moe_experts else 0

    def block_bytes(self, block_size, shards=1):
        """Device bytes of one block of the paged pool, every layer, K and
        V — the one place that prices the layout `init_block_pool` makes.
        Under KV quantization the int8 rows plus one float32 scale a row;
        ``shards > 1`` gives the PER-DEVICE bytes of a sub-mesh replica
        (embed axis split where the mesh divides it, scales replicated,
        as `kv_shardings` places them)."""
        rows = self.num_layers * 2 * int(block_size)
        embed = self.num_embed // shards \
            if self.num_embed % int(shards) == 0 else self.num_embed
        if self.kv_quant is not None:
            return rows * (embed + 4)
        return rows * embed * self.dtype.itemsize

    def with_quant(self, quant, kv_quant):
        """A shallow copy of this geometry with the given quantization
        specs (the engine's ``MXNET_SERVE_QUANT`` entry point: one model
        object can serve a quantized engine and a full-precision oracle
        side by side — each view builds its own programs)."""
        quant = quant_resolve(quant)
        kv_quant = quant_resolve(kv_quant)
        if quant == self.quant and kv_quant == self.kv_quant:
            return self
        m = copy.copy(self)
        m.quant = quant
        m.kv_quant = kv_quant
        return m

    # -- parameters --------------------------------------------------------
    def param_shapes(self):
        """{name: shape} for every weight the programs read — the subset
        of `get_transformer_lm(...).list_arguments()` that is a parameter
        (everything but data/softmax_label)."""
        e, f, v = self.num_embed, self.num_ffn_hidden, self.vocab_size
        shapes = {
            "embed_weight": (v, e),
            "pos_embed_weight": (1, self.seq_len, e),
            "final_ln_gamma": (e,),
            "final_ln_beta": (e,),
            "pred_weight": (v, e),
        }
        if self.use_bias:
            shapes["pred_bias"] = (v,)
        for i in range(self.num_layers):
            p = "layer%d_" % i
            shapes[p + "ln1_gamma"] = (e,)
            shapes[p + "ln1_beta"] = (e,)
            shapes[p + "ln2_gamma"] = (e,)
            shapes[p + "ln2_beta"] = (e,)
            projs = [("q", (e, e)), ("k", (e, e)), ("v", (e, e)),
                     ("attn_out", (e, e))]
            if not self.moe_experts:
                projs += [("ffn1", (f, e)), ("ffn2", (e, f))]
            for proj, (nh, nin) in projs:
                shapes[p + proj + "_weight"] = (nh, nin)
                if self.use_bias:
                    shapes[p + proj + "_bias"] = (nh,)
            if self.moe_experts:
                # expert banks (biasless, Switch-style): the router is
                # O(e*E); w1/w2 stack every expert's FFN on axis 0 —
                # the axis a sub-mesh replica shards for expert
                # parallelism
                shapes[p + "moe_router_weight"] = (e, self.moe_experts)
                shapes[p + "moe_w1"] = (self.moe_experts, e, f)
                shapes[p + "moe_w2"] = (self.moe_experts, f, e)
        return shapes

    def init_params(self, rng=None, scale=0.02):
        """Random parameter dict (bench/tests; real deployments load a
        checkpoint)."""
        rng = rng or np.random.RandomState(0)
        params = {}
        for name, shape in self.param_shapes().items():
            if name.endswith("_gamma"):
                params[name] = np.ones(shape, self.dtype)
            elif name.endswith(("_beta", "_bias")):
                params[name] = np.zeros(shape, self.dtype)
            else:
                params[name] = (rng.randn(*shape) * scale).astype(self.dtype)
        return params

    def check_params(self, params):
        missing = [n for n in self.param_shapes() if n not in params]
        if missing:
            raise MXNetError(
                "TransformerKVModel: params missing %s" % missing)

    def _quant_weight_names(self):
        """The matmul weights the weight-quant spec applies to: every
        2-D projection (per-channel scales need a channel axis).  The
        tiny 1-D tensors (LN gammas/betas, biases) and the positional
        table stay full precision — they are O(E) bytes and sit on
        addition paths where a scale would buy nothing."""
        names = ["embed_weight", "pred_weight"]
        projs = ("q", "k", "v", "attn_out")
        if not self.moe_experts:
            # the stacked (E, ., .) expert banks stay full precision:
            # the codec's per-output-channel scheme is 2-D, and the MoE
            # serving story is capacity-via-sharding, not weight quant
            projs = projs + ("ffn1", "ffn2")
        for i in range(self.num_layers):
            p = "layer%d_" % i
            names += [p + s + "_weight" for s in projs]
        return names

    def quantize_params(self, params):
        """Quantize the matmul weights once at load: each weight is
        replaced by its int8/fp8 storage under the SAME name, with the
        per-output-channel f32 scales beside it as ``<name>_qscale``
        (the programs pick the scaled-matmul path whenever the scale
        key exists).  Idempotent: an already-quantized dict (the
        respawn path shares device-resident params) passes through."""
        if self.quant is None:
            return params
        if any(k.endswith("_qscale") for k in params):
            return params
        out = dict(params)
        for name in self._quant_weight_names():
            q, scale = quantize(out[name], self.quant, axis=0)
            out[name] = q
            out[name + "_qscale"] = scale
        return out

    # -- sub-mesh sharding rules -------------------------------------------
    def param_shardings(self, mesh, axis="model"):
        """{name: NamedSharding} for a sub-mesh serving replica — the
        serving counterpart of `SPMDTrainer`'s auto-param-sharding
        rules (tensor-parallel projections and head, replicated norms):

        * q/k/v/ffn1 weights column-split ``P(axis, None)`` (biases
          ``P(axis)``) — each shard owns a slice of heads / hidden;
        * attn_out/ffn2 weights row-split ``P(None, axis)`` (biases
          replicated: they add AFTER the cross-shard reduction);
        * embed/pred head vocab-split ``P(axis, None)`` (pred bias
          ``P(axis)``) — the trainer's CE-shard head rule;
        * MoE expert banks ``P(axis, None, None)`` (expert
          parallelism), the router replicated (every shard routes);
        * everything 1-D on the residual path (LN gammas/betas,
          pos_embed) replicated.

        Any dimension the mesh axis doesn't divide falls back to
        replicated for that tensor — the rules never reject a
        geometry, they just shard less of it.  Quantized-weight scale
        vectors (``<name>_qscale``) follow their weight's axis-0
        split (per-OUTPUT-channel scales live on the column axis)."""
        n = int(mesh.shape[axis])
        repl = NamedSharding(mesh, PartitionSpec())

        def ns(*spec):
            return NamedSharding(mesh, PartitionSpec(*spec))

        out = {}
        for name, shape in self.param_shapes().items():
            sh = repl
            if name.endswith(("moe_w1", "moe_w2")):
                if shape[0] % n == 0:
                    sh = ns(axis, None, None)
            elif name.endswith("moe_router_weight"):
                sh = repl
            elif name in ("embed_weight", "pred_weight") or \
                    name.endswith(("q_weight", "k_weight", "v_weight",
                                   "ffn1_weight")):
                if shape[0] % n == 0:
                    sh = ns(axis, None)
            elif name == "pred_bias" or \
                    name.endswith(("q_bias", "k_bias", "v_bias",
                                   "ffn1_bias")):
                if shape[0] % n == 0:
                    sh = ns(axis)
            elif name.endswith(("attn_out_weight", "ffn2_weight")):
                if shape[1] % n == 0:
                    sh = ns(None, axis)
            out[name] = sh
        if self.quant is not None:
            for wname in self._quant_weight_names():
                spec = out[wname].spec
                out[wname + "_qscale"] = \
                    ns(spec[0]) if len(spec) and spec[0] else repl
        return out

    def kv_shardings(self, mesh, axis="model"):
        """(pool, scales) shardings for the sub-mesh replica's KV
        buffers: the paged pool (L, 2, n_blocks, bs, E) splits on the
        trailing embed (head) axis — every shard holds ITS heads' K/V for ALL blocks,
        so block tables, the allocator, the prefix cache and all
        host-side scheduling stay replica-global exactly as on one
        device — while the KV-quant scales (one f32 per token row, no
        embed axis) replicate.  Falls back to fully replicated when
        the mesh axis doesn't divide the embed width."""
        repl = NamedSharding(mesh, PartitionSpec())
        if self.num_embed % int(mesh.shape[axis]):
            return repl, repl
        return (NamedSharding(mesh,
                              PartitionSpec(None, None, None, None, axis)),
                repl)

    # -- shared pieces -----------------------------------------------------
    def _proj(self, params, x, name):
        w = params[name + "_weight"]
        qs = params.get(name + "_weight_qscale")
        if qs is None:
            y = jnp.dot(x, w.T,
                        preferred_element_type=jnp.float32).astype(x.dtype)
        else:
            # scaled matmul: the quantized weight upcasts INSIDE the dot
            # (XLA fuses the convert — HBM reads 1-byte rows) and the
            # per-output-channel scale folds into the f32 product before
            # the downcast: exact dequantize-then-matmul, never a
            # materialized f32 weight
            y = (jnp.dot(x, w.T.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
                 * qs).astype(x.dtype)
        if self.use_bias:
            y = y + params[name + "_bias"]
        return y

    @jax.named_scope("attn_out")
    def _attn_out(self, params, attn, p):
        return self._proj(params, attn, p + "attn_out")

    @jax.named_scope("ffn")
    def _ffn(self, params, h2, p, tape=None):
        """Layer ``p``'s FFN over flattened (n, e) rows: the dense
        gelu(ffn1) @ ffn2 pair, or — when the geometry is MoE
        (``moe_experts > 0``) — a top-1 routed mixture of expert FFNs.

        The MoE dispatch is DENSE: every row runs every expert and a
        one-hot gate keeps the winner's output.  No capacity factor, no
        drops — a row's result is one expert's FFN exactly, independent
        of what the rest of the batch routed, so serving stays
        batch-invariant and an expert-sharded mesh replica matches the
        replicated oracle token for token (each row's sum is one
        nonzero term plus exact zeros).  Under GSPMD the (E, ., .)
        expert banks shard on axis 0, making both einsums
        expert-parallel with no shard_map and no program change.

        ``tape`` (a list or None) collects this layer's per-expert
        routed row counts — (E,) int32, padding rows included — for
        the engine's ``serve.<name>.expert_load`` gauges.
        """
        if not self.moe_experts:
            f = jax.nn.gelu(self._proj(params, h2, p + "ffn1"))
            return self._proj(params, f, p + "ffn2")
        probs = jax.nn.softmax(
            jnp.dot(h2.astype(jnp.float32),
                    params[p + "moe_router_weight"].astype(jnp.float32)),
            axis=-1)                                        # (n, E) f32
        gate = jnp.max(probs, axis=-1)                      # (n,)
        onehot = jax.nn.one_hot(jnp.argmax(probs, axis=-1),
                                self.moe_experts, dtype=jnp.float32)
        if tape is not None:
            tape.append(jnp.sum(onehot, axis=0).astype(jnp.int32))
        hb = jax.nn.gelu(jnp.einsum(
            "nd,edf->nef", h2.astype(jnp.float32),
            params[p + "moe_w1"].astype(jnp.float32)))      # (n, E, f)
        y = jnp.einsum("nef,efd->ned", hb,
                       params[p + "moe_w2"].astype(jnp.float32))
        return jnp.einsum("ned,ne->nd", y,
                          onehot * gate[:, None]).astype(h2.dtype)

    @jax.named_scope("embed")
    def _embed(self, params, tokens):
        """Token embedding lookup — under weight quant the gathered int8
        rows dequantize by their per-row (per-vocab-entry) scale, so the
        (V, E) table, the largest weight after the head, also stores
        1-byte entries."""
        ids = tokens.astype(jnp.int32)
        x = jnp.take(params["embed_weight"], ids, axis=0)
        qs = params.get("embed_weight_qscale")
        if qs is not None:
            x = (x.astype(jnp.float32)
                 * jnp.take(qs, ids, axis=0)[..., None]).astype(self.dtype)
        return x

    @jax.named_scope("lm_head")
    def _head(self, params, x):
        return self._proj(params, layer_norm(
            x, params["final_ln_gamma"], params["final_ln_beta"], self.eps),
            "pred")

    # -- paged cache -------------------------------------------------------
    @staticmethod
    def cache_lost(cache):
        """True when any leaf of a cache/pool value (an array, or the
        (pool, scales) pair under KV quant) was consumed by a failed
        donating launch — the engine's and the drafter's shared
        pool-loss probe."""
        for c in jax.tree_util.tree_leaves(cache):
            if getattr(c, "is_deleted", None) is not None \
                    and c.is_deleted():
                return True
        return False

    def _pool_parts(self, cache):
        """Split the engine's opaque paged-cache value: ``(pool, None)``
        full precision, ``(int8 pool, f32 scales)`` under KV quant —
        every paged method accepts either and returns the same kind."""
        if self.kv_quant is not None:
            return cache
        return cache, None

    def _pack_pool(self, pool, scales):
        return pool if scales is None else (pool, scales)

    def paged_decode_kernel(self, cache):
        """Whether `decode_paged` over ``cache``, traced here, attends with
        the Pallas kernel (`ops.attention.paged_decode_attention`).  The
        int8 pool never does: its rows are dequantized by their scales as
        they are gathered (`_gather_ctx`)."""
        pool, scales = self._pool_parts(cache)
        return scales is None and paged_decode_kernel_applies(
            pool, self.num_heads)

    def paged_prefill_kernel(self, cache, c):
        """Whether `prefill_paged` of a chunk of ``c`` tokens over
        ``cache``, traced here, attends with the Pallas kernel
        (`ops.attention.paged_chunk_attention`).  The int8 pool never
        does."""
        pool, scales = self._pool_parts(cache)
        return scales is None and paged_chunk_kernel_applies(
            pool, self.num_heads, None, c, -(-self.seq_len // pool.shape[3]))

    @jax.named_scope("kv_scatter")
    def _scatter_kv(self, pool, scales, layer, at, k, v):
        """Write one layer's new K and V rows into the pool at ``at`` (a
        (block,) index for whole blocks, a (block, offset) pair for single
        rows) — under KV quantization quantize-on-write, one scale per
        cached token row.  Returns (pool, scales)."""
        if scales is None:
            pool = pool.at[(layer, 0) + at].set(k.astype(pool.dtype))
            pool = pool.at[(layer, 1) + at].set(v.astype(pool.dtype))
            return pool, None
        kq, ks = quantize_rows(k, self.kv_quant)
        vq, vs = quantize_rows(v, self.kv_quant)
        pool = pool.at[(layer, 0) + at].set(kq)
        pool = pool.at[(layer, 1) + at].set(vq)
        scales = scales.at[(layer, 0) + at].set(ks)
        scales = scales.at[(layer, 1) + at].set(vs)
        return pool, scales

    @jax.named_scope("kv_gather")
    def _gather_ctx(self, pool, scales, layer, which, tables):
        """Materialize one layer's K (or V) context through the block
        tables, dequantizing in-graph when the pool stores int8: the
        gathered rows upcast to f32 and multiply by their gathered
        per-row scales before the attention math (which runs f32
        softmax statistics regardless)."""
        ctx = gather_paged_kv(pool, layer, which, tables)
        if scales is None:
            return ctx
        sc = gather_paged_kv(scales, layer, which, tables)
        return ctx.astype(jnp.float32) * sc[..., None]

    def init_block_pool(self, n_blocks, block_size, device=None):
        """Zeroed paged K/V pool: (num_layers, 2, n_blocks, block_size,
        embed) — under KV quantization the (pool, scales) PAIR, with the
        pool in the quantized dtype and per-row f32 scales
        (num_layers, 2, n_blocks, block_size).  Block 0 is the trash
        block (serving/paged.py).

        ``device`` places the buffers (the engine's ctor AND its
        cache-rebuild recovery path: when a failed donating launch
        consumes the pool, a fresh one is allocated here without
        touching the compiled executables — rebuild compiles nothing)."""
        shape = (self.num_layers, 2, int(n_blocks), int(block_size),
                 self.num_embed)
        # a sub-mesh engine passes ``device`` as the (pool, scales)
        # sharding PAIR — the pool splits on the embed axis but the
        # per-row scales have no embed axis and replicate
        pdev, sdev = device if isinstance(device, tuple) else (device,
                                                              device)
        if self.kv_quant is None:
            if pdev is None:
                return jnp.zeros(shape, self.dtype)
            return jax.device_put(np.zeros(shape, self.dtype), pdev)
        qdt = np.dtype(self.kv_quant.qdtype(np))
        pool = np.zeros(shape, qdt)
        scales = np.zeros(shape[:-1], np.float32)
        if pdev is None:
            return jnp.asarray(pool), jnp.asarray(scales)
        return (jax.device_put(pool, pdev),
                jax.device_put(scales, sdev))

    def block_run_placeholder(self, k, block_size):
        """Zeroed HOST staging buffers for a ``k``-block run — the
        host-tier restore's transfer payload and compile placeholder:
        one (num_layers, 2, k, block_size, embed) array, or the
        (int8 data, f32 scales) pair under KV quantization (spilled
        blocks live on the host in the pool's dtype, so restores move
        1-byte rows over PCIe)."""
        shape = (self.num_layers, 2, int(k), int(block_size),
                 self.num_embed)
        if self.kv_quant is None:
            return np.zeros(shape, self.dtype)
        return (np.zeros(shape, np.dtype(self.kv_quant.qdtype(np))),
                np.zeros(shape[:-1], np.float32))

    def slice_block(self, cache, block):
        """One block's device rows — every layer, K and V — as the
        spill payload: an array, or the (int8 data, scales) pair under
        KV quantization (the host tier then stores exactly the pool's
        bytes — spilling never dequantizes)."""
        pool, scales = self._pool_parts(cache)
        data = pool[:, :, block]
        if scales is None:
            return data
        return data, scales[:, :, block]

    def copy_block(self, pool, src, dst):
        """Copy one block's cached rows — every layer, K and V — from
        block ``src`` to block ``dst`` (both (1,) int32): the
        copy-on-write body.  A writer about to touch a SHARED block gets
        a private copy first, so the cached original keeps serving other
        readers byte-for-byte.  Gather + scatter on the block axis, the
        same primitives the paged attention path uses; the pool is
        donated by the engine's compiled wrapper, so the copy is
        in-place on the device.  Under KV quantization the per-row
        scales copy WITH the rows — a CoW'd block dequantizes
        identically to its original."""
        pool, scales = self._pool_parts(pool)
        src = src.astype(jnp.int32)
        dst = dst.astype(jnp.int32)
        pool = pool.at[:, :, dst].set(pool[:, :, src])
        if scales is not None:
            scales = scales.at[:, :, dst].set(scales[:, :, src])
        return self._pack_pool(pool, scales)

    def write_block(self, pool, dst, data):
        """Scatter a staged run of K/V blocks — every layer, K and V —
        into the pool at blocks ``dst`` ((k,) int32): the host-tier
        RESTORE body.  ``data`` is the `(num_layers, 2, k, block_size,
        embed)` device array (or the (int8 data, scales) pair under KV
        quantization) ONE async `jax.device_put` staged from the host
        pool while the previous decode iteration ran — a whole restored
        prefix costs one transfer and one launch, not one per block.
        Padding entries past the real run point ``dst`` at the trash
        block (the engine pads k up to a fixed bucket), so the
        program's shape set is small and compiled at warmup like
        `copy_block`.  The pool is donated by the engine's compiled
        wrapper, so the write is in-place on the device."""
        pool, scales = self._pool_parts(pool)
        dst = dst.astype(jnp.int32)
        if scales is None:
            return pool.at[:, :, dst].set(data.astype(pool.dtype))
        dq, ds = data
        pool = pool.at[:, :, dst].set(dq.astype(pool.dtype))
        scales = scales.at[:, :, dst].set(ds.astype(jnp.float32))
        return self._pack_pool(pool, scales)

    def prefill_paged(self, params, pool, tokens, start, length, tables,
                      moe_tape=None):
        """One chunked-prefill step over the paged pool.

        tokens: (b, c) int32 — a chunk of the prompt, rows padded past
                ``length``; c must be a multiple of the pool block size.
        start:  (b,) int32 — the chunk's absolute start position (a
                multiple of the block size: chunks are bucket-sized and
                every prefill bucket is block-aligned).
        length: (b,) int32 — real tokens in THIS chunk (>= 1).
        tables: (b, m) int32 block tables; entries covering
                ``start .. start+c-1`` must be allocated.
        Returns (logits, pool): logits of each row's last real chunk
        token (only meaningful for the prompt's final chunk — that row
        is position ``start+length-1``, the first sampling decision),
        and the pool with the chunk's K/V scattered in by block index.

        A short prompt is the degenerate single chunk (start 0), so one
        compiled program per chunk bucket serves both the single-shot
        and the streaming case — chunked prefill adds no shapes.
        Attention is `paged_chunk_attention` over the row's blocks
        (cached prefix + the chunk itself), which is exactly the
        training causal mask once start=0; the int8 pool's rows are
        gathered and dequantized for `chunk_attention` (`_gather_ctx`).
        """
        pool, scales = self._pool_parts(pool)
        b, c = tokens.shape
        h, e = self.num_heads, self.num_embed
        bs = pool.shape[3]
        m = tables.shape[1]
        start = start.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        nb = c // bs  # chunk blocks (c is a validated multiple of bs)
        # table entries covering the chunk: start//bs + 0..nb-1 per row.
        # A short final chunk's bucket can extend past the table width
        # (positions >= the block-rounded cache depth — all padding rows);
        # those entries redirect to the trash block EXPLICITLY rather
        # than leaning on take_along_axis's out-of-bounds fill behavior.
        ent = start[:, None] // bs + jnp.arange(nb, dtype=jnp.int32)[None]
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1), axis=1)
        blk = jnp.where(ent < m, blk, 0)                      # (b, nb)
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        x = self._embed(params, tokens)
        x = x + jnp.take(params["pos_embed_weight"][0], positions, axis=0)
        for i in range(self.num_layers):
            p = "layer%d_" % i
            hn = layer_norm(x, params[p + "ln1_gamma"],
                            params[p + "ln1_beta"], self.eps)
            hf = hn.reshape(-1, e)
            with jax.named_scope("qkv_proj"):
                q = self._proj(params, hf, p + "q").reshape(b, c, e)
                k = self._proj(params, hf, p + "k").reshape(b, c, e)
                v = self._proj(params, hf, p + "v").reshape(b, c, e)
            # scatter the chunk's K/V rows into their blocks, THEN gather
            # the whole context so the chunk attends to itself too.
            # Rows past `length` write garbage into the chunk's own
            # blocks — never visible: decode overwrites position
            # start+length first and every mask is `j <= own position`.
            kw = k.reshape(b, nb, bs, e)
            vw = v.reshape(b, nb, bs, e)
            pool, scales = self._scatter_kv(pool, scales, i, (blk,), kw, vw)
            if scales is None:
                attn = paged_chunk_attention(q, pool, i, tables, start, h)
            else:
                kc = self._gather_ctx(pool, scales, i, 0, tables)
                vc = self._gather_ctx(pool, scales, i, 1, tables)
                attn = chunk_attention(q, kc, vc, start, h)
            x = x + self._attn_out(params, attn.reshape(-1, e),
                                   p).reshape(b, c, e)
            hn = layer_norm(x, params[p + "ln2_gamma"],
                            params[p + "ln2_beta"], self.eps)
            x = x + self._ffn(params, hn.reshape(-1, e), p,
                              tape=moe_tape).reshape(b, c, e)
        last = jnp.take_along_axis(
            x, (length.astype(jnp.int32) - 1)[:, None, None], axis=1
        )[:, 0, :]
        return self._head(params, last), self._pack_pool(pool, scales)

    def decode_paged(self, params, pool, token, pos, tables,
                     moe_tape=None):
        """One generation step over the paged pool.

        pool:   (num_layers, 2, n_blocks, block_size, embed), donated.
        token:  (b,) int32 — each row's current token.
        pos:    (b,) int32 — the position ``token`` occupies; its block
                (``tables[r, pos // block_size]``) must be allocated.
        tables: (b, m) int32 — block tables; padding rows are all-trash
                with pos 0, so their scatter lands in the trash block.
        Returns (logits (b, vocab), new_pool).
        """
        pool, scales = self._pool_parts(pool)
        e = self.num_embed
        bs = pool.shape[3]
        m = tables.shape[1]
        pos = pos.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        # positions past the table's coverage redirect to the trash
        # block EXPLICITLY (the speculative drafter's in-graph scan can
        # run a row past the cache end; clamping the table lookup would
        # scatter into a REAL tail block instead)
        ent = pos // bs
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1)[:, None],
                                  axis=1)[:, 0]               # (b,)
        blk = jnp.where(ent < m, blk, 0)
        off = pos % bs
        x = self._embed(params, token)
        x = x + jnp.take(params["pos_embed_weight"][0],
                         jnp.minimum(pos, self.seq_len - 1), axis=0)
        for i in range(self.num_layers):
            p = "layer%d_" % i
            hn = layer_norm(x, params[p + "ln1_gamma"],
                            params[p + "ln1_beta"], self.eps)
            with jax.named_scope("qkv_proj"):
                q = self._proj(params, hn, p + "q")
                k = self._proj(params, hn, p + "k")
                v = self._proj(params, hn, p + "v")
            pool, scales = self._scatter_kv(pool, scales, i, (blk, off),
                                            k, v)
            if scales is None:
                # the whole pool: a kernel where one applies, which reads
                # the row's live blocks in place
                attn = paged_decode_attention(q, pool, i, tables, pos,
                                              self.num_heads)
            else:
                kc = self._gather_ctx(pool, scales, i, 0, tables)
                vc = self._gather_ctx(pool, scales, i, 1, tables)
                attn = decode_attention(q, kc, vc, pos, self.num_heads)
            x = x + self._attn_out(params, attn, p)
            hn = layer_norm(x, params[p + "ln2_gamma"],
                            params[p + "ln2_beta"], self.eps)
            x = x + self._ffn(params, hn, p, tape=moe_tape)
        return self._head(params, x), self._pack_pool(pool, scales)

    def decode_megastep(self, params, pool, token, pos, left, eos, tables,
                        steps, pick, moe_tape=None):
        """``steps`` fused generation steps in ONE launch: a `lax.scan`
        over the `decode_paged` body with per-row active masks, so a row
        that finishes (EOS / generation budget / cache depth) mid-scan
        retires IN-GRAPH — its remaining iterations run at the DEAD
        position one past the table's coverage, which `decode_paged`'s
        trash redirect sends to block 0 (and the pos-embed clamp keeps
        in range), exactly the mechanism the speculative drafter's scan
        already rides.

        token: (b,) int32 — each row's current token (fed at ``pos``).
        pos:   (b,) int32 — the position ``token`` occupies.
        left:  (b,) int32 — tokens the row may still emit
               (``max_new_tokens - n_new``); <= 0 marks the row inactive
               from step 0 (padding rows pass 0).
        eos:   (b,) int32 — per-row EOS id, -1 for none.
        steps: int (a warmup-table constant, never per-request) — the
               scan length m.
        pick:  ``pick(logits, newpos) -> (b,) int32`` — the engine's
               sampling tail (position-folded RNG + quant logit guard).
               Each scan step passes the CARRIED position + 1, so the
               fused run draws with the same fold keys as ``steps``
               sequential launches: bit-identical tokens.

        Returns ``(toks (b, steps) int32, new_pool)``.  Row semantics of
        ``toks[r, j]``: >= 0 — the j-th token emitted by row r (host
        bookkeeping replays them one at a time through the sequential
        accounting); -1 — the quant logit guard tripped at this step
        (earlier emits stand, the row froze in-graph); -2 — the row was
        already retired (or never active) when step j ran.
        """
        raw, _ = self._pool_parts(pool)
        bs = raw.shape[3]
        # one past the table's coverage: decode_paged redirects the
        # write to the trash block instead of clamping onto a real one
        dead = jnp.int32(tables.shape[1] * bs)
        seq_end = jnp.int32(self.seq_len)
        # MoE expert-load counts ride the scan carry (one (E,) int32
        # accumulator summed over layers and steps) and come out as a
        # single tape entry — a scan can't append per-step
        want = bool(self.moe_experts) and moe_tape is not None

        def step(carry, _):
            if want:
                pool, tok, p, lf, act, cnt = carry
            else:
                pool, tok, p, lf, act = carry
            tape = [] if want else None
            logits, pool = self.decode_paged(
                params, pool, tok, jnp.where(act, p, dead), tables,
                moe_tape=tape)
            picked = pick(logits, p + 1)
            trip = act & (picked < 0)
            adv = act & ~trip
            p2 = jnp.where(adv, p + 1, p)
            lf2 = jnp.where(adv, lf - 1, lf)
            tok2 = jnp.where(adv, picked, tok)
            # the same three stop predicates _seq_finished checks host-
            # side, evaluated on the post-advance state — a finishing
            # token is emitted and THEN deactivates the row
            fin = ((eos >= 0) & (picked == eos)) | (lf2 <= 0) | \
                (p2 >= seq_end)
            act2 = adv & ~fin
            emit = jnp.where(act, picked, jnp.int32(-2))
            if want:
                cnt = cnt + jnp.sum(jnp.stack(tape), axis=0)
                return (pool, tok2, p2, lf2, act2, cnt), emit
            return (pool, tok2, p2, lf2, act2), emit

        carry = (pool, token.astype(jnp.int32), pos.astype(jnp.int32),
                 left.astype(jnp.int32), left > 0)
        if want:
            carry = carry + (jnp.zeros((self.moe_experts,), jnp.int32),)
        out, toks = jax.lax.scan(step, carry, None, length=steps)
        pool = out[0]
        if want:
            moe_tape.append(out[5])
        return toks.T, pool

    def verify_paged(self, params, pool, tokens, pos, length, tables,
                     moe_tape=None):
        """Speculative-decoding verify: score a whole draft run with ONE
        launch (the draft-verify counterpart of `decode_paged`).

        tokens: (b, c) int32 — column 0 is each row's last emitted token
                (what single-token decode would feed), columns 1..c-1
                its draft proposals.
        pos:    (b,) int32 — the absolute position column 0 occupies;
                tokens[:, j] is fed at pos + j.
        length: (b,) int32 — real fed tokens per row (rows clipped at
                the cache end feed fewer; padding rows feed 1).
        tables: (b, m) int32 block tables; blocks covering
                pos .. pos+length-1 must be EXCLUSIVELY owned (the
                engine's span-grow/CoW guarantees it — this scatters).
        Returns (logits (b, c, vocab), pool): logits at EVERY fed
        position, so the accept rule can compare the target's own pick
        at pos+j against draft j+1 — identical context to sequential
        decode up to the first rejection, hence token-for-token parity.

        Unlike `prefill_paged`, c need not be block-aligned and pos is
        arbitrary: K/V scatter by per-position (block, offset) pairs,
        exactly `decode_paged`'s addressing vectorized over the chunk.
        Positions past the table's coverage (speculation clipped at the
        cache end) redirect to the trash block explicitly.
        """
        pool, scales = self._pool_parts(pool)
        b, c = tokens.shape
        h, e = self.num_heads, self.num_embed
        bs = pool.shape[3]
        m = tables.shape[1]
        pos = pos.astype(jnp.int32)
        length = length.astype(jnp.int32)
        tables = tables.astype(jnp.int32)
        positions = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        ent = positions // bs                                  # (b, c)
        blk = jnp.take_along_axis(tables, jnp.minimum(ent, m - 1), axis=1)
        blk = jnp.where(ent < m, blk, 0)
        off = positions % bs
        x = self._embed(params, tokens)
        x = x + jnp.take(params["pos_embed_weight"][0],
                         jnp.minimum(positions, self.seq_len - 1), axis=0)
        for i in range(self.num_layers):
            p = "layer%d_" % i
            hn = layer_norm(x, params[p + "ln1_gamma"],
                            params[p + "ln1_beta"], self.eps)
            hf = hn.reshape(-1, e)
            with jax.named_scope("qkv_proj"):
                q = self._proj(params, hf, p + "q").reshape(b, c, e)
                k = self._proj(params, hf, p + "k").reshape(b, c, e)
                v = self._proj(params, hf, p + "v").reshape(b, c, e)
            # scatter the whole fed span, then gather the context: the
            # draft tokens attend to each other causally, exactly as
            # sequential decode would have cached them one by one
            pool, scales = self._scatter_kv(pool, scales, i, (blk, off),
                                            k, v)
            kc = self._gather_ctx(pool, scales, i, 0, tables)
            vc = self._gather_ctx(pool, scales, i, 1, tables)
            attn = verify_attention(q, kc, vc, pos, length, h)
            x = x + self._attn_out(params, attn.reshape(-1, e),
                                   p).reshape(b, c, e)
            hn = layer_norm(x, params[p + "ln2_gamma"],
                            params[p + "ln2_beta"], self.eps)
            x = x + self._ffn(params, hn.reshape(-1, e), p,
                              tape=moe_tape).reshape(b, c, e)
        logits = self._head(params, x.reshape(-1, e)).reshape(
            b, c, self.vocab_size)
        return logits, self._pack_pool(pool, scales)
