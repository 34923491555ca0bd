"""The plain reference of the Kimi-K2 / DeepSeek-V3 language-model block:
the forward pass in straightforward `jax.numpy` and float32 at the highest
matmul precision.  No kernels, no cache, no batching, and nothing of
`mxnet_tpu`: it reads a parameter dict under the names
`LatentMoEKVModel.param_shapes()` gives and a configuration under the keys of
the published `config.json`.  `benchmark/reference/kimi_k2.py` is a copy of
this file (the benchmark imports nothing of the program); a test holds the
two to the same logits.

The equations, for a row ``x`` of ``hidden_size`` (RMSNorm with
``rms_norm_eps``, no biases anywhere):

* block: ``h = x + MLA(norm(x))``, ``y = h + FFN(norm(h))``; FFN is the SwiGLU
  ``W_down(silu(W_gate u) * W_up u)`` of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers and the expert layer after them; a final
  RMSNorm and an untied head.
* MLA: ``c_q = norm(u W_qa)``; ``q = c_q W_qb`` as heads x (nope + rope);
  ``[c_kv, k_pe] = u W_kva``; ``c_kv = norm(c_kv)``; ``k_pe = RoPE(k_pe)``, one
  vector shared by all heads; ``[k_nope, v] = c_kv W_kvb`` as heads x (nope +
  v); ``q_pe = RoPE(q_pe)``; scores ``(q_nope . k_nope + q_pe . k_pe) * s``,
  causal, softmax, times ``v``, concatenated through ``W_o``.  ``s =
  (nope + rope)^-0.5 * m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor) +
  1``.  RoPE on the ``rope`` dims with YaRN's frequencies; dim ``i`` pairs
  with dim ``i + rope / 2`` (the published checkpoints store the pairs
  interleaved: a fixed permutation of columns, nothing under random weights).
* expert layer: ``sc = sigmoid(u W_g)``; the ``num_experts_per_tok`` largest
  of ``sc + b`` are chosen (``n_group`` = ``topk_group`` = 1: no group limit);
  weights ``w = sc[chosen]`` without ``b``, ``w / (sum(w) + 1e-20)``
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``y = sum_i w_i
  E_i(u) + E_shared(u)``, each ``E`` a SwiGLU of ``moe_intermediate_size``.

**The share.**  ``cfg["experts_held"] = [lo, hi)`` names the routed experts
whose weights the parameter dict holds (stacked, in that order): only the
terms of chosen experts in that range are added, plus the shared expert; what
the other experts would add is left out.  ``vocab_size`` is the slice of the
vocabulary the embedding and the head hold.

`mode` selects the arithmetic of the projections: "f32" is the reference;
"fp8" is the control (inputs and weights of every projection rounded to
float8_e4m3 with one scale per operand; sums stay float32).  `fault` plants
one fault, for the tests of the comparison: "top7" (one expert too few),
"no_routed_scale", "no_k_rope" (``k_pe`` cached unrotated), "drop_expert"
(the first held expert's output left out).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("top7", "no_routed_scale", "no_k_rope", "drop_expert")


# -- rotary positions ---------------------------------------------------------


def yarn_inv_freq(dim, theta, scaling):
    """``dim // 2`` inverse frequencies: ``theta^(-2i/dim)``, each blended
    with itself over ``factor`` by the linear ramp between the correction
    dims of ``beta_fast`` and ``beta_slow`` turns in the original context."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return extra

    def correction_dim(turns):
        return dim * math.log(scaling["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001), 0, 1)
    return extra / scaling["factor"] * ramp + extra * (1 - ramp)


def softmax_scale(cfg):
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim"):
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
        s *= m * m
    return s


def _rope(x, positions, inv_freq):
    """Rotate the last axis of ``x`` (s, ..., dim) by ``positions`` (s,)."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32).reshape((-1,) + (1,) * (x.ndim - 1)) \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- the block ----------------------------------------------------------------


def _round(x, mode):
    if mode == "f32":
        return x
    if mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError("unknown arithmetic %r" % (mode,))


def _proj(x, w, mode):
    """x (n, in) @ w (out, in)^T, in float32 whatever dtype ``w`` is stored
    in (raised here, at its use, so that no float32 copy of a whole bank of
    experts is made)."""
    return jnp.dot(_round(x, mode), _round(w.astype(jnp.float32), mode).T,
                   precision=HIGHEST)


def _rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma.astype(jnp.float32)


def _swiglu(u, w_gate, w_up, w_down, mode):
    return _proj(jax.nn.silu(_proj(u, w_gate, mode)) * _proj(u, w_up, mode),
                 w_down, mode)


#: heads attended at a time, so that 16k positions fit beside the weights
_HEAD_GROUP = 16


def _mla(u, lp, cfg, mode, fault, q_block):
    s, d = u.shape
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv_freq = yarn_inv_freq(rope, cfg["rope_theta"], cfg.get("rope_scaling"))
    positions = jnp.arange(s)
    scale = softmax_scale(cfg)
    c_q = _rms_norm(_proj(u, lp["q_a_weight"], mode), lp["q_a_ln_gamma"], eps)
    kv = _proj(u, lp["kv_a_weight"], mode)
    c_kv = _rms_norm(kv[:, :rank], lp["kv_a_ln_gamma"], eps)
    k_pe = kv[:, rank:]
    if fault != "no_k_rope":
        k_pe = _rope(k_pe, positions, inv_freq)
    # Blocks, so that the scores and the per-head keys and values fit: the
    # heads a group at a time (each group's rows of W_qb and W_kvb and its
    # columns of W_o; the groups' outputs add up to the whole projection),
    # and within a group the queries a block at a time.  The same sums.
    g = min(_HEAD_GROUP, h)
    n_blocks = -(-s // q_block)
    pad = ((0, n_blocks * q_block - s), (0, 0), (0, 0))

    def group(out, weights):
        w_qb, w_kvb, w_o = weights
        q = _proj(c_q, w_qb, mode).reshape(s, g, nope + rope)
        q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], positions,
                                            inv_freq)
        kvb = _proj(c_kv, w_kvb, mode).reshape(s, g, nope + vd)
        k_nope, v = kvb[..., :nope], kvb[..., nope:]

        def block(args):
            qn, qp, first = args
            sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=HIGHEST)
                  + jnp.einsum("qhd,kd->hqk", qp, k_pe,
                               precision=HIGHEST)) * scale
            seen = jnp.arange(s)[None, :] \
                <= first + jnp.arange(q_block)[:, None]
            p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

        attn = jax.lax.map(block, (
            jnp.pad(q_nope, pad).reshape(n_blocks, q_block, g, nope),
            jnp.pad(q_pe, pad).reshape(n_blocks, q_block, g, rope),
            jnp.arange(n_blocks) * q_block))
        attn = attn.reshape(n_blocks * q_block, g * vd)[:s]
        return out + _proj(attn, w_o, mode), None

    out, _ = jax.lax.scan(group, jnp.zeros((s, d), jnp.float32), (
        lp["q_b_weight"].reshape(h // g, g * (nope + rope), -1),
        lp["kv_b_weight"].reshape(h // g, g * (nope + vd), rank),
        lp["attn_out_weight"].reshape(d, h // g, g * vd).transpose(1, 0, 2)))
    return out


def _expert_layer(u, lp, cfg, mode, fault):
    lo, hi = cfg["experts_held"]
    top_k = cfg["num_experts_per_tok"] - (fault == "top7")
    sc = jax.nn.sigmoid(jnp.dot(
        u, lp["moe_router_weight"].astype(jnp.float32).T, precision=HIGHEST))
    _, chosen = jax.lax.top_k(
        sc + lp["moe_router_bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(sc, chosen, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        w = w * cfg["routed_scaling_factor"]

    def add(y, e):
        # the weight of held expert e for each row: 0 where it was not
        # chosen.  The banks are stacked (in, out): E_e(u) as a SwiGLU of
        # (out, in) matrices
        w_e = jnp.sum(jnp.where(chosen == lo + e, w, 0.0), axis=1)
        return y + w_e[:, None] * _swiglu(
            u, lp["moe_gate"][e].T, lp["moe_up"][e].T, lp["moe_down"][e].T,
            mode), None

    shared = _swiglu(u, lp["shared_gate_weight"], lp["shared_up_weight"],
                     lp["shared_down_weight"], mode)
    y, _ = jax.lax.scan(add, shared,
                        jnp.arange(int(fault == "drop_expert"), hi - lo))
    return y


#: the keys of a configuration that the block reads
_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
         "rope_scaling", "experts_held", "num_experts_per_tok",
         "routed_scaling_factor")


def _freeze(cfg):
    """What `_layer` reads of ``cfg``, as a value that hashes (a jitted
    function's static argument)."""
    return tuple((k, tuple(sorted(cfg[k].items()))
                  if isinstance(cfg[k], dict) else
                  tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in _KEYS if cfg.get(k) is not None)


@functools.partial(jax.jit, static_argnames=("dense", "frozen", "mode",
                                             "fault", "q_block"))
def _layer(x, lp, *, dense, frozen, mode, fault, q_block):
    cfg = dict(frozen)
    if "rope_scaling" in cfg:
        cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    eps = cfg["rms_norm_eps"]
    x = x + _mla(_rms_norm(x, lp["ln1_gamma"], eps), lp, cfg, mode, fault,
                 q_block)
    u = _rms_norm(x, lp["ln2_gamma"], eps)
    if dense:  # mxlint: disable=trace-py-branch -- `dense` is a static argument of the jitted layer
        return x + _swiglu(u, lp["ffn_gate_weight"], lp["ffn_up_weight"],
                           lp["ffn_down_weight"], mode)
    return x + _expert_layer(u, lp, cfg, mode, fault)


def hidden(params, tokens, cfg, mode="f32", fault=None, q_block=256):
    """(s,) token ids of ONE sequence -> (s, hidden) states before the final
    norm.  The parameters may be of any float dtype: each is raised to
    float32 where it is used, so a bf16-rounded set that fills most of a
    chip needs no float32 copy of itself beside it."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed_weight"], tokens, axis=0).astype(jnp.float32)
    frozen = _freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d_" % i
        lp = {n[len(p):]: v for n, v in params.items() if n.startswith(p)}
        x = _layer(x, lp, dense=i < cfg["first_k_dense_replace"],
                   frozen=frozen, mode=mode, fault=fault, q_block=q_block)
    return x


def logits_of(params, x, cfg, mode="f32"):
    """(n, hidden) states before the final norm -> (n, vocab) logits."""
    return _proj(_rms_norm(x, params["final_ln_gamma"], cfg["rms_norm_eps"]),
                 params["pred_weight"], mode)


def forward(params, tokens, cfg, mode="f32", fault=None):
    """(s,) token ids -> (s, vocab) logits: the full forward pass."""
    return logits_of(params, hidden(params, tokens, cfg, mode, fault), cfg,
                     mode)


# -- serving ------------------------------------------------------------------


def served_gaps(params, prompt, out_tokens, cfg, pad_to, control=None,
                q_block=256):
    """For one request, the gap by which each served token's reference logit
    lies below the reference's best, (served tokens,) float32; with
    ``control`` ("fp8", or one of `FAULTS`), also the gaps of the tokens that
    arithmetic puts first at the same positions (else None).  The row is
    padded to ``pad_to`` positions (causal: padding changes nothing before
    it)."""
    n, m = len(prompt), len(out_tokens)
    row = np.zeros((pad_to,), np.int32)
    row[:n] = prompt
    row[n:n + m - 1] = out_tokens[:-1]
    at = slice(n - 1, n - 1 + m)             # the positions that were served
    served = jnp.asarray(np.asarray(out_tokens, np.int32))
    logits = logits_of(params, hidden(params, row, cfg,
                                      q_block=q_block)[at], cfg)
    best = jnp.max(logits, axis=-1)

    def gap_of(tokens):
        return np.asarray(best - jnp.take_along_axis(
            logits, tokens[:, None], axis=1)[:, 0])

    if control is None:
        return gap_of(served), None
    mode, fault = ("fp8", None) if control == "fp8" else ("f32", control)
    first = jnp.argmax(logits_of(
        params, hidden(params, row, cfg, mode, fault, q_block)[at], cfg,
        mode), -1)
    return gap_of(served), gap_of(first)
