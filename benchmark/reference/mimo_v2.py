"""The plain reference of the MiMo-V2 language-model block: the forward pass
in straightforward `jax.numpy` and float32 at the highest matmul precision.
No kernels, no cache, no batching, and nothing of `mxnet_tpu`: it reads a
parameter dict under the names `HybridWindowMoEKVModel.param_shapes()` gives
and a configuration under the keys of the published `config.json`
(`model_type: mimo_v2`), with this chip's share of the experts
(`experts_held`) and of the vocabulary (`vocab_size`).

The equations, for a row ``x`` at position ``t`` in layer ``i`` (RMSNorm
``n(.)`` with a learned gain and ``layernorm_epsilon``, no biases anywhere):

    h = x + A_i(n_a(x));   y = h + F_i(n_f(h));   logits = n_out(y) W_head^T

* ``A``: ``q = u W_q^T`` (``num_attention_heads`` x ``head_dim``), ``k = u
  W_k^T`` (``K_i`` x ``head_dim``), ``v = u W_v^T`` (``K_i`` x
  ``v_head_dim``), ``K_i`` = ``num_key_value_heads`` in a full layer
  (``hybrid_layer_pattern[i] == 0``) and ``swa_num_key_value_heads`` in a
  window layer (1).  The first ``floor(partial_rotary_factor * head_dim)``
  dims of every q and k head are rotated (rotate-half: dim ``j`` pairs with
  ``j + rotary / 2``; ``rope_theta`` in full layers, ``swa_rope_theta`` in
  window layers), the rest pass.  Query head ``h`` reads K/V head ``h //
  (heads / K_i)``; ``s_j = q k_j / sqrt(head_dim)``.  Full layers: softmax
  over ``j <= t``.  Window layers: ``j`` in ``(t - sliding_window, t]``, and
  ``p_j = exp(s_j) / (exp(b_h) + sum exp(s))`` with the head's learned sink
  ``b_h`` (``add_swa_attention_sink_bias``).  ``A(u) = attention_value_scale
  * concat_h(sum_j p_j v_j) W_o^T``.
* ``F``: the SwiGLU ``W_down(silu(W_gate u) * W_up u)`` of
  ``intermediate_size`` where ``moe_layer_freq[i] == 0``; else ``sc =
  sigmoid(u W_r^T)`` over all ``n_routed_experts`` in float32, the
  ``num_experts_per_tok`` largest of ``sc + e_bias`` chosen (the bias
  chooses and does not weigh), ``w = sc[chosen] / sum(sc[chosen])``
  (``norm_topk_prob``; ``routed_scaling_factor`` null is 1) and ``F(u) =
  sum_e w_e SwiGLU_e(u)`` of ``moe_intermediate_size``, over the chosen
  experts HELD here (``experts_held``, this chip's share; a one-chip program
  has no exchange that would add the others').  No shared expert.
* the head is ``pred_weight``, untied, over the vocabulary rows held here.

Departures: the held share of the experts and of the vocabulary (the
program's, on purpose); the window is a band mask over the whole sequence,
where the program keeps a ring.  No other is intended.

`mode` selects the arithmetic of the projections: "f32" is the reference;
"fp8" is the control (inputs and weights of every projection rounded to
float8_e4m3 with one scale per operand; sums stay float32).  `fault` plants
one fault, for the tests of the comparison (`FAULTS`): "no_sink" (the sink
left out of the denominator), "window_129" and "window_127" (a window one
wider or narrower), "no_value_scale", "rope_all" (every dim of the head
rotated), "theta_swapped" (each kind's theta the other's), "kv_head_mod"
(query head ``h`` reads K/V head ``h % K_i``), "ring_unmasked" (a window
layer's positions below 0 read as zero K and V rows, as a fresh slot's ring
would be read at a sequence's first chunk without its mask).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("no_sink", "window_129", "window_127", "no_value_scale",
          "rope_all", "theta_swapped", "kv_head_mod", "ring_unmasked")


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
    in (raised here, at its use)."""
    return jnp.dot(_round(x, mode), _round(w.astype(jnp.float32), mode).T,
                   precision=HIGHEST)


def _rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gamma.astype(jnp.float32)


def _swiglu(u, w_gate, w_up, w_down, mode):
    return _proj(jax.nn.silu(_proj(u, w_gate, mode)) * _proj(u, w_up, mode),
                 w_down, mode)


def rotary_dim(cfg):
    """The rotated dims of a head: ``floor(partial_rotary_factor *
    head_dim)``, 64 of 192 at the published sizes."""
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"])


def _rope(x, theta, rot):
    """Rotate the first ``rot`` dims of the last axis of ``x`` (s, heads,
    d) by the row's position; the rest pass."""
    half = rot // 2
    inv_freq = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _attention(u, lp, cfg, window, mode, fault, q_block):
    s = u.shape[0]
    h, d, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                cfg["v_head_dim"])
    kvh = cfg["swa_num_key_value_heads"] if window \
        else cfg["num_key_value_heads"]
    thetas = (cfg["swa_rope_theta"], cfg["rope_theta"])
    if fault == "theta_swapped":
        thetas = thetas[::-1]
    theta = thetas[0] if window else thetas[1]
    rot = d if fault == "rope_all" else rotary_dim(cfg)
    width = cfg["sliding_window"] + {"window_129": 1, "window_127": -1}.get(
        fault, 0)
    q = _rope(_proj(u, lp["q_weight"], mode).reshape(s, h, d), theta, rot)
    k = _rope(_proj(u, lp["k_weight"], mode).reshape(s, kvh, d), theta, rot)
    v = _proj(u, lp["v_weight"], mode).reshape(s, kvh, dv)
    # the K/V head each query head reads: h // (h / kvh)
    reads = np.arange(h) % kvh if fault == "kv_head_mod" \
        else np.arange(h) // (h // kvh)
    k, v = k[:, reads], v[:, reads]
    sink = lp["attn_sink_bias"].astype(jnp.float32) if window else None
    n_blocks = -(-s // q_block)

    def block(args):
        qb, first = args
        t = first + jnp.arange(q_block)[:, None]          # query positions
        j = jnp.arange(s)[None, :]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / d ** 0.5
        seen = j <= t
        if window:
            seen = seen & (j > t - width)
        sc = jnp.where(seen[None], sc, -jnp.inf)
        if window and fault == "ring_unmasked":
            # the window's positions below 0 as zero rows: a score of 0 each
            # (and a value of 0), beside the real keys
            zeros = jnp.broadcast_to(jnp.maximum(width - 1 - t, 0)[None],
                                     (h, q_block, 1)).astype(jnp.float32)
            m = jnp.maximum(jnp.max(sc, axis=-1, keepdims=True), 0.0)
            e = jnp.exp(sc - m)
            extra = zeros * jnp.exp(-m)
        else:
            m = jnp.max(sc, axis=-1, keepdims=True)
            e = jnp.exp(sc - m)
            extra = 0.0
        if window and fault != "no_sink":
            bh = sink[:, None, None]
            m2 = jnp.maximum(m, bh)
            e = e * jnp.exp(m - m2)
            extra = extra * jnp.exp(m - m2) + jnp.exp(bh - m2)
        p = e / (jnp.sum(e, axis=-1, keepdims=True) + extra)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    # the queries a block at a time, so that the scores fit: the same sums
    attn = jax.lax.map(block, (
        jnp.pad(q, ((0, n_blocks * q_block - s), (0, 0), (0, 0))).reshape(
            n_blocks, q_block, h, d),
        jnp.arange(n_blocks) * q_block))
    attn = attn.reshape(n_blocks * q_block, h * dv)[:s]
    if fault != "no_value_scale":
        attn = attn * cfg["attention_value_scale"]
    return _proj(attn, lp["attn_out_weight"], mode)


def _expert_layer(u, lp, cfg, mode):
    lo, hi = cfg["experts_held"]
    sc = jax.nn.sigmoid(jnp.dot(
        u, lp["moe_router_weight"].astype(jnp.float32).T, precision=HIGHEST))
    _, chosen = jax.lax.top_k(
        sc + lp["moe_router_bias"].astype(jnp.float32),
        cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(sc, chosen, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
    w = w * (cfg["routed_scaling_factor"] or 1.0)

    def add(y, e):
        # the weight of expert lo + e for each row: 0 where it was not
        # chosen.  The banks are stacked (in, out): an expert at a time, so
        # that no float32 copy of a bank is made
        w_e = jnp.sum(jnp.where(chosen == lo + e, w, 0.0), axis=1)
        return y + w_e[:, None] * _swiglu(
            u, lp["moe_gate"][e].T, lp["moe_up"][e].T, lp["moe_down"][e].T,
            mode), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u), jnp.arange(hi - lo))
    return y


#: the keys of a configuration that a layer reads
_KEYS = ("hidden_size", "layernorm_epsilon", "num_attention_heads",
         "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
         "v_head_dim", "partial_rotary_factor", "rope_theta", "swa_rope_theta",
         "sliding_window", "attention_value_scale", "num_experts_per_tok",
         "routed_scaling_factor", "experts_held")


@functools.partial(jax.jit, static_argnames=("window", "dense", "frozen",
                                             "mode", "fault", "q_block"))
def _layer(x, lp, *, window, dense, frozen, mode, fault, q_block):
    cfg = dict(frozen)
    eps = cfg["layernorm_epsilon"]
    u = _rms_norm(x, lp["ln1_gamma"], eps)
    x = x + _attention(u, lp, cfg, window, mode, fault, q_block)
    u = _rms_norm(x, lp["ln2_gamma"], eps)
    if dense:  # mxlint: disable=trace-py-branch -- `dense` is a static argument of the jitted layer
        return x + _swiglu(u, lp["ffn_gate_weight"], lp["ffn_up_weight"],
                           lp["ffn_down_weight"], mode)
    return x + _expert_layer(u, lp, cfg, mode)


def hidden(params, tokens, cfg, mode="f32", fault=None, q_block=128):
    """(s,) token ids of ONE sequence -> (s, hidden) states before the final
    norm.  The parameters may be of any float dtype: each is raised to
    float32 where it is used."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed_weight"], tokens, axis=0).astype(jnp.float32)
    frozen = tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                   for k in _KEYS)
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(cfg["hybrid_layer_pattern"]):
            p = "layer%d_" % i
            lp = {n[len(p):]: v for n, v in params.items()
                  if n.startswith(p)}
            x = _layer(x, lp, window=kind == 1,
                       dense=cfg["moe_layer_freq"][i] == 0, frozen=frozen,
                       mode=mode, fault=fault, q_block=q_block)
    return x


def logits_of(params, x, cfg, mode="f32"):
    """(n, hidden) states before the final norm -> (n, vocab) logits."""
    return _proj(_rms_norm(x, params["final_ln_gamma"],
                           cfg["layernorm_epsilon"]),
                 params["pred_weight"], mode)


def forward(params, tokens, cfg, mode="f32", fault=None, q_block=128):
    """(s,) token ids -> (s, vocab) logits: the full forward pass."""
    return logits_of(params, hidden(params, tokens, cfg, mode, fault,
                                    q_block), cfg, mode)


# -- serving ------------------------------------------------------------------


def served_gaps(params, prompt, out_tokens, cfg, pad_to, control=None,
                q_block=128):
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
