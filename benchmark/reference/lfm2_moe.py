"""The plain reference of the LFM2-MoE language-model block: the forward pass
in straightforward `jax.numpy` and float32 at the highest matmul precision.
No kernels, no cache, no batching, and nothing of `mxnet_tpu`: it reads a
parameter dict under the names `ShortConvMoEKVModel.param_shapes()` gives and
a configuration under the keys of the published `config.json`
(`model_type: lfm2_moe`).

The equations, for a row ``x`` of ``hidden_size`` (RMSNorm ``n(.)`` with a
learned gain and ``norm_eps``, no biases anywhere):

    h = x + Op_i(n_op(x));   y = h + FFN_i(n_ffn(h));
    logits = n_emb(y) W_head^T

* ``layer_types[i] == "conv"``: ``[B, C, z] = u W_in^T`` (``hidden`` ->
  3 x ``hidden``, split in that order); ``s_t = B_t * z_t``; ``c_t =
  sum_j w_j * s_(t-(L-1)+j)`` over the ``L = conv_L_cache`` taps (depthwise,
  causal, ``s`` zero before the sequence starts, ``w`` of shape (hidden, L));
  ``Op(u)_t = (C_t * c_t) W_out^T``.  No activation.
* ``"full_attention"``: ``q = u W_q^T`` (heads x d), ``k = u W_k^T``, ``v =
  u W_v^T`` (K/V heads x d); RMSNorm over each head of q and of k (gains of
  shape (d,)); RoPE (rotate-half: dim ``i`` pairs with dim ``i + d/2``;
  ``rope_theta``, all d dims) on q and k; causal softmax(``q k^T / sqrt(d)``)
  with query head ``h`` reading K/V head ``h // (heads / K/V heads)``;
  ``Op(u) = attn W_o^T``.
* FFN: the SwiGLU ``W_down(silu(W_gate u) * W_up u)`` of ``intermediate_size``
  in the first ``num_dense_layers`` layers; after them ``sc = sigmoid(u
  W_g^T)``, the ``num_experts_per_tok`` largest of ``sc + b`` are chosen
  (``use_expert_bias``: ``b`` chooses and does not weigh), ``w = sc[chosen] /
  (sum(sc[chosen]) + 1e-6)`` (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``FFN(u) = sum_e w_e E_e(u)``, each ``E`` a SwiGLU
  of ``moe_intermediate_size``.  No shared expert.
* the head is the embedding (``tie_word_embeddings``), unless the parameters
  hold a ``pred_weight``.

No departure from these is intended.

`mode` selects the arithmetic of the projections: "f32" is the reference;
"fp8" is the control (inputs and weights of every projection rounded to
float8_e4m3 with one scale per operand; sums stay float32).  `fault` plants
one fault, for the tests of the comparison (`FAULTS`): "state_cut" (the
conv's ``s`` read as zero across every ``chunk``-th position of the prompt:
the state lost at a chunk boundary), "stale_state" (``s`` before the sequence
is not zero: a slot not reset at admission; it takes the sequence's own
first values), "no_k_rope", "no_qk_norm", "kv_head_mod" (query head ``h``
reads K/V head ``h % kv_heads``), "top3" (the last chosen expert dropped).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("state_cut", "stale_state", "no_k_rope", "no_qk_norm",
          "kv_head_mod", "top3")


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


def _rope(x, inv_freq):
    """Rotate the last axis of ``x`` (s, heads, d) by its row's position."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _short_conv(u, lp, cfg, mode, fault, cuts):
    s_len, d = u.shape
    taps = cfg["conv_L_cache"]
    bcz = _proj(u, lp["conv_in_weight"], mode)
    b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    s = b * z
    # the values before the sequence: zeros
    before = jnp.zeros((taps - 1, d), jnp.float32)
    if fault == "stale_state":
        before = s[:taps - 1]
    run = jnp.concatenate([before, s])
    w = lp["conv_weight"].astype(jnp.float32)               # (d, taps)
    t = jnp.arange(s_len)
    conv = jnp.zeros_like(s)
    for j in range(taps):
        term = run[j:j + s_len] * w[:, j]
        if fault == "state_cut":
            # tap j of position t reads position t - (taps - 1) + j: lost if
            # a cut lies between the two
            src = t - (taps - 1) + j
            lost = jnp.zeros((s_len,), bool)
            for cut in cuts:
                lost = lost | ((src < cut) & (t >= cut))
            term = jnp.where(lost[:, None], 0.0, term)
        conv = conv + term
    return _proj(c * conv, lp["conv_out_weight"], mode)


def _attention(u, lp, cfg, mode, fault, q_block):
    s = u.shape[0]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    eps = cfg["norm_eps"]
    inv_freq = 1.0 / cfg["rope_theta"] ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    q = _proj(u, lp["q_weight"], mode).reshape(s, h, d)
    k = _proj(u, lp["k_weight"], mode).reshape(s, kvh, d)
    v = _proj(u, lp["v_weight"], mode).reshape(s, kvh, d)
    if fault != "no_qk_norm":
        q = _rms_norm(q, lp["q_ln_gamma"], eps)
        k = _rms_norm(k, lp["k_ln_gamma"], eps)
    q = _rope(q, inv_freq)
    if fault != "no_k_rope":
        k = _rope(k, inv_freq)
    # the K/V head each query head reads: h // (h / kvh)
    reads = np.arange(h) % kvh if fault == "kv_head_mod" \
        else np.arange(h) // (h // kvh)
    k, v = k[:, reads], v[:, reads]                          # (s, h, d)
    n_blocks = -(-s // q_block)

    def block(args):
        qb, first = args
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / d ** 0.5
        seen = jnp.arange(s)[None, :] <= first + jnp.arange(q_block)[:, None]
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    # the queries a block at a time, so that the scores fit: the same sums
    attn = jax.lax.map(block, (
        jnp.pad(q, ((0, n_blocks * q_block - s), (0, 0), (0, 0))).reshape(
            n_blocks, q_block, h, d),
        jnp.arange(n_blocks) * q_block))
    return _proj(attn.reshape(n_blocks * q_block, h * d)[:s],
                 lp["attn_out_weight"], mode)


def _expert_layer(u, lp, cfg, mode, fault):
    top_k = cfg["num_experts_per_tok"] - (fault == "top3")
    sc = jax.nn.sigmoid(jnp.dot(
        u, lp["moe_router_weight"].astype(jnp.float32).T, precision=HIGHEST))
    _, chosen = jax.lax.top_k(
        sc + lp["moe_router_bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(sc, chosen, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-6)
    w = w * cfg["routed_scaling_factor"]

    def add(y, e):
        # the weight of expert e for each row: 0 where it was not chosen.
        # The banks are stacked (in, out): E_e(u) as a SwiGLU of (out, in)
        # matrices, an expert at a time so that no float32 copy of a bank
        # is made
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=1)
        return y + w_e[:, None] * _swiglu(
            u, lp["moe_gate"][e].T, lp["moe_up"][e].T, lp["moe_down"][e].T,
            mode), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(u),
                        jnp.arange(cfg["num_experts"]))
    return y


#: the keys of a configuration that a layer reads
_KEYS = ("conv_L_cache", "hidden_size", "norm_eps", "num_attention_heads",
         "num_key_value_heads", "rope_theta", "num_experts",
         "num_experts_per_tok", "routed_scaling_factor")


@functools.partial(jax.jit, static_argnames=("kind", "dense", "frozen",
                                             "mode", "fault", "q_block",
                                             "cuts"))
def _layer(x, lp, *, kind, dense, frozen, mode, fault, q_block, cuts):
    cfg = dict(frozen)
    eps = cfg["norm_eps"]
    u = _rms_norm(x, lp["ln1_gamma"], eps)
    if kind == "conv":  # mxlint: disable=trace-py-branch -- `kind` is a static argument of the jitted layer
        x = x + _short_conv(u, lp, cfg, mode, fault, cuts)
    else:
        x = x + _attention(u, lp, cfg, mode, fault, q_block)
    u = _rms_norm(x, lp["ln2_gamma"], eps)
    if dense:  # mxlint: disable=trace-py-branch -- `dense` is a static argument of the jitted layer
        return x + _swiglu(u, lp["ffn_gate_weight"], lp["ffn_up_weight"],
                           lp["ffn_down_weight"], mode)
    return x + _expert_layer(u, lp, cfg, mode, fault)


def hidden(params, tokens, cfg, mode="f32", fault=None, q_block=256,
           cuts=()):
    """(s,) token ids of ONE sequence -> (s, hidden) states before the final
    norm.  The parameters may be of any float dtype: each is raised to
    float32 where it is used.  ``cuts`` are the positions the "state_cut"
    fault loses the conv state at."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed_weight"], tokens, axis=0).astype(jnp.float32)
    frozen = tuple((k, cfg[k]) for k in _KEYS)
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(cfg["layer_types"]):
            p = "layer%d_" % i
            lp = {n[len(p):]: v for n, v in params.items()
                  if n.startswith(p)}
            x = _layer(x, lp, kind=kind, dense=i < cfg["num_dense_layers"],
                       frozen=frozen, mode=mode, fault=fault,
                       q_block=q_block, cuts=tuple(cuts))
    return x


def logits_of(params, x, cfg, mode="f32"):
    """(n, hidden) states before the final norm -> (n, vocab) logits."""
    head = params.get("pred_weight", params["embed_weight"])
    return _proj(_rms_norm(x, params["final_ln_gamma"], cfg["norm_eps"]),
                 head, mode)


def forward(params, tokens, cfg, mode="f32", fault=None, cuts=()):
    """(s,) token ids -> (s, vocab) logits: the full forward pass."""
    return logits_of(params, hidden(params, tokens, cfg, mode, fault,
                                    cuts=cuts), cfg, mode)


# -- serving ------------------------------------------------------------------


def served_gaps(params, prompt, out_tokens, cfg, pad_to, control=None,
                q_block=256, chunk=None):
    """For one request, the gap by which each served token's reference logit
    lies below the reference's best, (served tokens,) float32; with
    ``control`` ("fp8", or one of `FAULTS`), also the gaps of the tokens that
    arithmetic puts first at the same positions (else None).  The row is
    padded to ``pad_to`` positions (causal: padding changes nothing before
    it).  ``chunk`` is the prefill chunk whose boundaries inside the prompt
    the "state_cut" fault cuts at."""
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
    cuts = tuple(range(chunk, n, chunk)) if chunk else ()
    first = jnp.argmax(logits_of(
        params, hidden(params, row, cfg, mode, fault, q_block, cuts)[at],
        cfg, mode), -1)
    return gap_of(served), gap_of(first)
