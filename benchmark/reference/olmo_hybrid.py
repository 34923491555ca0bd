"""The plain reference of the OLMo hybrid language-model block: the forward
pass in straightforward `jax.numpy` and float32 at the highest matmul
precision.  No kernels, no cache, no batching, and nothing of `mxnet_tpu`: it
reads a parameter dict under the names `HybridDeltaKVModel.param_shapes()`
gives and a configuration under the keys of the published `config.json`
(`model_type: olmo_hybrid`).

The equations, for a row ``x`` at position ``t`` in layer ``i`` (RMSNorm
``n(.)`` with a learned gain and ``rms_norm_eps``, no biases anywhere; OLMo
3's block, each sublayer normed after it and no norm on its input):

    h = x + n_op(Op_i(x));   y = h + n_ffn(W_down(silu(W_gate h) * W_up h))
    logits = n_out(y) W_head^T

* ``Op`` of a "full_attention" layer: ``q = n_q(x W_q^T)`` and ``k = n_k(x
  W_k^T)``, each norm over the whole projection (``num_attention_heads`` x
  head and ``num_key_value_heads`` x head, head = ``hidden_size /
  num_attention_heads``); ``v = x W_v^T``; causal softmax at
  ``1/sqrt(head)``, query head ``h`` reading K/V head ``h // (heads /
  kv_heads)``; ``W_o``.  No rotary positions: the source's
  ``rope_parameters.rope_theta`` is null.
* ``Op`` of a "linear_attention" layer (Gated DeltaNet with an output gate,
  short convolutions and ``linear_allow_neg_eigval``): ``[q, k, v] =
  silu(conv([x W_q^T, x W_k^T, x W_v^T]))``, each channel convolved
  causally over ``linear_conv_kernel_dim`` taps with zeros before the
  sequence starts; per head (``linear_num_key_heads`` = value heads)
  ``q <- q / sqrt(|q|^2 + 1e-6)`` and ``k`` likewise; ``beta = 2
  sigmoid(x W_b^T)``; ``g = -exp(a_log) softplus(x W_a^T + dt_bias)``; the
  state ``S`` (``linear_key_head_dim`` x ``linear_value_head_dim``) a head,
  zero at position 0, position by position::

      S <- exp(g_t) S;  u_t = beta_t (v_t - S^T k_t);  S <- S + k_t u_t^T
      o_t = S^T q_t / sqrt(linear_key_head_dim)

  then ``Op(x) = (n_head(o) * silu(x W_g^T)) W_o^T``, ``n_head`` an RMSNorm
  over each head's value width with one gain the heads share.

The program computes the linear layers in sub-chunks (the chunkwise-parallel
form); this runs the recurrence a position at a time, so the two reach the
state by different roads.  Departures: the full layers' queries are taken in
blocks of ``q_block`` positions so that the scores fit (the same sums).  No
other is intended.

`mode` selects the arithmetic of the projections: "f32" is the reference;
"fp8" is the control (inputs and weights of every projection rounded to
float8_e4m3 with one scale per operand; sums stay float32).  `fault` plants
one fault, for the tests of the comparison (`FAULTS`): "beta_not_doubled"
(``beta = sigmoid``, no negative eigenvalue), "no_decay" (``exp(g) = 1``),
"state_bf16" (``S`` rounded to bfloat16 after every position, a precision
below the configuration's), "conv_shifted" (the taps read one position
earlier), "no_l2norm" (q and k left at their length), "no_output_gate",
"no_qk_norm" (the full layers' q/k norms dropped).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("beta_not_doubled", "no_decay", "state_bf16", "conv_shifted",
          "no_l2norm", "no_output_gate", "no_qk_norm")


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


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_recurrence(q, k, v, g, beta, state=None, round_state=False):
    """The gated delta rule a position at a time: ``q``, ``k`` (s, H, dk),
    ``v`` (s, H, dv), ``g``, ``beta`` (s, H), ``state`` (H, dk, dv) or zeros.
    Returns (o (s, H, dv), the last state).  ``round_state`` rounds ``S`` to
    bfloat16 after every position (a planted fault)."""
    s, h, dk = k.shape
    if state is None:
        state = jnp.zeros((h, dk, v.shape[-1]), jnp.float32)

    def step(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        st = st * jnp.exp(g_t)[:, None, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", st, k_t,
                                             precision=HIGHEST))
        st = st + k_t[:, :, None] * u[:, None, :]
        o = jnp.einsum("hkv,hk->hv", st, q_t, precision=HIGHEST) / dk ** 0.5
        if round_state:
            st = st.astype(jnp.bfloat16).astype(jnp.float32)
        return st, o

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def _linear(x, lp, cfg, mode, fault):
    s = x.shape[0]
    h, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    taps = cfg["linear_conv_kernel_dim"]
    mixed = jnp.concatenate([_proj(x, lp[n + "_weight"], mode)
                             for n in ("q", "k", "v")], axis=-1)
    if fault == "conv_shifted":
        mixed = jnp.pad(mixed, ((1, 0), (0, 0)))[:s]
    w = lp["conv_weight"].astype(jnp.float32)              # (channels, taps)
    run = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    y = jax.nn.silu(sum(run[j:j + s] * w[:, j] for j in range(taps)))
    q = y[:, :h * dk].reshape(s, h, dk)
    k = y[:, h * dk:2 * h * dk].reshape(s, h, dk)
    v = y[:, 2 * h * dk:].reshape(s, h, dv)
    if fault != "no_l2norm":
        q, k = _l2norm(q), _l2norm(k)
    beta = jax.nn.sigmoid(jnp.dot(x, lp["b_weight"].astype(jnp.float32).T,
                                  precision=HIGHEST))
    if cfg["linear_allow_neg_eigval"] and fault != "beta_not_doubled":
        beta = 2.0 * beta
    g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        jnp.dot(x, lp["a_weight"].astype(jnp.float32).T, precision=HIGHEST)
        + lp["dt_bias"].astype(jnp.float32))
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    o, _ = delta_recurrence(q, k, v, g, beta,
                            round_state=fault == "state_bf16")
    o = _rms_norm(o, lp["o_norm_gamma"], cfg["rms_norm_eps"])
    if fault != "no_output_gate":
        o = o * jax.nn.silu(_proj(x, lp["gate_weight"], mode)).reshape(
            s, h, dv)
    return _proj(o.reshape(s, h * dv), lp["attn_out_weight"], mode)


def _full(x, lp, cfg, mode, fault, q_block):
    s = x.shape[0]
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    eps = cfg["rms_norm_eps"]
    q = _proj(x, lp["q_weight"], mode)
    k = _proj(x, lp["k_weight"], mode)
    if fault != "no_qk_norm":
        q = _rms_norm(q, lp["q_norm_gamma"], eps)
        k = _rms_norm(k, lp["k_norm_gamma"], eps)
    reads = np.arange(h) // (h // kvh)
    k = k.reshape(s, kvh, d)[:, reads]
    v = _proj(x, lp["v_weight"], mode).reshape(s, kvh, d)[:, reads]
    n_blocks = -(-s // q_block)

    def block(args):
        qb, first = args
        t = first + jnp.arange(q_block)[:, None]          # query positions
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) / d ** 0.5
        sc = jnp.where((jnp.arange(s)[None, :] <= t)[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

    # the queries a block at a time, so that the scores fit: the same sums
    attn = jax.lax.map(block, (
        jnp.pad(q.reshape(s, h, d),
                ((0, n_blocks * q_block - s), (0, 0), (0, 0))).reshape(
                    n_blocks, q_block, h, d),
        jnp.arange(n_blocks) * q_block))
    attn = attn.reshape(n_blocks * q_block, h * d)[:s]
    return _proj(attn, lp["attn_out_weight"], mode)


#: the keys of a configuration that a layer reads
_KEYS = ("hidden_size", "rms_norm_eps", "num_attention_heads",
         "num_key_value_heads", "linear_num_value_heads",
         "linear_key_head_dim", "linear_value_head_dim",
         "linear_conv_kernel_dim", "linear_allow_neg_eigval")


@functools.partial(jax.jit, static_argnames=("linear", "frozen", "mode",
                                             "fault", "q_block"))
def _layer(x, lp, *, linear, frozen, mode, fault, q_block):
    cfg = dict(frozen)
    eps = cfg["rms_norm_eps"]
    if linear:  # mxlint: disable=trace-py-branch -- `linear` is a static argument of the jitted layer
        op = _linear(x, lp, cfg, mode, fault)
    else:
        op = _full(x, lp, cfg, mode, fault, q_block)
    h = x + _rms_norm(op, lp["post_op_ln_gamma"], eps)
    ffn = _proj(jax.nn.silu(_proj(h, lp["ffn_gate_weight"], mode))
                * _proj(h, lp["ffn_up_weight"], mode),
                lp["ffn_down_weight"], mode)
    return h + _rms_norm(ffn, lp["post_ffn_ln_gamma"], eps)


def hidden(params, tokens, cfg, mode="f32", fault=None, q_block=128):
    """(s,) token ids of ONE sequence -> (s, hidden) states before the final
    norm.  The parameters may be of any float dtype: each is raised to
    float32 where it is used."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = jnp.take(params["embed_weight"], tokens, axis=0).astype(jnp.float32)
    frozen = tuple((k, cfg[k]) for k in _KEYS)
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(cfg["layer_types"]):
            p = "layer%d_" % i
            lp = {n[len(p):]: v for n, v in params.items()
                  if n.startswith(p)}
            x = _layer(x, lp, linear=kind == "linear_attention",
                       frozen=frozen, mode=mode, fault=fault,
                       q_block=q_block)
    return x


def logits_of(params, x, cfg, mode="f32"):
    """(n, hidden) states before the final norm -> (n, vocab) logits."""
    with jax.default_matmul_precision("highest"):
        return _proj(_rms_norm(x, params["final_ln_gamma"],
                               cfg["rms_norm_eps"]),
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
