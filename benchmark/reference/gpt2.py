"""The plain reference: GPT-2's forward pass, loss, gradients and Adam in
straightforward `jax.numpy` and float32, at the highest matmul precision.

No kernels, no cache, no batching tricks, and nothing of `mxnet_tpu`: it reads
a parameter dict under the names `benchmark/weights.py` draws and follows the
published block (pre-LN, LayerNorm eps 1e-5, `gelu_new` i.e. the tanh form,
learned positions, biases everywhere).  One departure from GPT-2, stated in
the configuration files: the output head `pred_weight`/`pred_bias` is a
matrix of its own, not the embedding.

`mode` selects the arithmetic of the projections: "f32" is the reference;
"fp8" is the control (inputs and weights of every projection rounded to
float8_e4m3 with one scale per tensor, as an fp8 recipe would; sums stay
float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

_LAYER_LEAVES = ("ln1_gamma", "ln1_beta", "ln2_gamma", "ln2_beta",
                 "q_weight", "q_bias", "k_weight", "k_bias",
                 "v_weight", "v_bias", "attn_out_weight", "attn_out_bias",
                 "ffn1_weight", "ffn1_bias", "ffn2_weight", "ffn2_bias")
_TOP_LEAVES = ("embed_weight", "pos_embed_weight", "final_ln_gamma",
               "final_ln_beta", "pred_weight", "pred_bias")


def stack(params, n_layer):
    """{name: array} -> {"top": {...}, "layers": {leaf: (L, ...)}} in float32,
    so that the layers run as one `lax.scan` and compile once."""
    top = {n: jnp.asarray(params[n], jnp.float32) for n in _TOP_LEAVES}
    layers = {leaf: jnp.stack([jnp.asarray(params["layer%d_%s" % (i, leaf)],
                                           jnp.float32)
                               for i in range(n_layer)])
              for leaf in _LAYER_LEAVES}
    return {"top": top, "layers": layers}


def leaf_names(n_layer):
    """Flat names in the order `leaf_vector` lays numbers out."""
    names = list(_TOP_LEAVES)
    for leaf in _LAYER_LEAVES:
        names += ["layer%d_%s" % (i, leaf) for i in range(n_layer)]
    return names


def leaf_norms(tree):
    """L2 norm of every leaf of a stacked tree, as one vector ordered like
    `leaf_names`."""
    out = [jnp.sqrt(jnp.sum(jnp.square(tree["top"][n]))) [None]
           for n in _TOP_LEAVES]
    for leaf in _LAYER_LEAVES:
        a = tree["layers"][leaf]
        out.append(jnp.sqrt(jnp.sum(jnp.square(a.reshape(a.shape[0], -1)),
                                    axis=1)))
    return jnp.concatenate(out)


def _round(x, mode):
    if mode == "f32":
        return x
    if mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 448.0
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
        # straight-through: the rounding has no gradient of its own
        return x + jax.lax.stop_gradient(q * scale - x)
    raise ValueError("unknown arithmetic %r" % (mode,))


def _proj(x, w, b, mode):
    """x (n, in) @ w (out, in)^T + b."""
    return jnp.dot(_round(x, mode), _round(w, mode).T, precision=HIGHEST) + b


def _layer_norm(x, gamma, beta, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(x, lp, n_head, eps, mode):
    b, s, e = x.shape
    d = e // n_head
    h = _layer_norm(x, lp["ln1_gamma"], lp["ln1_beta"], eps).reshape(-1, e)

    def heads(name):
        y = _proj(h, lp[name + "_weight"], lp[name + "_bias"], mode)
        return y.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)

    q, k, v = heads("q"), heads("k"), heads("v")
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        precision=HIGHEST) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v,
                      precision=HIGHEST)
    attn = attn.transpose(0, 2, 1, 3).reshape(-1, e)
    x = x + _proj(attn, lp["attn_out_weight"], lp["attn_out_bias"],
                  mode).reshape(b, s, e)
    h = _layer_norm(x, lp["ln2_gamma"], lp["ln2_beta"], eps).reshape(-1, e)
    f = _gelu_new(_proj(h, lp["ffn1_weight"], lp["ffn1_bias"], mode))
    return x + _proj(f, lp["ffn2_weight"], lp["ffn2_bias"],
                     mode).reshape(b, s, e)


def hidden(tree, tokens, n_head, eps, mode="f32"):
    """(b, s) token ids -> (b, s, e) final hidden states, after the last
    LayerNorm."""
    top = tree["top"]
    s = tokens.shape[1]
    x = jnp.take(top["embed_weight"], tokens, axis=0) \
        + top["pos_embed_weight"][0, :s]

    @jax.checkpoint
    def body(x, lp):
        return _block(x, lp, n_head, eps, mode), None

    x, _ = jax.lax.scan(body, x, tree["layers"])
    return _layer_norm(x, top["final_ln_gamma"], top["final_ln_beta"], eps)


def logits_of(tree, x, mode="f32"):
    """(n, e) hidden rows -> (n, vocab) logits."""
    return _proj(x, tree["top"]["pred_weight"], tree["top"]["pred_bias"],
                 mode)


def nll(tree, tokens, labels, n_head, eps, vocab, mode="f32"):
    """Per-token negative log-likelihood, (b*s,).  Rows of the table past
    ``vocab`` (padding, if the table was padded) take no part."""
    x = hidden(tree, tokens, n_head, eps, mode)
    logits = logits_of(tree, x.reshape(-1, x.shape[-1]), mode)[:, :vocab]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=1)[:, 0]


# -- training -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "vocab",
                                              "mode"))
def _micro_grad(tree, tokens, labels, n_head, eps, vocab, mode):
    def loss(t):
        per_token = nll(t, tokens, labels, n_head, eps, vocab, mode)
        return jnp.sum(per_token), jnp.sum(per_token)

    (_, total), grads = jax.value_and_grad(loss, has_aux=True)(tree)
    return total, grads


@jax.jit
def _accumulate(acc, grads):
    return jax.tree_util.tree_map(jnp.add, acc, grads)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps"),
                   donate_argnums=(0, 1, 2))
def _adam(tree, m, v, grads, t, lr, b1, b2, eps):
    """Adam as the configuration states it: bias-corrected step size,
    epsilon outside the root, no weight decay."""
    lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v,
                               grads)
    tree = jax.tree_util.tree_map(
        lambda p, a, c: p - lr_t * a / (jnp.sqrt(c) + eps), tree, m, v)
    return tree, m, v


@jax.jit
def _scaled(tree, k):
    return jax.tree_util.tree_map(lambda a: a * k, tree)


@jax.jit
def _change_norms(new, old):
    return leaf_norms(jax.tree_util.tree_map(jnp.subtract, new, old))


def train_readings(params, batches, cfg, opt, steps=3, micro_rows=2,
                   mode="f32"):
    """Follow ``steps`` training steps from ``params`` on ``batches`` =
    (tokens (n, B, S), labels (n, B, S)).

    The loss of a step is the sum of the per-token NLL over a row, averaged
    over the rows (the gradient the optimizer gets is d(sum NLL)/B), as the
    configuration states.  Returns host numbers: the mean per-token loss of
    every step, the per-leaf norm of the first gradient, and the per-leaf
    norm of the parameters' change after the last step."""
    n_head, eps, vocab = cfg["n_head"], cfg["layer_norm_epsilon"], \
        cfg["vocab_size"]
    tree = stack(params, cfg["n_layer"])
    start = jax.tree_util.tree_map(jnp.copy, tree)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, tree)
    m, v = zeros, jax.tree_util.tree_map(jnp.zeros_like, tree)
    tokens, labels = batches
    rows = tokens.shape[1]
    losses, grad_norms = [], None
    for step in range(steps):
        acc, total = None, 0.0
        for lo in range(0, rows, micro_rows):
            part, grads = _micro_grad(
                tree, tokens[step, lo:lo + micro_rows],
                labels[step, lo:lo + micro_rows], n_head, eps, vocab, mode)
            acc = grads if acc is None else _accumulate(acc, grads)
            total = total + part
        grads = _scaled(acc, 1.0 / rows)
        if step == 0:
            grad_norms = np.asarray(leaf_norms(grads))
        losses.append(float(total) / (rows * tokens.shape[2]))
        tree, m, v = _adam(tree, m, v, grads, jnp.float32(step + 1),
                           jnp.float32(opt["lr"]), opt["beta1"],
                           opt["beta2"], opt["epsilon"])
    change = np.asarray(_change_norms(tree, start))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "names": leaf_names(cfg["n_layer"])}


# -- serving --------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_head", "eps", "vocab",
                                              "control"))
def _served_gaps(tree, tokens, served, n_head, eps, vocab, control):
    """One padded row: ``tokens`` (1, S) is the prompt followed by the served
    tokens; ``served`` (S,) holds at position j the token that was served
    after position j, or -1 where none was.  Returns per position the gap by
    which the served token's reference logit lies below the reference's best
    and, if ``control`` names an arithmetic, the same gap for the token that
    arithmetic puts first."""
    x = hidden(tree, tokens, n_head, eps, "f32")[0]
    logits = logits_of(tree, x, "f32")[:, :vocab]
    best = jnp.max(logits, axis=-1)
    took = jnp.take_along_axis(logits, jnp.maximum(served, 0)[:, None],
                               axis=1)[:, 0]
    gap = jnp.where(served >= 0, best - took, 0.0)
    if control is None:
        return gap, jnp.zeros_like(gap)
    xc = hidden(tree, tokens, n_head, eps, control)[0]
    first = jnp.argmax(logits_of(tree, xc, control)[:, :vocab], axis=-1)
    ctook = jnp.take_along_axis(logits, first[:, None], axis=1)[:, 0]
    return gap, jnp.where(served >= 0, best - ctook, 0.0)


def served_gaps(tree, prompt, out_tokens, cfg, pad_to, control=None):
    """Widest gap over one request's served tokens (and the control's)."""
    n, m = len(prompt), len(out_tokens)
    row = np.zeros((1, pad_to), np.int32)
    row[0, :n] = prompt
    row[0, n:n + m - 1] = out_tokens[:-1]
    served = np.full((pad_to,), -1, np.int32)
    served[n - 1:n - 1 + m] = out_tokens
    gap, cgap = _served_gaps(tree, jnp.asarray(row), jnp.asarray(served),
                             cfg["n_head"], cfg["layer_norm_epsilon"],
                             cfg["vocab_size"], control)
    return float(jnp.max(gap)), float(jnp.max(cgap))
