"""Weights and token batches, made on the device from `--seed`.

The benchmark makes them, not the program: the same call gives the program
its parameters (under `param_shapes()`' names) and the plain reference its
copy, so neither takes anything the other has made.
"""
from __future__ import annotations

import functools


def fold_seed(seed):
    """A PRNG key from any whole number (`--seed` may pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def lm_param_shapes(cfg):
    """{name: shape} of the repo's transformer LM at ``cfg``'s sizes, in a
    fixed order (`TransformerKVModel.param_shapes()` and
    `get_transformer_lm(...).list_arguments()` name the same set)."""
    e, f, v = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"], \
        cfg["vocab_size"]
    shapes = {"embed_weight": (v, e),
              "pos_embed_weight": (1, cfg["n_positions"], e)}
    for i in range(cfg["n_layer"]):
        p = "layer%d_" % i
        for ln in ("ln1", "ln2"):
            shapes[p + ln + "_gamma"] = (e,)
            shapes[p + ln + "_beta"] = (e,)
        for proj, (nh, nin) in (("q", (e, e)), ("k", (e, e)), ("v", (e, e)),
                                ("attn_out", (e, e)), ("ffn1", (f, e)),
                                ("ffn2", (e, f))):
            shapes[p + proj + "_weight"] = (nh, nin)
            shapes[p + proj + "_bias"] = (nh,)
    shapes["final_ln_gamma"] = (e,)
    shapes["final_ln_beta"] = (e,)
    shapes["pred_weight"] = (v, e)
    shapes["pred_bias"] = (v,)
    return shapes


@functools.lru_cache(maxsize=None)
def _maker(names, shapes, dtype, init_std):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(zip(names, shapes)):
            k = jax.random.fold_in(key, i)
            if name.endswith("_gamma"):
                # GPT-2 starts gains at 1; a little spread keeps every leaf's
                # gradient apart from its neighbours' in the comparison
                w = 1.0 + 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith(("_beta", "_bias")):
                w = 0.02 * jax.random.normal(k, shape, jnp.float32)
            elif name == "pos_embed_weight":
                w = 0.5 * init_std * jax.random.normal(k, shape, jnp.float32)
            else:
                w = init_std * jax.random.normal(k, shape, jnp.float32)
            out[name] = w.astype(dtype)
        return out

    return jax.jit(make)


def make_params(seed, shapes, dtype, init_std=0.02, device=None):
    """All parameters in one jitted call on the device.  The values are drawn
    in float32 and rounded to ``dtype`` once, so a float32 copy of a bfloat16
    set is `astype`, not a second draw."""
    import jax
    import jax.numpy as jnp

    names = tuple(shapes)
    fn = _maker(names, tuple(tuple(shapes[n]) for n in names),
                jnp.dtype(dtype).name, float(init_std))
    key = fold_seed(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(jax.random.fold_in(key, 0x77))


@functools.lru_cache(maxsize=None)
def _batcher(n, rows, seq, vocab):
    import jax
    import jax.numpy as jnp

    def make(key):
        toks = jax.random.randint(key, (n, rows, seq + 1), 0, vocab,
                                  jnp.int32)
        return toks[:, :, :-1], toks[:, :, 1:]

    return jax.jit(make)


def make_batches(seed, n, rows, seq, vocab, device=None):
    """``n`` batches of (rows, seq) token ids with their next-token labels
    (the labels of one row are its ids shifted by one, as in a corpus)."""
    import jax

    key = jax.random.fold_in(fold_seed(seed), 0xBA7C)
    if device is not None:
        key = jax.device_put(key, device)
    return _batcher(int(n), int(rows), int(seq), int(vocab))(key)
