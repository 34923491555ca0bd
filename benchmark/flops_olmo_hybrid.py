"""Operations and bytes of the OLMo hybrid block (gated delta-rule linear
attention layers beside full multi-head layers, a SwiGLU in every layer, an
untied head), from shapes: the keys of the published `config.json`, with the
cut `layer_types`.

Kept with the benchmark so that a roofline share reads the same work
whatever later implements it, and so that the sizes the cell is cut by
(weights, a slot's state, a token's K and V) are counted from the
configuration, not from the program.  Only what the algorithm needs counts:
the matmuls' parameters, the delta rule's products with its state.
"""
from __future__ import annotations


def linear_layers(cfg):
    return sum(1 for k in cfg["layer_types"] if k == "linear_attention")


def full_layers(cfg):
    return sum(1 for k in cfg["layer_types"] if k == "full_attention")


def head_dim(cfg):
    """A full layer's head: hidden / heads (the source gives none)."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _linear_shape(cfg):
    return (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def conv_channels(cfg):
    """A linear layer's convolution runs over q's, k's and v's channels."""
    h, dk, dv = _linear_shape(cfg)
    return h * (2 * dk + dv)


def linear_op_params(cfg):
    """Matmul parameters of one linear layer's operator: q, k, v, the output
    gate, the out-projection, and the decay's and write strength's
    projections (a head each)."""
    d = cfg["hidden_size"]
    h, dk, dv = _linear_shape(cfg)
    return d * h * (2 * dk + 3 * dv) + 2 * d * h


def full_op_params(cfg):
    """Matmul parameters of one full layer's operator: q, k, v and the
    out-projection (k and v at the K/V heads' width)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def layer_params(cfg, layer):
    """Matmul parameters every token goes through in ``layer``: the operator
    and the SwiGLU."""
    op = linear_op_params(cfg) \
        if cfg["layer_types"][layer] == "linear_attention" \
        else full_op_params(cfg)
    return op + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def param_count(cfg):
    """Every parameter of the cut model: the layers' matrices, their gains
    and the linear layers' taps and decay parameters, the embedding, the
    final gain and the head."""
    d = cfg["hidden_size"]
    h, _, dv = _linear_shape(cfg)
    taps = cfg["linear_conv_kernel_dim"]
    n = 0
    for i, kind in enumerate(cfg["layer_types"]):
        n += layer_params(cfg, i) + 2 * d           # and the two post-norms
        if kind == "linear_attention":
            n += conv_channels(cfg) * taps + 2 * h + dv
        else:
            n += (cfg["num_attention_heads"]
                  + cfg["num_key_value_heads"]) * head_dim(cfg)
    return n + 2 * cfg["vocab_size"] * d + d


def state_slot_bytes(cfg, itemsize=2):
    """One sequence's state: in every linear layer the conv's tail of
    ``linear_conv_kernel_dim - 1`` rows in the model's dtype and ``S`` in
    float32."""
    h, dk, dv = _linear_shape(cfg)
    tail = (cfg["linear_conv_kernel_dim"] - 1) * conv_channels(cfg) \
        * itemsize
    return linear_layers(cfg) * (tail + 4 * h * dk * dv)


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V of one position in every full layer."""
    return full_layers(cfg) * 2 * cfg["num_key_value_heads"] \
        * head_dim(cfg) * itemsize


def delta_flops_token(cfg):
    """The delta rule's products with the state for ONE position in every
    linear layer, a head at a time: the decay (dk x dv), S^T k, the
    rank-one write and S^T q (2 dk dv each)."""
    h, dk, dv = _linear_shape(cfg)
    return 7 * h * dk * dv * linear_layers(cfg)


def delta_decode(cfg, rows):
    """(FLOPs, bytes) of the delta rule's decode step for ``rows`` rows in
    every linear layer: each row's ``S`` read once and written once in
    float32, and its q, k (dk), v, o (dv), decay and write strength (a head
    each) in and out in float32."""
    h, dk, dv = _linear_shape(cfg)
    layers = linear_layers(cfg)
    nbytes = rows * layers * h * (2 * dk * dv + 2 * dk + 2 * dv + 2) * 4
    return float(rows * delta_flops_token(cfg)), float(nbytes)
