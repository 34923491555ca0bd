"""Operations and bytes of the LFM2-MoE block (gated short convolutions,
grouped-query attention, a sparse expert layer with every expert held), from
shapes: the keys of the published `config.json`, with the cut `layer_types`.

Kept with the benchmark so that a roofline share or an MFU reads the same
work whatever later implements it.  Only what the algorithm needs counts:
the matmuls' multiply-adds, the convolution's taps, attention over each
token's own context.  Routed experts are counted for the (row, expert) pairs
the program really routed, never for an expected share.
"""
from __future__ import annotations


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_params(cfg):
    """Matmul parameters of one conv layer's operator: the in-projection to
    3 x hidden and the out-projection."""
    d = cfg["hidden_size"]
    return 3 * d * d + d * d


def attn_params(cfg):
    """Matmul parameters of one attention layer's operator: q, k, v and the
    out-projection (k and v at the K/V heads' width)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d


def expert_params(cfg):
    """Matmul parameters of one expert: its three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg, layer):
    """Matmul parameters every token goes through in ``layer``, the routed
    experts apart: the operator, then the dense FFN or the router."""
    d = cfg["hidden_size"]
    op = conv_params(cfg) if cfg["layer_types"][layer] == "conv" \
        else attn_params(cfg)
    if layer < cfg["num_dense_layers"]:
        return op + 3 * d * cfg["intermediate_size"]
    return op + d * cfg["num_experts"]


def attn_layers(cfg):
    return sum(1 for k in cfg["layer_types"] if k == "full_attention")


def conv_layers(cfg):
    return sum(1 for k in cfg["layer_types"] if k == "conv")


def attn_flops_token(cfg, context):
    """Forward FLOPs of causal attention, all attention layers, for ONE
    token that attends to ``context`` positions: QK^T and PV over the head's
    size, every query head, 2 FLOPs a multiply-add."""
    return (4 * attn_layers(cfg) * cfg["num_attention_heads"]
            * head_dim(cfg) * context)


def tap_flops_token(cfg):
    """The convolutions' own multiply-adds for one token: ``conv_L_cache``
    taps a channel in every conv layer."""
    return 2 * conv_layers(cfg) * cfg["conv_L_cache"] * cfg["hidden_size"]


def pairs_per_row(cfg):
    """(row, expert) pairs one token routes: every expert is held here."""
    return cfg["num_experts_per_tok"] * (len(cfg["layer_types"])
                                         - cfg["num_dense_layers"])


def serve_flops(cfg, tokens, context_sum, pairs, head_rows):
    """Forward FLOPs of serving ``tokens`` prompt and decode tokens whose
    contexts sum to ``context_sum``: the layers' matmuls and taps, the
    routed experts for the ``pairs`` (row, expert) pairs routed, attention
    over each token's context, and the head once for each sampled row."""
    dense = sum(layer_params(cfg, i) for i in range(len(cfg["layer_types"])))
    return ((2 * dense + tap_flops_token(cfg)) * tokens
            + 2 * expert_params(cfg) * pairs
            + attn_flops_token(cfg, context_sum)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * head_rows)


def gqa_decode(cfg, block_tokens, rows, itemsize=2):
    """(FLOPs, bytes) of ONE layer's decode attention over ``block_tokens``
    cached positions in all (whole live blocks: what a paged kernel has to
    read, each once) for ``rows`` queries: K and V at the K/V heads' width
    in the pool's dtype, each row's query in and its output out."""
    h, hd = cfg["num_attention_heads"], head_dim(cfg)
    flops = 4.0 * h * hd * block_tokens
    nbytes = (2 * cfg["num_key_value_heads"] * hd * block_tokens
              + 2 * rows * h * hd) * itemsize
    return flops, float(nbytes)


def expert_products(cfg, pairs, hits, itemsize=2):
    """(FLOPs, bytes) of the routed experts' three products for ``pairs``
    (row, expert) pairs spread over ``hits`` (layer, expert) matrix sets:
    each such expert's three matrices read once, each pair's row in and
    out."""
    flops = 2.0 * expert_params(cfg) * pairs
    nbytes = (expert_params(cfg) * hits
              + 2 * cfg["hidden_size"] * pairs) * itemsize
    return flops, float(nbytes)
