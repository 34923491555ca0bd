"""Operations and bytes of the MiMo-V2 block (window attention layers with a
sink beside full grouped-query layers, q/k heads of ``head_dim`` and v heads
of ``v_head_dim``, a dense SwiGLU or a share of a sparse expert layer), from
shapes: the keys of the published `config.json`, with the cut
`hybrid_layer_pattern`, `moe_layer_freq`, `experts_held` and `vocab_size`.

Kept with the benchmark so that a roofline share or an MFU reads the same
work whatever later implements it.  Only what the algorithm needs counts: the
matmuls' multiply-adds, attention over each token's own context (a full
layer's whole prefix, a window layer's last ``sliding_window`` positions).
Routed experts are counted for the (row, expert) pairs the program really
routed to experts held here, never for an expected share.
"""
from __future__ import annotations


def kinds(cfg):
    """Each layer's kind: 0 full, 1 window (`hybrid_layer_pattern`)."""
    return cfg["hybrid_layer_pattern"]


def full_layers(cfg):
    return sum(1 for k in kinds(cfg) if k == 0)


def window_layers(cfg):
    return sum(1 for k in kinds(cfg) if k == 1)


def kv_heads(cfg, kind):
    return cfg["swa_num_key_value_heads"] if kind == 1 \
        else cfg["num_key_value_heads"]


def attn_params(cfg, kind):
    """Matmul parameters of one attention layer of ``kind``: q, k (at the
    q/k width), v (at the v width) and the out-projection."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, vd = cfg["head_dim"], cfg["v_head_dim"]
    kv = kv_heads(cfg, kind)
    return d * h * hd + d * kv * (hd + vd) + h * vd * d


def expert_params(cfg):
    """Matmul parameters of one expert: its three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg, layer):
    """Matmul parameters every token goes through in ``layer``, the routed
    experts apart: attention, then the dense FFN or the router over all the
    experts."""
    d = cfg["hidden_size"]
    op = attn_params(cfg, kinds(cfg)[layer])
    if cfg["moe_layer_freq"][layer] == 0:
        return op + 3 * d * cfg["intermediate_size"]
    return op + d * cfg["n_routed_experts"]


def pair_flops(cfg):
    """FLOPs of one (query, key) pair of one query head: QK^T at the q/k
    width and PV at the v width, 2 FLOPs a multiply-add."""
    return 2 * (cfg["head_dim"] + cfg["v_head_dim"])


def window_context(cfg, position):
    """Keys a token at ``position`` (0-based) attends to in a window
    layer."""
    return min(position + 1, cfg["sliding_window"])


def prompt_window_context(cfg, p):
    """The window-layer keys of a whole prompt of ``p`` tokens, summed."""
    w = cfg["sliding_window"]
    if p <= w:
        return p * (p + 1) // 2
    return w * (w + 1) // 2 + (p - w) * w


def serve_flops(cfg, tokens, context_sum, window_context_sum, pairs,
                head_rows):
    """Forward FLOPs of serving ``tokens`` prompt and decode tokens whose
    full-layer contexts sum to ``context_sum`` and window-layer contexts to
    ``window_context_sum``: the layers' matmuls, the routed experts for the
    ``pairs`` (row, expert) pairs that fell on experts held here, attention
    over each token's contexts, and the head once for each sampled row."""
    dense = sum(layer_params(cfg, i) for i in range(len(kinds(cfg))))
    h = cfg["num_attention_heads"]
    return (2 * dense * tokens + 2 * expert_params(cfg) * pairs
            + pair_flops(cfg) * h * (full_layers(cfg) * context_sum
                                     + window_layers(cfg)
                                     * window_context_sum)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * head_rows)


def gqa_decode(cfg, block_tokens, rows, itemsize=2):
    """(FLOPs, bytes) of the full layers' decode attention over
    ``block_tokens`` cached positions in all (whole live blocks, every full
    layer: what a paged kernel has to read, each once) for ``rows`` (row,
    layer) queries: K at the q/k width and V at the v width of the K/V heads
    in the pool's dtype, each query in and its output out."""
    h, hd, vd = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["v_head_dim"])
    flops = pair_flops(cfg) * h * block_tokens
    nbytes = (cfg["num_key_value_heads"] * (hd + vd) * block_tokens
              + rows * h * (hd + vd)) * itemsize
    return float(flops), float(nbytes)


def full_prefill(cfg, start, tokens, itemsize=2):
    """(FLOPs, bytes) of one chunk's attention in all full layers: its
    ``tokens`` real tokens causal over a cached prefix of ``start``, every
    query head; a layer's K and V rows of prefix and chunk read once at the
    K/V heads' widths, and each query head's queries in and outputs out."""
    h, hd, vd = (cfg["num_attention_heads"], cfg["head_dim"],
                 cfg["v_head_dim"])
    layers = full_layers(cfg)
    context = tokens * start + tokens * (tokens + 1) / 2.0
    nbytes = layers * ((start + tokens) * cfg["num_key_value_heads"]
                       * (hd + vd) + tokens * h * (hd + vd)) * itemsize
    return float(pair_flops(cfg) * h * layers * context), float(nbytes)
