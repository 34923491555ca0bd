"""Operations and bytes of the latent-attention / sparse-expert block, from
shapes (the keys of the published `config.json`, with this chip's share:
`experts_held`, the sliced `vocab_size`, the cut `num_hidden_layers`).

Kept with the benchmark so that a roofline share or an MFU reads the same
work whatever later implements it.  Attention is counted in the expanded
form (each head's own keys of nope + rope and values of v): an absorbed or a
re-expanding implementation is not credited for its extra operations.
Routed experts are counted for the (row, expert) pairs that really fell on
held experts, as the program counts them, never for an expected share.
"""
from __future__ import annotations


def attn_params(cfg):
    """Matmul parameters of one layer's attention: the five projections."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * (nope + rope)
            + d * (cfg["kv_lora_rank"] + rope)
            + cfg["kv_lora_rank"] * h * (nope + v) + h * v * d)


def expert_params(cfg):
    """Matmul parameters of one expert, routed or shared: its three
    matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg, layer):
    """Matmul parameters every token goes through in ``layer``, the routed
    experts apart: attention, then the dense FFN, or the router and the
    shared experts."""
    d = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        return attn_params(cfg) + 3 * d * cfg["intermediate_size"]
    return (attn_params(cfg) + d * cfg["n_routed_experts"]
            + cfg["n_shared_experts"] * expert_params(cfg))


def attn_flops_token(cfg, context):
    """Forward FLOPs of causal attention, all layers, for ONE token that
    attends to ``context`` positions: QK^T over nope + rope and PV over v,
    every head, 2 FLOPs a multiply-add."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
               + cfg["v_head_dim"]) * context)


def serve_flops(cfg, tokens, context_sum, held_pairs, head_rows):
    """Forward FLOPs of serving ``tokens`` prompt and decode tokens whose
    contexts sum to ``context_sum``: the layers' matmuls, the routed
    experts for the ``held_pairs`` (row, expert) pairs that fell on experts
    held here, attention over each token's context, and the head once for
    each sampled row."""
    dense = sum(layer_params(cfg, i)
                for i in range(cfg["num_hidden_layers"]))
    return (2 * dense * tokens + 2 * expert_params(cfg) * held_pairs
            + attn_flops_token(cfg, context_sum)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"] * head_rows)


def latent_row_bytes(cfg, itemsize=2):
    """Bytes one cached token holds in one layer: ``c_kv`` and ``k_pe``."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize


def latent_decode(cfg, block_tokens, rows, itemsize=2):
    """(FLOPs, bytes) of ONE layer's decode attention over ``block_tokens``
    cached positions in all (whole live blocks: what a paged kernel has to
    read, each once) for ``rows`` queries: expanded-form FLOPs; the latent
    rows in, each row's absorbed query in and its latent-space output
    out."""
    h = cfg["num_attention_heads"]
    flops = 2.0 * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                       + cfg["v_head_dim"]) * block_tokens
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    nbytes = (latent_row_bytes(cfg, itemsize) * block_tokens
              + rows * h * (width + cfg["kv_lora_rank"]) * itemsize)
    return flops, float(nbytes)


def expert_products(cfg, pairs, hits, itemsize=2):
    """(FLOPs, bytes) of the routed experts' three products for ``pairs``
    (row, expert) pairs spread over ``hits`` (layer, expert) matrices sets:
    each such expert's three matrices read once, each pair's row in and
    out."""
    flops = 2.0 * expert_params(cfg) * pairs
    nbytes = (expert_params(cfg) * hits
              + 2 * cfg["hidden_size"] * pairs) * itemsize
    return flops, float(nbytes)
