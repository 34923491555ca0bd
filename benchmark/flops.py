"""Operations and bytes the algorithms need, computed from shapes.

Kept with the benchmark so that a roofline share or an MFU reads the same
work whatever later implements it.  Recomputed operations never count.
"""
from __future__ import annotations


def matmul_params(cfg):
    """Parameters that take part in a matrix multiplication: the four
    attention projections and the two FFN projections of every layer, and the
    output head.  The embedding and position tables are gathers."""
    e = cfg["n_embd"]
    f = cfg["n_inner"] or 4 * e
    return cfg["n_layer"] * (4 * e * e + 2 * e * f) + e * cfg["vocab_size"]


def attn_flops_token(cfg, context):
    """Forward FLOPs of causal attention for ONE token that attends to
    ``context`` positions: QK^T and PV, 2 FLOPs a multiply-add each."""
    return 4 * cfg["n_layer"] * cfg["n_embd"] * context


def train_flops_token(cfg, seq):
    """Forward + backward FLOPs per trained token at sequence length
    ``seq``: 6 per matmul parameter, and three times the causal attention
    forward, whose mean context is seq/2 (the arithmetic of
    `tools/benchmark_transformer.py:109-113`: 6*N + 12*L*D*S/2)."""
    return 6 * matmul_params(cfg) + 3 * attn_flops_token(cfg, seq / 2.0)


def serve_flops(cfg, prompt_tokens, prompt_context_sum, decode_tokens,
                decode_context_sum, head_rows):
    """Forward FLOPs of serving: every prompt and decode token goes through
    the layers' matmuls and attends to its context; the head runs once for
    each sampled row (``head_rows``), not for every prompt position."""
    e = cfg["n_embd"]
    layer_mm = matmul_params(cfg) - e * cfg["vocab_size"]
    tokens = prompt_tokens + decode_tokens
    return (2 * layer_mm * tokens
            + 4 * cfg["n_layer"] * e * (prompt_context_sum
                                        + decode_context_sum)
            + 2 * e * cfg["vocab_size"] * head_rows)


def flash_fwd(batch, heads, seq, head_dim, causal=True, itemsize=2):
    """(FLOPs, bytes) of one attention forward over (batch, heads, seq,
    head_dim): QK^T and PV, halved by the causal mask; reads Q, K, V and
    writes O once."""
    flops = 4.0 * batch * heads * seq * seq * head_dim
    if causal:
        flops /= 2
    nbytes = 4.0 * batch * heads * seq * head_dim * itemsize
    return flops, nbytes


def flash_bwd(batch, heads, seq, head_dim, causal=True, itemsize=2):
    """(FLOPs, bytes) of the attention backward: dV, dP, dQ and dK are four
    matmuls of the forward's size, twice the forward's two.  A flash kernel
    also computes the scores again; that is recomputation and is not counted,
    so the share reads low rather than high.  Reads Q, K, V, O, dO and writes
    dQ, dK, dV."""
    f, _ = flash_fwd(batch, heads, seq, head_dim, causal, itemsize)
    nbytes = 8.0 * batch * heads * seq * head_dim * itemsize
    return 2.0 * f, nbytes


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
