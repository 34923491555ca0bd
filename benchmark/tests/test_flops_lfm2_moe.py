"""`benchmark/flops_lfm2_moe.py` against counts made by hand for the
configuration as it is run (the published widths, 16 layers: 12 conv and 4
attention, 2 dense and 14 expert layers)."""
import pytest

from benchmark import flops, flops_lfm2_moe as F, spec

CFG = spec.config("lfm2-8b-a1b-1chip")
D, V = 2048, 65536


def test_the_layers_are_the_cut_the_file_states():
    assert CFG["layer_types"].count("conv") == 12 == F.conv_layers(CFG)
    assert CFG["layer_types"].count("full_attention") == 4 \
        == F.attn_layers(CFG)
    assert F.head_dim(CFG) == 64
    assert F.pairs_per_row(CFG) == 4 * 14


def test_parameters_by_hand():
    # conv: 2,048 -> 6,144 and 2,048 -> 2,048
    assert F.conv_params(CFG) == 2048 * 6144 + 2048 * 2048 == 16_777_216
    # attention: q 2,048 x 2,048, k and v 2,048 x 512 each, o 2,048 x 2,048
    assert F.attn_params(CFG) == 2 * 2048 * 2048 + 2 * 2048 * 512 \
        == 10_485_760
    # an expert: three matrices of 2,048 x 1,792
    assert F.expert_params(CFG) == 3 * 2048 * 1792 == 11_010_048
    # layer 0: conv and the dense SwiGLU of 7,168
    assert F.layer_params(CFG, 0) == 16_777_216 + 3 * 2048 * 7168
    # layer 2: attention and the router's 32 rows
    assert F.layer_params(CFG, 2) == 10_485_760 + 2048 * 32
    # layer 3: conv and the router
    assert F.layer_params(CFG, 3) == 16_777_216 + 2048 * 32
    # the whole model, as the issue counts it: 5.40 B parameters
    total = (sum(F.layer_params(CFG, i) for i in range(16))
             + 14 * 32 * F.expert_params(CFG) + V * D)
    assert round(total / 1e9, 2) == 5.40


def test_serve_flops_by_hand():
    dense = 12 * 16_777_216 + 4 * 10_485_760 + 2 * 3 * 2048 * 7168 \
        + 14 * 2048 * 32
    taps = 2 * 12 * 3 * 2048
    # one decode token at context 1,000, routed to 56 pairs, sampled
    got = F.serve_flops(CFG, 1, 1000, 56, 1)
    want = (2 * dense + taps + 2 * 11_010_048 * 56
            + 4 * 4 * 32 * 64 * 1000 + 2 * D * V)
    assert got == want
    # 1.08 B active parameters in the 16 layers and the head (0.33 B the
    # token's own layers, 0.62 B its 56 experts, 0.13 B the head): 2.17
    # GFLOP a token before attention
    assert 2.16e9 < got - 4 * 4 * 32 * 64 * 1000 < 2.18e9
    # a 512-token prompt: every token through the layers, the causal
    # context 512 * 513 / 2, the head once
    got = F.serve_flops(CFG, 512, 512 * 513 // 2, 512 * 56, 1)
    assert got == ((2 * dense + taps) * 512 + 2 * 11_010_048 * 512 * 56
                   + 4 * 4 * 32 * 64 * (512 * 513 // 2) + 2 * D * V)


def test_decode_attention_reads_each_live_block_once():
    # 30 rows over 600 live blocks of 64 tokens: K and V of 8 heads of 64
    f, b = F.gqa_decode(CFG, 600 * 64, 30)
    assert f == 4.0 * 32 * 64 * 600 * 64
    assert b == (2 * 512 * 600 * 64 + 2 * 30 * 2048) * 2
    t, bound = flops.roofline_seconds(f, b, {"bf16_flops": 197e12,
                                             "hbm_bytes_s": 819e9})
    assert bound == "bytes" and t == pytest.approx(b / 819e9)


def test_expert_products_are_the_other_blocks_count():
    """The reader `expert_roofline` calls `flops_latent_moe.expert_products`
    with this configuration: the same keys, the same count."""
    from benchmark import flops_latent_moe

    assert F.expert_products(CFG, 128, 32) \
        == flops_latent_moe.expert_products(CFG, 128, 32)
    f, b = F.expert_products(CFG, 128, 32)
    assert f == 2.0 * 11_010_048 * 128
    # 32 experts' matrices once, 128 rows of 2,048 in and out, bfloat16
    assert b == (11_010_048 * 32 + 2 * 2048 * 128) * 2
