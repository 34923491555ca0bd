"""`benchmark/flops_mimo.py` against counts made by hand for the
configuration as it is run (the published widths, 11 layers: 2 full and 9
window, layer 0 dense and 10 expert layers, 16 of 256 experts held, an
eighth of the vocabulary), and the model's own shapes against the same
count (a layer's parts and the whole, by hand)."""
import numpy as np
import pytest

from benchmark import flops, flops_latent_moe, flops_mimo as F, spec

CFG = spec.config("mimo-v2.5-ep16")
D, V = 4096, 19072


def test_the_layers_are_the_cut_the_file_states():
    assert F.full_layers(CFG) == 2 and F.window_layers(CFG) == 9
    assert [i for i, k in enumerate(F.kinds(CFG)) if k == 0] == [0, 5]
    assert F.kv_heads(CFG, 0) == 4 and F.kv_heads(CFG, 1) == 8
    assert F.pair_flops(CFG) == 2 * (192 + 128)


def test_parameters_by_hand():
    q, o = 4096 * 64 * 192, 64 * 128 * 4096           # 50.33 M, 33.55 M
    assert (q, o) == (50_331_648, 33_554_432)
    # full layers: k 4 heads of 192, v 4 of 128
    assert F.attn_params(CFG, 0) == q + o + 4096 * 4 * (192 + 128) \
        == 89_128_960
    # window layers: 8 K/V heads
    assert F.attn_params(CFG, 1) == q + o + 4096 * 8 * (192 + 128) \
        == 94_371_840
    assert F.expert_params(CFG) == 3 * 4096 * 2048 == 25_165_824
    # layer 0: attention and the dense SwiGLU of 16,384
    assert F.layer_params(CFG, 0) == 89_128_960 + 3 * 4096 * 16384 \
        == 290_455_552
    # layer 5: full attention, the router over all 256 experts
    assert F.layer_params(CFG, 5) == 89_128_960 + 4096 * 256
    # the issue's layer 5 (+ 16 experts) and window expert layer
    assert F.layer_params(CFG, 5) + 16 * 25_165_824 == 492_830_720
    assert F.layer_params(CFG, 1) + 16 * 25_165_824 == 498_073_600


def test_the_models_own_shapes_are_the_issues_count():
    """5.42 B parameters = 10.84 GB in bfloat16, the 64 sinks of each
    window layer counted with everything else."""
    from benchmark import serve_mimo

    shapes = serve_mimo.build_model(CFG).param_shapes()
    total = sum(int(np.prod(s)) for s in shapes.values())
    matmul = (sum(F.layer_params(CFG, i) for i in range(11))
              + 10 * 16 * F.expert_params(CFG) + 2 * V * D)
    # the rest: 2 gains a layer and the final one, 256 expert biases a
    # router, 64 sinks a window layer
    assert total == matmul + 11 * 2 * D + D + 10 * 256 + 9 * 64
    assert round(total / 1e9, 2) == 5.42
    assert round(2 * total / 1e9, 2) == 10.84
    assert shapes["layer1_moe_gate"] == (16, D, 2048)
    assert shapes["pred_weight"] == shapes["embed_weight"] == (V, D)


def test_serve_flops_by_hand():
    dense = sum(F.layer_params(CFG, i) for i in range(11))
    e = F.expert_params(CFG)
    # one decode token at position 9,000 (context 9,001 in the full layers,
    # 128 in the window layers), 5 held pairs, sampled
    got = F.serve_flops(CFG, 1, 9001, 128, 5, 1)
    want = (2 * dense + 2 * e * 5 + 640 * 64 * (2 * 9001 + 9 * 128)
            + 2 * D * V)
    assert got == want
    # a prompt of 300 tokens: the window layers' keys are 1 + 2 + ... + 128
    # and then 128 a token
    assert F.prompt_window_context(CFG, 300) == 128 * 129 // 2 + 172 * 128
    assert F.prompt_window_context(CFG, 5) == 15
    assert F.window_context(CFG, 0) == 1 and F.window_context(CFG, 500) \
        == 128


def test_decode_attention_reads_each_live_block_once():
    # 40 rows over 6,000 live blocks of 64 tokens in both full layers: K of
    # 4 heads of 192 and V of 4 of 128
    f, b = F.gqa_decode(CFG, 6000 * 64, 80)
    assert f == 640.0 * 64 * 6000 * 64
    assert b == (4 * 320 * 6000 * 64 + 80 * 64 * 320) * 2
    t, bound = flops.roofline_seconds(f, b, {"bf16_flops": 197e12,
                                             "hbm_bytes_s": 819e9})
    assert bound == "bytes" and t == pytest.approx(b / 819e9)


def test_a_chunks_full_attention_by_hand():
    # 512 tokens over a prefix of 4,096, both full layers
    f, b = F.full_prefill(CFG, 4096, 512)
    context = 512 * 4096 + 512 * 513 / 2.0
    assert f == 640.0 * 64 * 2 * context
    assert b == 2 * ((4096 + 512) * 4 * 320 + 512 * 64 * 320) * 2
    t, bound = flops.roofline_seconds(f, b, {"bf16_flops": 197e12,
                                             "hbm_bytes_s": 819e9})
    assert bound == "flops"


def test_expert_products_are_read_with_the_other_blocks_count():
    """The reader `expert_roofline` calls `flops_latent_moe.expert_products`
    with this configuration: it reads the two widths this one has."""
    f, b = flops_latent_moe.expert_products(CFG, 256, 16)
    assert f == 2.0 * 25_165_824 * 256
    assert b == (25_165_824 * 16 + 2 * D * 256) * 2
