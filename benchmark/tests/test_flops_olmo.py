"""`benchmark/flops_olmo_hybrid.py` against counts made by hand for the
configuration as it is run (the published widths, 12 layers: 9 linear and 3
full, the whole vocabulary), and the model's own shapes against the same
count: the 6.54 GB of weights, the 20.5 MB of state a sequence and the
46,080 bytes of K/V a token that the cell is sized by."""
import numpy as np

from benchmark import flops, flops_olmo_hybrid as F, spec

CFG = spec.config("olmo-hybrid-7b-1chip")
D, V = 3840, 100352


def test_the_layers_are_the_cut_the_file_states():
    assert F.linear_layers(CFG) == 9 and F.full_layers(CFG) == 3
    assert F.head_dim(CFG) == 128
    assert F.conv_channels(CFG) == 30 * (96 + 96 + 192) == 11520


def test_parameters_by_hand():
    # a linear layer: q/k 22.12 M, v/g/o 66.36 M, a/b 0.23 M, MLP 126.81 M
    assert 2 * D * 2880 == 22_118_400
    assert 3 * D * 5760 == 66_355_200
    assert F.linear_op_params(CFG) == 22_118_400 + 66_355_200 + 2 * D * 30
    assert F.layer_params(CFG, 0) == F.linear_op_params(CFG) \
        + 3 * D * 11008 == 215_516_160
    # a full layer: four 3,840-square projections and the MLP
    assert F.full_op_params(CFG) == 4 * D * D == 58_982_400
    assert F.layer_params(CFG, 3) == 185_794_560
    # with taps (11,520 x 4), decay parameters (2 x 30), the head norm's 192
    # gains and two post-norms: 215.57 M a linear layer, 185.81 M a full one
    lin = 215_516_160 + 11520 * 4 + 60 + 192 + 2 * D
    full = 185_794_560 + 2 * D + 2 * D
    assert round(lin / 1e6, 2) == 215.57 and round(full / 1e6, 2) == 185.81
    # a period's mean, the catalog's ~208 M
    assert round((3 * lin + full) / 4 / 1e6, 1) == 208.1
    assert F.param_count(CFG) == 9 * lin + 3 * full + 2 * V * D + D


def test_the_models_own_shapes_are_the_hand_count():
    """3,268.3 M parameters = 6.54 GB in bfloat16."""
    from benchmark import serve_olmo_hybrid

    shapes = serve_olmo_hybrid.build_model(CFG).param_shapes()
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == F.param_count(CFG)
    assert round(total / 1e6, 1) == 3268.3
    assert round(2 * total / 1e9, 2) == 6.54
    assert shapes["pred_weight"] == shapes["embed_weight"] == (V, D)
    assert shapes["layer0_gate_weight"] == (5760, D)


def test_state_and_cache_sizes_by_hand():
    """S of 30 x 96 x 192 float32 and three conv rows of 11,520 bf16, in 9
    layers: 20.5 MB a sequence; the program holds S at 256 lanes (whole
    tiles) and so 27.2 MB; 65 slots 1.33 GB of state, 1.77 GB held."""
    per_layer = 30 * 96 * 192 * 4 + 3 * 11520 * 2
    assert per_layer == 2_280_960
    assert F.state_slot_bytes(CFG) == 9 * per_layer == 20_528_640
    assert round(65 * 20_528_640 / 1e9, 2) == 1.33
    from benchmark import serve_olmo_hybrid

    model = serve_olmo_hybrid.build_model(CFG)
    assert model.state_slot_bytes() == 9 * (30 * 96 * 256 * 4
                                            + 3 * 11520 * 2)
    assert round(65 * model.state_slot_bytes() / 1e9, 2) == 1.77
    # K/V: 3 full layers x 2 x 3,840 x 2 bytes a token, 2.95 MB a block
    assert F.kv_bytes_per_token(CFG) == 46_080
    assert model.block_bytes(64) == 64 * 46_080 == 2_949_120


def test_the_decode_steps_work_by_hand():
    # decode: S read and written, 2.21 MB a layer a row, and the row's
    # q, k, v, o, decay and write strength
    f, b = F.delta_decode(CFG, 2)
    assert f == 2 * 7 * 30 * 96 * 192 * 9
    assert b == 2 * 9 * 30 * (2 * 96 * 192 + 2 * 96 + 2 * 192 + 2) * 4
    t, bound = flops.roofline_seconds(f, b, {"bf16_flops": 197e12,
                                             "hbm_bytes_s": 819e9})
    assert bound == "bytes" and abs(t - b / 819e9) < 1e-12
