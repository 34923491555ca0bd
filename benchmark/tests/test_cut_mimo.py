"""The cut of `mimo-v2.5-ep16`, beside what `test_cut_configs.py` holds
every cut configuration to (the guide's section 4): one of 16 chips that
share each layer (16 of 256 experts, an eighth of the vocabulary), the
published pattern's first 11 layers (a whole period: one full layer and five
window layers, beside the dense full layer 0), every width as published;
the engine's tables reach the mix's longest request, and the pool holds what
the cell keeps in flight."""
from benchmark import spec

CFG = spec.config("mimo-v2.5-ep16")
CELL = spec.workload("mimo-v2.5-ep16.doc8k-out1k-near-knee")


def test_depth_experts_and_vocabulary_are_cut_and_the_pattern_with_them():
    assert CFG["reduced"] == ["num_hidden_layers", "hybrid_layer_pattern",
                              "moe_layer_freq", "n_routed_experts_held",
                              "vocab_size"]
    pub = CFG["published"]
    assert pub["num_hidden_layers"] == 48 == len(pub["hybrid_layer_pattern"])
    assert CFG["num_hidden_layers"] == 11
    assert CFG["hybrid_layer_pattern"] == pub["hybrid_layer_pattern"][:11]
    assert CFG["moe_layer_freq"] == pub["moe_layer_freq"][:11]
    assert CFG["vocab_size"] * 8 == pub["vocab_size"]
    assert CFG["experts_held"] == [0, 16] == [0, CFG["n_routed_experts_held"]]


def test_a_whole_period_and_the_published_share_of_full_layers():
    kinds, pub = CFG["hybrid_layer_pattern"], CFG["published"]
    # layers 5-10: one full layer then five window layers
    assert kinds[5:] == [0, 1, 1, 1, 1, 1]
    assert kinds.count(0) == 2 and kinds.count(1) == 9
    assert pub["hybrid_layer_pattern"].count(0) == 9
    # 18 % full here against the published 19 %
    assert abs(2 / 11 - 9 / 48) < 0.01
    # layer 0 dense, ten expert layers after it
    assert CFG["moe_layer_freq"].count(0) == 1 and CFG["moe_layer_freq"][0] \
        == 0


def test_every_width_is_published():
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["swa_num_key_value_heads"],
            CFG["head_dim"], CFG["v_head_dim"], CFG["swa_head_dim"],
            CFG["swa_v_head_dim"]) == (4096, 64, 4, 8, 192, 128, 192, 128)
    assert (CFG["intermediate_size"], CFG["moe_intermediate_size"],
            CFG["n_routed_experts"], CFG["num_experts_per_tok"],
            CFG["sliding_window"]) == (16384, 2048, 256, 8, 128)
    assert int(CFG["partial_rotary_factor"] * CFG["head_dim"]) == 64


def test_the_tables_and_the_pool_fit_the_mix():
    mix = CELL["traffic_mix"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        == CFG["n_positions"] == 16384
    bs, rows = CFG["engine"]["block_size"], CFG["engine"]["max_batch"]
    assert CELL["state_slots"] == rows
    assert max(CELL["prefill_buckets"]) == 512
    assert all(b % bs == 0 for b in CELL["prefill_buckets"])
    # the pool holds the mix's mean request (8,192 + 1,024 tokens) in every
    # row but a fifth; a row at full depth takes 256 blocks
    mean = (mix["prompt_len"]["mean"] + mix["output_len"]["mean"]) // bs
    assert CELL["n_blocks"] >= 0.8 * rows * mean
    # the issue's lengths, as named
    assert (mix["prompt_len"]["mean"], mix["prompt_len"]["stddev"],
            mix["prompt_len"]["min"], mix["prompt_len"]["max"]) \
        == (8192, 3072, 1024, 14336)
    assert (mix["output_len"]["mean"], mix["output_len"]["stddev"],
            mix["output_len"]["min"], mix["output_len"]["max"]) \
        == (1024, 256, 256, 2048)
    assert mix["arrivals"]["ramp_s"] >= 30
    assert mix["shape_seed"] == 20261015
