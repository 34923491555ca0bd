"""The cut of `olmo-hybrid-7b-1chip`, beside what `test_cut_configs.py`
holds every cut configuration to (the guide's section 4): the first pipeline
stage of a deployment with one chip a stage, the published pattern's first
12 layers (three whole periods of linear x3 and full), every width and the
whole vocabulary as published; the engine's tables reach the mix's longest
request, and the pool holds what the cell keeps in flight."""
from benchmark import spec

CFG = spec.config("olmo-hybrid-7b-1chip")
CELL = spec.workload("olmo-hybrid-7b-1chip.reasoning-near-knee")


def test_depth_alone_is_cut_and_the_pattern_with_it():
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types"]
    pub = CFG["published"]
    assert pub["num_hidden_layers"] == 32 == len(pub["layer_types"])
    assert CFG["num_hidden_layers"] == 12 == len(CFG["layer_types"])
    assert CFG["layer_types"] == pub["layer_types"][:12]
    assert "first pipeline stage" in CFG["deployment"]


def test_three_whole_periods_in_the_published_ratio():
    period = ["linear_attention"] * 3 + ["full_attention"]
    assert CFG["layer_types"] == period * 3
    assert pub_ratio() == 3 == CFG["layer_types"].count(
        "linear_attention") // CFG["layer_types"].count("full_attention")


def pub_ratio():
    kinds = CFG["published"]["layer_types"]
    return kinds.count("linear_attention") // kinds.count("full_attention")


def test_every_width_and_the_vocabulary_are_published():
    assert (CFG["hidden_size"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["intermediate_size"],
            CFG["vocab_size"]) == (3840, 30, 30, 11008, 100352)
    assert (CFG["linear_num_key_heads"], CFG["linear_num_value_heads"],
            CFG["linear_key_head_dim"], CFG["linear_value_head_dim"],
            CFG["linear_conv_kernel_dim"]) == (30, 30, 96, 192, 4)
    assert CFG["linear_allow_neg_eigval"] is True
    assert CFG["rope_parameters"] == {"rope_theta": None}
    assert CFG["linear_chunk_size"] == 64


def test_the_tables_and_the_pool_fit_the_mix():
    mix = CELL["traffic_mix"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        == CFG["n_positions"] == 5120
    bs, rows = CFG["engine"]["block_size"], CFG["engine"]["max_batch"]
    assert CELL["state_slots"] == rows == 64
    assert max(CELL["prefill_buckets"]) == 512 and max(
        CELL["decode_buckets"]) == rows
    assert all(b % bs == 0 for b in CELL["prefill_buckets"])
    # the pool holds the mean request (1,024 + 1,536 tokens) in 3 rows of 4
    mean = (mix["prompt_len"]["mean"] + mix["output_len"]["mean"]) // bs
    assert CELL["n_blocks"] >= 0.75 * rows * mean
    assert (mix["prompt_len"]["mean"], mix["prompt_len"]["stddev"],
            mix["prompt_len"]["min"], mix["prompt_len"]["max"]) \
        == (1024, 384, 128, 2048)
    assert (mix["output_len"]["mean"], mix["output_len"]["stddev"],
            mix["output_len"]["min"], mix["output_len"]["max"]) \
        == (1536, 512, 256, 3072)
    assert mix["arrivals"]["ramp_s"] >= 45
    assert mix["shape_seed"] == 20261018
