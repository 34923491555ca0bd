"""The cut of `lfm2-8b-a1b-1chip`, beside what `test_cut_configs.py` holds
every cut configuration to: depth alone is cut, to the published list's
first 16 layer types (whole periods of the 3 : 1 pattern, the leading dense
layers counted once, at least four expert layers after them); every expert,
every K/V head and the whole vocabulary are here; the engine's tables fit
the mix's longest request."""
from benchmark import spec

CFG = spec.config("lfm2-8b-a1b-1chip")
CELL = spec.workload("lfm2-8b-a1b-1chip.conversation-near-knee")


def test_depth_alone_is_cut_and_layer_types_with_it():
    assert CFG["reduced"] == ["num_hidden_layers", "layer_types"]
    pub = CFG["published"]
    assert pub["num_hidden_layers"] == 24 == len(pub["layer_types"])
    assert CFG["num_hidden_layers"] == 16 == len(CFG["layer_types"])
    assert CFG["layer_types"] == pub["layer_types"][:16]


def test_every_kind_of_layer_is_there_in_its_published_ratio():
    kinds, pub = CFG["layer_types"], CFG["published"]["layer_types"]
    assert kinds.count("conv") == 12 and kinds.count("full_attention") == 4
    assert pub.count("conv") == 18 and pub.count("full_attention") == 6
    # 3 : 1 in both; a whole period is conv, conv, attention, conv
    assert kinds.count("conv") * pub.count("full_attention") \
        == pub.count("conv") * kinds.count("full_attention")
    # the leading dense layers, then at least four expert layers
    assert CFG["num_hidden_layers"] - CFG["num_dense_layers"] == 14 >= 4


def test_nothing_else_is_a_share():
    assert CFG["num_experts"] == 32 and CFG["num_experts_per_tok"] == 4
    assert CFG["vocab_size"] == 65536
    assert CFG["num_key_value_heads"] == 8
    assert "experts_held" not in CFG


def test_the_tables_and_the_pool_fit_the_mix():
    mix = CELL["traffic_mix"]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        <= CFG["n_positions"]
    bs, rows = CFG["engine"]["block_size"], CFG["engine"]["max_batch"]
    # every row at full depth, and the trash block
    assert CELL["n_blocks"] >= rows * (CFG["n_positions"] // bs) + 1
    assert CELL["state_slots"] == rows
    assert max(CELL["prefill_buckets"]) % bs == 0
    # the issue's lengths, as named
    assert (mix["prompt_len"]["mean"], mix["prompt_len"]["stddev"],
            mix["prompt_len"]["min"], mix["prompt_len"]["max"]) \
        == (1155, 600, 64, 4096)
    assert (mix["output_len"]["mean"], mix["output_len"]["stddev"],
            mix["output_len"]["min"], mix["output_len"]["max"]) \
        == (211, 80, 16, 512)
    assert mix["arrivals"]["ramp_s"] == 30
