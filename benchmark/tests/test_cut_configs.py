"""Every configuration with a non-empty `reduced` is held to the cut the
`model-configs` guide's section 4 allows (ISSUE 30): each key listed is in the
file with its published value beside it, the deployment is stated, no width is
named, and the floors hold.  (`test_files.py::test_cell_resolves` asserts
`reduced == []`, written when both configurations were whole: a cut
configuration fails that one case by design.)"""
import json
import os

import pytest

from benchmark import spec

BENCH = spec.benchmark()
CUT = [c["name"] for c in BENCH["configs"] if c["reduced"]]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: what `reduced` may never name
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "head_dim",
          "num_experts_per_tok")


def test_there_is_a_cut_configuration_to_hold():
    assert CUT


@pytest.mark.parametrize("name", CUT)
def test_cut_is_stated_in_the_file(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    cfg = spec.config(name)
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["source"] == entry["source"]
    for key in cfg["reduced"]:
        assert key in cfg, key
        assert key in cfg["published"], "no published value beside %s" % key
        assert key not in WIDTHS and not key.endswith(("_dim", "_rank"))
    assert len(cfg["deployment"]) > 40 and cfg["assumed"]


@pytest.mark.parametrize("name", CUT)
def test_floors_hold(name):
    cfg = spec.config(name)
    dense = cfg.get("first_k_dense_replace", 0)
    # a whole period and at least four of the layers that follow the
    # leading dense ones (the pattern is uniform after them)
    assert cfg["num_hidden_layers"] - dense >= 4
    if "n_routed_experts_held" in cfg["reduced"]:
        lo, hi = cfg["experts_held"]
        assert hi - lo == cfg["n_routed_experts_held"] >= 8
        assert 0 <= lo < hi <= cfg["n_routed_experts"]
    if "vocab_size" in cfg["reduced"]:
        assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]


@pytest.mark.parametrize("name", CUT)
def test_every_other_number_is_the_sources(name):
    """Against the catalog beside the guide, where this machine has it."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    cfg = spec.config(name)
    rows = [json.loads(line) for line in open(CATALOG)]
    row = next((r for r in rows if r["source_url"] == cfg["source"]), None)
    if row is None:
        pytest.skip("the source is not in the catalog")
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # a tiny group changes sizes for the CPU rehearsal, never a cell's
    assert not set(cfg["reduced"]) - set(cfg)
