"""The one traffic generator: the mix fixes the sizes and the instants, the
seed draws the token ids, so no seed changes how much work a run holds."""
import numpy as np
import pytest

from benchmark import spec, traffic

MIX = {
    "shape_seed": 7,
    "arrivals": {"process": "poisson", "rate_rps": 4.0, "ramp_s": 5},
    "prompt_len": {"dist": "normal", "mean": 550, "stddev": 150,
                   "min": 16, "max": 832},
    "output_len": {"dist": "normal", "mean": 150, "stddev": 10,
                   "min": 110, "max": 190},
}


def _schedule(reqs):
    return [(r["due"], len(r["prompt"]), r["max_new"]) for r in reqs]


def test_same_seed_same_inputs_and_large_seeds():
    a = traffic.generate(MIX, 2 ** 31 + 5, 20, 50257)
    b = traffic.generate(MIX, 2 ** 31 + 5, 20, 50257)
    assert a == b
    c = traffic.generate(MIX, 5, 20, 50257)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


def test_every_seed_gets_the_same_schedule_and_its_own_tokens():
    a = traffic.generate(MIX, 1, 20, 50257)
    b = traffic.generate(MIX, 2, 20, 50257)
    assert len(a) == len(b) == round(4.0 * 25)       # the rate stated
    assert _schedule(a) == _schedule(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    due = [r["due"] for r in a]
    assert due == sorted(due) and due[0] >= -5.0 and due[-1] <= 20.0
    assert sum(d < 0 for d in due) > 0                # the ramp's requests
    for r in a:
        assert 16 <= len(r["prompt"]) <= 832 and 110 <= r["max_new"] <= 190
        assert 0 <= min(r["prompt"]) and max(r["prompt"]) < 50257
    other = traffic.generate(dict(MIX, shape_seed=8), 1, 20, 50257)
    assert _schedule(other) != _schedule(a)


@pytest.mark.parametrize("spec_, lo, hi, mid", [
    ({"dist": "normal", "mean": 550, "stddev": 150, "min": 16, "max": 832},
     16, 832, 550),
    ({"dist": "uniform", "min": 512, "max": 960}, 512, 960, 736),
    ({"dist": "const", "value": 128}, 128, 128, 128),
])
def test_length_distributions(spec_, lo, hi, mid):
    x = traffic._lengths(spec_, 4000, np.random.default_rng(3))
    assert x.min() >= lo and x.max() <= hi
    assert abs(float(np.median(x)) - mid) <= 0.05 * mid


def test_closed_loop_has_no_schedule():
    mix = {"shape_seed": 7,
           "arrivals": {"process": "closed", "clients": 8, "ramp_s": 2,
                        "max_rps": 10},
           "prompt_len": {"dist": "uniform", "min": 512, "max": 960},
           "output_len": {"dist": "const", "value": 16}}
    reqs = traffic.generate(mix, 3, 10, 50257)
    assert len(reqs) == 8 + 10 * 12
    assert all(r["due"] is None and r["max_new"] == 16 for r in reqs)
    assert all(512 <= len(r["prompt"]) <= 960 for r in reqs)


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.benchmark()["workloads"]])
def test_cells_mix_never_overruns_the_context(cell):
    c = spec.workload(cell)
    if "traffic_mix" not in c:
        pytest.skip("not a serving cell")
    cfg = spec.config(c["config"])
    for r in traffic.generate(c["traffic_mix"], 11, 50, cfg["vocab_size"]):
        assert len(r["prompt"]) + r["max_new"] <= cfg["n_positions"]
