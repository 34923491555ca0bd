"""The FLOP and byte functions against hand counts for GPT-2 medium."""
import pytest

from benchmark import flops, spec, weights

MEDIUM = {"n_layer": 24, "n_embd": 1024, "n_head": 16, "n_inner": None,
          "vocab_size": 50257, "n_positions": 1024}


def test_matmul_params_medium():
    # 24 layers x (4 x 1024^2 attention + 2 x 1024 x 4096 FFN) + head
    assert flops.matmul_params(MEDIUM) == 24 * 12582912 + 1024 * 50257
    assert flops.matmul_params(MEDIUM) == 353453056


def test_train_flops_token_medium():
    # 6 N + 12 L D S/2 at S=1024
    want = 6 * 353453056 + 12 * 24 * 1024 * 512
    assert flops.train_flops_token(MEDIUM, 1024) == want
    assert want == pytest.approx(2.2717e9, rel=1e-4)


def test_parameter_count_medium():
    shapes = weights.lm_param_shapes(MEDIUM)
    n = 0
    for s in shapes.values():
        k = 1
        for d in s:
            k *= d
        n += k
    # GPT-2 medium has 354,823,168 parameters with the head tied to the
    # embedding; untied, the head adds its matrix and its bias; q, k, v
    # apart hold what c_attn holds
    assert n == 354823168 + 1024 * 50257 + 50257


def test_flash_counts():
    f, b = flops.flash_fwd(8, 16, 1024, 64)
    assert f == 4 * 8 * 16 * 1024 * 1024 * 64 / 2
    assert b == 4 * 8 * 16 * 1024 * 64 * 2
    f2, b2 = flops.flash_bwd(8, 16, 1024, 64)
    assert f2 == 2 * f and b2 == 2 * b
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    t, bound = flops.roofline_seconds(f, b, peaks)
    assert bound == "flops" and t == pytest.approx(f / 197e12)
    t, bound = flops.roofline_seconds(1.0, 819e9, peaks)
    assert bound == "bytes" and t == pytest.approx(1.0)


def test_serve_flops_by_hand():
    cfg = spec.config("gpt2-large")
    layer_mm = 36 * 12 * 1280 * 1280
    # one request: a 100-token prompt and 3 decoded tokens after it
    got = flops.serve_flops(cfg, 100, 100 * 101 // 2, 3, 101 + 102 + 103, 4)
    want = (2 * layer_mm * 103 + 4 * 36 * 1280 * (5050 + 306)
            + 2 * 1280 * 50257 * 4)
    assert got == want
