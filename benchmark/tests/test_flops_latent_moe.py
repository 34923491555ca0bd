"""`flops_latent_moe` against hand counts for `kimi-k2.5-ep32` (ISSUE 30's
arithmetic), and the new readers on made-up runs."""
import pytest

from benchmark import flops, flops_latent_moe as f, spec
from benchmark.readers import (expert_load_skew, expert_roofline,
                               latent_decode_roofline, latent_launch_mfu)

CFG = spec.config("kimi-k2.5-ep32")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


def test_parameters_by_hand():
    # q_a 7168x1536 + q_b 1536x12288 + kv_a 7168x576 + kv_b 512x16384
    # + o 8192x7168 = 101.12 M
    assert f.attn_params(CFG) == (11010048 + 18874368 + 4128768 + 8388608
                                  + 58720256) == 101122048
    assert f.expert_params(CFG) == 3 * 7168 * 2048 == 44040192
    # the dense layer: attention + 3 x 7168 x 18432
    assert f.layer_params(CFG, 0) == 101122048 + 396361728
    # an expert layer beside its routed experts: attention, the router's
    # 384 x 7168, one shared expert
    assert f.layer_params(CFG, 1) == 101122048 + 2752512 + 44040192


def test_the_models_parameter_count_is_the_issues():
    """5.526 B parameters on this chip: 497.5 M + 7 x 676.4 M + 293.6 M."""
    from benchmark import serve_latent_moe

    n = 0
    for shape in serve_latent_moe.build_model(CFG).param_shapes().values():
        k = 1
        for d in shape:
            k *= d
        n += k
    # the matrices above, 12 held experts a layer, the vocabulary's slice
    # twice, and the norms' gains and the router's biases
    matrices = (f.layer_params(CFG, 0) + 7 * (f.layer_params(CFG, 1)
                + 12 * f.expert_params(CFG)) + 2 * 20480 * 7168)
    small = 8 * (2 * 7168 + 1536 + 512) + 7168 + 7 * 384
    assert n == matrices + small
    assert n == pytest.approx(5.526e9, rel=1e-3)


def test_serve_flops_by_hand():
    # one request: a 100-token prompt and 3 decoded tokens after it, of
    # whose 103 x 8 x 7 routed pairs 25 fell on held experts
    dense = (101122048 + 396361728) + 7 * (101122048 + 2752512 + 44040192)
    got = f.serve_flops(CFG, 103, 5050 + 306, 25, 4)
    want = (2 * dense * 103 + 2 * 44040192 * 25
            + 2 * 8 * 64 * 320 * (5050 + 306) + 2 * 7168 * 20480 * 4)
    assert got == want
    # ISSUE 30: ~4.5 GFLOP a prompt token at a context of 4k
    per_token = f.serve_flops(CFG, 1, 4096, 0.25 * 7, 0)
    assert per_token == pytest.approx(4.5e9, rel=0.05)


def test_kernel_counts_by_hand():
    # 12 rows over 1,500 live blocks of 64 tokens, one layer: 1,152 bytes a
    # cached token; a row's query in (64 x 576) and output (64 x 512)
    fl, by = f.latent_decode(CFG, 1500 * 64, 12)
    assert fl == 2 * 64 * 320 * 96000
    assert by == 1152 * 96000 + 12 * 64 * (576 + 512) * 2
    t, bound = flops.roofline_seconds(fl, by, PEAKS)
    assert bound == "bytes" and t == pytest.approx(by / 819e9)
    # 20 pairs over 9 (layer, expert) sets of three matrices
    fl, by = f.expert_products(CFG, 20, 9)
    assert fl == 2 * 44040192 * 20
    assert by == (44040192 * 9 + 2 * 7168 * 20) * 2
    assert flops.roofline_seconds(fl, by, PEAKS)[1] == "bytes"
    # a chunk's worth: 300 pairs over 12 experts is bound by the matrices
    # still; 6,000 would be bound by the products
    assert flops.roofline_seconds(*f.expert_products(CFG, 300, 12),
                                  PEAKS)[1] == "bytes"
    assert flops.roofline_seconds(*f.expert_products(CFG, 6000, 12),
                                  PEAKS)[1] == "flops"


# -- the readers, on made-up runs ---------------------------------------------


class _Run:
    """What the new readers touch of a `harness.Run`."""

    def __init__(self, records, ops, modules, t_trace=10.0):
        self.cfg, self.peaks = CFG, PEAKS
        self.t_open, self.t_close, self._t_trace = 0.0, 14.0, t_trace
        self._spans = {"bench": records}
        self._raw = {"ops": ops, "modules": modules,
                     "spans": [(0, 1, "sched.launch", "main")]}
        self.notes = []

    def note(self, msg):
        self.notes.append(msg)


def _iteration(t0, **attrs):
    return {"type": "span", "phase": "iteration", "t0": t0, "t1": t0 + 0.05,
            "ms": 50.0, "attrs": attrs}


def _op(start_ms, dur_ms, tf_op):
    return (int(start_ms * 1e6), int(dur_ms * 1e6),
            {"tf_op": tf_op, "display_name": "fusion"})


def test_roofline_readers_read_the_traced_records_against_the_kernels():
    records = [
        _iteration(5.0, ctx_blocks=9999, rows=64, expert_rows=999,
                   expert_hits=99, expert_load_max=9),     # before the trace
        _iteration(11.0, ctx_blocks=1500, rows=12, expert_rows=20,
                   expert_hits=9, expert_load_max=4),
        _iteration(12.0, ctx_blocks=1500, rows=12, expert_rows=20,
                   expert_hits=9, expert_load_max=6)]
    least_attn = 8 * (1152 * 96000 + 12 * 64 * 1088 * 2) / 819e9
    least_experts = 2 * (44040192 * 9 + 2 * 7168 * 20) * 2 / 819e9
    d = "jit(serve_decode_b16)/"
    ops = []
    for at in (11000.0, 12000.0):
        ops += [_op(at, 2 * 1e3 * least_attn,
                    d + "decode_attention/jit(_latent_decode)/"
                    "latent_decode_attn/pallas_call:"),
                _op(at + 5, 4 * 1e3 * least_experts / 2,
                    d + "moe_loop/while/body/moe_experts/dot_general:"),
                _op(at + 8, 1.0, d + "lm_head/dot_general:")]
    modules = [(int(11000e6), int(20e6), "jit_serve_decode_b16(1)"),
               (int(12000e6), int(20e6), "jit_serve_decode_b16(1)")]
    run = _Run(records, ops, modules)
    args = {"replica": "bench", "programs": ["serve_decode_"]}
    assert latent_decode_roofline.read(
        run, scopes=["decode_attention"], **args) == pytest.approx(50.0)
    assert expert_roofline.read(
        run, scopes=["moe_experts"], **args) == pytest.approx(25.0)
    assert any("bound by bytes" in n for n in run.notes)
    # over the whole window (the record before the trace too):
    # (9 + 4 + 6) x 12 experts / (999 + 20 + 20) rows
    assert expert_load_skew.read(run, "bench", "iteration",
                                 "n_routed_experts_held") \
        == pytest.approx(19 * 12 / 1039.0)


def test_roofline_readers_return_nothing_or_raise():
    args = {"replica": "bench", "programs": ["serve_decode_"],
            "scopes": ["decode_attention"]}
    # an untraced run, or a CPU trace: nothing to read
    run = _Run([], [], [])
    run._raw = None
    assert latent_decode_roofline.read(run, **args) is None
    assert expert_roofline.read(run, **args) is None
    # a program that writes its names but not this one: an error
    run = _Run([_iteration(11.0, rows=3)],
               [_op(11000.0, 1.0, "jit(serve_decode_b16)/lm_head/dot:")],
               [(int(11000e6), int(20e6), "jit_serve_decode_b16(1)")])
    with pytest.raises(ValueError, match="latent_decode_roofline"):
        latent_decode_roofline.read(run, **args)
    with pytest.raises(ValueError, match="expert_roofline"):
        expert_roofline.read(run, **args)


def test_launch_mfu_counts_the_dense_part_of_the_mean_launch():
    """2.5 rows a launch through the layers' matmuls and the head, no
    attention context and no routed expert, over the median launch."""
    modules = [(int(at * 1e6), int(ms * 1e6), "jit_serve_decode_b4(1)")
               for at, ms in ((11000, 4.0), (11100, 5.0), (11200, 9.0))]
    run = _Run([], [], modules)
    run.devices = [object()]
    run.counters = {"decode_steps": 4, "decode_rows": 10}
    attn, expert = 101_122_048, 44_040_192
    per_row = 2 * (attn + 3 * 7168 * 18432
                   + 7 * (attn + expert + 384 * 7168)) + 2 * 7168 * 20480
    assert f.serve_flops(CFG, 1, 0, 0, 1) == per_row
    assert latent_launch_mfu.read(run, ["serve_decode_"]) == pytest.approx(
        100.0 * 2.5 * per_row / 5e-3 / 197e12)
    # a window that launched no decode, or an untraced run: nothing
    run.counters = {"decode_steps": 0, "decode_rows": 0}
    assert latent_launch_mfu.read(run, ["serve_decode_"]) is None
    run._raw = None
    assert latent_launch_mfu.read(run, ["serve_decode_"]) is None
