"""The readers this configuration brought, on event lists built by hand:
`unscoped_ms` (the loops' own events apart), `span_attr_mean`,
`gqa_decode_roofline`, `lfm2_launch_mfu`."""
from types import SimpleNamespace

import pytest

from benchmark import flops, flops_lfm2_moe, spec
from benchmark.readers import (gqa_decode_roofline, lfm2_launch_mfu,
                               scope_ms, span_attr_mean, unscoped_ms)

US = 1000
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
CFG = spec.config("lfm2-8b-a1b-1chip")


def _op(start, dur, tf_op=None, display="fusion.1"):
    meta = {"name": "%" + display, "display_name": display}
    if tf_op:
        meta["tf_op"] = tf_op
    return (start * US, dur * US, meta)


def _raw():
    """Two decode launches whose experts run in a loop: the loop's own
    `while` event spans its steps and carries no name stack."""
    d = "jit(serve_decode_b32)/"
    ops = []
    for t0 in (1000, 5000):
        ops += [
            _op(t0, 100, d + "embed/gather:"),
            _op(t0 + 100, 1000, None, "while.3"),      # spans the two steps
            _op(t0 + 100, 450, d + "moe_loop/while/body/moe_experts/dot:"),
            _op(t0 + 560, 500, d + "moe_loop/while/body/moe_experts/dot:"),
            _op(t0 + 1100, 200, d + "decode_attention/jit(_paged_decode)/"
                "paged_decode_attn/pallas_call:", "paged_decode_attn.1"),
            _op(t0 + 1300, 40, None, "copy.7"),        # a clone with no name
        ]
    modules = [(1000, 1400, "jit_serve_decode_b32(5)"),
               (5000, 1400, "jit_serve_decode_b32(5)")]
    line = "python3"
    spans = [(900, 1600, "sched.iteration", line),
             (4900, 1600, "sched.iteration", line)]
    return {"ops": ops, "modules": [(s * US, d * US, n) for s, d, n in modules],
            "spans": sorted((s * US, d * US, n, ln)
                            for s, d, n, ln in spans)}


def _run(**kw):
    notes = []
    return SimpleNamespace(_raw=_raw(), note=notes.append, notes=notes, **kw)


def test_unscoped_leaves_the_loops_own_events_out():
    run = _run()
    # `scope_ms` reads the while's 1,000 us as time with no name ...
    assert scope_ms.read(run, programs=["serve_decode_"], unscoped=True) \
        == pytest.approx(1.040)
    # ... beside the same time under the steps' own scope
    assert scope_ms.read(run, programs=["serve_decode_"],
                         scopes=["moe_experts"]) == pytest.approx(0.950)
    # what is left is the clone
    assert unscoped_ms.read(run, programs=["serve_decode_"]) \
        == pytest.approx(0.040)
    assert any("loops' own events (0.0020s) apart" in n for n in run.notes)


def test_unscoped_of_a_renamed_program_is_an_error():
    with pytest.raises(ValueError, match="no launch"):
        unscoped_ms.read(_run(), programs=["serve_verify_"])


def _records():
    t = 1.0
    out = []
    for chunks, hits, rows, blocks in ((0, 440, 30, 600), (2, 896, 30, 620),
                                       (0, 446, 32, 640)):
        out.append({"phase": "iteration", "t0": t, "t1": t + 0.02,
                    "ms": 20.0,
                    "attrs": {"chunks": chunks, "expert_hits": hits,
                              "rows": rows, "ctx_blocks": blocks}})
        t += 0.03
    out.append({"phase": "queue_wait", "t0": 1.0, "t1": 1.1, "ms": 100.0,
                "attrs": {}})
    return out


def test_mean_of_an_attribute_over_the_records_that_match():
    run = _run(_spans={"bench": _records()}, t_open=0.0, t_close=9.0)
    assert span_attr_mean.read(run, "bench", "iteration", "expert_hits",
                               where={"chunks": 0}) == pytest.approx(443.0)
    assert span_attr_mean.read(run, "bench", "iteration", "expert_hits") \
        == pytest.approx((440 + 896 + 446) / 3.0)
    assert span_attr_mean.read(run, "bench", "iteration", "no_such") is None
    with pytest.raises(ValueError, match="nothing to read"):
        span_attr_mean.read(run, "bench", "prefill", "tokens")


def test_the_decode_kernels_share_of_its_roofline_by_hand():
    run = _run(_spans={"bench": _records()}, t_open=0.0, t_close=9.0,
               _t_trace=0.5, cfg=CFG, peaks=PEAKS)
    got = gqa_decode_roofline.read(run, "bench", ["serve_decode_"],
                                   ["decode_attention"])
    # the mean record's least time over 4 attention layers, over the 200 us
    # each launch spent under the scope
    least = sum(4 * flops.roofline_seconds(
        *flops_lfm2_moe.gqa_decode(CFG, b * 64, r), PEAKS)[0]
        for b, r in ((600, 30), (620, 30), (640, 32))) / 3
    assert got == pytest.approx(100.0 * least / 200e-6)
    # 620 blocks of 64 tokens x 1 KiB of K and 1 KiB of V: 81 MB a layer
    assert least == pytest.approx(4 * 620 * 64 * 2048 / 819e9, rel=0.01)


def test_a_decode_launchs_share_of_the_peak_by_hand():
    run = _run(cfg=CFG, peaks=PEAKS, devices=[0],
               counters={"decode_steps": 10, "decode_rows": 300})
    got = lfm2_launch_mfu.read(run, ["serve_decode_"])
    # 30 rows, each through the layers, its 56 experts and the head
    want = flops_lfm2_moe.serve_flops(CFG, 30, 0, 30 * 56, 30)
    assert got == pytest.approx(100.0 * want / 1400e-6 / 197e12)
    assert lfm2_launch_mfu.read(_run(cfg=CFG, peaks=PEAKS, devices=[0],
                                     counters={}), ["serve_decode_"]) is None
