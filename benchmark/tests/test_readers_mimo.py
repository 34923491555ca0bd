"""The readers this configuration brought, on event lists built by hand:
`mimo_decode_roofline`, `mimo_prefill_roofline`, `mimo_launch_mfu`."""
from types import SimpleNamespace

import pytest

from benchmark import flops, flops_mimo, spec
from benchmark.readers import (mimo_decode_roofline, mimo_launch_mfu,
                               mimo_prefill_roofline)

US = 1000
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
CFG = spec.config("mimo-v2.5-ep16")


def _op(start, dur, tf_op=None, display="fusion.1"):
    meta = {"name": "%" + display, "display_name": display}
    if tf_op:
        meta["tf_op"] = tf_op
    return (start * US, dur * US, meta)


def _raw():
    """Two decode launches, each with 300 us under `decode_attention`, and
    two chunks, each with 2,000 us under `chunk_attention` (a loop event
    that spans its steps and the steps' own operations)."""
    d, p = "jit(serve_decode_b32)/", "jit(serve_prefill_s512)/"
    ops, modules = [], []
    for t0 in (1000, 5000):
        ops += [_op(t0, 100, d + "embed/gather:"),
                _op(t0 + 100, 300, d + "decode_attention/jit(_paged_decode)/"
                    "paged_decode_attn/pallas_call:", "paged_decode_attn.1")]
        modules.append((t0, 1400, "jit_serve_decode_b32(5)"))
    for t0 in (10000, 20000):
        ops += [_op(t0, 2000, p + "chunk_attention/while/body/dot:"),
                _op(t0 + 2000, 500, p + "moe_loop/while/body/moe_experts/"
                    "dot:")]
        modules.append((t0, 3000, "jit_serve_prefill_s512(6)"))
    spans = [(900, 1600, "sched.iteration", "python3"),
             (4900, 1600, "sched.iteration", "python3")]
    return {"ops": ops, "modules": [(s * US, d * US, n) for s, d, n in modules],
            "spans": sorted((s * US, d * US, n, ln)
                            for s, d, n, ln in spans)}


def _records():
    out, t = [], 1.0
    for rows, blocks in ((30, 8000), (32, 8400)):
        out.append({"phase": "iteration", "t0": t, "t1": t + 0.02,
                    "ms": 20.0, "attrs": {"rows": rows, "chunks": 0,
                                          "ctx_blocks": blocks // 2,
                                          "full_ctx_blocks": blocks}})
        t += 0.03
    for start, tokens in ((4096, 512), (8192, 300)):
        out.append({"phase": "prefill_chunk", "t0": t, "t1": t + 0.04,
                    "ms": 40.0, "attrs": {"start": start, "tokens": tokens}})
        t += 0.05
    return out


def _run(**kw):
    notes = []
    return SimpleNamespace(_raw=_raw(), note=notes.append, notes=notes,
                           _spans={"bench": _records()}, t_open=0.0,
                           t_close=9.0, _t_trace=0.5, cfg=CFG, peaks=PEAKS,
                           devices=[0], **kw)


def test_the_full_layers_decode_share_of_its_roofline_by_hand():
    got = mimo_decode_roofline.read(_run(), "bench", ["serve_decode_"],
                                    ["decode_attention"])
    # each record's blocks, both full layers together, read once: 64 tokens
    # of K at 4 x 192 and V at 4 x 128 a block, and each (row, layer)'s
    # query in and output out
    least = sum(flops.roofline_seconds(
        *flops_mimo.gqa_decode(CFG, b * 64, r * 2), PEAKS)[0]
        for b, r in ((8000, 30), (8400, 32))) / 2
    assert got == pytest.approx(100.0 * least / 300e-6)
    assert least == pytest.approx(8200 * 64 * 1280 * 2 / 819e9, rel=0.01)


def test_a_chunks_full_attention_share_of_its_roofline_by_hand():
    got = mimo_prefill_roofline.read(_run(), "bench", ["serve_prefill_"],
                                     ["chunk_attention"])
    least = sum(flops.roofline_seconds(
        *flops_mimo.full_prefill(CFG, s, n), PEAKS)[0]
        for s, n in ((4096, 512), (8192, 300))) / 2
    assert got == pytest.approx(100.0 * least / 2000e-6)


def test_a_renamed_scope_is_an_error():
    with pytest.raises(ValueError, match="other names"):
        mimo_decode_roofline.read(_run(), "bench", ["serve_decode_"],
                                  ["no_such_scope"])


def test_a_decode_launchs_share_of_the_peak_by_hand():
    run = _run(counters={"decode_steps": 10, "decode_rows": 400})
    got = mimo_launch_mfu.read(run, ["serve_decode_"])
    # 40 rows, each through the layers and the head; experts and attention
    # counted as 0
    want = flops_mimo.serve_flops(CFG, 40, 0, 0, 0, 40)
    assert got == pytest.approx(100.0 * want / 1400e-6 / 197e12)
    assert mimo_launch_mfu.read(_run(counters={}), ["serve_decode_"]) is None
