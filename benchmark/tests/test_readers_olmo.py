"""The reader this configuration brought, `olmo_delta_decode_roofline`, on
event lists built by hand."""
from types import SimpleNamespace

import pytest

from benchmark import flops, flops_olmo_hybrid, spec
from benchmark.readers import olmo_delta_decode_roofline

US = 1000
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
CFG = spec.config("olmo-hybrid-7b-1chip")


def _op(start, dur, tf_op=None, display="fusion.1"):
    meta = {"name": "%" + display, "display_name": display}
    if tf_op:
        meta["tf_op"] = tf_op
    return (start * US, dur * US, meta)


def _raw():
    """Two decode launches, each with 2,000 us under `delta_rule`, 1,000 us
    under `linear_state` and 300 us under `decode_attention`."""
    d = "jit(serve_decode_b64)/"
    ops, modules = [], []
    for t0 in (1000, 9000):
        ops += [_op(t0, 100, d + "embed/gather:"),
                _op(t0 + 100, 1000, d + "linear_state/gather:"),
                _op(t0 + 1100, 2000, d + "delta_rule/multiply:"),
                _op(t0 + 3100, 300, d + "decode_attention/jit(_paged_decode)"
                    "/paged_decode_attn/pallas_call:", "paged_decode_attn.1")]
        modules.append((t0, 4000, "jit_serve_decode_b64(5)"))
    spans = [(900, 4200, "sched.iteration", "python3"),
             (8900, 4200, "sched.iteration", "python3")]
    return {"ops": ops, "modules": [(s * US, d * US, n) for s, d, n in modules],
            "spans": sorted((s * US, d * US, n, ln)
                            for s, d, n, ln in spans)}


def _records():
    out, t = [], 1.0
    for rows, blocks in ((50, 1400), (54, 1500)):
        out.append({"phase": "iteration", "t0": t, "t1": t + 0.02,
                    "ms": 20.0, "attrs": {"rows": rows, "chunks": 0,
                                          "ctx_blocks": blocks}})
        t += 0.03
    return out


def _run(**kw):
    notes = []
    return SimpleNamespace(_raw=_raw(), note=notes.append, notes=notes,
                           _spans={"bench": _records()}, t_open=0.0,
                           t_close=9.0, _t_trace=0.5, cfg=CFG, peaks=PEAKS,
                           devices=[0], **kw)


def test_the_decode_steps_share_of_its_roofline_by_hand():
    got = olmo_delta_decode_roofline.read(
        _run(), "bench", ["serve_decode_"], ["delta_rule", "linear_state"])
    # each real row's S (30 x 96 x 192 float32) read and written in 9
    # layers, and its q, k, v, o, decay and write strength
    least = sum(flops.roofline_seconds(
        *flops_olmo_hybrid.delta_decode(CFG, r), PEAKS)[0]
        for r in (50, 54)) / 2
    assert got == pytest.approx(100.0 * least / 3000e-6)
    assert least == pytest.approx(52 * 9 * 2 * 30 * 96 * 192 * 4 / 819e9,
                                  rel=0.02)


def test_a_renamed_scope_is_an_error():
    with pytest.raises(ValueError, match="other names"):
        olmo_delta_decode_roofline.read(_run(), "bench", ["serve_decode_"],
                                        ["no_such_scope"])
