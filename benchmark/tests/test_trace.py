"""The trace reducer against one small recorded trace (`record_trace.py`, one
v5e chip): four calls of a jitted program that holds the repo's flash
forward, dq and dk/dv kernels over (2, 4, 512, 64) and a matmul, with a 50 ms
sleep between the second and the third call."""
import os

import pytest

from benchmark import flops, trace

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")
WINDOW_S = 0.055613          # as the recording's host clock read it
PALLAS = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(PATH, window_s=WINDOW_S)


def test_planes_and_ops(reduced):
    assert reduced["n_devices"] == 1
    assert reduced["n_ops"] == 64                 # 16 operations x 4 calls
    assert reduced["window_s"] == WINDOW_S
    assert 0.054 < reduced["span_s"] < WINDOW_S


def test_busy_is_the_union_not_the_sum(reduced):
    total = sum(d for _, d, *_ in reduced["events"]) / 1e9
    assert reduced["busy_s"] == pytest.approx(225.586e-6, rel=1e-6)
    assert reduced["busy_s"] <= total
    busy, merged, gaps = trace.busy_union(
        [(0, 10, "a", ""), (5, 10, "b", ""), (2000, 5, "c", "")])
    assert busy == pytest.approx(20e-9)
    assert merged == [[0, 15], [2000, 2005]] and gaps == [(15, 2000)]


def test_flash_kernels_found_by_their_instructions(reduced):
    seconds, launches = trace.kernel_seconds(
        reduced["events"], (PALLAS, "bf16[2,4,512,64]"))
    assert launches == 12                          # fwd, dq, dk/dv x 4 calls
    assert seconds == pytest.approx(174.918e-6, rel=1e-6)
    assert trace.kernel_seconds(reduced["events"],
                                (PALLAS, "bf16[8,16,1024,64]")) == (0.0, 0)
    # their share of the roofline, as the reader reckons it
    shape = (2, 4, 512, 64)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
    least = sum(flops.roofline_seconds(*f(*shape), peaks)[0]
                for f in (flops.flash_fwd, flops.flash_bwd))
    share = 100.0 * least * 4 / seconds
    assert 0 < share < 100


def test_flash_reader_reads_or_fails_loudly(reduced):
    """Through the reader itself: the recorded shape reads a share; a cell
    whose shape the trace does not hold is an error, never a silent None."""
    from types import SimpleNamespace

    from benchmark.readers import flash_roofline

    def run(rows):
        return SimpleNamespace(
            reduced=reduced, cfg={"n_embd": 256, "n_head": 4},
            cell={"batch_rows": rows, "seq_len": 512},
            peaks={"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
            note=lambda msg: None)

    assert 0 < flash_roofline.read(run(2), ["fwd", "bwd"]) < 100
    with pytest.raises(RuntimeError, match="no Pallas custom call"):
        flash_roofline.read(run(8), ["fwd", "bwd"])
    no_trace = run(2)
    no_trace.reduced = None            # a CPU trace: nothing to read
    assert flash_roofline.read(no_trace, ["fwd", "bwd"]) is None


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert len(ops) <= 10
    assert ops[0][0] == "transpose_jvp___ custom-call bf16[2,4,512,64]"
    assert ops[1][0] == "jvp__ custom-call bf16[2,4,512,64]"
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert sum(t for _, t in ops) == pytest.approx(
        sum(d for _, d, *_ in reduced["events"]) / 1e9)
    gaps = reduced["breakdown"]["idle_gaps"]
    assert len(gaps) <= 10
    what, seconds = gaps[0]
    assert "sleep" in what and seconds == pytest.approx(0.0515, abs=5e-4)


def test_kind_of():
    text = ("%jvp__.147 = (f32[1,8192]{1,0:T(1,128)S(1)}, f32[8192,1024]"
            "{1,0:T(8,128)}) custom-call(bf16[8192,1024]{1,0:T(8,128)(2,1)} "
            "%pallas_call.832, bf16[51200,1024]{1,0} %x), custom_call_target="
            '"tpu_custom_call"')
    assert trace.kind_of("jvp__.147", text) \
        == "jvp__ custom-call bf16[8192,1024]"
    assert trace.kind_of("fusion.5169", "%fusion.5169 = bf16[8,8]{1,0} "
                         "fusion(bf16[8,8]{1,0} %a), kind=kLoop") == "fusion"
    assert trace.kind_of("copy-done.1") == "copy-done"
    assert trace.kind_of("slice_bitcast_fusion.71.remat") \
        == trace.kind_of("slice_bitcast_fusion.5") == "slice_bitcast_fusion"
