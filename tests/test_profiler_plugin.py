"""Profiler hooks + plugin iterator tests."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.plugin.sframe import SFrameIter


def test_trace_writes_logdir(tmp_path):
    import jax.numpy as jnp

    logdir = str(tmp_path / "xprof")
    with mx.profiler.trace(logdir):
        with mx.profiler.annotate("matmul"):
            (jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    # a trace run directory must exist with at least one event file
    found = [f for _, _, fs in os.walk(logdir) for f in fs]
    assert found, "no trace output written"


def test_nested_trace_rejected(tmp_path):
    with mx.profiler.trace(str(tmp_path / "a")):
        with pytest.raises(MXNetError):
            with mx.profiler.trace(str(tmp_path / "b")):
                pass


def test_step_timer():
    t = mx.profiler.StepTimer(warmup=0)
    for _ in range(5):
        t.tic()
    s = t.summary()
    assert s["steps"] == 4 and s["mean_ms"] >= 0


def test_device_memory_profile(tmp_path):
    path = str(tmp_path / "mem.prof")
    mx.profiler.save_device_memory_profile(path)
    assert os.path.getsize(path) > 0


def test_sframe_iter_dict_backend():
    table = {"x": np.random.rand(10, 3).astype(np.float32),
             "y": np.arange(10, dtype=np.float32)}
    it = SFrameIter(table, data_field="x", label_field="y", batch_size=4)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (4, 3)
    assert batches[2].pad == 2
    it.reset()
    assert next(it).label[0].asnumpy()[0] == 0.0


def test_sframe_iter_multi_column():
    table = {"a": np.ones((6, 2), np.float32),
             "b": np.zeros((6, 3), np.float32)}
    it = SFrameIter(table, data_field=["a", "b"], batch_size=2)
    b = next(it)
    assert b.data[0].shape == (2, 5)


def test_sframe_iter_bad_column():
    with pytest.raises(MXNetError):
        SFrameIter({"x": np.ones(4)}, data_field="nope", batch_size=2)


def test_execution_plan_and_debug_str():
    """profiler.plan / Executor.debug_str: the GraphExecutor::Print
    analogue must itemize per-node FLOPs/bytes and carry XLA's aggregate
    cost analysis of the compiled program."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import profiler

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data=data, kernel=(3, 3), num_filter=8,
                             pad=(1, 1), name="conv0")
    net = mx.sym.Activation(data=net, act_type="relu", name="relu0")
    net = mx.sym.FullyConnected(data=net, num_hidden=10, name="fc0")
    net = mx.sym.SoftmaxOutput(data=net, name="softmax")
    exe = net.simple_bind(ctx=mx.cpu(), data=(4, 3, 16, 16),
                          softmax_label=(4,))

    p = profiler.plan(exe)
    assert p.mode == "train_step"
    by_name = {n.name: n for n in p.nodes}
    conv = by_name["conv0"]
    # 2 * out_elems * Cin * k*k = 2 * (4*8*16*16) * 3 * 9
    assert conv.flops == 2 * 4 * 8 * 16 * 16 * 3 * 9
    assert conv.out_shapes == [(4, 8, 16, 16)]
    fc = by_name["fc0"]
    assert fc.flops == 2 * 4 * 10 * (8 * 16 * 16)
    assert p.total_flops == sum(n.flops for n in p.nodes)
    # table sorted by decreasing flops and percentages sum to ~100
    rows = p.table()
    assert rows[0]["flops"] >= rows[-1]["flops"]
    assert abs(sum(r["flops_pct"] for r in rows) - 100.0) < 1e-6
    # XLA analysis present on the CPU backend, and counts the backward too
    assert p.xla.get("flops", 0) > p.total_flops
    assert "module" in p.hlo

    s = exe.debug_str()
    assert "conv0" in s and "GFLOPs" in s and "analytic totals" in s

    # eval mode compiles the inference program
    p_eval = profiler.plan(exe, mode="eval")
    assert p_eval.mode == "eval"
    assert p_eval.xla.get("flops", 0) < p.xla.get("flops", float("inf"))


def test_hlo_breakdown_parses_compiled_program():
    """profiler.hlo_breakdown: per-instruction bytes + conv/dot FLOPs of
    the optimized HLO, with operand shapes resolved through the symbol
    table (scheduled HLO prints operands bare)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.profiler import hlo_breakdown, format_breakdown

    def f(x, w, m):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return jnp.tanh(y @ m).sum()

    x = jnp.ones((2, 3, 8, 8), jnp.float32)
    w = jnp.ones((4, 3, 3, 3), jnp.float32)
    m = jnp.ones((8, 8), jnp.float32)
    compiled = jax.jit(f).lower(x, w, m).compile()
    bd = hlo_breakdown(compiled.as_text())
    assert bd["total_bytes"] > 0
    # conv FLOPs are padding-aware-exact: valid (out,k) pairs per spatial
    # dim at out=8,k=3,pad=1 is 7+8+7=22, so MACs = 2*4*3*22*22 and the
    # dot adds 2 * (2*4*8*8) * 8
    conv_flops = 2 * (2 * 4 * 3) * 22 * 22
    dot_flops = 2 * (2 * 4 * 8 * 8) * 8
    assert bd["total_flops"] == conv_flops + dot_flops
    assert any(op in bd["by_op"] for op in ("fusion", "convolution"))
    txt = format_breakdown(bd, peak_flops=1e12, peak_gbps=100)
    assert "roofline" in txt and "total:" in txt
