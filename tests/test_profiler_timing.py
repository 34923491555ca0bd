"""Timing helpers of the on-chip benchmarks.

`device_sync` is the execution barrier that closes a timed window (JAX
dispatch is asynchronous); `timed_median` must reject a one-off stall window
(a stall in a differenced window once fabricated a 3.8x speedup —
docs/mfu_roofline.md).
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import profiler


def test_device_sync_handles_arbitrary_pytrees():
    x = jnp.ones((8, 8))
    profiler.device_sync(x)
    profiler.device_sync({"a": [x, None], "b": 3})
    profiler.device_sync((None, "s"))  # no array leaves: no-op
    profiler.device_sync(jnp.ones(()))  # 0-d leaf has size 1


def test_device_sync_forces_value_dependency():
    # the probe's value depends on the producing computation: a wrong
    # implementation (e.g. syncing a constant) would not raise on NaNs
    # nor wait; here we just assert the probe reads through a jit chain
    f = jax.jit(lambda a: a * 2.0)
    out = f(jnp.full((4, 4), 21.0))
    profiler.device_sync(out)
    assert float(out[0, 0]) == 42.0


def test_timed_median_rejects_one_off_stall(monkeypatch):
    calls = {"n": 0}

    def run():
        calls["n"] += 1

    # fake a stall in the FIRST window by patching the clock: windows
    # measure [10s, 1s, 1s] -> median must be ~1s/rep, not the mean
    times = iter([0.0, 10.0,      # window 0: stall
                  10.0, 11.0,     # window 1
                  11.0, 12.0])    # window 2

    monkeypatch.setattr(time, "perf_counter", lambda: next(times))
    monkeypatch.setattr(profiler, "device_sync", lambda tree: None)
    dt = profiler.timed_median(run, lambda: None, reps=1, windows=3)
    assert dt == pytest.approx(1.0)
    assert calls["n"] == 3


def test_timed_median_divides_by_reps(monkeypatch):
    times = iter([0.0, 4.0, 0.0, 4.0, 0.0, 4.0])
    monkeypatch.setattr(time, "perf_counter", lambda: next(times))
    monkeypatch.setattr(profiler, "device_sync", lambda tree: None)
    dt = profiler.timed_median(lambda: None, lambda: None, reps=2,
                               windows=3)
    assert dt == pytest.approx(2.0)


def test_bench_has_no_probe_replay_or_retry():
    """A failed leg fails the run: bench.py carries no child-process probe,
    no replay of stored results and no transient-error retry."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "bench.py")
    spec = importlib.util.spec_from_file_location("bench", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    for gone in ("_device_probe", "_run_with_oom_retry", "_TRANSIENT_ERRS"):
        assert not hasattr(bench, gone)
    with open(path) as f:
        src = f.read()
    for gone in ("BENCH_SKIP_PROBE", "BENCH_PROBE_TIMEOUT", "PEAK_FLOPS",
                 "197e12", "subprocess.run([sys.executable, \"-c\""):
        assert gone not in src
    # the peak comes from the device table, which refuses what it does
    # not know
    chip_env = bench._tool("chip_env")
    with pytest.raises(KeyError):
        chip_env.peak_flops(jax.devices()[0])
