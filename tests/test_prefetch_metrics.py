"""Zero-sync train loop (ISSUE 5): double-buffered device prefetch +
on-device metric accumulation.

Covers the acceptance criteria:

* parity — prefetch on/off and MXNET_METRIC_INTERVAL 1 vs N produce
  identical parameters and final metric values;
* steady-state regression — with the device prefetcher and interval-N
  metrics, the loop performs at most ONE blocking host fetch per interval
  (`train.host_blocking_fetches`) and the per-step jitted dispatch count
  is unchanged from the PR 1 fused path;
* the `MXNET_DEVICE_PREFETCH=0` kill-switch;
* PrefetchingIter / DevicePrefetchIter worker-thread lifecycle (close is
  idempotent, joins the worker, and the training loops' finally blocks
  call it on exceptions);
* mid-pass auto-resume with `epoch_size` below a full data pass (the
  iterator cursor satellite);
* the in-graph step counter: MXNET_NONFINITE_GUARD-skipped steps no
  longer advance Adam's bias correction.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from common import blob_data as _data, mlp_classifier as _mlp
from mxnet_tpu import checkpoint, io as io_mod, metric as metric_mod
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.optimizer import Adam, get_fused_updater


def _fit_params(monkeypatch, prefetch, interval, layers=2, epochs=2):
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", str(prefetch))
    monkeypatch.setenv("MXNET_METRIC_INTERVAL", str(interval))
    mx.random.seed(5)
    np.random.seed(5)
    X, y = _data(n=128, seed=5)
    it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=True)
    mod = mx.mod.Module(_mlp(layers), context=mx.cpu())
    captured = {}

    def grab(p):
        captured["metric"] = p.eval_metric

    mod.fit(it, num_epoch=epochs, batch_end_callback=grab,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    arg, _ = mod.get_params()
    # the final epoch's train metric: interval mode drains at epoch end,
    # so by the time fit returns both paths cover every batch
    name, value = captured["metric"].get()
    return {k: v.asnumpy() for k, v in arg.items()}, (name, value)


def test_prefetch_and_metric_interval_parity(monkeypatch):
    """Params bit-for-bit and final accuracy identical across prefetch
    on/off x metric interval 1/N (the tentpole's kill-switch contract)."""
    base_params, base_metric = _fit_params(monkeypatch, prefetch=0,
                                           interval=1)
    for prefetch, interval in [(2, 1), (0, 4), (2, 4)]:
        params, met = _fit_params(monkeypatch, prefetch=prefetch,
                                  interval=interval)
        for k in base_params:
            np.testing.assert_array_equal(
                params[k], base_params[k],
                err_msg="%s (prefetch=%s interval=%s)"
                        % (k, prefetch, interval))
        assert met == base_metric, (prefetch, interval)


def test_device_prefetch_fast_path_used(monkeypatch):
    """With the prefetcher on, batches arrive pre-staged and
    load_data_batch takes the pointer-share path (io.device_batches)."""
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "2")
    reg = telemetry.registry()
    before = reg._counters.get("io.device_batches", 0)
    _fit_params(monkeypatch, prefetch=2, interval=1, epochs=1)
    assert reg._counters.get("io.device_batches", 0) > before


def test_device_prefetch_multi_device_parity(monkeypatch):
    """Pre-staged per-device slices on a 2-device group must match the
    synchronous slice-copy path bit-for-bit."""

    def run(prefetch):
        monkeypatch.setenv("MXNET_DEVICE_PREFETCH", str(prefetch))
        mx.random.seed(3)
        np.random.seed(3)
        X, y = _data(n=128, seed=3)
        it = mx.io.NDArrayIter(X, y, batch_size=32)
        mod = mx.mod.Module(_mlp(2), context=[mx.cpu(0), mx.cpu(1)])
        mod.fit(it, num_epoch=2,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
        arg, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}

    on = run(2)
    off = run(0)
    for k in on:
        np.testing.assert_array_equal(on[k], off[k], err_msg=k)


def _warm_module(interval_metric=None):
    mx.random.seed(0)
    X, y = _data()
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(_mlp(2), context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Uniform(0.05))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    if interval_metric is not None:
        assert mod._metric_stats_install(interval_metric)
    b = next(iter(it))
    mod.forward(b)
    mod.backward()
    mod.update()  # warm: everything compiled
    return mod, b


def test_steady_state_one_blocking_fetch_per_interval():
    """The zero-sync acceptance counter: 4 steps + one interval fetch
    advance train.host_blocking_fetches by exactly 1, and the in-graph
    metric matches the per-batch host metric exactly (same seeded run,
    legacy path)."""
    dev_metric = mx.metric.Accuracy()
    mod, b = _warm_module(interval_metric=dev_metric)
    mod._metric_stats_fetch(dev_metric)  # drain the warmup step
    dev_metric.reset()
    reg = telemetry.registry()
    before = reg._counters.get("train.host_blocking_fetches", 0)
    for _ in range(4):
        mod.forward(b)
        mod.backward()
        mod.update()
    mod._metric_stats_fetch(dev_metric)
    after = reg._counters.get("train.host_blocking_fetches", 0)
    assert after - before == 1, \
        "expected exactly one blocking fetch per interval, got %d" \
        % (after - before)
    assert dev_metric.num_inst == 4 * 32
    # parity with the legacy host path: an identical seeded run updating
    # the metric per batch (each step's metric covers that step's outputs)
    host_metric = mx.metric.Accuracy()
    mod2, b2 = _warm_module()
    for _ in range(4):
        mod2.forward(b2)
        mod2.backward()
        mod2.update()
        mod2.update_metric(host_metric, b2.label)
    assert dev_metric.get() == host_metric.get()


def test_metric_stats_dispatch_count_unchanged_from_pr1():
    """Metric stats ride the fused train-step program: warm per-step jit
    dispatches with the in-graph metric installed equal the plain fused
    path (PR 1's O(1) contract), still <= 4."""
    mod, b = _warm_module()
    with profiler.count_dispatches() as d_plain:
        mod.forward(b)
        mod.backward()
        mod.update()

    metric = mx.metric.Accuracy()
    mod2, b2 = _warm_module(interval_metric=metric)
    with profiler.count_dispatches() as d_stats:
        mod2.forward(b2)
        mod2.backward()
        mod2.update()
    assert d_stats.jit_entries == d_plain.jit_entries, (
        d_stats.as_dict(), d_plain.as_dict())
    assert d_stats.jit_entries <= 4, d_stats.as_dict()


def test_composite_and_metric_device_stats_match_host():
    """device_batch_stats == host update() for every supported metric,
    including a composite, on the same data."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    pred = rng.rand(32, 5).astype(np.float32)
    pred /= pred.sum(axis=1, keepdims=True)
    label = rng.randint(0, 5, 32).astype(np.float32)
    metrics = [mx.metric.Accuracy(), mx.metric.TopKAccuracy(top_k=2),
               mx.metric.CrossEntropy(), mx.metric.MSE(), mx.metric.MAE(),
               mx.metric.RMSE(),
               mx.metric.CompositeEvalMetric(["acc", "ce"])]
    for m in metrics:
        if isinstance(m, (mx.metric.MSE, mx.metric.MAE, mx.metric.RMSE)):
            lab, prd = label[:, None] / 5.0, pred[:, :1]
        else:
            lab, prd = label, pred
        stats = np.asarray(m.device_batch_stats([jnp.asarray(lab)],
                                                [jnp.asarray(prd)]))
        host = type(m)() if not isinstance(m, mx.metric.TopKAccuracy) \
            else mx.metric.TopKAccuracy(top_k=2)
        if isinstance(m, mx.metric.CompositeEvalMetric):
            host = mx.metric.CompositeEvalMetric(["acc", "ce"])
        host.update([mx.nd.array(lab)], [mx.nd.array(prd)])
        m.reset()
        m.apply_device_stats(stats)
        np.testing.assert_allclose(
            np.asarray(m.get()[1], np.float64),
            np.asarray(host.get()[1], np.float64),
            rtol=1e-6, err_msg=m.name)


def test_prefetching_iter_close_idempotent_and_revives():
    X = np.arange(60).reshape(60, 1).astype(np.float32)
    base = mx.io.NDArrayIter(X, np.zeros(60), batch_size=10)
    it = io_mod.PrefetchingIter(base)
    assert next(it) is not None  # worker spun up
    thread = it._thread
    assert thread is not None and thread.is_alive()
    it.close()
    it.close()  # idempotent
    assert not thread.is_alive()
    it.reset()
    assert len(list(it)) == 6  # revived after close
    it.close()


def test_device_prefetch_iter_close_and_errors():
    X = np.arange(40).reshape(40, 1).astype(np.float32)
    base = mx.io.NDArrayIter(X, np.zeros(40), batch_size=10)
    it = io_mod.DevicePrefetchIter(base, depth=2)
    got = list(it)
    assert len(got) == 4
    np.testing.assert_allclose(got[0].data[0].asnumpy(), X[:10])
    it.reset()
    assert len(list(it)) == 4
    it.close()
    assert not any(t.is_alive() for t in it._threads or ())

    class Boom(Exception):
        pass

    class FailingIter(mx.io.DataIter):
        def __init__(self):
            super().__init__()
            self.batch_size = 10
            self.n = 0

        def next(self):
            self.n += 1
            if self.n > 2:
                raise Boom("decode failed")
            return base.next()

        def reset(self):
            self.n = 0
            base.reset()

    base.reset()
    it2 = io_mod.DevicePrefetchIter(FailingIter(), depth=2)
    with pytest.raises(Boom):
        list(it2)  # worker exception surfaces on the consumer thread
    it2.close()


def test_fit_exception_joins_prefetch_workers(monkeypatch):
    """An in-loop exception must not leak the prefetch worker threads
    (the train loops' finally blocks close the wrapper)."""
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "2")

    class Stop(Exception):
        pass

    def boom(p):
        if p.nbatch == 2:
            raise Stop()

    mx.random.seed(0)
    X, y = _data(n=128)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp(1), context=mx.cpu())
    with pytest.raises(Stop):
        mod.fit(it, num_epoch=1, batch_end_callback=boom,
                optimizer_params={"learning_rate": 0.1})
    time.sleep(0.1)
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("mx-device-prefetch")]
    assert not leaked, leaked


def test_midpass_resume_with_epoch_size_bitforbit(tmp_path, monkeypatch):
    """ROADMAP PR 3 open item: with `epoch_size` below a full data pass,
    epoch boundaries are NOT reset boundaries — the saved iterator cursor
    (iter_pos) must restore the mid-pass position, not re-enter at a
    reset.  Interrupt mid-epoch after a checkpoint, resume, and match the
    uninterrupted run bit-for-bit.  Runs with the device prefetcher ON so
    queued-but-unconsumed batches are proven to count as not consumed."""
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "2")
    X, y = _data(n=128, seed=9)  # 8 batches/pass at batch 16

    def model():
        return mx.model.FeedForward(
            symbol=_mlp(2), ctx=mx.cpu(), num_epoch=4, epoch_size=5,
            learning_rate=0.1, momentum=0.9, numpy_batch_size=16)

    mx.random.seed(11)
    np.random.seed(11)
    ref = model()
    ref.fit(X, y, auto_checkpoint=str(tmp_path / "ref"),
            checkpoint_every=2)
    ref_params = {k: v.asnumpy() for k, v in ref.arg_params.items()}

    class Interrupt(Exception):
        pass

    def boom(p):
        if p.epoch == 2 and p.nbatch == 3:
            raise Interrupt()  # mid-epoch-2, after the nbatch=2 checkpoint

    prefix = str(tmp_path / "auto")
    mx.random.seed(11)
    np.random.seed(11)
    broken = model()
    with pytest.raises(Interrupt):
        broken.fit(X, y, auto_checkpoint=prefix, checkpoint_every=2,
                   batch_end_callback=boom)
    state = checkpoint.load_auto(prefix)
    assert state["epoch"] == 2 and state["nbatch"] == 2
    # epoch 2 started mid-pass: the cursor differs from nbatch — exactly
    # the case the old nbatch-only replay got wrong
    assert state["iter_pos"] != state["nbatch"]

    mx.random.seed(11)
    np.random.seed(11)
    resumed = model()
    resumed.fit(X, y, auto_checkpoint=prefix, checkpoint_every=2,
                resume="auto")
    for k, v in ref_params.items():
        np.testing.assert_array_equal(
            resumed.arg_params[k].asnumpy(), v, err_msg=k)


def test_auto_resume_composes_with_prefetch_and_interval(
        tmp_path, monkeypatch):
    """Chaos-smoke compose check: auto-resume + device prefetch + interval
    metrics together still land bit-for-bit (Module.fit path)."""
    monkeypatch.setenv("MXNET_DEVICE_PREFETCH", "2")
    monkeypatch.setenv("MXNET_METRIC_INTERVAL", "3")
    X, y = _data(n=128, seed=4)
    opt = {"learning_rate": 0.1, "momentum": 0.9}

    def fit(mod, **kw):
        it = mx.io.NDArrayIter(X, y, batch_size=16, shuffle=True)
        mod.fit(it, num_epoch=3, optimizer_params=opt, **kw)

    mx.random.seed(21)
    np.random.seed(21)
    ref = mx.mod.Module(_mlp(2), context=mx.cpu())
    fit(ref, auto_checkpoint=str(tmp_path / "ref"), checkpoint_every=3)
    ref_params = {k: v.asnumpy() for k, v in ref.get_params()[0].items()}

    class Interrupt(Exception):
        pass

    def boom(p):
        if p.epoch == 1 and p.nbatch == 4:
            raise Interrupt()

    prefix = str(tmp_path / "auto")
    mx.random.seed(21)
    np.random.seed(21)
    broken = mx.mod.Module(_mlp(2), context=mx.cpu())
    with pytest.raises(Interrupt):
        fit(broken, auto_checkpoint=prefix, checkpoint_every=3,
            batch_end_callback=boom)

    mx.random.seed(21)
    np.random.seed(21)
    resumed = mx.mod.Module(_mlp(2), context=mx.cpu())
    fit(resumed, auto_checkpoint=prefix, checkpoint_every=3, resume="auto")
    for k, v in ref_params.items():
        np.testing.assert_array_equal(
            resumed.get_params()[0][k].asnumpy(), v, err_msg=k)


def test_adam_guard_skipped_step_does_not_advance_bias_correction(
        monkeypatch):
    """The in-graph step counter: with MXNET_NONFINITE_GUARD=1, a run
    whose k-th step is guarded away is bit-identical to a run where that
    step never happened — Adam's bias correction no longer sees the
    host-side count of the skipped step (ROADMAP PR 3 open item)."""
    monkeypatch.setenv("MXNET_NONFINITE_GUARD", "1")

    def run(grads):
        opt = Adam(learning_rate=0.01)
        upd = get_fused_updater(opt)
        w = mx.nd.array(np.linspace(-1, 1, 8).astype(np.float32))
        for g in grads:
            upd([0], [mx.nd.array(g)], [w])
        m, v = upd.states[0]
        return w.asnumpy(), m.asnumpy(), v.asnumpy(), opt

    g1 = np.full((8,), 0.5, np.float32)
    g2 = np.full((8,), -0.25, np.float32)
    nan = np.full((8,), np.nan, np.float32)
    w_skip, m_skip, v_skip, opt_skip = run([g1, nan, g2])
    w_ref, m_ref, v_ref, _ = run([g1, g2])
    np.testing.assert_array_equal(w_skip, w_ref)
    np.testing.assert_array_equal(m_skip, m_ref)
    np.testing.assert_array_equal(v_skip, v_ref)
    # host-side counts still advance (they feed checkpoints/schedulers) —
    # the documented drift the device counter exists to bypass
    assert opt_skip._index_update_count[0] == 3


def test_adam_guard_counter_survives_auto_checkpoint(tmp_path, monkeypatch):
    """The applied-step counter is part of the checkpointed optimizer
    state: resuming after a guarded-away step must continue from the
    skip-corrected schedule, not re-absorb the skip from host counts."""
    monkeypatch.setenv("MXNET_NONFINITE_GUARD", "1")
    g1 = np.full((8,), 0.5, np.float32)
    g2 = np.full((8,), -0.25, np.float32)
    nan = np.full((8,), np.nan, np.float32)
    w0 = np.linspace(-1, 1, 8).astype(np.float32)

    def fresh():
        opt = Adam(learning_rate=0.01)
        return opt, get_fused_updater(opt)

    # uninterrupted: g1, nan(skipped), g2
    opt, upd = fresh()
    w = mx.nd.array(w0)
    for g in (g1, nan, g2):
        upd([0], [mx.nd.array(g)], [w])
    w_ref = w.asnumpy()

    # interrupted after the skip, checkpointed, resumed in fresh objects
    opt, upd = fresh()
    w = mx.nd.array(w0)
    for g in (g1, nan):
        upd([0], [mx.nd.array(g)], [w])
    checkpoint.save_auto(str(tmp_path / "g"), {"w": w}, {}, updater=upd)
    state = checkpoint.load_auto(str(tmp_path / "g"))
    opt2, upd2 = fresh()
    checkpoint.restore_auto(state, upd2)
    w2 = mx.nd.array(state["arg"]["w"].asnumpy())
    upd2([0], [mx.nd.array(g2)], [w2])
    np.testing.assert_array_equal(w2.asnumpy(), w_ref)


def test_adam_guard_mode_close_to_unguarded(monkeypatch):
    """Guard-mode Adam folds bias correction in-graph (f32) instead of
    host f64: with no bad steps the two paths agree to float tolerance."""

    def run(guard):
        if guard:
            monkeypatch.setenv("MXNET_NONFINITE_GUARD", "1")
        else:
            monkeypatch.delenv("MXNET_NONFINITE_GUARD", raising=False)
        opt = Adam(learning_rate=0.01)
        upd = get_fused_updater(opt)
        w = mx.nd.array(np.linspace(-1, 1, 8).astype(np.float32))
        for i in range(3):
            upd([0], [mx.nd.array(np.full((8,), 0.3 * (i + 1),
                                          np.float32))], [w])
        return w.asnumpy()

    np.testing.assert_allclose(run(True), run(False), rtol=2e-6, atol=1e-7)


def test_overlap_bench_smoke(monkeypatch, tmp_path):
    """bench.py --overlap: the synthetic input-bound benchmark runs and
    records the speedup + input_wait_frac artifact.  How large the speedup
    is belongs to the chip: on a CPU shared with the suite's other workers
    the ratio measures the machine's load, so it is not asserted here."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    monkeypatch.setenv("OVERLAP_BATCHES", "12")
    monkeypatch.setenv("OVERLAP_BATCH", "128")
    monkeypatch.setenv("OVERLAP_HIDDEN", "512")
    result = bench.overlap_bench(record=False)
    assert set(result) >= {"metric", "value", "sync_ms_per_step",
                           "overlap_ms_per_step", "input_wait_frac"}
    assert result["value"] > 0 and result["sync_ms_per_step"] > 0 \
        and result["overlap_ms_per_step"] > 0, result
    assert 0.0 <= result["input_wait_frac"] <= 1.0, result
