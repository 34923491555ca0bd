"""FusedSoftmaxCE: flash-style projection+CE head.

Contract: identical loss values and parameter gradients to the dense
FullyConnected -> SoftmaxOutput composite it replaces (reference semantics
`fully_connected-inl.h` + `softmax_output-inl.h`), without materializing
the (tokens, vocab) logits.  The Pallas TPU kernels are checked against the
jnp fallback on real hardware (tests/test_tpu_kernels.py-style gate);
everything here runs the fallback on CPU.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas_kernels.fused_ce import fused_softmax_ce


def _dense_ref(x, w, b, label):
    logits = x.astype(np.float32) @ w.astype(np.float32).T + b
    m = logits.max(axis=1, keepdims=True)
    lse = (m + np.log(np.exp(logits - m).sum(axis=1, keepdims=True)))[:, 0]
    picked = logits[np.arange(len(label)), label.astype(int)]
    return lse - picked


def _make(n=24, d=16, v=37, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d).astype(dtype) * 0.5
    w = rng.randn(v, d).astype(dtype) * 0.3
    b = rng.randn(v).astype(np.float32) * 0.1
    label = rng.randint(0, v, (n,)).astype(np.float32)
    return x, w, b, label


def test_forward_matches_dense():
    x, w, b, label = _make()
    nll = np.asarray(fused_softmax_ce(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), jnp.asarray(label),
        block_v=16))  # forces multiple tiles + a ragged last tile
    np.testing.assert_allclose(nll, _dense_ref(x, w, b, label),
                               rtol=1e-5, atol=1e-5)


def test_gradients_match_dense_head_composite():
    """vjp through the fused op == vjp through FC+SoftmaxOutput with the
    all-ones cotangent the training loop uses."""
    x, w, b, label = _make(n=20, d=12, v=29)
    xj, wj, bj, lj = map(jnp.asarray, (x, w, b, label))

    # loss-head semantics: cotangent is ignored, so drive vjp directly
    _, vjp = jax.vjp(
        lambda x_, w_, b_: fused_softmax_ce(x_, w_, b_, lj, block_v=8),
        xj, wj, bj)
    dx, dw, db = vjp(jnp.ones((len(x),), jnp.float32))

    # dense composite with identical numerics
    from mxnet_tpu.ops.loss import _softmax_output

    def dense(x_, w_, b_):
        logits = x_ @ w_.T + b_
        return _softmax_output(logits, lj, 1.0, -1.0, False, False)

    _, vjp_d = jax.vjp(dense, xj, wj, bj)
    probs = np.asarray(dense(xj, wj, bj))
    dx_d, dw_d, db_d = vjp_d(jnp.ones_like(jnp.asarray(probs)))

    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_d),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_d),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_d),
                               rtol=1e-4, atol=1e-5)


def test_grad_scale_scales_grads_not_loss():
    x, w, b, label = _make(n=8, d=8, v=11)
    xj, wj, bj, lj = map(jnp.asarray, (x, w, b, label))

    def run(gs):
        out, vjp = jax.vjp(
            lambda x_: fused_softmax_ce(x_, wj, bj, lj, grad_scale=gs,
                                        block_v=4), xj)
        (dx,) = vjp(jnp.ones_like(out))
        return np.asarray(out), np.asarray(dx)

    nll1, dx1 = run(1.0)
    nll2, dx2 = run(2.5)
    np.testing.assert_allclose(nll1, nll2, rtol=1e-6)
    np.testing.assert_allclose(dx2, dx1 * 2.5, rtol=1e-5, atol=1e-6)


def test_use_ignore_masks_rows():
    x, w, b, label = _make(n=10, d=8, v=13)
    label = np.arange(10, dtype=np.float32)
    label[5] = 6.0  # keep the ignore class only on rows 3 and 7
    label[3] = label[7] = 5.0
    xj, wj, bj = map(jnp.asarray, (x, w, b))
    lj = jnp.asarray(label)
    out, vjp = jax.vjp(
        lambda x_: fused_softmax_ce(x_, wj, bj, lj, ignore_label=5.0,
                                    use_ignore=True, block_v=8), xj)
    (dx,) = vjp(jnp.ones_like(out))
    out, dx = np.asarray(out), np.asarray(dx)
    assert out[3] == 0.0 and out[7] == 0.0
    assert np.all(out[[0, 1, 2, 4, 5, 6, 8, 9]] > 0)
    np.testing.assert_allclose(dx[3], 0.0, atol=1e-7)
    np.testing.assert_allclose(dx[7], 0.0, atol=1e-7)
    assert np.abs(dx[0]).max() > 0


def test_symbol_op_shapes_and_executor():
    """FusedSoftmaxCE as a Symbol: shape inference + bound train step, and
    weight grads equal the dense head's through the executor path."""
    v, d, n = 21, 10, 12
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    net = mx.sym.FusedSoftmaxCE(data=data, label=label, num_hidden=v,
                                name="pred")
    arg_shapes, out_shapes, _ = net.infer_shape(data=(n, d),
                                                softmax_label=(n,))
    assert out_shapes == [(n,)]
    shape_of = dict(zip(net.list_arguments(), arg_shapes))
    assert shape_of["pred_weight"] == (v, d)
    assert shape_of["pred_bias"] == (v,)

    dense = mx.sym.SoftmaxOutput(
        data=mx.sym.FullyConnected(data=data, num_hidden=v, name="pred"),
        label=label, name="softmax")

    rng = np.random.RandomState(3)
    args = {"data": mx.nd.array(rng.randn(n, d).astype(np.float32)),
            "softmax_label": mx.nd.array(
                rng.randint(0, v, (n,)).astype(np.float32)),
            "pred_weight": mx.nd.array(
                rng.randn(v, d).astype(np.float32) * 0.2),
            "pred_bias": mx.nd.array(np.zeros(v, np.float32))}

    grads = {}
    for which, s in (("fused", net), ("dense", dense)):
        g = {k: mx.nd.zeros(a.shape) for k, a in args.items()}
        exe = s.bind(mx.cpu(), {k: a.copy() for k, a in args.items()},
                     args_grad=g)
        exe.forward(is_train=True)
        exe.backward()
        grads[which] = {k: a.asnumpy() for k, a in g.items()}

    for k in ("pred_weight", "pred_bias", "data"):
        np.testing.assert_allclose(grads["fused"][k], grads["dense"][k],
                                   rtol=1e-4, atol=1e-5,
                                   err_msg="grad mismatch for %s" % k)


@pytest.mark.parametrize("single_pass", ["0", "1"])
def test_transformer_fused_head_grads_match_dense(monkeypatch, single_pass):
    """End-to-end: get_transformer_lm(fused_head=True) must produce the
    same parameter gradients as the dense-head model — under BOTH the
    round-5 5-pass recompute structure (MXNET_CE_SINGLE_PASS=0) and the
    round-6 single-pass structure."""
    monkeypatch.setenv("MXNET_CE_SINGLE_PASS", single_pass)
    from mxnet_tpu import models

    vocab, seq, batch = 19, 6, 4
    kwargs = dict(vocab_size=vocab, seq_len=seq, num_layers=1, num_heads=2,
                  num_embed=16)
    rng = np.random.RandomState(0)
    X = rng.randint(0, vocab, (batch, seq)).astype(np.float32)
    Y = rng.randint(0, vocab, (batch, seq)).astype(np.float32)

    grads = {}
    for which, fused in (("fused", True), ("dense", False)):
        net = models.get_transformer_lm(fused_head=fused, **kwargs)
        arg_shapes, _, _ = net.infer_shape(data=(batch, seq),
                                           softmax_label=(batch, seq))
        prng = np.random.RandomState(7)
        args, g = {}, {}
        for name, s in zip(net.list_arguments(), arg_shapes):
            if name == "data":
                args[name] = mx.nd.array(X)
            elif name == "softmax_label":
                args[name] = mx.nd.array(Y)
            else:
                args[name] = mx.nd.array(
                    prng.randn(*s).astype(np.float32) * 0.1)
            g[name] = mx.nd.zeros(s)
        exe = net.bind(mx.cpu(), args, args_grad=g)
        exe.forward(is_train=True)
        exe.backward()
        grads[which] = {k: a.asnumpy() for k, a in g.items()}

    for k in grads["fused"]:
        if k in ("data", "softmax_label"):
            continue
        np.testing.assert_allclose(
            grads["fused"][k], grads["dense"][k], rtol=2e-4, atol=1e-5,
            err_msg="grad mismatch for %s" % k)


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="pallas kernels need real TPU")
def test_pallas_matches_jnp_on_tpu():
    """The Pallas forward/backward kernels vs the jnp fallback, on-chip,
    at shapes that take the kernel path (round-2 lesson: the interpreter
    passing is not evidence — verify lowering on hardware)."""
    from mxnet_tpu.ops.pallas_kernels import fused_ce

    n, d, v = 1024, 256, 4100  # ragged vocab tile + padded tokens
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, d).astype(np.float32) * 0.5,
                    jnp.bfloat16)
    w = jnp.asarray(rng.randn(v, d).astype(np.float32) * 0.3, jnp.bfloat16)
    b = jnp.asarray(rng.randn(v).astype(np.float32) * 0.1, jnp.bfloat16)
    label = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)

    assert fused_ce._use_pallas(x, w)
    fwd_p = jax.jit(lambda: fused_ce._fwd_pallas(
        x, w, b, label, 1.0, -1.0, False, 512, 2048))
    fwd_j = jax.jit(lambda: fused_ce._fwd_jnp(
        x, w, b, label, 1.0, -1.0, False, 2048))
    (nll_p, lse_p), (nll_j, lse_j) = fwd_p(), fwd_j()
    np.testing.assert_allclose(np.asarray(nll_p), np.asarray(nll_j),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_j),
                               rtol=2e-3, atol=2e-3)

    bwd_p = jax.jit(lambda: fused_ce._bwd_pallas(
        x, w, b, label, lse_j, 1.0, -1.0, False, 512, 2048))
    bwd_j = jax.jit(lambda: fused_ce._bwd_jnp(
        x, w, b, label, lse_j, 1.0, -1.0, False, 2048))
    (dx_p, dw_p, db_p), (dx_j, dw_j, db_j) = bwd_p(), bwd_j()
    np.testing.assert_allclose(np.asarray(dx_p, np.float32),
                               np.asarray(dx_j, np.float32),
                               rtol=5e-2, atol=2e-3)
    np.testing.assert_allclose(np.asarray(dw_p, np.float32),
                               np.asarray(dw_j, np.float32),
                               rtol=5e-2, atol=2e-3)
    np.testing.assert_allclose(np.asarray(db_p, np.float32),
                               np.asarray(db_j, np.float32),
                               rtol=5e-2, atol=2e-3)


def test_fused_head_dp_grads_match_single_device():
    """Data-parallel SPMD training with the fused head must reproduce the
    single-device parameter trajectory exactly (XLA inserts the dW psum
    over the sharded token axis; a wrong collective would diverge here)."""
    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    vocab, seq, batch = 24, 8, 16
    rng = np.random.RandomState(0)
    data = rng.randint(0, vocab, (batch, seq)).astype(np.int32)
    label = rng.randint(0, vocab, (batch, seq)).astype(np.float32)
    batch_d = {"data": data, "softmax_label": label}

    def trajectory(n_dev):
        mx.random.seed(0)
        net = models.get_transformer_lm(
            vocab_size=vocab, seq_len=seq, num_layers=1, num_heads=2,
            num_embed=16, fused_head=True)
        mesh = make_mesh(shape=(n_dev,), axis_names=("data",))
        # sgd, not adam: the attention k_bias gradient is analytically
        # zero (softmax shift invariance), and adam's m/sqrt(v) on pure
        # reduction-order noise is not reproducible across device counts
        tr = SPMDTrainer(net, mesh,
                         data_shapes={"data": (batch, seq),
                                      "softmax_label": (batch, seq)},
                         lr=1e-2, optimizer="sgd", momentum=0.9, wd=0.0)
        for _ in range(3):
            tr.step(batch_d)
        arg, _ = tr.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}

    p1 = trajectory(1)
    p8 = trajectory(8)
    for k in p1:
        np.testing.assert_allclose(p8[k], p1[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_bias_none_and_int_labels_under_grad():
    """bias=None derives a zero bias from the weight (vma-type inheritance
    under shard_map depends on this — a fresh jnp.zeros would not carry
    varying axes) and integer labels take a float0 cotangent."""
    x, w, b, label = _make(n=12, d=8, v=17)
    xj, wj = jnp.asarray(x), jnp.asarray(w)
    li = jnp.asarray(label, jnp.int32)

    nll_none = fused_softmax_ce(xj, wj, None, li, block_v=8)
    nll_zero = fused_softmax_ce(xj, wj, jnp.zeros((17,), jnp.float32), li,
                                block_v=8)
    np.testing.assert_allclose(np.asarray(nll_none), np.asarray(nll_zero),
                               rtol=1e-6)

    # int labels under jax.grad must not raise (float0 cotangent)
    g = jax.grad(lambda x_: jnp.sum(
        fused_softmax_ce(x_, wj, None, li, block_v=8)))(xj)
    assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# round 6: single-pass structure + vocab sharding
# ---------------------------------------------------------------------------


def _vjp_all(fn, x, w, b):
    out, vjp = jax.vjp(fn, x, w, b)
    dx, dw, db = vjp(jnp.ones_like(out))
    return tuple(np.asarray(t) for t in (out, dx, dw, db))


def _ignore_case(n=24, d=16, v=40):
    x, w, b, label = _make(n=n, d=d, v=v)
    label[3] = label[7] = 5.0  # exercised ignore rows
    return (jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
            jnp.asarray(label))


def test_single_pass_matches_five_pass(monkeypatch):
    """MXNET_CE_SINGLE_PASS=1 (store the p@W residual, 4 logit passes)
    must reproduce the 5-pass structure's loss AND gradients, including
    grad_scale and ignore_label; =0 is the bit-for-bit kill-switch (same
    code path as round 5)."""
    xj, wj, bj, lj = _ignore_case()
    kw = dict(grad_scale=1.7, ignore_label=5.0, use_ignore=True, block_v=8)

    def run(flag):
        monkeypatch.setenv("MXNET_CE_SINGLE_PASS", flag)
        return _vjp_all(
            lambda x_, w_, b_: fused_softmax_ce(x_, w_, b_, lj, **kw),
            xj, wj, bj)

    ref = run("0")
    got = run("1")
    # the non-vjp forward shares the stats implementation: bit-identical
    nll0 = np.asarray(fused_softmax_ce(xj, wj, bj, lj, **kw))
    monkeypatch.setenv("MXNET_CE_SINGLE_PASS", "0")
    np.testing.assert_array_equal(
        nll0, np.asarray(fused_softmax_ce(xj, wj, bj, lj, **kw)))
    for name, a, g in zip(("nll", "dx", "dw", "db"), ref, got):
        np.testing.assert_allclose(g, a, rtol=1e-5, atol=1e-6,
                                   err_msg="single-pass %s" % name)
    # kill-switch really is the round-5 entry point
    from mxnet_tpu.ops.pallas_kernels.fused_ce import _fused_ce

    direct = _vjp_all(
        lambda x_, w_, b_: _fused_ce(x_, w_, b_, lj, 1.7, 5.0, True,
                                     512, 8), xj, wj, bj)
    for name, a, g in zip(("nll", "dx", "dw", "db"), ref, direct):
        np.testing.assert_array_equal(a, g,
                                      err_msg="kill-switch %s" % name)


def test_single_pass_out_of_range_labels(monkeypatch):
    """Out-of-range labels (label -1 — the MXNet padding convention —
    WITHOUT use_ignore, or label >= vocab) match no onehot column in the
    5-pass structure, so the single-pass dx must not subtract any W row
    for them either."""
    x, w, b, label = _make(n=24, d=16, v=40)
    label[0] = -1.0
    label[5] = 40.0
    xj, wj, bj, lj = (jnp.asarray(t) for t in (x, w, b, label))
    kw = dict(grad_scale=1.3, use_ignore=False, block_v=8)

    def run(flag):
        monkeypatch.setenv("MXNET_CE_SINGLE_PASS", flag)
        return _vjp_all(
            lambda x_, w_, b_: fused_softmax_ce(x_, w_, b_, lj, **kw),
            xj, wj, bj)

    ref = run("0")
    got = run("1")
    for name, a, g in zip(("nll", "dx", "dw", "db"), ref, got):
        np.testing.assert_allclose(g, a, rtol=1e-5, atol=1e-6,
                                   err_msg="out-of-range %s" % name)


@pytest.mark.parametrize("single_pass", ["0", "1"])
def test_sharded_matches_dense_on_cpu_mesh(monkeypatch, single_pass):
    """fused_softmax_ce_sharded inside shard_map (tokens over "data",
    vocab over "model") vs the unsharded op: losses and every gradient,
    with grad_scale + ignore_label, under both backward structures."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.ops.pallas_kernels.fused_ce import \
        fused_softmax_ce_sharded
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import shard_map

    monkeypatch.setenv("MXNET_CE_SINGLE_PASS", single_pass)
    xj, wj, bj, lj = _ignore_case(n=24, d=16, v=40)
    kw = dict(grad_scale=1.7, ignore_label=5.0, use_ignore=True, block_v=8)
    ref = _vjp_all(
        lambda x_, w_, b_: fused_softmax_ce(x_, w_, b_, lj, **kw),
        xj, wj, bj)

    mesh = make_mesh(shape=(2, 4), axis_names=("data", "model"))

    def sharded(x_, w_, b_):
        def body(xs, ws, bs, ys):
            return fused_softmax_ce_sharded(xs, ws, bs, ys, "model", **kw)

        return shard_map(body, mesh=mesh,
                         in_specs=(P("data", None), P("model", None),
                                   P("model"), P("data")),
                         out_specs=P("data"))(x_, w_, b_, lj)

    got = _vjp_all(sharded, xj, wj, bj)
    for name, a, g in zip(("nll", "dx", "dw", "db"), ref, got):
        np.testing.assert_allclose(g, a, rtol=1e-4, atol=1e-5,
                                   err_msg="sharded %s" % name)


def test_ce_shard_trainer_trajectory_matches_replicated(monkeypatch):
    """MXNET_CE_SHARD=1 end-to-end: an SPMDTrainer on a (data, model)
    mesh (head weight stored in V/tp slices, lse reduce on the mesh)
    must walk the same parameter trajectory as the replicated-head
    single-device trainer."""
    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    vocab, seq, batch = 24, 8, 16
    rng = np.random.RandomState(0)
    bd = {"data": rng.randint(0, vocab, (batch, seq)).astype(np.int32),
          "softmax_label": rng.randint(0, vocab, (batch, seq)).astype(
              np.float32)}

    def traj(mesh_shape, axes, shard):
        monkeypatch.setenv("MXNET_CE_SHARD", "1" if shard else "0")
        mx.random.seed(0)
        net = models.get_transformer_lm(
            vocab_size=vocab, seq_len=seq, num_layers=1, num_heads=2,
            num_embed=16, fused_head=True)
        mesh = make_mesh(shape=mesh_shape, axis_names=axes)
        tr = SPMDTrainer(net, mesh,
                         data_shapes={"data": (batch, seq),
                                      "softmax_label": (batch, seq)},
                         lr=1e-2, optimizer="sgd", momentum=0.9, wd=0.0)
        if shard:
            # the head really is stored sharded (momenta included)
            from jax.sharding import PartitionSpec as P

            spec = tr._param_sharding["pred_weight"].spec
            assert spec == P("model", None), spec
        for _ in range(3):
            tr.step(bd)
        arg, _ = tr.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}

    ref = traj((1,), ("data",), False)
    got = traj((2, 4), ("data", "model"), True)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-4, atol=1e-5,
                                   err_msg=k)


def test_single_pass_dispatch_count_unchanged(monkeypatch):
    """The single-pass structure changes kernels, not dispatch topology:
    one fused fwd+bwd program per train step either way
    (profiler.count_dispatches, the PR-1 O(1) contract)."""
    from mxnet_tpu import profiler

    v, d, n = 21, 10, 12
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    net = mx.sym.FusedSoftmaxCE(data=data, label=label, num_hidden=v,
                                name="pred")
    rng = np.random.RandomState(3)
    counts = {}
    for flag in ("0", "1"):
        monkeypatch.setenv("MXNET_CE_SINGLE_PASS", flag)
        args = {"data": mx.nd.array(rng.randn(n, d).astype(np.float32)),
                "softmax_label": mx.nd.array(
                    rng.randint(0, v, (n,)).astype(np.float32)),
                "pred_weight": mx.nd.array(
                    rng.randn(v, d).astype(np.float32) * 0.2),
                "pred_bias": mx.nd.array(np.zeros(v, np.float32))}
        g = {k: mx.nd.zeros(a.shape) for k, a in args.items()}
        exe = net.bind(mx.cpu(), args, args_grad=g)
        exe.forward(is_train=True)
        exe.backward()  # warm: compile outside the counted window
        exe.forward(is_train=True)
        with profiler.count_dispatches() as dcount:
            exe.backward()
        counts[flag] = dcount.jit_entries
    assert counts["0"] == counts["1"] == 1, counts


def test_ce_shard_zero_steady_state_retraces(monkeypatch):
    """With the sharded head enabled, a fixed-shape training loop must
    not recompile after warmup: the retrace watchdog (fed by
    SPMDTrainer.step) records zero 'trainer.step' retrace events."""
    from mxnet_tpu import models, telemetry
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    monkeypatch.setenv("MXNET_CE_SHARD", "1")
    vocab, seq, batch = 24, 8, 16
    rng = np.random.RandomState(0)
    bd = {"data": rng.randint(0, vocab, (batch, seq)).astype(np.int32),
          "softmax_label": rng.randint(0, vocab, (batch, seq)).astype(
              np.float32)}
    mx.random.seed(0)
    net = models.get_transformer_lm(vocab_size=vocab, seq_len=seq,
                                    num_layers=1, num_heads=2,
                                    num_embed=16, fused_head=True)
    mesh = make_mesh(shape=(4, 2), axis_names=("data", "model"))
    tr = SPMDTrainer(net, mesh,
                     data_shapes={"data": (batch, seq),
                                  "softmax_label": (batch, seq)},
                     lr=1e-2, optimizer="sgd")
    before = len([e for e in telemetry.events("retrace")
                  if e.get("site") == "trainer.step"])
    for _ in range(4):
        tr.step(bd)
    after = [e for e in telemetry.events("retrace")
             if e.get("site") == "trainer.step"]
    assert len(after) == before, after[before:]


@pytest.mark.parametrize("cast_weight", [True, False])
def test_fused_ce_inside_shard_map(cast_weight):
    """The long-context configuration: tokens sharded over a mesh axis,
    fused head inside shard_map with a replicated weight — cast to varying
    by the caller (shard_map's transpose then psums dW) or used as it is
    (the head's own vjp rule psums it); either way dW must equal the
    replicated gradient of the unsharded computation."""
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import shard_map

    n, d, v = 32, 8, 19
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, d).astype(np.float32) * 0.5)
    w = jnp.asarray(rng.randn(v, d).astype(np.float32) * 0.3)
    label = jnp.asarray(rng.randint(0, v, (n,)), jnp.int32)
    mesh = make_mesh(shape=(8,), axis_names=("seq",))

    def sharded_loss(x_, w_):
        def local(xs, wr, ys):
            if cast_weight:
                wr = jax.lax.pcast(wr, ("seq",), to="varying")
            return fused_softmax_ce(xs, wr, None, ys,
                                    grad_scale=1.0 / n, block_v=8)

        fn = shard_map(local, mesh=mesh,
                       in_specs=(P("seq"), P(), P("seq")),
                       out_specs=P("seq"))
        return fn(x_, w_, label).mean()

    def plain_loss(x_, w_):
        return fused_softmax_ce(x_, w_, None, label,
                                grad_scale=1.0 / n, block_v=8).mean()

    ls, (dxs, dws) = jax.value_and_grad(sharded_loss, argnums=(0, 1))(x, w)
    lp, (dxp, dwp) = jax.value_and_grad(plain_loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(ls), float(lp), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dxs), np.asarray(dxp),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dws), np.asarray(dwp),
                               rtol=1e-5, atol=1e-6)
