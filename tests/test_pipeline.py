"""Pipeline parallelism tests: the GPipe schedule over a 'pipe' mesh axis
must match sequentially applying the stages (loss AND gradients)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import make_mesh
from mxnet_tpu.parallel.pipeline import PipelineParallel

S, D = 4, 8  # stages, feature dim


def stage_fn(p, x):
    return jax.nn.tanh(x @ p["w"] + p["b"])


def loss_fn(y, label):
    return jnp.sum((y - label) ** 2)


def make_params(rng):
    return {"w": jnp.asarray(rng.randn(S, D, D) * 0.4, jnp.float32),
            "b": jnp.asarray(rng.randn(S, D) * 0.1, jnp.float32)}


def sequential_loss(params, x, labels, M):
    """Ground truth: apply stages in order per microbatch, mean the loss."""
    xs = x.reshape((M, -1) + x.shape[1:])
    ls = labels.reshape((M, -1) + labels.shape[1:])
    total = 0.0
    for m in range(M):
        y = xs[m]
        for s in range(S):
            y = stage_fn({"w": params["w"][s], "b": params["b"][s]}, y)
        total = total + loss_fn(y, ls[m])
    return total / M


@pytest.fixture
def pipe():
    mesh = make_mesh(shape=(S,), axis_names=("pipe",))
    return PipelineParallel(stage_fn, loss_fn, mesh, axis="pipe",
                            num_microbatches=4)


def test_pipeline_loss_matches_sequential(pipe):
    rng = np.random.RandomState(0)
    params = make_params(rng)
    x = jnp.asarray(rng.randn(16, D), jnp.float32)
    labels = jnp.asarray(rng.randn(16, D), jnp.float32)
    got = float(pipe.loss(params, x, labels))
    want = float(sequential_loss(params, x, labels, 4))
    assert np.isclose(got, want, rtol=1e-5), (got, want)


def test_pipeline_grads_match_sequential(pipe):
    rng = np.random.RandomState(1)
    params = make_params(rng)
    x = jnp.asarray(rng.randn(16, D), jnp.float32)
    labels = jnp.asarray(rng.randn(16, D), jnp.float32)
    _, grads = pipe.grad_step(params, x, labels)
    want = jax.grad(lambda p: sequential_loss(p, x, labels, 4))(params)
    for k in ("w", "b"):
        np.testing.assert_allclose(np.asarray(grads[k]),
                                   np.asarray(want[k]),
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def test_pipeline_trains(pipe):
    rng = np.random.RandomState(2)
    params = make_params(rng)
    x = jnp.asarray(rng.randn(16, D), jnp.float32)
    labels = jnp.asarray(np.tanh(rng.randn(16, D)), jnp.float32)
    l0, params = pipe.grad_step(params, x, labels, lr=0.05)
    for _ in range(30):
        l1, params = pipe.grad_step(params, x, labels, lr=0.05)
    assert float(l1) < float(l0) * 0.5, (float(l0), float(l1))


def test_grad_step_compiles_once():
    """grad_step traces the schedule once per input sharding, not once per
    call, and a new lr never retraces; the compiled step computes what an
    eager value_and_grad through the same schedule does."""
    traces = []

    def counted_stage_fn(p, x):
        traces.append(1)
        return stage_fn(p, x)

    mesh = make_mesh(shape=(S,), axis_names=("pipe",))
    pipe = PipelineParallel(counted_stage_fn, loss_fn, mesh, axis="pipe",
                            num_microbatches=4)
    rng = np.random.RandomState(4)
    params = make_params(rng)
    x = jnp.asarray(rng.randn(16, D), jnp.float32)
    labels = jnp.asarray(np.tanh(rng.randn(16, D)), jnp.float32)
    counts = []
    for lr in (0.05, 0.04, 0.03, 0.02, 0.01):
        last = params
        loss, params = pipe.grad_step(params, x, labels, lr=lr)
        counts.append(len(traces))
    # the second call may compile once more: its params come back from the
    # first call sharded over the mesh; after that nothing retraces
    assert counts[1] == counts[-1], counts
    # the steady program (params from the mesh) against an eager
    # value_and_grad through the same schedule on the same inputs: the same
    # loss, and the same update from the same gradients
    want, grads = jax.value_and_grad(pipe.loss)(last, x, labels)
    np.testing.assert_array_equal(np.asarray(loss), np.asarray(want))
    for k in ("w", "b"):
        np.testing.assert_array_equal(
            np.asarray(params[k]),
            np.asarray(last[k] - 0.01 * grads[k]), err_msg=k)


def test_microbatch_divisibility_checked(pipe):
    rng = np.random.RandomState(3)
    params = make_params(rng)
    with pytest.raises(MXNetError):
        pipe.loss(params, jnp.zeros((10, D)), jnp.zeros((10, D)))


def test_bad_axis_rejected():
    mesh = make_mesh(shape=(4,), axis_names=("data",))
    with pytest.raises(MXNetError):
        PipelineParallel(stage_fn, loss_fn, mesh, axis="pipe")


def test_multihost_env_parsing(monkeypatch):
    """init_from_env resolves coordinator/rank from either env contract
    without initializing when unconfigured."""
    from mxnet_tpu.parallel import multihost

    for k in ("JAX_COORDINATOR_ADDRESS", "DMLC_PS_ROOT_URI"):
        monkeypatch.delenv(k, raising=False)
    assert multihost.init_from_env() == 1  # no config: single-process no-op

    monkeypatch.setenv("DMLC_PS_ROOT_URI", "10.0.0.1")
    monkeypatch.setenv("DMLC_PS_ROOT_PORT", "9091")
    assert multihost._dmlc_coordinator() == "10.0.0.1:9092"

    with pytest.raises(MXNetError):
        multihost.init_from_env(coordinator="x:1", num_processes=2,
                                process_id=5)
