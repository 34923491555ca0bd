"""Execute the real Pallas kernel bodies on the CPU mesh via interpret mode.

VERDICT r3 weak #3: the CPU suite only ever ran the jnp fallbacks (the
kernels gate on `jax.default_backend() == "tpu"`), so a kernel-body
regression shipped green and was only caught by the on-chip preflight.
These tests flip the module-level `_INTERPRET` switch so `pl.pallas_call`
runs the kernels through the Pallas interpreter — same jaxpr, no Mosaic —
and check them against the jnp fallbacks.  (Mosaic lowering constraints —
tile shapes, layouts — still need the chip: scripts/pallas_preflight.py.)
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.pallas_kernels import flash_attention_mod as fa
from mxnet_tpu.ops.pallas_kernels import fused_ce_mod as fc


@pytest.fixture()
def interpret(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)
    monkeypatch.setattr(fc, "_INTERPRET", True)


def _maxerr(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


@pytest.mark.parametrize("causal,sq,skv", [(True, 256, 256),
                                           (False, 256, 192)])
def test_flash_fwd_kernels_match_jnp(interpret, causal, sq, skv):
    rng = np.random.RandomState(0)
    b, h, d = 2, 3, 64
    q = jnp.asarray(rng.randn(b, h, sq, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(b, h, skv, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(b, h, skv, d) * 0.5, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    zero = jnp.asarray(0, jnp.int32)
    o_j, lse_j = jax.jit(lambda q, k, v: fa._flash_fwd_jnp(
        q, k, v, zero, zero, scale, causal, 128))(q, k, v)
    # hsd kernel
    o_h, lse_h = jax.jit(lambda q, k, v: fa._flash_fwd_pallas(
        q, k, v, zero, zero, scale, causal, 128, 128))(q, k, v)
    assert _maxerr(o_h, o_j) < 1e-5
    assert _maxerr(lse_h, lse_j) < 1e-5
    # dS kernel
    o_d, lse_d = jax.jit(lambda q, k, v: fa._flash_fwd_pallas_ds(
        q.swapaxes(2, 3), k.swapaxes(2, 3), v.swapaxes(2, 3),
        zero, zero, scale, causal, 128, 128))(q, k, v)
    assert _maxerr(o_d.swapaxes(2, 3), o_j) < 1e-5
    assert _maxerr(lse_d, lse_j) < 1e-5


@pytest.mark.parametrize("causal,sq,skv", [(True, 256, 256),
                                           (False, 256, 192)])
def test_flash_bwd_kernels_match_jnp(interpret, causal, sq, skv):
    rng = np.random.RandomState(1)
    b, h, d = 2, 3, 64
    q = jnp.asarray(rng.randn(b, h, sq, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(b, h, skv, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(b, h, skv, d) * 0.5, jnp.float32)
    g = jnp.asarray(rng.randn(b, h, sq, d) * 0.5, jnp.float32)
    scale = 1.0 / np.sqrt(d)
    zero = jnp.asarray(0, jnp.int32)
    o, lse = jax.jit(lambda q, k, v: fa._flash_fwd_jnp(
        q, k, v, zero, zero, scale, causal, 128))(q, k, v)
    grads = (g, jnp.zeros_like(lse))
    res = (q, k, v, o, lse, zero, zero)
    ref = jax.jit(lambda r, gr: fa._flash_bwd(
        scale, causal, 128, r, gr)[:3])(res, grads)
    hsd = jax.jit(lambda r, gr: fa._flash_bwd_pallas(
        scale, causal, 128, 128, r, gr)[:3])(res, grads)
    res_ds = (q.swapaxes(2, 3), k.swapaxes(2, 3), v.swapaxes(2, 3),
              o.swapaxes(2, 3), lse, zero, zero)
    ds = jax.jit(lambda r, gr: fa._flash_bwd_pallas_ds(
        scale, causal, 128, 128, r, gr)[:3])(res_ds, grads)
    for name, a, b_ in zip(("dq", "dk", "dv"), hsd, ref):
        assert _maxerr(a, b_) < 1e-4, ("hsd", name)
    for name, a, b_ in zip(("dq", "dk", "dv"), ds, ref):
        assert _maxerr(a, b_) < 1e-4, ("ds", name)


def test_flash_public_api_grad_via_interpret(interpret, monkeypatch):
    """End-to-end: _pick_impl routes to a Pallas impl under interpret
    (hsd by default, ds via MXNET_FLASH_LAYOUT), and the custom_vjp grad
    through the kernels matches the jnp impl."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 640, 64) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 640, 64) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(1, 2, 640, 64) * 0.5, jnp.float32)
    monkeypatch.delenv("MXNET_FLASH_LAYOUT", raising=False)
    assert fa._pick_impl(q, 640) == "pallas_hsd"
    monkeypatch.setenv("MXNET_FLASH_LAYOUT", "ds")
    assert fa._pick_impl(q, 640) == "pallas_ds"

    def loss(q, k, v):
        return (fa.flash_attention(q, k, v, causal=True) ** 2).sum()

    got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    scale = 1.0 / np.sqrt(64)

    def loss_jnp(q, k, v):
        out, _ = fa._flash(q, k, v, 0.0, 0.0, scale, True, 128, 128, "jnp")
        return (out ** 2).sum()

    want = jax.jit(jax.grad(loss_jnp, argnums=(0, 1, 2)))(q, k, v)
    for name, a, b_ in zip("qkv", got, want):
        assert _maxerr(a, b_) < 1e-3, name


def test_fused_ce_kernels_match_jnp(interpret):
    rng = np.random.RandomState(3)
    N, D, V = 512, 128, 2048
    x = jnp.asarray(rng.randn(N, D) * 0.5, jnp.float32)
    w = jnp.asarray(rng.randn(V, D) * 0.05, jnp.float32)
    b = jnp.asarray(rng.randn(V) * 0.1, jnp.float32)
    lbl = jnp.asarray(rng.randint(0, V, N), jnp.int32)
    args = (1.0, float(V // 2), True)
    nll_p, lse_p = jax.jit(lambda x, w, b, l: fc._fwd_pallas(
        x, w, b, l, *args, 256, 1024))(x, w, b, lbl)
    nll_j, lse_j = jax.jit(lambda x, w, b, l: fc._fwd_jnp(
        x, w, b, l, *args, 1024))(x, w, b, lbl)
    assert _maxerr(nll_p, nll_j) < 1e-4
    assert _maxerr(lse_p, lse_j) < 1e-4
    got = jax.jit(lambda x, w, b, l, s: fc._bwd_pallas(
        x, w, b, l, s, *args, 256, 1024))(x, w, b, lbl, lse_j)
    want = jax.jit(lambda x, w, b, l, s: fc._bwd_jnp(
        x, w, b, l, s, *args, 1024))(x, w, b, lbl, lse_j)
    for name, a, b_ in zip(("dx", "dw", "db"), got, want):
        assert _maxerr(a, b_) < 1e-4, name


def test_fused_ce_single_pass_kernels_match_jnp(interpret):
    """Round-6 kernels: the stats+residual forward (`_fwd_sp_*`) and the
    row-scaled dW/dx backwards (`_bwd_*_rs_*`) — the single-pass and
    vocab-sharded structures — against their jnp fallbacks, at a shape
    with a ragged vocab tile and padded token blocks."""
    rng = np.random.RandomState(5)
    N, D, V = 512, 128, 2100
    x = jnp.asarray(rng.randn(N, D) * 0.5, jnp.float32)
    w = jnp.asarray(rng.randn(V, D) * 0.05, jnp.float32)
    b = jnp.asarray(rng.randn(V) * 0.1, jnp.float32)
    lbl = jnp.asarray(rng.randint(0, V, N), jnp.int32)
    assert fc._use_pallas(x, w)

    got = jax.jit(lambda *t: fc._fwd_sp_pallas(*t, 256, 1024))(x, w, b, lbl)
    want = jax.jit(lambda *t: fc._fwd_sp_jnp(*t, 1024))(x, w, b, lbl)
    for name, p, j in zip(("lse", "picked", "dxp"), got, want):
        assert _maxerr(p, j) < 1e-4, name
    lse = want[0]

    # per-row coefficient folds grad_scale/ignore/padding in one vector
    r = jnp.asarray(rng.rand(N).astype(np.float32))
    got = jax.jit(lambda *t: fc._bwd_dw_rs_pallas(*t, 256, 1024))(
        x, w, b, lbl, lse, r)
    want = jax.jit(lambda *t: fc._bwd_dw_rs_jnp(*t, 1024))(
        x, w, b, lbl, lse, r)
    for name, p, j in zip(("dw", "db"), got, want):
        assert _maxerr(p, j) < 1e-4, name
    dx_p = jax.jit(lambda *t: fc._bwd_dx_rs_pallas(*t, 256, 1024))(
        x, w, b, lbl, lse, r)
    dx_j = jax.jit(lambda *t: fc._bwd_dx_rs_jnp(*t, 1024))(
        x, w, b, lbl, lse, r)
    assert _maxerr(dx_p, dx_j) < 1e-4


def test_fused_ce_single_pass_public_grad_via_interpret(interpret,
                                                        monkeypatch):
    """End-to-end through fused_softmax_ce with MXNET_CE_SINGLE_PASS=1:
    the custom_vjp over the interpreted Pallas kernels matches the
    5-pass jnp reference gradients."""
    monkeypatch.setenv("MXNET_CE_SINGLE_PASS", "1")
    rng = np.random.RandomState(6)
    N, D, V = 512, 128, 2048
    x = jnp.asarray(rng.randn(N, D) * 0.5, jnp.float32)
    w = jnp.asarray(rng.randn(V, D) * 0.05, jnp.float32)
    b = jnp.asarray(rng.randn(V) * 0.1, jnp.float32)
    lbl = jnp.asarray(rng.randint(0, V, N), jnp.int32)
    assert fc._use_pallas(x, w)
    out, vjp = jax.vjp(
        lambda x_, w_, b_: fc.fused_softmax_ce(x_, w_, b_, lbl,
                                               grad_scale=1.3), x, w, b)
    dx, dw, db = vjp(jnp.ones_like(out))

    monkeypatch.setenv("MXNET_CE_SINGLE_PASS", "0")
    monkeypatch.setattr(fc, "_INTERPRET", False)  # jnp fallback reference
    out_r, vjp_r = jax.vjp(
        lambda x_, w_, b_: fc.fused_softmax_ce(x_, w_, b_, lbl,
                                               grad_scale=1.3), x, w, b)
    dx_r, dw_r, db_r = vjp_r(jnp.ones_like(out_r))
    assert _maxerr(out, out_r) < 1e-4
    for name, a, b_ in zip(("dx", "dw", "db"), (dx, dw, db),
                           (dx_r, dw_r, db_r)):
        assert _maxerr(a, b_) < 1e-4, name


@pytest.mark.parametrize("causal,sq,skv", [(True, 256, 256),
                                           (False, 256, 384)])
def test_flash_bsd_kernels_match_jnp(interpret, causal, sq, skv):
    """The transposeless (B, S, E) kernels: fwd + both backward passes
    against the jnp reference on head-split views."""
    rng = np.random.RandomState(3)
    b, h, d = 2, 2, 128  # lane-aligned head_dim: the bsd Pallas gate
    e = h * d
    scale = 1.0 / np.sqrt(d)
    zero = jnp.asarray(0, jnp.int32)
    q = jnp.asarray(rng.randn(b, sq, e) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(b, skv, e) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(b, skv, e) * 0.5, jnp.float32)
    q4, k4, v4 = (t.reshape(t.shape[0], t.shape[1], h, d).transpose(
        0, 2, 1, 3) for t in (q, k, v))
    o_j, lse_j = jax.jit(lambda q, k, v: fa._flash_fwd_jnp(
        q, k, v, zero, zero, scale, causal, 128))(q4, k4, v4)

    o_b, lse_b = jax.jit(lambda q, k, v: fa._flash_fwd_pallas_bsd(
        q, k, v, zero, zero, scale, causal, 128, 128, h))(q, k, v)
    o_b4 = o_b.reshape(b, sq, h, d).transpose(0, 2, 1, 3)
    assert _maxerr(o_b4, o_j) < 1e-5
    assert _maxerr(lse_b, lse_j) < 1e-5

    do = jnp.asarray(rng.randn(b, sq, e), jnp.float32)
    do4 = do.reshape(b, sq, h, d).transpose(0, 2, 1, 3)
    res_b = (q, k, v, o_b, lse_b, zero, zero)
    dq_b, dk_b, dv_b = jax.jit(
        lambda res, g: fa._flash_bwd_pallas_bsd(
            scale, causal, 128, 128, h, res, g)[:3])(
        res_b, (do, jnp.zeros_like(lse_b)))
    res_j = (q4, k4, v4, o_j, lse_j, zero, zero)
    dq_j, dk_j, dv_j = jax.jit(
        lambda res, g: fa._flash_bwd(scale, causal, 128, res, g)[:3])(
        res_j, (do4, jnp.zeros_like(lse_j)))
    for got, want, tag in ((dq_b, dq_j, "dq"), (dk_b, dk_j, "dk"),
                           (dv_b, dv_j, "dv")):
        got4 = got.reshape(b, -1, h, d).transpose(0, 2, 1, 3)
        assert _maxerr(got4, want) < 1e-4, tag


@pytest.mark.parametrize("causal,sq,skv", [(True, 256, 256),
                                           (False, 256, 384)])
def test_flash_bsd_grid_streamed_kernels_match_jnp(interpret, causal, sq,
                                                   skv):
    """MXNET_FLASH_BSD_KERNEL=stream: the grid-streamed bsd variants
    (scratch accumulators over an arbitrary K/Q grid axis) against the
    jnp reference."""
    rng = np.random.RandomState(4)
    b, h, d = 2, 2, 128
    e = h * d
    scale = 1.0 / np.sqrt(d)
    zero = jnp.asarray(0, jnp.int32)
    q = jnp.asarray(rng.randn(b, sq, e) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(b, skv, e) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(b, skv, e) * 0.5, jnp.float32)
    q4, k4, v4 = (t.reshape(t.shape[0], t.shape[1], h, d).transpose(
        0, 2, 1, 3) for t in (q, k, v))
    o_j, lse_j = jax.jit(lambda q, k, v: fa._flash_fwd_jnp(
        q, k, v, zero, zero, scale, causal, 128))(q4, k4, v4)

    o_b, lse_b = jax.jit(lambda q, k, v: fa._flash_fwd_pallas_bsd_gs(
        q, k, v, zero, zero, scale, causal, 128, 128, h))(q, k, v)
    o_b4 = o_b.reshape(b, sq, h, d).transpose(0, 2, 1, 3)
    assert _maxerr(o_b4, o_j) < 1e-5
    assert _maxerr(lse_b, lse_j) < 1e-5

    do = jnp.asarray(rng.randn(b, sq, e), jnp.float32)
    do4 = do.reshape(b, sq, h, d).transpose(0, 2, 1, 3)
    res_b = (q, k, v, o_b, lse_b, zero, zero)
    dq_b, dk_b, dv_b = jax.jit(
        lambda res, g: fa._flash_bwd_pallas_bsd_gs(
            scale, causal, 128, 128, h, res, g)[:3])(
        res_b, (do, jnp.zeros_like(lse_b)))
    res_j = (q4, k4, v4, o_j, lse_j, zero, zero)
    dq_j, dk_j, dv_j = jax.jit(
        lambda res, g: fa._flash_bwd(scale, causal, 128, res, g)[:3])(
        res_j, (do4, jnp.zeros_like(lse_j)))
    for got, want, tag in ((dq_b, dq_j, "dq"), (dk_b, dk_j, "dk"),
                           (dv_b, dv_j, "dv")):
        got4 = got.reshape(b, -1, h, d).transpose(0, 2, 1, 3)
        assert _maxerr(got4, want) < 1e-4, tag
