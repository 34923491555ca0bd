"""On-chip Pallas kernel parity gate (VERDICT r3 next #3).

The CPU test mesh always runs the jnp fallbacks (`flash_attention.py`
`_use_pallas` gates on the tpu backend), so the 400-test suite validates
the fallback math, not the kernels — a kernel regression would ship green.
This preflight runs the Pallas flash-attention forward+backward and
FusedSoftmaxCE forward+backward ON THE CHIP against the jnp fallbacks and
fails on divergence.  Wired into bench.py: the result lands in the bench
JSON (`pallas_parity`), and divergence fails the bench run.

Run standalone: python scripts/pallas_preflight.py
"""
from __future__ import annotations

import math
import sys

import numpy as np


def _maxerr(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    denom = np.maximum(np.abs(b), 1e-3)
    return float(np.max(np.abs(a - b) / denom))


def run(verbose=True):
    """Returns {"status": "pass"|"FAIL: ...", checks...}; off the chip the
    status is a FAIL naming the backend found — there is nothing to
    compare there, and a gate that reports success without running is no
    gate."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_mod as fa
    from mxnet_tpu.ops.pallas_kernels import fused_ce_mod as fc

    if jax.default_backend() != "tpu":
        return {"status": "FAIL: backend is %s, the kernels need a TPU"
                % jax.default_backend()}
    try:
        return _run_checks(jax, jnp, fa, fc, verbose)
    except Exception as e:
        # past the backend gate an exception IS a kernel regression
        # (compile error, signature drift)
        return {"status": "FAIL: preflight raised %s: %s"
                % (type(e).__name__, str(e)[:300])}


def _run_checks(jax, jnp, fa, fc, verbose):
    checks = {}
    failures = []

    def check(name, got, want, tol):
        err = _maxerr(got, want)
        checks[name] = round(err, 6)
        if verbose:
            print("preflight %-28s rel err %.3e (tol %.1e)"
                  % (name, err, tol))
        if not (err <= tol):  # NaN-safe: NaN fails
            failures.append("%s err %.3e > %.0e" % (name, err, tol))

    # ---- flash attention: fwd + bwd, causal and full ------------------
    rng = np.random.RandomState(0)
    B, H, S, D = 2, 4, 256, 64
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    do = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    scale = 1.0 / math.sqrt(D)
    zero = jnp.asarray(0.0, jnp.int32)
    assert fa._use_pallas(q, kv_len=S), "shapes must take the pallas path"
    for causal in (False, True):
        tag = "causal" if causal else "full"
        o_p, lse_p = jax.jit(
            lambda q, k, v, c=causal: fa._flash_fwd_pallas(
                q, k, v, zero, zero, scale, c, 128, 128))(q, k, v)
        o_j, lse_j = jax.jit(
            lambda q, k, v, c=causal: fa._flash_fwd_jnp(
                q, k, v, zero, zero, scale, c, 128))(q, k, v)
        # bf16 inputs, f32 accumulation both sides: agreement well under 1%
        check("flash_fwd_%s_out" % tag, o_p, o_j, 2e-2)
        check("flash_fwd_%s_lse" % tag, lse_p, lse_j, 1e-3)

        res = (q, k, v, o_j, lse_j, zero, zero)
        grads = (do, jnp.zeros_like(lse_j))
        dq_p, dk_p, dv_p = jax.jit(
            lambda res, grads, c=causal: fa._flash_bwd_pallas(
                scale, c, 128, 128, res, grads)[:3])(res, grads)
        dq_j, dk_j, dv_j = jax.jit(
            lambda res, grads, c=causal: fa._flash_bwd(
                scale, c, 128, res, grads)[:3])(res, grads)
        check("flash_bwd_%s_dq" % tag, dq_p, dq_j, 3e-2)
        check("flash_bwd_%s_dk" % tag, dk_p, dk_j, 3e-2)
        check("flash_bwd_%s_dv" % tag, dv_p, dv_j, 3e-2)

        # End-to-end: the bwd kernels consuming the Pallas fwd's OWN
        # o/lse residuals — the production path (ADVICE r5).  The
        # isolated checks above feed reference residuals, so an on-chip
        # o/lse inconsistency between the fwd kernel and what the bwd
        # kernel assumes would slip through them.  Tolerance is loosened
        # (1.5e-1 vs 3e-2): the fwd's tolerated ulp-level differences
        # compound through bf16 rounding cliffs in p=exp(s-lse) — the
        # round-5 chip campaign measured ~0.106 here on healthy kernels
        # — while a genuine residual-contract break (wrong lse scale,
        # stale o) lands orders of magnitude higher.
        res_self = (q, k, v, o_p, lse_p, zero, zero)
        dq_e, dk_e, dv_e = jax.jit(
            lambda res, grads, c=causal: fa._flash_bwd_pallas(
                scale, c, 128, 128, res, grads)[:3])(res_self, grads)
        check("flash_e2e_%s_dq" % tag, dq_e, dq_j, 1.5e-1)
        check("flash_e2e_%s_dk" % tag, dk_e, dk_j, 1.5e-1)
        check("flash_e2e_%s_dv" % tag, dv_e, dv_j, 1.5e-1)

        # the opt-in dS-layout kernels (MXNET_FLASH_LAYOUT=ds; hsd is the
        # ADR-10 default — dS trades speed for unpadded-tile capacity)
        o_d, lse_d = jax.jit(
            lambda q, k, v, c=causal: fa._flash_fwd_pallas_ds(
                q.swapaxes(2, 3), k.swapaxes(2, 3), v.swapaxes(2, 3),
                zero, zero, scale, c, 128, 128))(q, k, v)
        check("flash_fwd_ds_%s_out" % tag, o_d.swapaxes(2, 3), o_j, 2e-2)
        check("flash_fwd_ds_%s_lse" % tag, lse_d, lse_j, 1e-3)
        res_ds = (q.swapaxes(2, 3), k.swapaxes(2, 3), v.swapaxes(2, 3),
                  o_j.swapaxes(2, 3), lse_j, zero, zero)
        dq_d, dk_d, dv_d = jax.jit(
            lambda res, grads, c=causal: fa._flash_bwd_pallas_ds(
                scale, c, 128, 128, res, grads)[:3])(res_ds, grads)
        check("flash_bwd_ds_%s_dq" % tag, dq_d, dq_j, 3e-2)
        check("flash_bwd_ds_%s_dk" % tag, dk_d, dk_j, 3e-2)
        check("flash_bwd_ds_%s_dv" % tag, dv_d, dv_j, 3e-2)

    # ---- bsd-layout kernels (transposeless (B, S, E) path) ------------
    Hb, Db = 2, 128  # lane-aligned head_dim: the bsd Pallas gate
    Eb = Hb * Db
    qb = jnp.asarray(rng.randn(B, S, Eb), jnp.bfloat16)
    kb = jnp.asarray(rng.randn(B, S, Eb), jnp.bfloat16)
    vb = jnp.asarray(rng.randn(B, S, Eb), jnp.bfloat16)
    dob = jnp.asarray(rng.randn(B, S, Eb), jnp.bfloat16)
    scale_b = 1.0 / math.sqrt(Db)

    def split(t):
        return t.reshape(B, S, Hb, Db).transpose(0, 2, 1, 3)

    def merge(t):
        return t.transpose(0, 2, 1, 3).reshape(B, S, Eb)

    for causal in (False, True):
        tag = "causal" if causal else "full"
        o_b, lse_b = jax.jit(
            lambda q, k, v, c=causal: fa._flash_fwd_pallas_bsd(
                q, k, v, zero, zero, scale_b, c, 128, 128, Hb))(qb, kb, vb)
        o_j, lse_j = jax.jit(
            lambda q, k, v, c=causal: fa._flash_fwd_jnp(
                q, k, v, zero, zero, scale_b, c, 128))(
            split(qb), split(kb), split(vb))
        check("flash_fwd_bsd_%s_out" % tag, split(o_b), o_j, 2e-2)
        check("flash_fwd_bsd_%s_lse" % tag, lse_b, lse_j, 1e-3)

        # bwd isolation: feed the kernel the REFERENCE fwd outputs
        # (o_j/lse_j), exactly as the hsd checks above do.  Feeding the
        # kernel's own (o_b, lse_b) compounds the fwd's tolerated ulp-
        # level differences through bf16 rounding cliffs in p=exp(s-lse),
        # which the 1e-3 relative floor then inflates into on-chip "dv
        # err 0.106"-style false failures (seen in the round-5 chip campaign).
        res_b = (qb, kb, vb, merge(o_j), lse_j, zero, zero)
        dq_b, dk_b, dv_b = jax.jit(
            lambda res, grads, c=causal: fa._flash_bwd_pallas_bsd(
                scale_b, c, 128, 128, Hb, res, grads)[:3])(
            res_b, (dob, jnp.zeros_like(lse_j)))
        dq_j, dk_j, dv_j = jax.jit(
            lambda res, grads, c=causal: fa._flash_bwd(
                scale_b, c, 128, res, grads)[:3])(
            (split(qb), split(kb), split(vb), o_j, lse_j, zero, zero),
            (split(dob), jnp.zeros_like(lse_j)))
        check("flash_bwd_bsd_%s_dq" % tag, split(dq_b), dq_j, 3e-2)
        check("flash_bwd_bsd_%s_dk" % tag, split(dk_b), dk_j, 3e-2)
        check("flash_bwd_bsd_%s_dv" % tag, split(dv_b), dv_j, 3e-2)

    # ---- grid-streamed bsd variants (MXNET_FLASH_BSD_KERNEL=stream) ---
    for causal in (False, True):
        tag = ("causal" if causal else "full") + "_gs"
        o_g, lse_g = jax.jit(
            lambda q, k, v, c=causal: fa._flash_fwd_pallas_bsd_gs(
                q, k, v, zero, zero, scale_b, c, 128, 128, Hb))(qb, kb, vb)
        o_j, lse_j = jax.jit(
            lambda q, k, v, c=causal: fa._flash_fwd_jnp(
                q, k, v, zero, zero, scale_b, c, 128))(
            split(qb), split(kb), split(vb))
        check("flash_fwd_bsd_%s_out" % tag, split(o_g), o_j, 2e-2)
        check("flash_fwd_bsd_%s_lse" % tag, lse_g, lse_j, 1e-3)
        # same bwd isolation as the loop-variant bsd checks above
        dq_g, dk_g, dv_g = jax.jit(
            lambda res, grads, c=causal: fa._flash_bwd_pallas_bsd_gs(
                scale_b, c, 128, 128, Hb, res, grads)[:3])(
            (qb, kb, vb, merge(o_j), lse_j, zero, zero),
            (dob, jnp.zeros_like(lse_j)))
        dq_j, dk_j, dv_j = jax.jit(
            lambda res, grads, c=causal: fa._flash_bwd(
                scale_b, c, 128, res, grads)[:3])(
            (split(qb), split(kb), split(vb), o_j, lse_j, zero, zero),
            (split(dob), jnp.zeros_like(lse_j)))
        check("flash_bwd_bsd_%s_dq" % tag, split(dq_g), dq_j, 3e-2)
        check("flash_bwd_bsd_%s_dk" % tag, split(dk_g), dk_j, 3e-2)
        check("flash_bwd_bsd_%s_dv" % tag, split(dv_g), dv_j, 3e-2)

    # ---- fused softmax-CE: fwd + bwd ----------------------------------
    N, Dm, V = 512, 128, 4096
    x = jnp.asarray(rng.randn(N, Dm) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.randn(V, Dm) * 0.05, jnp.bfloat16)
    b = jnp.asarray(rng.randn(V) * 0.1, jnp.float32)
    lbl = jnp.asarray(rng.randint(0, V, N), jnp.int32)
    assert fc._use_pallas(x, w), "shapes must take the pallas path"
    args = dict(grad_scale=1.0, ignore_label=float(V // 2),
                use_ignore=True)
    nll_p, lse_p = jax.jit(lambda x, w, b, l: fc._fwd_pallas(
        x, w, b, l, args["grad_scale"], args["ignore_label"],
        args["use_ignore"], 256, 1024))(x, w, b, lbl)
    nll_j, lse_j = jax.jit(lambda x, w, b, l: fc._fwd_jnp(
        x, w, b, l, args["grad_scale"], args["ignore_label"],
        args["use_ignore"], 1024))(x, w, b, lbl)
    check("fused_ce_fwd_nll", nll_p, nll_j, 1e-2)
    check("fused_ce_fwd_lse", lse_p, lse_j, 1e-3)

    dx_p, dw_p, db_p = jax.jit(lambda x, w, b, l, lse: fc._bwd_pallas(
        x, w, b, l, lse, args["grad_scale"], args["ignore_label"],
        args["use_ignore"], 256, 1024))(x, w, b, lbl, lse_j)
    dx_j, dw_j, db_j = jax.jit(lambda x, w, b, l, lse: fc._bwd_jnp(
        x, w, b, l, lse, args["grad_scale"], args["ignore_label"],
        args["use_ignore"], 1024))(x, w, b, lbl, lse_j)
    check("fused_ce_bwd_dx", dx_p, dx_j, 3e-2)
    check("fused_ce_bwd_dw", dw_p, dw_j, 3e-2)
    check("fused_ce_bwd_db", db_p, db_j, 3e-2)

    # ---- round-6 single-pass structure: stats+residual fwd + row-scaled
    # dW/dx backwards (MXNET_CE_SINGLE_PASS=1, the default) -------------
    lse_sp, a_sp, dxp_sp = jax.jit(lambda x, w, b, l: fc._fwd_sp_pallas(
        x, w, b, l, 256, 1024))(x, w, b, lbl)
    lse_sj, a_sj, dxp_sj = jax.jit(lambda x, w, b, l: fc._fwd_sp_jnp(
        x, w, b, l, 1024))(x, w, b, lbl)
    check("fused_ce_sp_fwd_lse", lse_sp, lse_sj, 1e-3)
    check("fused_ce_sp_fwd_picked", a_sp, a_sj, 1e-2)
    check("fused_ce_sp_fwd_dxp", dxp_sp, dxp_sj, 3e-2)
    r = jnp.asarray(rng.rand(N).astype(np.float32))
    dwr_p, dbr_p = jax.jit(lambda *t: fc._bwd_dw_rs_pallas(
        *t, 256, 1024))(x, w, b, lbl, lse_sj, r)
    dwr_j, dbr_j = jax.jit(lambda *t: fc._bwd_dw_rs_jnp(
        *t, 1024))(x, w, b, lbl, lse_sj, r)
    check("fused_ce_rs_bwd_dw", dwr_p, dwr_j, 3e-2)
    check("fused_ce_rs_bwd_db", dbr_p, dbr_j, 3e-2)
    dxr_p = jax.jit(lambda *t: fc._bwd_dx_rs_pallas(
        *t, 256, 1024))(x, w, b, lbl, lse_sj, r)
    dxr_j = jax.jit(lambda *t: fc._bwd_dx_rs_jnp(
        *t, 1024))(x, w, b, lbl, lse_sj, r)
    check("fused_ce_rs_bwd_dx", dxr_p, dxr_j, 3e-2)

    status = "pass" if not failures else "FAIL: " + "; ".join(failures)
    out = {"status": status}
    out.update(checks)
    return out


if __name__ == "__main__":
    result = run()
    print(result)
    sys.exit(0 if result["status"] == "pass" else 1)
