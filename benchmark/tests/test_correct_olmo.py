"""`correct` of a `serve_olmo_hybrid` cell has to come out false when it
should: the fp8 control in the program's place, and a run driven on the CPU
at the `tiny` sizes (float32, where a sound gap is 0) with one fault planted
in the program underneath: a served token altered; beta not doubled; the
decay dropped; the convolution reading one position early; q and k left at
their length; the output gate dropped; the full layers' q/k norms dropped.
Each fault fails one of the two gaps (the widest, the mean) and nothing
else.  The state rounded to bfloat16 after every chunk and step moves the
tiny model's logits by hundredths and puts no other token first, so no gap
sees it here: `tests/test_delta_hybrid.py` holds the logits themselves to
that fault."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, serve_olmo_hybrid, spec

CELL = "olmo-hybrid-7b-1chip.reasoning-near-knee"
SEEDS = (1, 3000000019)


def _execute(seed=7, seconds=3.0):
    return harness.execute(CELL, seed, seconds, False, jax.devices()[:1],
                           time.perf_counter(), tiny=True)


def _failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if not harness.holds(c))


def test_sound_run_is_correct():
    result = _execute()
    assert result["correct"], _failing(result)
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["checks"]["served_logit_gap"]["value"] < 1e-3


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fp8_is_not_correct(seed):
    cell = spec.workload(CELL)
    cell.update(cell["tiny"])
    cfg = spec.config(cell["config"], tiny=True)
    run = harness.Run(cell, cfg, seed, 3.0, False, jax.devices()[:1], None,
                      time.perf_counter(), tiny=True)
    sample = []
    serve_olmo_hybrid.run(run, keep_sample=sample)
    assert all(harness.holds(c) for c in run.checks.values()), run.checks
    ctrl = serve_olmo_hybrid.compared(
        serve_olmo_hybrid.served_gaps(sample, seed, cfg, jax.devices()[0],
                                      control="fp8"), cell["limits"])
    assert harness.holds(ctrl["served_tokens_compared"])
    assert not harness.holds(ctrl["served_logit_gap_mean"]), ctrl


def _fault_token(monkeypatch):
    from mxnet_tpu.serving.engine import ServingEngine

    real = ServingEngine._advance_one

    def advance(self, seq, t):
        if len(seq.req.tokens) == 2:        # every request's third token
            t = (int(t) + 1) % self.model.vocab_size
        return real(self, seq, t)

    monkeypatch.setattr(ServingEngine, "_advance_one", advance)


def _rule_inputs(monkeypatch, change):
    """Both forms of the rule given ``change(g, beta)`` in place of their
    decay and write strength."""
    from mxnet_tpu.serving import linear

    for name in ("chunk_gated_delta", "gated_delta_slots"):
        real = getattr(linear, name)

        def wrapped(q, k, v, g, beta, *rest, real=real, **kw):
            return real(q, k, v, *change(g, beta), *rest, **kw)

        monkeypatch.setattr(linear, name, wrapped)


def _fault_beta_not_doubled(monkeypatch):
    _rule_inputs(monkeypatch, lambda g, beta: (g, beta / 2))


def _fault_no_decay(monkeypatch):
    _rule_inputs(monkeypatch, lambda g, beta: (jnp.zeros_like(g), beta))


def _fault_conv_shifted(monkeypatch):
    from mxnet_tpu.serving.linear import HybridDeltaKVModel as M

    real = M._taps

    def taps(self, params, p, run, c):
        return real(self, params, p, jnp.pad(run, ((0, 0), (1, 0), (0, 0)))
                    [:, :-1], c)

    monkeypatch.setattr(M, "_taps", taps)


def _fault_no_l2norm(monkeypatch):
    from mxnet_tpu.serving import linear

    monkeypatch.setattr(linear, "l2_normalize",
                        lambda x: x.astype(jnp.float32))


def _fault_no_output_gate(monkeypatch):
    from mxnet_tpu.serving.linear import HybridDeltaKVModel as M

    monkeypatch.setattr(M, "_gate", lambda self, params, p, x: 1.0)


def _fault_no_qk_norm(monkeypatch):
    from mxnet_tpu.serving.linear import HybridDeltaKVModel as M

    monkeypatch.setattr(M, "_qk_norm", lambda self, params, p, q, k: (q, k))


@pytest.mark.parametrize("fault", [
    _fault_token, _fault_beta_not_doubled, _fault_no_decay,
    _fault_conv_shifted, _fault_no_l2norm,
    _fault_no_output_gate, _fault_no_qk_norm],
    ids=lambda f: f.__name__[len("_fault_"):])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result = _execute()
    assert not result["correct"]
    assert set(_failing(result)) <= {"served_logit_gap",
                                     "served_logit_gap_mean"}


def test_sweep_of_this_kind_prints_a_line_for_every_rate():
    """`benchmark/sweep.py` names `serve.set_up` and `serve.measure`; run as
    a module, this kind's driver hands it its own two under that name."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.serve_olmo_hybrid", "--workload",
         CELL, "--rates", "4", "8", "--seconds", "2", "--tiny"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [r["rate_rps"] for r in rows] == [4.0, 8.0]
    assert all(r["lost"] == 0 and r["compiles_in_window"] == 0
               and r["serve_tok_s"] > 0 for r in rows)
