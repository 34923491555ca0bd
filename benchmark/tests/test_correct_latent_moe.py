"""`correct` of a `serve_latent_moe` cell has to come out false when it
should: the fp8 control in the program's place, and a run driven on the CPU
at the `tiny` sizes with one fault planted in the program underneath (a served
token altered; one expert too few; the routed scaling left out; `k_pe` cached
unrotated; one held expert's output dropped).  Each fault fails one of the
two gaps (the widest, the mean) and nothing else."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, serve_latent_moe, spec

CELL = "kimi-k2.5-ep32.longprompt-near-knee"
SEEDS = (1, 2, 3000000019)


def _execute(seed=7, seconds=1.0):
    return harness.execute(CELL, seed, seconds, False, jax.devices()[:1],
                           time.perf_counter(), tiny=True)


def _failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if not harness.holds(c))


def test_sound_run_is_correct():
    result = _execute()
    assert result["correct"], _failing(result)
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fp8_is_not_correct(seed):
    cell = spec.workload(CELL)
    cell.update(cell["tiny"])
    cfg = spec.config(cell["config"], tiny=True)
    run = harness.Run(cell, cfg, seed, 1.0, False, jax.devices()[:1], None,
                      time.perf_counter(), tiny=True)
    sample = []
    serve_latent_moe.run(run, keep_sample=sample)
    assert all(harness.holds(c) for c in run.checks.values()), run.checks
    ctrl = serve_latent_moe.compared(
        serve_latent_moe.served_gaps(sample, seed, cfg, jax.devices()[0],
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


def _fault_top7(monkeypatch):
    from mxnet_tpu.ops import moe

    real = moe.route
    monkeypatch.setattr(moe, "route", lambda u, w, b, top_k, scale:
                        real(u, w, b, top_k - 1, scale))


def _fault_no_routed_scale(monkeypatch):
    from mxnet_tpu.ops import moe

    real = moe.route
    monkeypatch.setattr(moe, "route", lambda u, w, b, top_k, scale:
                        real(u, w, b, top_k, 1.0))


def _fault_no_k_rope(monkeypatch):
    from mxnet_tpu.serving import latent

    real = latent.rope
    # `k_pe` is the one rotated operand without a head axis
    monkeypatch.setattr(latent, "rope", lambda x, *a:
                        x if x.ndim == 2 else real(x, *a))


def _fault_drop_expert(monkeypatch):
    from mxnet_tpu.ops import moe

    real = moe.held_share

    def held_share(u, idx, w, *banks, experts_held, **kw):
        lo, _ = experts_held
        return real(u, idx, jnp.where(idx == lo, 0.0, w), *banks,
                    experts_held=experts_held, **kw)

    monkeypatch.setattr(moe, "held_share", held_share)


@pytest.mark.parametrize("fault", [
    _fault_token, _fault_top7, _fault_no_routed_scale, _fault_no_k_rope,
    _fault_drop_expert], ids=lambda f: f.__name__[len("_fault_"):])
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
        [sys.executable, "-m", "benchmark.serve_latent_moe", "--workload",
         CELL, "--rates", "4", "8", "--seconds", "2", "--tiny"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [r["rate_rps"] for r in rows] == [4.0, 8.0]
    assert all(r["lost"] == 0 and r["compiles_in_window"] == 0
               and r["serve_tok_s"] > 0 for r in rows)
    assert rows[1]["due"] > rows[0]["due"]
