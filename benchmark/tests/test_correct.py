"""`correct` has to come out false when it should.

The control: the plain reference computed in the precision below the
configuration's (fp8 projections for bfloat16), put in the program's place,
fails at least one of the cell's numbers.  The faults: the rest of a run
driven on the CPU at the `tiny` sizes (the look for a chip skipped) with the
timed path broken underneath."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, serve, spec, train

TRAIN = "gpt2-medium.train-s1024"
SERVE = "gpt2-large.chat-near-knee"
SEEDS = (1, 2, 3000000019)


def _execute(cell, seed=7, seconds=1.0):
    return harness.execute(cell, seed, seconds, False, jax.devices()[:1],
                           time.perf_counter(), tiny=True)


def _failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if not harness.holds(c))


def _tiny(cell_name):
    cell = spec.workload(cell_name)
    cell.update(cell["tiny"])
    return cell, spec.config(cell["config"], tiny=True)


# -- sound runs -----------------------------------------------------------


@pytest.mark.parametrize("cell", [TRAIN, SERVE])
def test_sound_run_is_correct(cell):
    result = _execute(cell)
    assert result["correct"], _failing(result)
    assert result["failed"] == 0 and result["attempted"] > 0


def test_sound_closed_loop_run_is_correct():
    """The generator's other arrival process, which no cell uses yet
    (PERF.md, section 7: `gpt2-large.docs-closed`)."""
    cell, cfg = _tiny(SERVE)
    cell["traffic_mix"] = dict(
        cell["traffic_mix"], arrivals={"process": "closed", "clients": 8,
                                       "ramp_s": 1, "max_rps": 200})
    run = harness.Run(cell, cfg, 7, 1.0, False, jax.devices()[:1], None,
                      time.perf_counter(), tiny=True)
    serve.run(run)
    assert all(harness.holds(c) for c in run.checks.values()), run.checks
    assert run.failed == 0 and run.attempted >= 8


# -- the control ----------------------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_fp8_is_not_correct(seed):
    cell, cfg = _tiny(TRAIN)
    device = jax.devices()[0]
    ref = train.reference_readings(seed, cfg, cell, device)
    ctrl = train.reference_readings(seed, cfg, cell, device, mode="fp8")
    checks, _ = train.compared(train.compare(ctrl, ref, cell["limits"]))
    assert any(not harness.holds(c) for c in checks.values()), checks


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_fp8_is_not_correct(seed):
    cell, cfg = _tiny(SERVE)
    run = harness.Run(cell, cfg, seed, 1.0, False, jax.devices()[:1],
                      None, time.perf_counter(), tiny=True)
    sample = []
    serve.run(run, keep_sample=sample)
    assert harness.holds(run.checks["served_logit_gap"])
    _, ctrl, n = serve.served_gap(sample, seed, cfg, jax.devices()[0],
                                  control="fp8")
    assert n >= cell["limits"]["min_tokens_compared"]
    assert ctrl > cell["limits"]["served_logit_gap"]


# -- the faults a training cell can have ----------------------------------


def test_fault_step_returns_state_unchanged(monkeypatch):
    from mxnet_tpu.parallel import SPMDTrainer

    real = SPMDTrainer.step

    def step(self, batch):
        keep = jax.tree_util.tree_map(jnp.copy, (self.params, self.momenta))
        outs = real(self, batch)
        self.params, self.momenta = keep
        return outs

    monkeypatch.setattr(SPMDTrainer, "step", step)
    result = _execute(TRAIN)
    assert not result["correct"]
    assert {"grad_norm_gap", "change_norm_gap"} <= set(_failing(result))
    assert result["checks"]["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_of_the_batch_left_out(monkeypatch):
    real = train.make_ring
    monkeypatch.setattr(train, "make_ring",
                        lambda *a, **k: train._halved(real(*a, **k)))
    result = _execute(TRAIN)
    assert not result["correct"], result["checks"]


# -- the faults a serving cell can have -----------------------------------


def test_fault_served_token_altered(monkeypatch):
    from mxnet_tpu.serving.engine import ServingEngine

    real = ServingEngine._advance_one

    def advance(self, seq, t):
        if len(seq.req.tokens) == 2:        # every request's third token
            t = (int(t) + 1) % self.model.vocab_size
        return real(self, seq, t)

    monkeypatch.setattr(ServingEngine, "_advance_one", advance)
    result = _execute(SERVE)
    assert not result["correct"]
    assert _failing(result) == ["served_logit_gap"]


def test_fault_answer_never_comes(monkeypatch):
    """A request the engine refuses is lost, not dropped from the count."""
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.serving.engine import ServingEngine

    real = ServingEngine.submit
    seen = [0]

    def submit(self, prompt, **kw):
        seen[0] += 1
        if seen[0] % 5 == 0 and seen[0] > 8:    # past the warm-up
            raise MXNetError("refused by the test")
        return real(self, prompt, **kw)

    monkeypatch.setattr(ServingEngine, "submit", submit)
    result = _execute(SERVE, seconds=2.0)
    assert not result["correct"]
    assert "requests_lost" in _failing(result)
    assert result["failed"] > 0
