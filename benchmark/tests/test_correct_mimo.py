"""`correct` of a `serve_mimo` cell has to come out false when it should:
the fp8 control in the program's place, and a run driven on the CPU at the
`tiny` sizes (float32, where a sound gap is 0) with one fault planted in the
program underneath: a served token altered; the window's sink dropped; a
window one position wider or narrower; the value scale dropped; rotation on
every dim of the head; the two kinds' theta swapped; query head h reading
K/V head h % K; the ring not masked below a sequence's first position.  Each
fault fails one of the two gaps (the widest, the mean) and nothing else."""
import time

import jax
import numpy as np
import pytest

from benchmark import harness, serve_mimo, spec

CELL = "mimo-v2.5-ep16.doc8k-out1k-near-knee"
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
    assert result["checks"]["served_logit_gap"]["value"] < 1e-3


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fp8_is_not_correct(seed):
    cell = spec.workload(CELL)
    cell.update(cell["tiny"])
    cfg = spec.config(cell["config"], tiny=True)
    run = harness.Run(cell, cfg, seed, 1.0, False, jax.devices()[:1], None,
                      time.perf_counter(), tiny=True)
    sample = []
    serve_mimo.run(run, keep_sample=sample)
    assert all(harness.holds(c) for c in run.checks.values()), run.checks
    ctrl = serve_mimo.compared(
        serve_mimo.served_gaps(sample, seed, cfg, jax.devices()[0],
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


def _model_with(monkeypatch, **changed):
    """The program built with some of its arguments changed underneath the
    configuration (the benchmark's builder is left as it is)."""
    from mxnet_tpu.serving import window

    real = window.HybridWindowMoEKVModel.__init__

    def init(self, *args, **kw):
        real(self, *args, **kw)
        for name, fn in changed.items():
            setattr(self, name, fn(self))

    monkeypatch.setattr(window.HybridWindowMoEKVModel, "__init__", init)


def _fault_no_sink(monkeypatch):
    from mxnet_tpu.serving.window import HybridWindowMoEKVModel as M

    real = M._sink_softmax
    monkeypatch.setattr(M, "_sink_softmax", staticmethod(
        lambda s, valid, sink: real(s, valid, sink - 1e9)))


def _fault_window_wider(monkeypatch):
    _model_with(monkeypatch, window=lambda m: m.window + 1)


def _fault_window_narrower(monkeypatch):
    _model_with(monkeypatch, window=lambda m: m.window - 1)


def _fault_no_value_scale(monkeypatch):
    _model_with(monkeypatch, value_scale=lambda m: 1.0)


def _fault_rope_all(monkeypatch):
    from mxnet_tpu.ops.lm_parts import rope_inv_freq

    # each kind's theta from its frequencies: f[1] = theta^(-2 / rotary)
    _model_with(monkeypatch, rotary_dim=lambda m: m.head_dim,
                inv_freq=lambda m: {
                    k: rope_inv_freq(m.head_dim, f[1] ** -len(f))
                    for k, f in m.inv_freq.items()})


def _fault_theta_swapped(monkeypatch):
    _model_with(monkeypatch, inv_freq=lambda m: {
        0: m.inv_freq[1], 1: m.inv_freq[0]})


def _fault_kv_head_mod(monkeypatch):
    """Query head h reads K/V head h % K: the query heads handed to
    attention in the other order, and its result handed back."""
    from mxnet_tpu.serving.window import HybridWindowMoEKVModel as M

    real_qkv, real_out = M._qkv, M._attn_out

    def order(self, kind):
        h = self.num_heads
        kv = self._kv(kind)
        # the head that sits at place p reads K/V head p // (h / kv)
        return np.array([(p % (h // kv)) * kv + p // (h // kv)
                         for p in range(h)])

    def qkv(self, params, p, kind, u, positions):
        q, k, v = real_qkv(self, params, p, kind, u, positions)
        self._kinds_seen = getattr(self, "_kinds_seen", [])
        self._kinds_seen.append(kind)
        return q[:, order(self, kind)], k, v

    def attn_out(self, params, p, attn):
        kind = self._kinds_seen.pop(0)
        back = np.argsort(order(self, kind))
        shape = attn.shape
        attn = attn.reshape(shape[:-1] + (self.num_heads, -1))[..., back, :]
        return real_out(self, params, p, attn.reshape(shape))

    monkeypatch.setattr(M, "_qkv", qkv)
    monkeypatch.setattr(M, "_attn_out", attn_out)


def _fault_ring_unmasked(monkeypatch):
    """A ring row below the sequence's first position reads as its own: a
    fresh slot's zeros, or its last holder's rows, enter the window."""
    from mxnet_tpu.serving.window import HybridWindowMoEKVModel as M

    monkeypatch.setattr(M, "_own", staticmethod(lambda p: p > -(1 << 30)))


@pytest.mark.parametrize("fault", [
    _fault_token, _fault_no_sink, _fault_window_wider,
    _fault_window_narrower, _fault_no_value_scale, _fault_rope_all,
    _fault_theta_swapped, _fault_kv_head_mod, _fault_ring_unmasked],
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
        [sys.executable, "-m", "benchmark.serve_mimo", "--workload",
         CELL, "--rates", "4", "8", "--seconds", "2", "--tiny"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [r["rate_rps"] for r in rows] == [4.0, 8.0]
    assert all(r["lost"] == 0 and r["compiles_in_window"] == 0
               and r["serve_tok_s"] > 0 for r in rows)
    assert rows[1]["due"] > rows[0]["due"]
