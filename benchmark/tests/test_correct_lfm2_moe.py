"""`correct` of a `serve_lfm2_moe` cell has to come out false when it should:
the fp8 control in the program's place, and a run driven on the CPU at the
`tiny` sizes (float32, where a sound gap is 0) with one fault planted in the
program underneath: a served token altered; the conv state zeroed at every
chunk boundary; a slot's state not zeroed at admission; RoPE left out of k;
the norm of q and k left out; query head h reading K/V head h % kv_heads;
the last expert dropped.  Each fault fails one of the two gaps (the widest,
the mean) and nothing else."""
import time

import jax
import jax.numpy as jnp
import pytest

from benchmark import harness, serve_lfm2_moe, spec

CELL = "lfm2-8b-a1b-1chip.conversation-near-knee"
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
    serve_lfm2_moe.run(run, keep_sample=sample)
    assert all(harness.holds(c) for c in run.checks.values()), run.checks
    ctrl = serve_lfm2_moe.compared(
        serve_lfm2_moe.served_gaps(sample, seed, cfg, jax.devices()[0],
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


def _fault_state_cut(monkeypatch):
    """Every prefill chunk starts its convolutions from nothing: the state
    is lost at each chunk boundary."""
    from mxnet_tpu.serving.shortconv import ShortConvMoEKVModel

    real = ShortConvMoEKVModel._conv

    def conv(self, params, p, u, before):
        if u.shape[1] > 1:
            before = jnp.zeros_like(before)
        return real(self, params, p, u, before)

    monkeypatch.setattr(ShortConvMoEKVModel, "_conv", conv)


def _fault_stale_state(monkeypatch):
    """A first chunk reads what its slot holds, and the slot holds garbage:
    the reset at admission is gone.  (With the last holder's own values in
    the slot, which are as small as the sequence's, the served tokens stay
    the reference's first at this size and `correct` sees nothing: that
    case is tier-1's, `tests/test_shortconv_moe.py`, which compares the
    logits themselves.)"""
    from mxnet_tpu.serving import shortconv

    class Jnp:
        """`jax.numpy` whose `where(cond, 0, x)` is ``x`` and some: the one
        such call in the module is the reset."""

        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def where(cond, a, b):
            return b + 30.0 if isinstance(a, int) and a == 0 \
                else jnp.where(cond, a, b)

    monkeypatch.setattr(shortconv, "jnp", Jnp())


def _fault_no_k_rope(monkeypatch):
    from mxnet_tpu.serving import shortconv

    real = shortconv.rope
    kv_heads = spec.config("lfm2-8b-a1b-1chip",
                           tiny=True)["num_key_value_heads"]
    monkeypatch.setattr(shortconv, "rope", lambda x, *a:
                        x if x.shape[1] == kv_heads else real(x, *a))


def _fault_no_qk_norm(monkeypatch):
    from mxnet_tpu.serving import shortconv

    real = shortconv.rms_norm
    cfg = spec.config("lfm2-8b-a1b-1chip", tiny=True)
    head = cfg["hidden_size"] // cfg["num_attention_heads"]
    # the heads' norms are the ones whose gain is one head wide
    monkeypatch.setattr(shortconv, "rms_norm", lambda x, g, eps:
                        x if g.shape[0] == head else real(x, g, eps))


def _fault_kv_head_mod(monkeypatch):
    """Query head h reads K/V head h % kv_heads: the heads handed to the
    grouped attention in the other order, and its result handed back."""
    from mxnet_tpu.serving import shortconv

    def swapped(real, heads_at):
        def attention(q, *args, **kw):
            h, kvh = args[heads_at], kw["kv_heads"]
            shape = q.shape

            def to(x, a, b):
                x = x.reshape(shape[:-1] + (a, b, -1))
                return jnp.swapaxes(x, -3, -2).reshape(shape)

            out = real(to(q, h // kvh, kvh), *args, **kw)
            return to(out, kvh, h // kvh)
        return attention

    monkeypatch.setattr(shortconv, "chunk_attention",
                        swapped(shortconv.chunk_attention, 3))
    monkeypatch.setattr(shortconv, "paged_decode_attention",
                        swapped(shortconv.paged_decode_attention, 4))


def _fault_top3(monkeypatch):
    from mxnet_tpu.ops import moe

    real = moe.route
    monkeypatch.setattr(moe, "route", lambda u, w, b, top_k, scale, *eps:
                        real(u, w, b, top_k - 1, scale, *eps))


@pytest.mark.parametrize("fault", [
    _fault_token, _fault_state_cut, _fault_stale_state, _fault_no_k_rope,
    _fault_no_qk_norm, _fault_kv_head_mod, _fault_top3],
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
        [sys.executable, "-m", "benchmark.serve_lfm2_moe", "--workload",
         CELL, "--rates", "4", "8", "--seconds", "2", "--tiny"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(line) for line in p.stdout.strip().splitlines()]
    assert [r["rate_rps"] for r in rows] == [4.0, 8.0]
    assert all(r["lost"] == 0 and r["compiles_in_window"] == 0
               and r["serve_tok_s"] > 0 for r in rows)
    assert rows[1]["due"] > rows[0]["due"]
