"""`phase_host_ms` on a span store filled by hand, `idle_in_spans` over the
engine's waits, and the CPU rehearsal printing the scheduler's host
metrics in the four serving cells."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark.readers import idle_in_spans, phase_host_ms

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
PHASES = ("sweep", "prefill", "admit", "grow", "pack", "launch", "fetch",
          "publish", "account", "wait")
EXPOSED = ["sweep", "prefill", "admit", "grow", "pack", "launch", "publish",
           "account"]


@pytest.fixture(autouse=True)
def store(monkeypatch):
    from mxnet_tpu import tracing

    monkeypatch.delenv("MXNET_SERVE_TRACING", raising=False)
    monkeypatch.delenv("MXNET_TRACE_RING", raising=False)
    tracing.reset()
    yield tracing
    tracing.reset()


def _ms(scale):
    """A record's phase_ms: 1 ms of each phase, times ``scale``, and a long
    wait that no group of the host's work counts."""
    out = {p: 1.0 * scale for p in PHASES}
    out["wait"] = 50.0
    return out


def _fill(tracing, untraced=(1, 3), traced=(4, 8), phase_ms=True):
    """A warm-up record before the window opens at 10 s, then records a
    second apart before the trace starts at 13 s and after it."""
    tracing.add_span(0, "iteration", "bench", 1.0, 1.01,
                     **({"phase_ms": _ms(100)} if phase_ms else {}))
    times = [10.5 + i for i in range(len(untraced))] \
        + [13.5 + i for i in range(len(traced))]
    for t, scale in zip(times, untraced + traced):
        attrs = {"rows": 3}
        if phase_ms:
            attrs["phase_ms"] = _ms(scale)
        tracing.add_span(0, "iteration", "bench", t, t + 0.01, **attrs)
        tracing.add_span(0, "queue_wait", "bench", t, t + 0.001)
    return SimpleNamespace(t_open=10.0, t_close=20.0, _t_trace=13.0,
                           note=print)


def test_the_parts_split_at_the_traces_start(store):
    run = _fill(store)
    # a trace started at 14 s leaves the record that closed at 13.51 on the
    # untraced side: the parts are (1, 3, 4) and (8,)
    run._t_trace = 14.0
    assert phase_host_ms.read(run, "bench", ["sweep", "prefill", "admit",
                                             "grow"]) == pytest.approx(
        4 * (1 + 3 + 4) / 3.0)
    assert phase_host_ms.read(run, "bench", ["fetch"], part="traced") \
        == pytest.approx(8.0)


def test_the_groups_and_the_ratio(store):
    run = _fill(store)
    assert phase_host_ms.read(run, "bench", ["pack", "launch"]) \
        == pytest.approx(2 * (1 + 3) / 2.0)
    assert phase_host_ms.read(run, "bench", ["publish"], part="traced") \
        == pytest.approx((4 + 8) / 2.0)
    # the wait is in every record and in no group: the ratio of the exposed
    # phases is (8 x 6) / (8 x 2)
    assert phase_host_ms.read(run, "bench", EXPOSED, part="ratio") \
        == pytest.approx(3.0)
    assert phase_host_ms.read(run, "bench", ["wait"]) == pytest.approx(50.0)


def test_nothing_to_read_from_a_program_before_phase_ms(store):
    run = _fill(store, phase_ms=False)
    assert phase_host_ms.read(run, "bench", ["fetch"]) is None
    assert phase_host_ms.read(run, "bench", EXPOSED, part="ratio") is None


def test_an_empty_part_is_an_error(store):
    run = _fill(store, traced=())
    assert phase_host_ms.read(run, "bench", ["fetch"]) \
        == pytest.approx(2.0)
    with pytest.raises(ValueError, match="none in the traced part"):
        phase_host_ms.read(run, "bench", ["fetch"], part="traced")
    with pytest.raises(ValueError, match="none in the traced part"):
        phase_host_ms.read(run, "bench", EXPOSED, part="ratio")


def test_a_ring_that_lost_the_windows_start_is_an_error(store, monkeypatch):
    monkeypatch.setenv("MXNET_TRACE_RING", "8")
    store.reset()
    run = _fill(store)     # 9 records in a ring of 8: the warm-up's is gone
    with pytest.raises(ValueError, match="lost the window's start"):
        phase_host_ms.read(run, "bench", ["fetch"])


def test_idle_inside_the_engines_waits():
    """`idle_ms_per_iter.wait.kimi`: the device's idle time inside
    `sched.wait` spans, which lie outside any iteration, per launching
    iteration; `idle_unattributed` no longer counts it."""
    us = 1000
    line = "python3"
    spans = [(0, 1000, "sched.iteration", line),
             (100, 200, "sched.launch", line),
             (1000, 3000, "sched.wait", line),
             (4000, 1000, "sched.iteration", line),
             (4100, 200, "sched.launch", line)]
    ops = [(200 * us, 700 * us, {"name": "%fusion"}),
           (4300 * us, 600 * us, {"name": "%fusion"})]
    run = SimpleNamespace(
        _raw={"ops": ops, "modules": [],
              "spans": sorted((s * us, d * us, n, ln)
                              for s, d, n, ln in spans)},
        note=print)
    # idle 900..4300 us: 3,000 us of it in the wait, over two iterations;
    # 200 in the second launch, and 200 in no phase
    assert idle_in_spans.read(run, spans=["sched.wait"]) \
        == pytest.approx(1.5)
    assert idle_in_spans.read(run, outside=True) \
        == pytest.approx(100.0 * 200 / 3400)


def _program_metrics(cell):
    """The scheduler's host metrics a cell lists: read from the span store,
    so a CPU rehearsal prints them (`idle_ms_per_iter.wait.kimi` reads
    the device's trace, which a CPU run has not)."""
    return {m["name"] for m in BENCH["per_layer"]
            if cell in m.get("workloads", [])
            and m["name"].startswith(("host_ms_per_iter.",
                                      "profiler_host_factor"))}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]
                                  if w["name"] != "gpt2-medium.train-s1024"])
def test_the_rehearsal_prints_the_host_metrics(cell):
    assert len(_program_metrics(cell)) == 5
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 4321), "--seconds", "2",
         "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    got = line["metrics"]
    assert _program_metrics(cell) <= set(got)
    assert all(got[m]["value"] > 0 for m in _program_metrics(cell))
    assert "host ms an iteration by phase, untraced" in p.stderr
