"""Chaos-under-serve: the serving failure semantics driven by the
deterministic fault harness (MXNET_CHAOS serving clauses).

Contracts under test (ISSUE-8, docs/serving.md "Failure semantics"):

1. The serving clauses parse and draw from PER-CLAUSE deterministic
   streams — adding one clause to a spec does not change which launches
   another clause hits.
2. `queue_flood` drives the overload policy: synthetic requests pass
   through the same admission control, sheds count, real traffic
   completes.
3. `decode_slow` + deadlines: SLO pressure expires requests mid-flight
   with a typed error at iteration granularity; the engine stays up.
4. `launch_error` quarantines poisoned admissions; the scheduler
   survives 100% launch-poison traffic.
5. THE ACCEPTANCE GATE: 2-replica CPU-mesh router under Poisson load
   with one replica crashed mid-traffic (`engine_crash`) — every request
   resolves (tokens or typed error) within deadline+grace, nothing
   hangs, failover re-dispatches the dead replica's queue, the respawned
   replica serves, and `serve.aot.compiles` stays at its warmup value
   (recovery compiles NOTHING).
"""
import time

import numpy as np
import pytest

from mxnet_tpu import chaos, telemetry
from mxnet_tpu.serving import (ReplicaRouter, ServingEngine,
                               TransformerKVModel, ServeError, ServeTimeout,
                               ServeDeadlineExceeded, ServeQuarantined)

V, S, L, H, E = 61, 32, 2, 2, 32


@pytest.fixture
def model_and_params():
    model = TransformerKVModel(V, S, num_layers=L, num_heads=H, num_embed=E)
    return model, model.init_params(np.random.RandomState(7))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv("MXNET_CHAOS", raising=False)
    monkeypatch.setenv("MXNET_CHAOS_SEED", "0")
    telemetry.reset()
    chaos.reset()
    yield
    telemetry.reset()
    chaos.reset()


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("max_new_tokens", 4)
    # greedy-only programs (sampling coverage: tests/test_serve_paged.py)
    kw.setdefault("sampling", False)
    return ServingEngine(model, params, **kw)


def _chaos(monkeypatch, spec):
    monkeypatch.setenv("MXNET_CHAOS", spec)
    chaos.reset()


# ---------------------------------------------------------------------------
# 1. clause parsing + per-clause determinism
# ---------------------------------------------------------------------------

def test_serving_clauses_parse(monkeypatch):
    _chaos(monkeypatch, "decode_slow:0.25:15,engine_crash:7:replica1,"
                        "launch_error:0.1,queue_flood:4:64,"
                        "block_exhaust:0.3")
    s = chaos.spec()
    assert s.decode_slow == (0.25, 15.0)
    assert s.engine_crash == (7, "replica1")
    assert s.launch_error == 0.1
    assert s.queue_flood == (4, 64)
    assert s.block_exhaust == 0.3
    _chaos(monkeypatch, "engine_crash:3")
    assert chaos.spec().engine_crash == (3, "replica0")  # default target
    _chaos(monkeypatch, "decode_sloow:1:1")
    with pytest.raises(ValueError, match="unknown MXNET_CHAOS clause"):
        chaos.spec()


def test_per_clause_seeds_are_independent(monkeypatch):
    """The launch_error draw sequence must not shift when decode_slow
    joins the spec: each serving clause owns a deterministic stream keyed
    on (seed, role/rank, clause name)."""
    _chaos(monkeypatch, "launch_error:0.5")
    alone = [chaos.serve_launch_error() for _ in range(32)]
    _chaos(monkeypatch, "launch_error:0.5,decode_slow:0.5:1")
    mixed = [chaos.serve_launch_error() for _ in range(32)]
    assert alone == mixed
    assert any(alone) and not all(alone)  # a real 0.5 stream
    # and replaying the same spec replays the same faults
    _chaos(monkeypatch, "launch_error:0.5")
    assert [chaos.serve_launch_error() for _ in range(32)] == alone


def test_engine_crash_counts_per_replica_and_fires_once(monkeypatch):
    _chaos(monkeypatch, "engine_crash:3:replica0")
    hits = [chaos.serve_engine_crash("replica0") for _ in range(6)]
    assert hits == [False, False, True, False, False, False]
    # another replica's steps never trip the clause
    assert not any(chaos.serve_engine_crash("replica1") for _ in range(6))


# ---------------------------------------------------------------------------
# 2. queue_flood -> overload policy
# ---------------------------------------------------------------------------

def test_queue_flood_drives_shedding(model_and_params, monkeypatch):
    model, params = model_and_params
    eng = _engine(model, params, queue_max=2, overload="shed",
                  max_new_tokens=2)
    eng.warmup()
    real = eng.submit([3, 4, 5])
    _chaos(monkeypatch, "queue_flood:4:20")
    for _ in range(8):  # 4/step: the 20-request TOTAL cap spends in 5
        eng.step()
    reg = telemetry.registry()
    assert reg.counter("serve.chaos_flooded").value == 20  # cap honored
    monkeypatch.delenv("MXNET_CHAOS")
    chaos.reset()
    eng.run_until_idle(timeout=300)  # drain the admitted flood tail
    assert real.result(timeout=1) is not None  # real traffic survived
    assert reg.counter("serve.shed").value > 0  # bounded queue shed some
    assert eng._dead is None


# ---------------------------------------------------------------------------
# 3. decode_slow + deadlines
# ---------------------------------------------------------------------------

def test_decode_slow_expires_deadline_mid_flight(model_and_params,
                                                 monkeypatch):
    """SLO pressure: with every decode stalled 30 ms, a 60 ms deadline on
    a 50-token generation expires mid-flight — typed error at iteration
    granularity, partial tokens preserved, engine alive."""
    model, params = model_and_params
    _chaos(monkeypatch, "decode_slow:1.0:30")
    eng = _engine(model, params, max_new_tokens=50)
    eng.warmup()
    req = eng.submit([1, 2, 3], max_new_tokens=50, deadline_ms=60)
    eng.run_until_idle(timeout=300)
    with pytest.raises(ServeDeadlineExceeded):
        req.result(timeout=1)
    assert 1 <= len(req.tokens) < 50  # prefilled, then retired mid-decode
    assert eng._dead is None
    assert telemetry.registry().counter("serve.expired").value == 1


# ---------------------------------------------------------------------------
# 4. launch_error -> quarantine
# ---------------------------------------------------------------------------

def test_launch_error_quarantines_not_kills(model_and_params, monkeypatch):
    model, params = model_and_params
    eng = _engine(model, params)
    eng.warmup()
    _chaos(monkeypatch, "launch_error:1.0")
    reqs = [eng.submit([1 + i, 2]) for i in range(3)]
    eng.run_until_idle(timeout=300)
    for r in reqs:
        with pytest.raises(ServeQuarantined):
            r.result(timeout=1)
    assert eng._dead is None  # 100% poison traffic, scheduler alive
    monkeypatch.delenv("MXNET_CHAOS")
    chaos.reset()
    ok = eng.submit([9, 9])
    eng.run_until_idle(timeout=300)
    assert len(ok.result(timeout=1)) == 4
    assert telemetry.registry().counter("serve.quarantined").value == 3


# ---------------------------------------------------------------------------
# 4b. block_exhaust -> typed shed/requeue (paged pool)
# ---------------------------------------------------------------------------

def test_block_exhaust_denials_are_deterministic(monkeypatch):
    _chaos(monkeypatch, "block_exhaust:0.5")
    alone = [chaos.serve_block_exhaust() for _ in range(32)]
    assert any(alone) and not all(alone)
    _chaos(monkeypatch, "block_exhaust:0.5,decode_slow:0.5:1")
    assert [chaos.serve_block_exhaust() for _ in range(32)] == alone


def test_block_exhaust_total_denial_expires_typed_not_hangs(
        model_and_params, monkeypatch):
    """100% allocation denial: no request is ever admitted, every one
    expires TYPED at its deadline (queued requests retry each iteration
    and shed through the deadline machinery) — the scheduler never dies
    and nothing hangs."""
    model, params = model_and_params
    eng = _engine(model, params)
    eng.warmup()
    _chaos(monkeypatch, "block_exhaust:1.0")
    reqs = [eng.submit([1 + i, 2], deadline_ms=300) for i in range(3)]
    t0 = time.perf_counter()
    while not all(r.done for r in reqs):
        assert time.perf_counter() - t0 < 60, "denial hung the scheduler"
        eng.step()
    for r in reqs:
        with pytest.raises(ServeDeadlineExceeded):
            r.result(timeout=1)
    assert eng._dead is None
    assert eng._alloc.free_blocks == eng._alloc.capacity
    reg = telemetry.registry()
    assert reg.counter("serve.alloc_denied").value >= 3
    # with the clause gone the same engine serves immediately
    monkeypatch.delenv("MXNET_CHAOS")
    chaos.reset()
    ok = eng.submit([9, 9], max_new_tokens=2)
    eng.run_until_idle(timeout=300)
    assert len(ok.result(timeout=1)) == 2


def test_block_exhaust_partial_denial_completes_everything(
        model_and_params, monkeypatch):
    """50% denial: admissions and growths retry/preempt through the
    pressure and ALL traffic completes with the exact no-chaos greedy
    tokens (denial changes scheduling, never content)."""
    model, params = model_and_params
    rng = np.random.RandomState(21)
    prompts = [list(rng.randint(0, V, size=n)) for n in (3, 9, 5, 12)]

    clean_eng = _engine(model, params)
    clean = []
    for p in prompts:  # sequential solo runs on ONE engine (greedy truth)
        r = clean_eng.submit(p, max_new_tokens=6)
        clean_eng.run_until_idle(timeout=300)
        clean.append(r.result(1))

    _chaos(monkeypatch, "block_exhaust:0.5")
    eng = _engine(model, params)
    eng.warmup()
    reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle(timeout=300)
    assert [r.result(1) for r in reqs] == clean
    assert eng._dead is None
    # retired FULL blocks may stay parked in the prefix pool — free +
    # parked accounts for every block (leaked must be 0)
    parked = 0 if eng._prefix is None else eng._prefix.parked_count
    assert eng._alloc.free_blocks + parked == eng._alloc.capacity
    assert eng.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# 5. the acceptance gate
# ---------------------------------------------------------------------------

def test_chaos_failover_acceptance(model_and_params, monkeypatch):
    """ISSUE-8 acceptance: 2-replica CPU-mesh Poisson traffic with
    engine_crash + decode_slow injected — zero hung requests, every
    request resolves (result or typed error) within deadline+grace, and
    `serve.aot.compiles` stays at its warmup value after failover."""
    from mxnet_tpu.parallel import make_mesh

    model, params = model_and_params
    monkeypatch.setenv("MXNET_CHAOS_SEED", "7")
    _chaos(monkeypatch, "engine_crash:3:replica0,decode_slow:0.2:5")
    deadline_ms = 60000.0
    mesh = make_mesh(shape=(2,), axis_names=("data",))
    router = ReplicaRouter.from_mesh(
        model, params, mesh=mesh, max_batch=2, prefill_buckets=[8, 16],
        max_new_tokens=4, deadline_ms=deadline_ms, respawn=True,
        sampling=False)
    router.warmup()
    reg = telemetry.registry()
    compiles = reg.counter("serve.aot.compiles").value

    rng = np.random.RandomState(3)
    router.start()
    try:
        reqs = []
        for _ in range(14):
            prompt = list(rng.randint(0, V, size=int(rng.randint(1, 8))))
            reqs.append(router.submit(prompt))
            time.sleep(float(rng.exponential(0.02)))
        ok, typed = 0, 0
        for r in reqs:
            try:
                r.result(timeout=120)
                ok += 1
            except ServeTimeout:
                pytest.fail("request %d hung (no resolution)" % r.id)
            except ServeError:
                typed += 1
        assert ok + typed == len(reqs)       # everything resolved...
        assert all(r.done for r in reqs)
        grace_ms = 5000.0
        for r in reqs:                       # ...within deadline + grace
            assert r.latency_ms is not None
            assert r.latency_ms <= deadline_ms + grace_ms
        assert ok > 0                        # traffic kept flowing
        # the injected crash actually happened and failed over
        assert reg.counter("serve.failovers").value >= 1
        # respawn lands in the background; give the monitor a moment
        t0 = time.perf_counter()
        while reg.counter("serve.respawns").value < 1:
            assert time.perf_counter() - t0 < 30, "respawn never happened"
            time.sleep(0.05)
        # post-failover traffic serves on the respawned replica set
        tail = [router.submit(list(rng.randint(0, V, size=3)))
                for _ in range(4)]
        for r in tail:
            r.result(timeout=120)
    finally:
        router.stop()
    # the zero-recompile invariant survived the crash: respawn warmed
    # from the shared AotCache, steady state compiled nothing
    assert reg.counter("serve.aot.compiles").value == compiles
    serving_events = [e for e in telemetry.events("retrace")
                      if str(e.get("site", "")).startswith("serving.")]
    assert serving_events == [], serving_events
