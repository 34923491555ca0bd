"""Continuous-batching serving engine tests (mxnet_tpu/serving).

The contracts under test, in dependency order:

1. KV-cache numerics: chunked prefill + single-token decode over the
   paged pool reproduce the full-sequence `models/transformer.py` forward
   (the Symbol graph bound through Executor) within fp32 tolerance, token
   by token.
2. Scheduling: sequences admit and retire MID-batch (iteration-level,
   Orca-style) without perturbing their neighbours — batched greedy
   outputs are bit-identical to one-request-at-a-time runs.
3. Shape discipline: after `warmup()`, serving traffic compiles NOTHING
   (retrace watchdog event stream empty for `serving.*` sites,
   `serve.aot.compiles` static).
4. Scale-out: a 2-replica router on the CPU mesh completes everything it
   admits, on two distinct devices.
5. Failure semantics (docs/serving.md): every request resolves with
   tokens or a TYPED ServeError — deadlines/cancellation retire at
   iteration granularity, overload policies bound the queue, launch
   failures stay scoped (quarantine / cache rebuild) unless the device
   is gone, and a dead replica fails over to survivors (+ respawn off
   the shared AOT cache, compiling nothing).
"""
import time

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.transformer import get_transformer_lm
from mxnet_tpu.ops.attention import decode_attention
from mxnet_tpu.test_utils import FullForward
from mxnet_tpu.serving import (ReplicaRouter, ServingEngine,
                               TransformerKVModel, ServeTimeout,
                               ServeOverload, ServeDeadlineExceeded,
                               ServeCancelled, ServeQuarantined,
                               ServeCacheInvalidated, ServeEngineDead)

V, S, L, H, E = 61, 32, 2, 2, 32


@pytest.fixture
def model_and_params():
    model = TransformerKVModel(V, S, num_layers=L, num_heads=H, num_embed=E)
    return model, model.init_params(np.random.RandomState(7))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("max_new_tokens", 6)
    # greedy-only programs: sampling program coverage lives in
    # tests/test_serve_paged.py — compiling the sampler into every
    # engine here would roughly double the suite's AOT time
    kw.setdefault("sampling", False)
    return ServingEngine(model, params, **kw)


# ---------------------------------------------------------------------------
# 1. numerics
# ---------------------------------------------------------------------------

def test_decode_attention_matches_full_softmax():
    """decode_attention at position p == row p of masked full attention."""
    rng = np.random.RandomState(0)
    b, s, e, h = 3, 10, 16, 2
    k = rng.randn(b, s, e).astype(np.float32)
    v = rng.randn(b, s, e).astype(np.float32)
    q = rng.randn(b, e).astype(np.float32)
    pos = np.array([4, 9, 0], np.int32)
    got = np.asarray(decode_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(pos), h))
    hd = e // h
    for bi in range(b):
        p = pos[bi]
        for hi in range(h):
            qh = q[bi, hi * hd:(hi + 1) * hd]
            kh = k[bi, :p + 1].reshape(p + 1, h, hd)[:, hi]
            vh = v[bi, :p + 1].reshape(p + 1, h, hd)[:, hi]
            sc = kh @ qh / np.sqrt(hd)
            w = np.exp(sc - sc.max())
            w /= w.sum()
            want = w @ vh
            np.testing.assert_allclose(
                got[bi, hi * hd:(hi + 1) * hd], want, atol=1e-5)


def test_param_names_match_transformer_symbol(model_and_params):
    """The decode model's parameter dict must stay in lockstep with the
    names/shapes `get_transformer_lm` mints, or checkpoints stop serving."""
    model, _ = model_and_params
    net = get_transformer_lm(V, S, num_layers=L, num_heads=H, num_embed=E)
    logits_sym = net.get_internals()["pred_output"]
    sym_args = set(logits_sym.list_arguments()) - {"data"}
    assert sym_args == set(model.param_shapes())
    arg_shapes, _, _ = logits_sym.infer_shape(data=(2, S))
    by_name = dict(zip(logits_sym.list_arguments(), arg_shapes))
    for name, shape in model.param_shapes().items():
        assert tuple(by_name[name]) == tuple(shape), name


@pytest.mark.parametrize("block_size", [4, 8, 16])
def test_prefill_decode_parity_vs_full_forward(model_and_params, block_size):
    """Acceptance gate: the programs the engine launches, `prefill_paged`
    in chunks (one row a launch, as the engine admits), `verify_paged`
    over a fed span and `decode_paged` over a ragged batch, through block
    tables that are a shuffle of the pool, give the full-sequence
    forward's logits at every position, within fp32 tolerance."""
    model, params = model_and_params
    full_forward = FullForward(model, params)
    rng = np.random.RandomState(0)
    lens = [5, 13, 22]
    B, m = len(lens), S // block_size
    toks = rng.randint(0, V, size=(B, S))
    full = np.stack([full_forward.logits(t) for t in toks])

    tables = 1 + rng.permutation(B * m).reshape(B, m)   # block 0 is trash
    assert (tables != 1 + np.arange(B * m).reshape(B, m)).any()
    tables = jnp.asarray(tables, jnp.int32)
    pool = model.init_block_pool(1 + B * m, block_size)
    pj = {k: jnp.asarray(v) for k, v in params.items()}
    chunk = max(8, block_size)
    for i, n in enumerate(lens):
        for start in range(0, n, chunk):
            piece = np.zeros((1, chunk), np.int32)
            length = min(chunk, n - start)
            piece[0, :length] = toks[i, start:start + length]
            lg, pool = model.prefill_paged(
                pj, pool, jnp.asarray(piece), jnp.asarray([start], jnp.int32),
                jnp.asarray([length], jnp.int32), tables[i:i + 1])
        np.testing.assert_allclose(np.asarray(lg[0]), full[i, n - 1],
                                   atol=2e-5)
    # the speculative verify launch scores a fed span in one program: the
    # same logits at every fed position (its pool is dropped: the decode
    # steps below write those positions themselves)
    span = np.stack([toks[i, n:n + 4] for i, n in enumerate(lens)])
    lg, _ = model.verify_paged(
        pj, pool, jnp.asarray(span, jnp.int32), jnp.asarray(lens, jnp.int32),
        jnp.full((B,), 4, jnp.int32), tables)
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(lg[i]), full[i, n:n + 4],
                                   atol=2e-5)
    for step in range(S - max(lens)):
        pos = np.asarray(lens) + step
        lg, pool = model.decode_paged(
            pj, pool, jnp.asarray(toks[np.arange(B), pos], jnp.int32),
            jnp.asarray(pos, jnp.int32), tables)
        np.testing.assert_allclose(
            np.asarray(lg), full[np.arange(B), pos], atol=2e-5,
            err_msg="decode diverged at positions %s" % pos)


def test_ragged_prefill_lengths_isolated(model_and_params):
    """Rows with different prompt lengths in one padded `prefill_paged`
    launch must each give their own sequence's logits (right-padding is
    inert, whatever it holds, and rows do not see each other)."""
    model, params = model_and_params
    full_forward = FullForward(model, params)
    pj = {k: jnp.asarray(v) for k, v in params.items()}
    rng = np.random.RandomState(3)
    lens = [3, 8, 5]
    s_bucket, bs = 8, 4
    toks = rng.randint(0, V, size=(len(lens), s_bucket)).astype(np.int32)
    tables = 1 + np.arange(len(lens) * (S // bs)).reshape(len(lens), -1)
    logits, _ = model.prefill_paged(
        pj, model.init_block_pool(1 + tables.size, bs), jnp.asarray(toks),
        jnp.zeros((len(lens),), jnp.int32), jnp.asarray(lens, jnp.int32),
        jnp.asarray(tables, jnp.int32))
    for i, n in enumerate(lens):
        np.testing.assert_allclose(np.asarray(logits[i]),
                                   full_forward.logits(toks[i, :n])[-1],
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# 2. scheduling
# ---------------------------------------------------------------------------

_oracle_state = {}


def _oracle(model, params, prompt, max_new=6):
    """One-request-at-a-time greedy generation (the batching-free truth).
    The oracle engine is built once and its outputs memoized — the model
    and params are identical in every test (seeded fixture), and a fresh
    engine per call made AOT compilation dominate the suite's runtime."""
    key = (tuple(prompt), max_new)
    if key not in _oracle_state:
        cfg = (model.vocab_size, model.seq_len, model.num_layers,
               model.num_heads, model.num_embed)
        if _oracle_state.get("cfg", cfg) != cfg:
            # the memo is only valid for one geometry (params are the
            # seeded fixture, identical per geometry); a test with a
            # different model must not inherit another's tokens
            _oracle_state.clear()
        _oracle_state["cfg"] = cfg
        eng = _oracle_state.get("engine")
        if eng is None:
            eng = _oracle_state["engine"] = _engine(model, params,
                                                   max_batch=1)
        req = eng.submit(prompt, max_new_tokens=max_new)
        eng.run_until_idle(timeout=300)
        _oracle_state[key] = req.result(1)
    return _oracle_state[key]


def test_admit_retire_mid_batch(model_and_params):
    """Requests joining and leaving the running batch at step granularity
    must not change any sequence's greedy output."""
    model, params = model_and_params
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(0, V, size=n)) for n in (3, 7, 5, 9, 2, 4)]
    # staggered max_new makes retirement happen mid-batch, and staggered
    # submission makes admission happen mid-batch
    max_news = [2, 6, 3, 5, 6, 4]

    eng = _engine(model, params, max_batch=3)
    eng.warmup()
    first = [eng.submit(p, max_new_tokens=m)
             for p, m in zip(prompts[:4], max_news[:4])]
    for _ in range(3):       # run a few steps with the initial wave
        eng.step()
    late = [eng.submit(p, max_new_tokens=m)
            for p, m in zip(prompts[4:], max_news[4:])]
    eng.run_until_idle(timeout=300)
    outs = [r.result(1) for r in first + late]

    assert all(r.done for r in first + late)
    for p, m, o in zip(prompts, max_news, outs):
        assert o == _oracle(model, params, p, max_new=m), \
            "batched output diverged from solo run for prompt %s" % p
        assert len(o) == m
    assert eng.stats["completed"] == len(prompts)
    assert not eng._active and len(eng._free) == eng.max_batch


def test_eos_retires_early(model_and_params):
    model, params = model_and_params
    prompt = [5, 9, 11]
    base = _oracle(model, params, prompt, max_new=6)
    eos = base[2]
    eng = _engine(model, params)
    req = eng.submit(prompt, max_new_tokens=6, eos_id=eos)
    eng.run_until_idle(timeout=300)
    got = req.result(1)
    assert got == base[:base.index(eos) + 1]


def test_capacity_bound_request_uses_full_cache(model_and_params):
    """A request that hits the context limit generates through the LAST
    cache row (position seq_len - 1), not one short of it: 1 prefill
    token + one decode per remaining position."""
    model, params = model_and_params
    eng = ServingEngine(model, params, max_batch=2,
                        prefill_buckets=[16, S], max_new_tokens=4)
    plen = S - 2
    req = eng.submit(list(np.arange(plen) % V), max_new_tokens=10)
    eng.run_until_idle(timeout=300)
    assert len(req.result(1)) == S - plen + 1  # 3, not 2


def test_prompt_too_long_rejected(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params)
    # the largest prefill bucket (16) is no ceiling: up to seq_len - 1
    # tokens a prompt streams through it in chunks
    req = eng.submit(list(range(S - 1)), max_new_tokens=1)
    eng.run_until_idle(timeout=300)
    assert len(req.result(1)) == 1
    with pytest.raises(MXNetError, match="empty prompt"):
        eng.submit([])
    with pytest.raises(MXNetError, match="leaves no room"):
        eng.submit(list(range(32)))  # a full-context prompt still rejects
    with pytest.raises(MXNetError, match="max_new_tokens"):
        eng.submit([1, 2], max_new_tokens=0)  # not silently the default
    with pytest.raises(MXNetError, match="max_new_tokens"):
        ServingEngine(model, params, max_new_tokens=0)


def test_scheduler_death_fails_requests_not_hangs(model_and_params,
                                                  monkeypatch):
    """A scheduler-fatal error (anything escaping step(), e.g. a decode
    launch failure) must fail every outstanding request promptly and mark
    the engine dead — not strand clients in result() until timeout."""
    model, params = model_and_params
    eng = _engine(model, params)
    eng.warmup()

    def boom(b_bucket):
        raise RuntimeError("device exploded")

    monkeypatch.setattr(eng, "_compiled_decode", boom)
    eng.start()
    req = eng.submit([1, 2, 3])
    with pytest.raises(MXNetError, match="device exploded"):
        req.result(timeout=60)  # prompt failure, not a 60 s hang
    eng.stop()
    with pytest.raises(MXNetError, match="scheduler died"):
        eng.submit([4, 5])


def test_prefill_launch_failure_quarantines_when_cache_survives(
        model_and_params, monkeypatch):
    """Scoped failure: a prefill launch that fails WITHOUT consuming the
    donated K/V cache poisons only its own request — typed
    `ServeQuarantined`, engine stays up, the rest of the traffic serves
    (the PR-7 behavior killed the whole scheduler here)."""
    model, params = model_and_params
    eng = _engine(model, params)
    eng.warmup()
    real = eng._compiled_prefill
    poison = [True]

    def flaky(s):
        compiled = real(s)

        def call(*a, **k):
            if poison[0]:
                poison[0] = False
                raise RuntimeError("launch blew up")
            return compiled(*a, **k)

        return call

    monkeypatch.setattr(eng, "_compiled_prefill", flaky)
    eng.start()
    bad = eng.submit([1, 2, 3])
    with pytest.raises(ServeQuarantined, match="launch blew up"):
        bad.result(timeout=60)
    ok = eng.submit([4, 5], max_new_tokens=2)
    assert len(ok.result(timeout=60)) == 2  # engine survived the poison
    eng.stop()
    assert eng._dead is None
    assert telemetry.registry().counter("serve.quarantined").value == 1


def test_cache_invalidation_rebuilds_and_keeps_serving(model_and_params,
                                                       monkeypatch):
    """A launch that CONSUMED the donated cache fails every admitted
    sequence with `ServeCacheInvalidated`, rebuilds the buffer, and keeps
    serving the queue — compiling nothing new (rebuild is a device_put,
    not a recompile)."""
    model, params = model_and_params
    eng = _engine(model, params, max_batch=2)
    eng.warmup()
    reg = telemetry.registry()
    compiles = reg.counter("serve.aot.compiles").value
    real = eng._compiled_decode
    armed = [True]

    def bomb(b):
        compiled = real(b)

        def call(*a):
            if armed[0]:
                armed[0] = False
                a[1].delete()  # the donation landed, then the launch died
                raise RuntimeError("launch exploded mid-donation")
            return compiled(*a)

        return call

    monkeypatch.setattr(eng, "_compiled_decode", bomb)
    lost = [eng.submit([3 + i, 5], max_new_tokens=4) for i in range(2)]
    eng.run_until_idle(timeout=300)
    for r in lost:
        with pytest.raises(ServeCacheInvalidated):
            r.result(timeout=1)
    ok = eng.submit([7, 8], max_new_tokens=2)
    eng.run_until_idle(timeout=300)
    assert len(ok.result(timeout=1)) == 2
    assert eng._dead is None
    assert reg.counter("serve.cache_rebuilds").value == 1
    assert reg.counter("serve.aot.compiles").value == compiles


def test_quarantine_leaves_surviving_rows_batch_invariant(model_and_params,
                                                          monkeypatch):
    """Mid-batch quarantine parity: poisoning ONE admission while a batch
    is decoding must not change any surviving sequence's greedy output
    (the admit/retire-parity contract extended to the failure path)."""
    model, params = model_and_params
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, V, size=n)) for n in (4, 6, 3)]
    eng = _engine(model, params, max_batch=3)
    eng.warmup()
    good = [eng.submit(p, max_new_tokens=5) for p in prompts[:2]]
    for _ in range(2):
        eng.step()
    real = eng._compiled_prefill
    poison = [True]

    def flaky(s):
        compiled = real(s)

        def call(*a, **k):
            if poison[0]:
                poison[0] = False
                raise RuntimeError("poisoned admission")
            return compiled(*a, **k)

        return call

    monkeypatch.setattr(eng, "_compiled_prefill", flaky)
    bad = eng.submit(prompts[2], max_new_tokens=5)
    late = eng.submit(list(rng.randint(0, V, size=5)), max_new_tokens=3)
    eng.run_until_idle(timeout=300)
    with pytest.raises(ServeQuarantined):
        bad.result(timeout=1)
    for p, r in zip(prompts[:2], good):
        assert r.result(timeout=1) == _oracle(model, params, p, max_new=5)
    assert late.result(timeout=1) == _oracle(
        model, params, late.prompt, max_new=3)


# ---------------------------------------------------------------------------
# 2b. deadlines, cancellation, admission control
# ---------------------------------------------------------------------------

def test_result_timeout_and_deadline_are_typed(model_and_params):
    """result(timeout) raises ServeTimeout; an expired queued request is
    retired with ServeDeadlineExceeded at the next iteration, costing no
    prefill dispatch."""
    model, params = model_and_params
    eng = _engine(model, params)
    req = eng.submit([1, 2], deadline_ms=1)
    with pytest.raises(ServeTimeout):
        req.result(timeout=0.01)  # engine not stepping: client-side wait
    time.sleep(0.01)
    eng.step()
    with pytest.raises(ServeDeadlineExceeded):
        req.result(timeout=1)
    assert eng.stats["prefills"] == 0  # shed before any dispatch
    assert telemetry.registry().counter("serve.expired").value == 1


def test_deadline_expires_mid_decode(model_and_params):
    """An ACTIVE sequence whose deadline passes leaves the batch at the
    next iteration (typed error, partial tokens preserved on the request,
    slot freed)."""
    model, params = model_and_params
    eng = _engine(model, params, max_batch=2)
    req = eng.submit([1, 2, 3], max_new_tokens=6, deadline_ms=60000)
    eng.step()          # prefill + first decode
    assert len(req.tokens) >= 1
    req.t_deadline = time.perf_counter() - 1.0  # force expiry
    eng.step()
    with pytest.raises(ServeDeadlineExceeded):
        req.result(timeout=1)
    assert not eng._active and len(eng._free) == eng.max_batch


def test_cancel_retires_at_iteration_granularity(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params, max_batch=2)
    rng = np.random.RandomState(4)
    keep_p = list(rng.randint(0, V, size=4))
    keep = eng.submit(keep_p, max_new_tokens=4)
    victim = eng.submit([5, 6], max_new_tokens=6)
    eng.step()
    victim.cancel()
    eng.run_until_idle(timeout=300)
    with pytest.raises(ServeCancelled):
        victim.result(timeout=1)
    # the survivor's greedy output is untouched by its neighbour leaving
    assert keep.result(timeout=1) == _oracle(model, params, keep_p,
                                             max_new=4)
    assert telemetry.registry().counter("serve.cancelled").value == 1


def test_overload_shed_and_degrade(model_and_params):
    """Bounded queue: `shed` raises typed ServeOverload at admission;
    `degrade` admits but caps max_new_tokens under pressure."""
    model, params = model_and_params
    eng = _engine(model, params, queue_max=2, overload="shed")
    eng.submit([1])
    eng.submit([2])
    with pytest.raises(ServeOverload):
        eng.submit([3])
    assert telemetry.registry().counter("serve.shed").value == 1

    deg = _engine(model, params, queue_max=1, overload="degrade",
                  max_new_tokens=8)
    deg.submit([1])                       # fills the bounded queue
    capped = deg.submit([2], max_new_tokens=8)
    assert capped.max_new_tokens == 2     # max(1, 8 // 4)
    deg.run_until_idle(timeout=300)
    assert len(capped.result(timeout=1)) == 2
    assert telemetry.registry().counter("serve.degraded").value == 1

    with pytest.raises(MXNetError, match="overload policy"):
        _engine(model, params, overload="panic")


def test_overload_block_policy_drains(model_and_params):
    """`block` admission waits for queue room instead of shedding; with a
    live scheduler every submit eventually lands and completes."""
    model, params = model_and_params
    eng = _engine(model, params, max_batch=2, queue_max=1,
                  overload="block", max_new_tokens=2)
    eng.warmup()
    eng.start()
    try:
        reqs = [eng.submit([1 + i]) for i in range(5)]
        outs = [r.result(timeout=120) for r in reqs]
    finally:
        eng.stop()
    assert all(len(o) == 2 for o in outs)


def test_submit_after_stop_raises_immediately(model_and_params):
    model, params = model_and_params
    eng = _engine(model, params)
    eng.start()
    eng.stop()
    with pytest.raises(ServeEngineDead, match="stopped"):
        eng.submit([1, 2])
    router = ReplicaRouter([_engine(model, params)], respawn=False)
    router.stop()
    with pytest.raises(ServeEngineDead, match="stopped"):
        router.submit([1, 2])


def test_run_until_idle_timeout_honored_with_dead_thread(model_and_params,
                                                         monkeypatch):
    """The router drain must honor its timeout as a WHOLE-drain bound,
    including when a replica can never drain (dead scheduler thread or a
    wedged step)."""
    model, params = model_and_params
    engines = [_engine(model, params) for _ in range(2)]
    router = ReplicaRouter(engines, respawn=False)
    monkeypatch.setattr(engines[0], "step", lambda: 1)  # never drains
    t0 = time.perf_counter()
    with pytest.raises(ServeTimeout):
        router.run_until_idle(timeout=0.3)
    assert time.perf_counter() - t0 < 5  # one shared budget, not n x t


def test_unsorted_bucket_kwargs_normalized(model_and_params):
    """Caller-supplied bucket lists are sorted+deduped: submit() reads
    [-1] as the largest bucket and _bucket_for scans ascending.
    Out-of-range buckets raise instead of being silently dropped."""
    model, params = model_and_params
    with pytest.raises(MXNetError, match="exceed max_batch"):
        ServingEngine(model, params, max_batch=4, decode_buckets=[2, 8])
    with pytest.raises(MXNetError, match="exceed seq_len"):
        ServingEngine(model, params, prefill_buckets=[8, 64])
    eng = ServingEngine(model, params, max_batch=4,
                        decode_buckets=[4, 2, 2], prefill_buckets=[16, 8],
                        max_new_tokens=2)
    assert eng.decode_buckets == [2, 4]
    assert eng.prefill_buckets == [8, 16]
    req = eng.submit(list(range(1, 13)))  # 12 tokens: needs bucket 16
    eng.run_until_idle(timeout=120)
    assert len(req.result(1)) == 2


def test_router_skips_dead_replica(model_and_params, monkeypatch):
    """One replica's scheduler dying must not black-hole the router:
    least-depth dispatch skips dead engines while any replica lives,
    and (ISSUE-12) the dead replica's admitted in-flight request
    MIGRATES to the survivor and completes instead of failing typed.
    (respawn=False keeps the dead replica dead for determinism — the
    respawn path has its own test.)"""
    model, params = model_and_params
    engines = [_engine(model, params, max_batch=2, max_new_tokens=2)
               for _ in range(2)]
    router = ReplicaRouter(engines, respawn=False)
    router.warmup()

    def boom(b_bucket):
        raise RuntimeError("replica0 device exploded")

    monkeypatch.setattr(engines[0], "_compiled_decode", boom)
    router.start()
    try:
        moved = engines[0].submit([1, 2])
        assert len(moved.result(timeout=60)) == 2  # journal migration
        reqs = [router.submit([3 + i]) for i in range(4)]
        outs = [r.result(timeout=60) for r in reqs]
    finally:
        router.stop()
    assert all(len(o) == 2 for o in outs)
    assert engines[0]._dead is not None
    assert engines[1].stats["completed"] == 5  # 4 routed + 1 migrated


def test_router_redispatches_queued_requests_on_death(model_and_params,
                                                      monkeypatch):
    """Failover with the journal DISABLED (the MXNET_SERVE_JOURNAL=0
    kill-switch contract, PR-8/11 semantics): a dying replica's
    queued-but-not-admitted requests move to survivors (same
    ServeRequest objects — deadlines ride along) and complete there;
    the admitted one fails typed (its K/V died with the cache and
    nothing replays it).  Journal-on migration coverage lives in
    tests/test_serve_durability.py."""
    model, params = model_and_params
    engines = [_engine(model, params, max_batch=1, max_new_tokens=2),
               _engine(model, params, max_batch=2, max_new_tokens=2)]
    engines[1].name = "replica1"
    engines[1]._gauge = "serve.replica1."
    router = ReplicaRouter(engines, respawn=False, journal=False)
    router.warmup()

    def boom(b_bucket):
        raise RuntimeError("replica0 device gone")

    monkeypatch.setattr(engines[0], "_compiled_decode", boom)
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(0, V, size=n)) for n in (3, 5, 4, 6)]
    # all queued on replica0 BEFORE it runs: max_batch=1 admits only the
    # first; the rest are queued-but-not-admitted when it dies
    reqs = [engines[0].submit(p) for p in prompts]
    router.start()
    try:
        with pytest.raises(ServeEngineDead):
            reqs[0].result(timeout=60)
        outs = [r.result(timeout=60) for r in reqs[1:]]
    finally:
        router.stop()
    for p, o in zip(prompts[1:], outs):
        assert o == _oracle(model, params, p, max_new=2)
    reg = telemetry.registry()
    assert reg.counter("serve.failovers").value == 1
    assert reg.counter("serve.redispatched").value == 3
    assert engines[1].stats["completed"] == 3


def test_router_respawns_dead_replica_compiling_nothing(model_and_params,
                                                        monkeypatch):
    """Background respawn: the router replaces a dead replica with a
    fresh engine on the same device that warms from the SHARED AotCache —
    `serve.aot.compiles` stays at its warmup value, the zero-retrace gate
    holds, and traffic completes on the respawned replica."""
    model, params = model_and_params
    engines = [_engine(model, params, max_batch=2, max_new_tokens=2)
               for _ in range(2)]
    engines[1].name = "replica1"
    engines[1]._gauge = "serve.replica1."
    router = ReplicaRouter(engines, respawn=True)
    router.warmup()
    reg = telemetry.registry()
    compiles = reg.counter("serve.aot.compiles").value

    def boom(b_bucket):
        raise RuntimeError("replica0 device gone")

    monkeypatch.setattr(engines[0], "_compiled_decode", boom)
    router.start()
    try:
        doomed = engines[0].submit([1, 2])
        # the in-flight request migrates to replica1 and completes (the
        # ISSUE-12 journal path) while the respawn replaces replica0
        assert len(doomed.result(timeout=60)) == 2
        deadline = time.perf_counter() + 30
        while router.engines[0] is engines[0]:
            assert time.perf_counter() < deadline, "respawn never happened"
            time.sleep(0.05)
        fresh = router.engines[0]
        assert fresh.name == "replica0" and fresh._dead is None
        assert fresh._aot is engines[0]._aot  # shared compiled set
        # the respawned replica itself serves (submit directly to it)
        req = fresh.submit([4, 5])
        assert len(req.result(timeout=60)) == 2
    finally:
        router.stop()
    assert reg.counter("serve.respawns").value == 1
    assert reg.counter("serve.aot.compiles").value == compiles
    serving_events = [e for e in telemetry.events("retrace")
                      if str(e.get("site", "")).startswith("serving.")]
    assert serving_events == []


# ---------------------------------------------------------------------------
# 3. zero steady-state recompiles
# ---------------------------------------------------------------------------

def test_bucketed_shapes_zero_retrace(model_and_params):
    """After warmup pre-AOT-compiles the bucket set, serving traffic of
    mixed prompt lengths and batch sizes must compile nothing: no
    `serving.*` retrace event, `serve.aot.compiles` static."""
    model, params = model_and_params
    eng = _engine(model, params)
    eng.warmup()
    reg = telemetry.registry()
    compiles_after_warmup = reg.counter("serve.aot.compiles").value
    # paged engines with prefix sharing (the default) also compile the
    # single CoW block-copy program at warmup
    assert compiles_after_warmup == \
        len(eng.prefill_buckets) + len(eng.decode_buckets) + \
        (1 if getattr(eng, "_prefix", None) is not None else 0)

    rng = np.random.RandomState(2)
    reqs = [eng.submit(list(rng.randint(0, V, size=n)),
                       max_new_tokens=int(m))
            for n, m in zip((3, 11, 7, 2, 16, 5, 9, 13),
                            (4, 2, 6, 3, 5, 6, 2, 4))]
    eng.run_until_idle(timeout=300)
    for r in reqs:
        r.result(1)

    serving_events = [e for e in telemetry.events("retrace")
                      if str(e.get("site", "")).startswith("serving.")]
    assert serving_events == [], serving_events
    assert reg.counter("serve.aot.compiles").value == compiles_after_warmup
    assert reg.counter("serve.aot.hits").value > 0
    assert reg.counter("serve.completed").value == len(reqs)


def test_watch_jit_seed_declares_without_firing():
    """telemetry.watch_jit(seed=True) joins the seen set silently; a
    signature OUTSIDE the seeded set still diagnoses as a retrace."""
    telemetry.reset()
    reg = telemetry.registry()
    sigs = [((("x", (b,), "int32"),), b) for b in (1, 2, 4)]
    for sig, b in sigs:
        assert reg.watch_jit("t.site", sig, scope=1, meta={"b": b},
                             seed=True) is None
    for sig, b in sigs:  # live traffic over the declared set: silent
        assert reg.watch_jit("t.site", sig, scope=1, meta={"b": b}) is None
    ev = reg.watch_jit("t.site", (("x", (3,), "int32"),), scope=1,
                       meta={"b": 3})
    assert ev is not None and ev["kind"] == "retrace"


# ---------------------------------------------------------------------------
# 4. multi-replica dispatch
# ---------------------------------------------------------------------------

def test_two_replica_cpu_mesh_dispatch(model_and_params):
    from mxnet_tpu.parallel import make_mesh

    model, params = model_and_params
    mesh = make_mesh(shape=(2,), axis_names=("data",))
    router = ReplicaRouter.from_mesh(
        model, params, mesh=mesh, max_batch=2, prefill_buckets=[8, 16],
        max_new_tokens=4, sampling=False)
    router.warmup()
    assert len(router.engines) == 2
    assert len({e._device for e in router.engines}) == 2

    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, V, size=n)) for n in (3, 6, 4, 8, 2, 5)]
    router.start()
    try:
        reqs = [router.submit(p) for p in prompts]
        outs = [r.result(120) for r in reqs]
    finally:
        router.stop()
    assert all(len(o) == 4 for o in outs)
    # least-depth routing under a burst must use both replicas
    assert all(e.stats["prefills"] > 0 for e in router.engines)
    for p, o in zip(prompts, outs):
        assert o == _oracle(model, params, p, max_new=4)


# ---------------------------------------------------------------------------
# 5. lock-discipline regressions (the mxlint lock-unguarded fixes, PR-15)
# ---------------------------------------------------------------------------

class _LockCheckedList(list):
    """`router.engines` stand-in recording reads made without
    `router._lock` held — the submit-vs-monitor replica-swap race the
    mxlint lock-unguarded rule proves absent statically
    (docs/static_analysis.md)."""

    def __init__(self, items, lock):
        super().__init__(items)
        self._lock = lock
        self.unlocked_reads = []

    def _note(self, op):
        if not self._lock.locked():
            self.unlocked_reads.append(op)

    def __len__(self):
        self._note("len")
        return super().__len__()

    def __iter__(self):
        self._note("iter")
        return super().__iter__()

    def __getitem__(self, i):
        self._note("getitem")
        return super().__getitem__(i)


def test_router_engine_list_reads_hold_lock(model_and_params):
    """Every post-warmup read of `router.engines` must hold `_lock`:
    the monitor and `drain` swap replicas under it, and an unlocked
    `len`/iteration races the swap (submit and start once read bare).
    The monitor thread is joined first so `_lock.locked()` reflects
    exactly the calling thread's holds."""
    model, params = model_and_params
    engines = [_engine(model, params, max_new_tokens=2) for _ in range(2)]
    engines[1].name = "replica1"
    engines[1]._gauge = "serve.replica1."
    router = ReplicaRouter(engines, respawn=False, journal=False)
    router.warmup()   # pre-start by serving contract: exempt from the rule
    router.start()
    router._mon_stop.set()
    router._monitor.join(timeout=10)
    router.engines = _LockCheckedList(engines, router._lock)
    try:
        router.start()                   # second start: idempotent path
        req = router.submit([1, 2, 3])
        assert len(req.result(timeout=60)) == 2
        assert router.depth() >= 0
        router.run_until_idle(timeout=30)
    finally:
        router.stop()
    assert router.engines.unlocked_reads == []
    assert telemetry.registry().gauge("serve.replicas").value == 2


def test_drain_returns_promptly_on_dead_engine(model_and_params,
                                               monkeypatch):
    """`drain` polls scheduler liveness under `_qlock` (the lock `_die`
    publishes `_dead` under): draining an engine whose scheduler died
    must return immediately — not spin stepping a dead engine until a
    deadline."""
    model, params = model_and_params
    eng = _engine(model, params)
    eng.warmup()

    def boom(b_bucket):
        raise RuntimeError("device exploded")

    monkeypatch.setattr(eng, "_compiled_decode", boom)
    eng.start()
    req = eng.submit([1, 2, 3])
    with pytest.raises(MXNetError, match="device exploded"):
        req.result(timeout=60)
    t0 = time.monotonic()
    stragglers = eng.drain()     # deadline None = wait-for-idle mode
    assert time.monotonic() - t0 < 10
    assert stragglers == []      # death already failed everything typed


def test_stop_resolves_active_and_queued_typed_releasing_slots(
        model_and_params):
    """`stop()` walks the same `_sweep_inflight` release path `_die` and
    `drain` use: active + queued requests all resolve typed
    `ServeEngineDead` and every slot returns to the free list."""
    model, params = model_and_params
    eng = _engine(model, params, max_batch=2)
    eng.warmup()
    reqs = [eng.submit([1 + i, 2, 3]) for i in range(4)]
    eng.step()                   # admit up to max_batch; rest stay queued
    assert len(eng._active) == 2 and len(eng._queue) == 2
    eng.stop()
    for r in reqs:
        with pytest.raises(ServeEngineDead):
            r.result(timeout=5)
    assert eng._active == {} and len(eng._free) == eng.max_batch
    assert eng.depth() == 0
