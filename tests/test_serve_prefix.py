"""Cross-request prefix caching: refcounted CoW paged KV blocks + the
radix prefix index (ISSUE-10).

Contracts under test:

1. `BlockAllocator` refcounts: alloc at 1, acquire adds readers, release
   drops them and hands refcount-0 blocks back; double-release, trash
   ops, and acquiring a free block all raise; `fragmentation()` counts
   each physical block once (and the trash block never).
2. `PrefixCache`: longest block-aligned prefix match on exact token
   runs, eager insert, LRU park/evict (leaves before roots, pool cap),
   clear.
3. Sharing: a request whose prompt extends a cached prefix acquires the
   cached blocks and prefills only the suffix; a fully covered prompt
   skips prefill (bootstrap decode).  Outputs are token-identical to
   the `MXNET_SERVE_PREFIX=0` single-owner oracle.
4. Copy-on-write: a writer never touches a shared/registered block — it
   copies first (`serve.cow_copies`); a DENIED CoW allocation preempts
   typed and replays, never aliases.
5. Preemption/failover hygiene: a preempted-then-resumed request that
   shares a prefix releases its refs exactly once — zero leaked blocks,
   unchanged tokens.
6. Eviction: refcount-0 registered blocks park (LRU) and evict only
   under allocation pressure (`serve.prefix_evictions`), the
   `prefix_evict:P` chaos clause forces the same path, and
   `block_exhaust:P` denial during sharing stays typed.
7. Zero-retrace: warmup compiles the bucket set + ONE CoW program and
   nothing afterwards; the frozen-cache witness stays 0.
8. `gather_paged_kv` with ALIASED tables (two rows naming one physical
   block) reads the shared rows correctly — sharing is gather-safe.
"""
import time

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu import chaos, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops.attention import gather_paged_kv
from mxnet_tpu.serving import (BlockAllocator, PrefixCache, ServingEngine,
                               TransformerKVModel, TRASH_BLOCK)

V, S, L, H, E = 61, 32, 2, 2, 32


@pytest.fixture
def model_and_params():
    model = TransformerKVModel(V, S, num_layers=L, num_heads=H, num_embed=E)
    return model, model.init_params(np.random.RandomState(7))


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    chaos.reset()
    yield
    telemetry.reset()
    chaos.reset()


def _engine(model, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("prefill_buckets", [8, 16])
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("sampling", False)  # the sampler AOT cost isn't under test
    return ServingEngine(model, params, **kw)


def _drain(eng, reqs, timeout=300):
    eng.run_until_idle(timeout=timeout)
    return [r.result(1) for r in reqs]


_oracle_state = {}


def _oracle(model, params, prompt, max_new):
    """Memoized single-request greedy truth from a SINGLE-OWNER engine
    (prefix=False): the independent reference every sharing/CoW/
    preemption path must reproduce token for token."""
    key = (tuple(prompt), max_new)
    if key not in _oracle_state:
        eng = _oracle_state.get("engine")
        if eng is None:
            eng = _oracle_state["engine"] = _engine(
                model, params, max_batch=1, prefix=False)
        req = eng.submit(prompt, max_new_tokens=max_new)
        eng.run_until_idle(timeout=300)
        _oracle_state[key] = req.result(1)
    return _oracle_state[key]


# ---------------------------------------------------------------------------
# 1. allocator refcounts
# ---------------------------------------------------------------------------

def test_allocator_refcount_invariants():
    a = BlockAllocator(8, 4)
    got = a.alloc(2)
    assert all(a.refcount(b) == 1 for b in got)
    a.acquire(got)
    assert all(a.refcount(b) == 2 for b in got)
    assert a.shared_blocks == 2 and a.used_blocks == 2
    assert a.release(got) == []          # readers remain: nothing zeroed
    zeroed = a.release(got)
    assert sorted(zeroed) == sorted(got)  # last reader out
    assert a.used_blocks == 0 and a.free_blocks == 5  # not yet reclaimed
    a.reclaim(zeroed)
    assert a.free_blocks == 7
    with pytest.raises(MXNetError, match="double free"):
        a.release([got[0]])
    with pytest.raises(MXNetError, match="reclaiming free"):
        a.reclaim([got[0]])
    with pytest.raises(MXNetError, match="acquiring free"):
        a.acquire([got[0]])
    with pytest.raises(MXNetError, match="trash"):
        a.acquire([TRASH_BLOCK])
    held = a.alloc(1)
    with pytest.raises(MXNetError, match="reclaiming held"):
        a.reclaim(held)
    a.free(held)                          # single-owner shortcut still works
    assert a.free_blocks == 7


def test_allocator_fragmentation_counts_physical_blocks_once():
    a = BlockAllocator(8, 4)
    got = a.alloc(2)                      # 8 token rows allocated
    a.acquire(got)                        # shared by a second holder
    # the 2 PHYSICAL blocks hold 8 rows once, however many readers: 6
    # live rows -> 25% waste, not the refcount-doubled 12/16
    assert a.fragmentation(6) == pytest.approx(0.25)
    assert a.fragmentation(8) == 0.0
    # parked prefix blocks extend capacity and are full by construction
    assert a.fragmentation(6 + 4, cached_blocks=1) == pytest.approx(0.5 / 3)
    assert BlockAllocator(8, 4).fragmentation(0) == 0.0


# ---------------------------------------------------------------------------
# 2. the radix prefix index
# ---------------------------------------------------------------------------

def test_prefix_cache_longest_match_and_dedupe():
    pc = PrefixCache(2)
    assert pc.insert([1, 2, 3, 4, 5, 6], [10, 11, 12], 3) == 3
    assert pc.lookup([1, 2, 3, 4, 5, 6]) == [10, 11, 12]
    assert pc.lookup([1, 2, 3, 4, 9, 9]) == [10, 11]
    assert pc.lookup([1, 2]) == [10]
    assert pc.lookup([1]) == []           # partial block: no match
    assert pc.lookup([9, 9]) == []
    # a second physical copy of a cached run does NOT displace the
    # original, but its novel tail still registers through the walk
    assert pc.insert([1, 2, 3, 4, 7, 7], [20, 21, 22], 3) == 1
    assert pc.lookup([1, 2, 3, 4, 7, 7]) == [10, 11, 22]
    assert not pc.contains(20) and pc.contains(22)


def test_prefix_cache_lru_eviction_leaf_first():
    pc = PrefixCache(2)
    pc.insert([1, 2, 3, 4, 5, 6], [10, 11, 12], 3)
    for b in (10, 11, 12):
        assert pc.park(b) == []
    assert pc.parked_count == 3
    # 10 is oldest but is the prefix ROOT: leaves die first
    assert pc.evict(1) == [12]
    assert pc.evict(1) == [11]
    assert pc.lookup([1, 2, 3, 4]) == [10]
    # touch keeps a hot root at the MRU end across a mixed pool
    # (a sequence sharing block 10 registers its novel tail under it)
    pc.insert([1, 2, 9, 9], [10, 30], 2)  # [1,2] -> 10; child [9,9] -> 30
    pc.park(30)
    pc.lookup([1, 2])                     # touches 10
    assert pc.evict(1) == [30]
    pc.unpark([10])
    assert pc.parked_count == 0 and pc.contains(10)
    pc.clear()
    assert pc.lookup([1, 2]) == [] and pc.cached_blocks == 0


def test_prefix_cache_pool_cap():
    pc = PrefixCache(2, pool_cap=1)
    pc.insert([1, 2, 3, 4], [10, 11], 2)
    assert pc.park(11) == []
    assert pc.park(10) == [11]            # cap 1: the leaf evicts
    assert pc.parked_count == 1
    pc0 = PrefixCache(2, pool_cap=0)
    pc0.insert([1, 2], [10], 1)
    assert pc0.park(10) == [10]           # park nothing: instant evict


def test_gather_paged_kv_aliased_tables():
    """Two rows naming the SAME physical block read identical shared
    rows — the read side of sharing needs no special casing."""
    rng = np.random.RandomState(3)
    pools = jnp.asarray(rng.randn(2, 2, 5, 4, 8).astype(np.float32))
    pool = pools[1, 0]
    tables = jnp.asarray(np.array([[1, 2], [1, 3]], np.int32))
    out = np.asarray(gather_paged_kv(pools, 1, 0, tables))
    np.testing.assert_array_equal(out[0, :4], np.asarray(pool)[1])
    np.testing.assert_array_equal(out[1, :4], np.asarray(pool)[1])
    np.testing.assert_array_equal(out[0, 4:], np.asarray(pool)[2])
    np.testing.assert_array_equal(out[1, 4:], np.asarray(pool)[3])


# ---------------------------------------------------------------------------
# 3. sharing parity
# ---------------------------------------------------------------------------

def test_shared_prefix_admission_prefills_only_the_suffix(model_and_params):
    """Requests extending a cached 16-token prefix acquire its 2 blocks
    and stream only their tails through prefill; outputs match the
    single-owner oracle token for token."""
    model, params = model_and_params
    rng = np.random.RandomState(11)
    sys_p = list(rng.randint(0, V, size=16))
    tails = [list(rng.randint(0, V, size=n)) for n in (3, 6, 1)]
    eng = _engine(model, params)
    assert eng._prefix is not None        # default-on with paging
    first = eng.submit(sys_p + tails[0], max_new_tokens=4)
    _drain(eng, [first])
    chunks_before = eng.stats["prefill_chunks"]
    later = [eng.submit(sys_p + t, max_new_tokens=4) for t in tails[1:]]
    outs = [first.result(1)] + _drain(eng, later)
    assert outs == [_oracle(model, params, sys_p + t, 4) for t in tails]
    assert eng.stats["prefix_hits"] == 2
    assert eng.stats["prefix_tokens"] == 32   # 2 x the 16-token prefix
    # the shared prefix never re-prefilled: each later request cost one
    # suffix chunk, not the two chunks the full prompt would take
    assert eng.stats["prefill_chunks"] - chunks_before == 2
    assert eng.leaked_blocks() == 0
    assert telemetry.registry().counter("serve.prefix_hits").value == 2
    g = telemetry.registry().gauge("serve.replica0.prefix_hit_rate")
    assert 0.0 < g.value <= 1.0


def test_concurrent_sharing_while_writer_still_decoding(model_and_params):
    """Eager registration: request B shares blocks request A still
    HOLDS (A is mid-decode), and both finish with oracle tokens —
    sharing is not restricted to retired prefixes."""
    model, params = model_and_params
    rng = np.random.RandomState(12)
    sys_p = list(rng.randint(0, V, size=16))
    pa, pb = sys_p + [1, 2, 3], sys_p + [4, 5]
    eng = _engine(model, params, max_batch=2, max_new_tokens=8)
    ra = eng.submit(pa, max_new_tokens=8)
    eng.step()                            # A prefilled: blocks registered
    rb = eng.submit(pb, max_new_tokens=8)
    eng.step()                            # B admitted while A decodes
    assert eng._alloc.shared_blocks >= 2  # the two prefix blocks
    outs = _drain(eng, [ra, rb])
    assert outs == [_oracle(model, params, pa, 8),
                    _oracle(model, params, pb, 8)]
    assert eng.leaked_blocks() == 0


def test_prefix_kill_switch_restores_single_owner(model_and_params):
    """`MXNET_SERVE_PREFIX=0` (prefix=False) restores PR-9 behavior:
    no index, eager frees, zero prefix accounting — and the prefix
    engine's outputs equal the single-owner engine's on the same
    traffic (the A/B parity the bench gate asserts)."""
    model, params = model_and_params
    rng = np.random.RandomState(13)
    sys_p = list(rng.randint(0, V, size=16))
    prompts = [sys_p + list(rng.randint(0, V, size=n)) for n in (2, 5, 3)]
    prompts.append(list(sys_p))           # full-cover bootstrap candidate
    outs = {}
    for prefix in (False, True):
        eng = _engine(model, params, prefix=prefix)
        reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
        outs[prefix] = _drain(eng, reqs)
        assert eng.leaked_blocks() == 0
        if not prefix:
            assert eng._prefix is None
            assert eng.stats["prefix_hits"] == 0
            assert eng._alloc.free_blocks == eng._alloc.capacity
    assert outs[True] == outs[False]


# ---------------------------------------------------------------------------
# 4. copy-on-write
# ---------------------------------------------------------------------------

def test_full_cover_bootstraps_with_cow(model_and_params):
    """An identical block-aligned prompt skips prefill entirely: the
    sequence bootstraps through decode, CoW-copying the shared block
    its first write lands in.  Tokens match the first run exactly."""
    model, params = model_and_params
    rng = np.random.RandomState(14)
    prompt = list(rng.randint(0, V, size=16))
    eng = _engine(model, params)
    a = _drain(eng, [eng.submit(prompt, max_new_tokens=5)])[0]
    prefills_before = eng.stats["prefills"]
    b = _drain(eng, [eng.submit(prompt, max_new_tokens=5)])[0]
    assert a == b == _oracle(model, params, prompt, 5)
    assert eng.stats["prefix_bootstraps"] == 1
    assert eng.stats["cow_copies"] >= 1
    assert eng.stats["prefills"] == prefills_before  # no prefill ran
    assert eng.leaked_blocks() == 0
    reg = telemetry.registry()
    assert reg.counter("serve.cow_copies").value >= 1
    assert reg.counter("serve.prefix_bootstraps").value == 1


def test_denied_cow_preempts_typed_never_aliases(model_and_params):
    """A CoW whose block allocation fails must NOT write the shared
    block: the sequence preempts (typed requeue), resumes off the
    partial prefix, and still produces oracle tokens — and the cached
    blocks the first request published stay byte-valid (its re-reader
    also matches)."""
    model, params = model_and_params
    rng = np.random.RandomState(15)
    prompt = list(rng.randint(0, V, size=16))
    # 3 usable blocks: run 1 uses all 3 (16 tokens + first write), parks
    # 2 full blocks and frees 1.  Run 2 full-covers, takes the last free
    # block for its decode tail, and finds NOTHING for the CoW copy.
    eng = _engine(model, params, n_blocks=4, max_new_tokens=4)
    a = _drain(eng, [eng.submit(prompt, max_new_tokens=4)])[0]
    assert eng._prefix.parked_count == 2
    assert eng._alloc.free_blocks == 1
    r2 = eng.submit(prompt, max_new_tokens=4)
    b = _drain(eng, [r2])[0]
    assert a == b == _oracle(model, params, prompt, 4)
    assert eng.stats["prefix_bootstraps"] >= 1
    assert eng.stats["cow_copies"] == 0       # the copy never got a block
    assert eng.stats["preemptions"] >= 1      # denied CoW -> typed preempt
    assert eng.leaked_blocks() == 0
    assert telemetry.registry().counter("serve.preempted").value >= 1


def test_preempted_resume_with_shared_prefix_releases_refs_once(
        model_and_params):
    """Regression (ISSUE-10 satellite): growth pressure preempts a
    sequence that holds SHARED prefix blocks; the resume re-acquires
    through the index.  Refs must drop exactly once per preemption —
    zero leaked blocks after the drain, tokens unchanged."""
    model, params = model_and_params
    rng = np.random.RandomState(16)
    sys_p = list(rng.randint(0, V, size=8))
    pa, pb = sys_p + [7], sys_p + [9]
    oracle = [_oracle(model, params, p, 12) for p in (pa, pb)]
    # 4 usable blocks of 8: the shared prefix block + one tail block
    # each admits both, but growth past pos 16 (a 3rd footprint block
    # per row) cannot fit two growers — one must preempt and resume
    eng = _engine(model, params, max_batch=2, n_blocks=5,
                  max_new_tokens=12)
    ra = eng.submit(pa, max_new_tokens=12)
    eng.step()                            # A's prefix block registers
    rb = eng.submit(pb, max_new_tokens=12)
    outs = _drain(eng, [ra, rb], timeout=300)
    assert outs == oracle
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["prefix_hits"] >= 1  # B (or the resume) shared
    assert eng.leaked_blocks() == 0
    parked = eng._prefix.parked_count
    assert eng._alloc.free_blocks + parked == eng._alloc.capacity


# ---------------------------------------------------------------------------
# 5. eviction
# ---------------------------------------------------------------------------

def test_parked_blocks_evict_under_allocation_pressure(model_and_params):
    """Retired prefixes survive in the parked pool until live traffic
    needs the HBM: a large unrelated admission evicts them LRU-first
    (`serve.prefix_evictions`) instead of failing — and an evicted
    prefix simply re-prefills on its next use."""
    model, params = model_and_params
    rng = np.random.RandomState(17)
    hot = list(rng.randint(0, V, size=16))
    eng = _engine(model, params, n_blocks=5, max_new_tokens=3)
    _drain(eng, [eng.submit(hot, max_new_tokens=3)])
    assert eng._prefix.parked_count == 2
    # 4 usable blocks, 2 parked: a 24-token stranger needs 4 -> pressure
    stranger = list(rng.randint(0, V, size=24))
    out = _drain(eng, [eng.submit(stranger, max_new_tokens=3)])[0]
    assert out == _oracle(model, params, stranger, 3)
    assert eng.stats["prefix_evictions"] >= 1
    assert telemetry.registry().counter(
        "serve.prefix_evictions").value >= 1
    # the hot prefix is gone but not forgotten wrongly: a rerun just
    # re-prefills and re-registers
    hits_before = eng.stats["prefix_hits"]
    again = _drain(eng, [eng.submit(hot + [5], max_new_tokens=3)])[0]
    assert again == _oracle(model, params, hot + [5], 3)
    assert eng.stats["prefix_hits"] == hits_before  # miss: evicted
    assert eng.leaked_blocks() == 0


def test_prefix_pool_cap_limits_parked(model_and_params):
    model, params = model_and_params
    rng = np.random.RandomState(18)
    eng = _engine(model, params, prefix_pool=1)
    reqs = [eng.submit(list(rng.randint(0, V, size=16)), max_new_tokens=2)
            for _ in range(3)]
    _drain(eng, reqs)
    assert eng._prefix.parked_count <= 1
    assert eng.stats["prefix_evictions"] >= 1
    assert eng.leaked_blocks() == 0


def test_chaos_prefix_evict_forces_pressure(model_and_params,
                                            monkeypatch):
    """`prefix_evict:1` evicts the LRU parked block every step: sharing
    decays to plain paging, but every request still completes with
    oracle tokens and nothing leaks."""
    model, params = model_and_params
    rng = np.random.RandomState(19)
    sys_p = list(rng.randint(0, V, size=16))
    prompts = [sys_p + list(rng.randint(0, V, size=n)) for n in (2, 4, 3)]
    oracle = [_oracle(model, params, p, 3) for p in prompts]
    monkeypatch.setenv("MXNET_CHAOS", "prefix_evict:1")
    chaos.reset()
    try:
        eng = _engine(model, params)
        # wave 1 parks its prefix at retire; wave 2's steps then run with
        # a non-empty parked pool for the clause to chew on
        outs = [_drain(eng, [eng.submit(prompts[0], max_new_tokens=3)])[0]]
        outs += _drain(eng, [eng.submit(p, max_new_tokens=3)
                             for p in prompts[1:]])
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        chaos.reset()
    assert outs == oracle
    assert eng.stats["prefix_evictions"] >= 1
    assert eng.leaked_blocks() == 0
    assert eng._dead is None


def test_chaos_block_exhaust_with_sharing_stays_typed(model_and_params,
                                                      monkeypatch):
    """`block_exhaust:P` under shared-prefix traffic: denials at admit,
    growth, and CoW all resolve typed (requeue/preempt) — outputs
    unchanged, zero leaks, scheduler alive.  Also pins the clause's
    no-cache-burn contract: a chaos denial with free blocks available
    must not evict parked prefixes."""
    model, params = model_and_params
    rng = np.random.RandomState(20)
    prompt = list(rng.randint(0, V, size=16))
    prompts = [prompt, prompt + [3], list(prompt), prompt + [8, 1]]
    oracle = [_oracle(model, params, p, 4) for p in prompts]
    monkeypatch.setenv("MXNET_CHAOS", "block_exhaust:0.3")
    monkeypatch.setenv("MXNET_CHAOS_SEED", "5")
    chaos.reset()
    try:
        eng = _engine(model, params)
        outs = _drain(eng, [eng.submit(p, max_new_tokens=4)
                            for p in prompts], timeout=300)
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        monkeypatch.delenv("MXNET_CHAOS_SEED")
        chaos.reset()
    assert outs == oracle
    assert eng.stats["prefix_evictions"] == 0  # denials never burn cache
    assert eng.leaked_blocks() == 0
    assert eng._dead is None


# ---------------------------------------------------------------------------
# 6. shape discipline
# ---------------------------------------------------------------------------

def test_prefix_zero_retrace_and_frozen_cache(model_and_params):
    """Warmup compiles the bucket set + exactly ONE CoW program; shared,
    bootstrapped, CoW'd, and chunked traffic afterwards compiles
    NOTHING: no `serving.*` retrace event, `serve.aot.compiles` static,
    `serve.aot.frozen_compiles` zero."""
    model, params = model_and_params
    eng = _engine(model, params, sampling=True)
    eng.warmup()
    reg = telemetry.registry()
    compiles = reg.counter("serve.aot.compiles").value
    assert compiles == len(eng.prefill_buckets) + \
        len(eng.decode_buckets) + 1       # + the CoW block-copy program
    assert eng._aot.frozen

    rng = np.random.RandomState(21)
    sys_p = list(rng.randint(0, V, size=16))
    prompts = [sys_p + list(rng.randint(0, V, size=3)),  # suffix share
               list(sys_p),                              # bootstrap + CoW
               sys_p + list(rng.randint(0, V, size=9)),  # chunked suffix
               list(rng.randint(0, V, size=25))]         # chunked stranger
    reqs = [eng.submit(p, max_new_tokens=m, temperature=0.0 if m % 2
                       else 0.7, seed=m)
            for p, m in zip(prompts, (4, 3, 5, 2))]
    _drain(eng, reqs)
    assert eng.stats["prefix_bootstraps"] >= 1
    assert eng.stats["cow_copies"] >= 1
    events = [e for e in telemetry.events("retrace")
              if str(e.get("site", "")).startswith("serving.")]
    assert events == [], events
    assert reg.counter("serve.aot.compiles").value == compiles
    assert reg.counter("serve.aot.frozen_compiles").value == 0
    assert eng.leaked_blocks() == 0


def test_block_gauges_sane_under_sharing(model_and_params):
    """`blocks_frag` stays in [0, 1] with refcounts > 1 (the old
    per-reference accounting would overcount used rows past capacity
    and clamp to 0 exactly when sharing was highest)."""
    model, params = model_and_params
    rng = np.random.RandomState(22)
    sys_p = list(rng.randint(0, V, size=16))
    eng = _engine(model, params, max_batch=2, max_new_tokens=8)
    ra = eng.submit(sys_p + [1], max_new_tokens=8)
    eng.step()
    rb = eng.submit(sys_p + [2, 3], max_new_tokens=8)
    eng.step()
    assert eng._alloc.shared_blocks >= 2
    reg = telemetry.registry()
    frag = reg.gauge("serve.replica0.blocks_frag").value
    assert 0.0 <= frag < 1.0
    # 2 sequences mid-flight with partially-filled tail blocks MUST show
    # some internal fragmentation — the zero-clamp was the PR-9 bug
    assert frag > 0.0
    assert reg.gauge("serve.replica0.blocks_shared").value >= 2
    _drain(eng, [ra, rb])
    assert eng.leaked_blocks() == 0


def test_chaos_spec_parses_prefix_evict(monkeypatch):
    monkeypatch.setenv("MXNET_CHAOS", "prefix_evict:0.25,block_exhaust:0.1")
    chaos.reset()
    try:
        s = chaos.spec()
        assert s.prefix_evict == 0.25
        assert s.block_exhaust == 0.1
    finally:
        monkeypatch.delenv("MXNET_CHAOS")
        chaos.reset()
