"""The in-graph sampler skips its sampling body when no row samples.

`serving/sampling.py: sample_tokens` branches at run time on the batch's
own temperatures: a batch with no row at temperature > 0 takes the argmax
alone, and a batch with any sampled row runs the whole body (one sort of
the vocabulary, the top-k and nucleus masks, a draw per row).  Contracts
under test:

1. the tokens are bit-identical to the unbranched body, frozen below as it
   was, for greedy, mixed and sampled batches;
2. the compiled program holds the branch, and every sort lies inside its
   sampled side: in the function alone and in the engine's decode and
   megastep programs;
3. the engine counts the rows that sample on the host, from the array that
   feeds the branch: `sampled_rows` on each `iteration` record, `sampled`
   on each `prefill_chunk` span.
"""
import re
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import tracing
from mxnet_tpu.serving import ServingEngine, TransformerKVModel
from mxnet_tpu.serving.sampling import _mask_top_k_top_p, sample_tokens

V = 1000


def _unbranched(logits, temperature, top_k, top_p, seed, newpos):
    """`sample_tokens` as it was before the branch: every row through the
    sampling body, greedy rows picked back by a `where`."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.where(temperature > 0, temperature, 1.0).astype(jnp.float32)
    scaled = logits / t[:, None]
    masked = _mask_top_k_top_p(scaled, top_k, top_p)

    def draw(seed_i, pos_i, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed_i), pos_i)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(draw)(seed.astype(jnp.uint32),
                             newpos.astype(jnp.int32),
                             masked).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def _batch(b, mix, rs):
    """Logits and per-row sampling arrays of one batch of ``b`` rows."""
    logits = (3.0 * rs.randn(b, V)).astype(np.float32)
    temp = np.zeros(b, np.float32)
    top_k = np.zeros(b, np.int32)
    top_p = np.ones(b, np.float32)
    if mix == "one_sampled":
        temp[b // 2] = 0.8
    elif mix == "all_sampled":
        temp[:] = rs.uniform(0.5, 1.5, b)
    elif mix == "knob_mix":
        temp[:] = np.resize([0.0, 0.7, 1.3, 1.0], b)
        top_k[:] = np.resize([0, 5, 0, 50], b)
        top_p[:] = np.resize([1.0, 0.9, 0.5, 1.0], b)
    seed = rs.randint(0, 2 ** 32, b, dtype=np.uint64).astype(np.uint32)
    newpos = rs.randint(1, 4096, b).astype(np.int32)
    return logits, temp, top_k, top_p, seed, newpos


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("mix", ["all_greedy", "one_sampled", "all_sampled",
                                 "knob_mix"])
def test_tokens_match_the_unbranched_body(b, mix):
    rs = np.random.RandomState(17 * b + len(mix))
    for _ in range(3):
        args = _batch(b, mix, rs)
        got = np.asarray(jax.jit(sample_tokens)(*args))
        want = np.asarray(jax.jit(_unbranched)(*args))
        np.testing.assert_array_equal(got, want)
        if mix == "all_greedy":
            np.testing.assert_array_equal(got, np.argmax(args[0], axis=-1))


# -- the compiled program -----------------------------------------------------

_CALLS = re.compile(r"(?:to_apply|calls|body|condition|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")


def _computations(text):
    """A compiled HLO module's text as {computation: (its instructions,
    the computations they call)}, and the entry's name."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(2)
            entry = name if head.group(1) else entry
            comps[name] = ([], set())
        elif name and line.startswith("  "):
            lines, calls = comps[name]
            lines.append(line)
            calls.update(_CALLS.findall(line))
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                calls.update(c.strip().lstrip("%") for c in group.split(","))
    return comps, entry


def _reach(comps, roots, cut=()):
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c not in seen and c not in cut:
            seen.add(c)
            todo.extend(comps[c][1])
    return seen


def _assert_sorts_only_when_sampling(text):
    comps, entry = _computations(text)
    branches = [re.search(r"branch_computations=\{%?([\w.\-]+), "
                          r"%?([\w.\-]+)\}", line).groups()
                for lines, _ in comps.values() for line in lines
                if " conditional(" in line and "sampler/cond" in line]
    assert branches, "no branch under the sampler's scope"
    greedy, sampled = zip(*branches)      # lax.cond: (false, true)
    sorts = {c for c, (lines, _) in comps.items()
             if any(" sort(" in line for line in lines)}
    assert sorts and sorts <= _reach(comps, sampled)
    assert not sorts & _reach(comps, greedy)
    assert not sorts & _reach(comps, [entry], cut=sampled)


@pytest.mark.parametrize("b", [1, 8])
def test_the_sort_lies_inside_the_sampled_branch(b):
    shapes = [jax.ShapeDtypeStruct(s, d) for s, d in (
        ((b, V), jnp.float32), ((b,), jnp.float32), ((b,), jnp.int32),
        ((b,), jnp.float32), ((b,), jnp.uint32), ((b,), jnp.int32))]
    text = jax.jit(sample_tokens).lower(*shapes).compile().as_text()
    _assert_sorts_only_when_sampling(text)


# -- the engine ---------------------------------------------------------------

PROMPTS = [[5, 9, 11, 2, 7], [3, 1, 4, 1, 5], [8, 6, 2, 9]]
NEW = 5


@pytest.fixture(scope="module", params=[0, 2], ids=["decode", "megastep"])
def engine(request):
    model = TransformerKVModel(61, 32, num_layers=2, num_heads=2,
                               num_embed=32)
    eng = ServingEngine(model, model.init_params(np.random.RandomState(7)),
                        max_batch=4, block_size=4, n_blocks=40,
                        prefill_buckets=[8], decode_buckets=[4],
                        max_new_tokens=NEW, sampling=True, prefix=False,
                        megastep=bool(request.param),
                        megastep_steps=request.param or None,
                        name="skip_%d" % request.param)
    eng.warmup()
    return eng


def _serve(eng, temps):
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new_tokens=NEW, temperature=t, seed=40 + i)
            for i, (p, t) in enumerate(zip(PROMPTS, temps))]
    eng.run_until_idle(timeout=300)
    outs = [r.result(5) for r in reqs]
    spans = tracing.window(eng.name, t0, time.perf_counter())
    launches = [s["attrs"] for s in spans
                if s["phase"] == "iteration" and "rows" in s["attrs"]]
    chunks = {s["trace"]: s["attrs"]["sampled"] for s in spans
              if s["phase"] == "prefill_chunk"}
    assert launches and eng.leaked_blocks() == 0
    return outs, launches, [chunks[r.id] for r in reqs]


def test_the_engines_programs_sort_only_when_sampling(engine):
    build = engine._compiled_mega if engine._mega_m else \
        engine._compiled_decode
    _assert_sorts_only_when_sampling(build(4).as_text())
    _assert_sorts_only_when_sampling(engine._compiled_prefill(8).as_text())


def test_greedy_launches_count_no_sampled_rows(engine):
    outs, launches, chunks = _serve(engine, (0.0, 0.0, 0.0))
    assert all(len(o) == NEW for o in outs)
    assert all(a["sampled_rows"] == 0 for a in launches)
    assert chunks == [0, 0, 0]


def test_mixed_launches_count_their_sampled_rows(engine):
    greedy, _, _ = _serve(engine, (0.0, 0.0, 0.0))
    outs, launches, chunks = _serve(engine, (0.9, 0.0, 1.3))
    # the greedy row's argmax, taken inside the sampled branch, is the one
    # the greedy branch took
    assert outs[1] == greedy[1]
    assert chunks == [1, 0, 1]
    assert all(0 <= a["sampled_rows"] <= min(2, a["rows"]) for a in launches)
    assert all(a["sampled_rows"] == 2 for a in launches if a["rows"] == 3)
    assert max(a["sampled_rows"] for a in launches) == 2
    if not engine._mega_m:
        # a sampled request's first token comes from its prefill, each
        # other from one decode launch
        assert sum(a["sampled_rows"] for a in launches) == 2 * (NEW - 1)
