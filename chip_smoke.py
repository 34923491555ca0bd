#!/usr/bin/env python
"""The quickest proof that the program still starts on the chip.

    python chip_smoke.py             # one TPU chip: device, legacy, train, serve
    python chip_smoke.py --chips 4   # four chips: only the paths across chips

One process (a chip belongs to one process), no child, no retry, no fallback:
the first phase that fails ends the run with a non-zero exit and no result
line.  With no TPU that is the `device` phase.  Every number printed on the
way is information about this run on the device named beside it — nothing
here is a benchmark.

One chip, in this order, at the widths of the repo's flagship LM record
(12 layers, E=768, 6 heads of 128, vocab 32768, S=1024, bf16, bias-free):

* ``device``  `jax.devices()` must be TPUs.
* ``legacy``  the README quickstart: a small symbol through
  `FeedForward.fit` on ``ctx=[mx.tpu(0)]``, then `predict`.
* ``train``   `get_transformer_lm` through `SPMDTrainer` on a one-device
  mesh, B=32, Adam: single `step()`s and one `run_steps()`; the loss is
  computed on the device, starts near ln(vocab) and falls on a repeated
  batch; the compiled step must hold the Pallas kernels.
* ``serve``   `TransformerKVModel` in bf16 on the parameters `train`
  produced, one `ServingEngine(ctx=mx.tpu(0))`: warmup, a handful of
  requests, parity with the one-request-at-a-time oracle, no leaked block,
  no compile after warmup.

``--chips 4`` runs the same widths at a cut depth over a 2x2 mesh: the
dp x tp trainer with the vocab-sharded fused head against a one-device
trainer, one engine sharded over four devices against a one-device engine,
and four one-chip replicas behind the router.

The last line of a run that passed is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import collections
import functools
import gc
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))

import chip_env  # noqa: E402

SEED = 20260926

Geometry = collections.namedtuple(
    "Geometry", "vocab seq_len layers heads embed batch")

#: tools/benchmark_transformer.py's flagship record (README "Benchmarks")
FULL = Geometry(vocab=32768, seq_len=1024, layers=12, heads=6, embed=768,
                batch=32)
#: the four-chip paths: same widths, depth cut to save four-chip time
CUT_LAYERS = 2

#: the smoke's cut of the engine's bucket sets (the defaults at S=1024 are
#: seven prefill and four decode buckets, ~12 s of compile each on the
#: chip's host).  One decode bucket, because in bf16 the bucket-1 and the
#: bucket-8 program round differently: on the near-flat logits of a model
#: trained for seven steps, greedy tokens then depend on how many requests
#: happen to be in flight, and no oracle can be compared exactly.
PREFILL_BUCKETS = (128, 1024)
DECODE_BUCKETS = (8,)
MAX_BATCH = 8
BLOCK_SIZE = 16
#: (prompt length, new tokens) of the smoke's requests
REQUESTS = ((24, 32), (47, 24), (96, 32), (180, 32), (300, 24), (450, 32),
            (37, 16), (620, 32), (75, 32), (260, 24))
MULTICHIP_REQUESTS = ((24, 16), (47, 12), (96, 16), (180, 16), (300, 12),
                      (410, 16), (37, 8), (75, 16))


class SmokeFailure(RuntimeError):
    """A phase found something wrong."""


def say(msg):
    print("[chip_smoke] " + msg, flush=True)


def check(ok, msg):
    if not ok:
        raise SmokeFailure(msg)


def require_tpu_device(device, what):
    """Every placement this script makes is checked here: ``what`` must
    have landed on a TPU."""
    check(device.platform == "tpu",
          "%s resolved to a %s device (%s), not a TPU"
          % (what, device.platform, device))


def kernel_calls(compiled_text):
    """Pallas kernels in a compiled program's text."""
    return compiled_text.count("tpu_custom_call")


def _gb(n):
    return "%.2f GB" % (n / 1e9)


# -- device ---------------------------------------------------------------


def phase_device(chips):
    devices = chip_env.require_tpu()
    check(len(devices) >= chips,
          "need %d chip(s), jax.devices() has %d" % (chips, len(devices)))
    say("device: %d x %s (%s)" % (len(devices), devices[0].device_kind,
                                  devices[0].platform))
    return devices


# -- legacy ---------------------------------------------------------------


def phase_legacy():
    """README quickstart on ctx=[mx.tpu(0)]."""
    import mxnet_tpu as mx

    ctx = mx.tpu(0)
    require_tpu_device(ctx.jax_device(), "mx.tpu(0)")
    rng = np.random.RandomState(SEED)
    n, dim, classes = 2048, 64, 10
    centers = rng.randn(classes, dim) * 3.0
    y = rng.randint(0, classes, n)
    X = (centers[y] + rng.randn(n, dim)).astype(np.float32)

    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data=data, num_hidden=128, name="fc1")
    act = mx.sym.Activation(data=fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(data=act, num_hidden=classes, name="fc2")
    net = mx.sym.SoftmaxOutput(data=fc2, name="softmax")

    mx.random.seed(SEED)
    model = mx.model.FeedForward(
        symbol=net, ctx=[ctx], num_epoch=1, optimizer="sgd",
        learning_rate=0.1, initializer=mx.init.Xavier())
    t0 = time.perf_counter()
    model.fit(X=mx.io.NDArrayIter(X, y.astype(np.float32), batch_size=128,
                                  shuffle=True))
    probs = model.predict(mx.io.NDArrayIter(X, batch_size=128))
    dt = time.perf_counter() - t0
    check(probs.shape == (n, classes), "predict gave shape %s"
          % (probs.shape,))
    check(bool(np.all(np.isfinite(probs))), "predict gave non-finite values")
    check(float(np.abs(probs.sum(axis=1) - 1.0).max()) < 1e-3,
          "predicted rows are not distributions")
    acc = float((probs.argmax(axis=1) == y).mean())
    check(acc > 0.8, "one epoch on separable blobs reached accuracy %.3f"
          % acc)
    exe = model._pred_exec
    for arr in list(exe.arg_arrays) + list(exe.outputs):
        for dev in arr.data.devices():
            require_tpu_device(dev, "a bound array of the predictor")
    say("legacy: FeedForward.fit + predict on %s in %.1fs, accuracy %.3f, "
        "%d bound arrays on the chip"
        % (ctx, dt, acc, len(exe.arg_arrays) + len(exe.outputs)))


# -- train ----------------------------------------------------------------


def _lm(geom, fused_head):
    from mxnet_tpu import models

    return models.get_transformer_lm(
        vocab_size=geom.vocab, seq_len=geom.seq_len, num_layers=geom.layers,
        num_heads=geom.heads, num_embed=geom.embed, use_bias=False,
        fused_head=fused_head)


def _lm_batch(geom):
    rng = np.random.RandomState(SEED)
    shape = (geom.batch, geom.seq_len)
    return {"data": rng.randint(0, geom.vocab, shape).astype(np.int32),
            "softmax_label": rng.randint(0, geom.vocab, shape).astype(
                np.float32)}


def _trainer(geom, net, mesh, **kw):
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.parallel import SPMDTrainer

    shape = (geom.batch, geom.seq_len)
    return SPMDTrainer(net, mesh,
                       data_shapes={"data": shape, "softmax_label": shape},
                       wd=0.0, dtype=bfloat16, **kw)


def require_kernels(trainer, batch, what):
    """The step `trainer.step()` is about to compile must hold the Pallas
    kernels: a gate that quietly chose the `jax.numpy` body fails here.
    (A trace of the trainer's own jitted step, not a compile.)"""
    import jax
    import jax.numpy as jnp

    lowered = trainer._step.lower(
        trainer.params, trainer.momenta, trainer.aux, batch,
        jax.random.PRNGKey(0), jnp.float32(trainer.lr))
    n = kernel_calls(lowered.as_text())
    check(n > 0, "%s: the lowered step holds no tpu_custom_call — the "
          "kernel gates chose the jax.numpy bodies" % what)
    return n


@functools.lru_cache(maxsize=None)
def _nll_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def nll(p, lbl):
        picked = jnp.take_along_axis(p, lbl[:, None], axis=1)[:, 0]
        return -jnp.mean(jnp.log(jnp.maximum(picked.astype(jnp.float32),
                                             1e-30)))

    return nll


def device_nll(probs, labels):
    """Mean negative log-likelihood of ``labels`` under the (tokens, vocab)
    probabilities `step()` returns — reduced on the device, so only a
    scalar crosses to the host."""
    return float(_nll_program()(probs, labels))


def phase_train(geom, n_single=3, n_fused=4):
    """Returns the trained parameters (bf16, on the device) for `serve`."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.parallel import make_mesh

    device = jax.devices()[0]
    require_tpu_device(device, "the trainer's mesh")
    mesh = make_mesh(shape=(1,), axis_names=("data",), devices=[device])
    net = _lm(geom, fused_head=False)
    mx.random.seed(SEED)
    trainer = _trainer(geom, net, mesh, lr=1e-3, optimizer="adam",
                       adam_v_dtype="bfloat16")
    batch = trainer.shard_batch(_lm_batch(geom))
    labels = batch["softmax_label"].reshape(-1).astype(jnp.int32)
    say("train: L=%d E=%d H=%d V=%d S=%d B=%d bf16 bias-free, Adam (bf16 v);"
        " %d Pallas kernel calls in the lowered step"
        % (geom.layers, geom.embed, geom.heads, geom.vocab, geom.seq_len,
           geom.batch, require_kernels(trainer, batch, "train")))

    def one_step():
        t0 = time.perf_counter()
        outs = trainer.step(batch)
        loss = device_nll(outs[0], labels)
        # the (B*S, V) probabilities are a sixth of the chip: drop them
        # before the next step asks for its own
        del outs
        return loss, time.perf_counter() - t0

    losses, times = zip(*[one_step() for _ in range(n_single)])
    t0 = time.perf_counter()
    trainer.run_steps(batch, n_fused)
    jax.block_until_ready(trainer.params)
    t_fused = time.perf_counter() - t0
    last, _ = one_step()
    losses = list(losses) + [last]
    say("train: step() losses %s (first call %.1fs with its compile, then "
        "%s s); run_steps(%d) %.1fs with its compile; loss after %d steps "
        "%.4f"
        % (", ".join("%.4f" % v for v in losses[:-1]), times[0],
           ", ".join("%.3f" % t for t in times[1:]), n_fused, t_fused,
           n_single + n_fused, last))
    check(all(math.isfinite(v) for v in losses), "loss is not finite: %s"
          % (losses,))
    check(abs(losses[0] - math.log(geom.vocab)) < 1.5,
          "first loss %.4f is not near ln(%d) = %.4f"
          % (losses[0], geom.vocab, math.log(geom.vocab)))
    check(all(b < a for a, b in zip(losses, losses[1:])),
          "loss does not fall on a repeated batch: %s" % (losses,))
    params = {k: v.astype(bfloat16) for k, v in trainer.params.items()}
    jax.block_until_ready(params)
    return params


# -- serve ----------------------------------------------------------------


def _kv_model(geom, dtype=None):
    from mxnet_tpu.base import bfloat16
    from mxnet_tpu.serving import TransformerKVModel

    return TransformerKVModel(
        geom.vocab, geom.seq_len, num_layers=geom.layers,
        num_heads=geom.heads, num_embed=geom.embed, use_bias=False,
        dtype=bfloat16 if dtype is None else dtype)


def _ready():
    """Wait for everything placed on the devices so far.  An engine's pool
    is zeros made on the host: its upload is asynchronous, and the first
    launch would otherwise wait for it inside a timed region."""
    import jax

    jax.block_until_ready(jax.live_arrays())


def _prompts(geom, requests):
    rng = np.random.RandomState(SEED + 1)
    return [([int(t) for t in rng.randint(0, geom.vocab, size=n)], m)
            for n, m in requests]


def _pool_blocks(geom, device, share):
    """Blocks of the paged K/V pool that fit in ``share`` of what is free
    on ``device`` now."""
    stats = device.memory_stats()
    free = stats["bytes_limit"] - stats["bytes_in_use"]
    per_block = geom.layers * 2 * BLOCK_SIZE * geom.embed * 2  # bf16
    n = int(free * share // per_block)
    say("serve: %s of %s free on the device; pool of %d blocks x %d tokens "
        "(%s)" % (_gb(free), _gb(stats["bytes_limit"]), n, BLOCK_SIZE,
                  _gb(n * per_block)))
    return n


def _drive(engine, prompts, one_at_a_time=False, timeout=600):
    """Serve ``prompts``; returns the generated token lists.  Nothing may
    fail, hang, or come back short."""
    if one_at_a_time:
        reqs = []
        for p, m in prompts:
            reqs.append(engine.submit(p, max_new_tokens=m))
            engine.run_until_idle(timeout=timeout)
    else:
        reqs = [engine.submit(p, max_new_tokens=m) for p, m in prompts]
        engine.run_until_idle(timeout=timeout)
    for r, (p, m) in zip(reqs, prompts):
        check(r.done, "request %d never finished" % r.id)
        check(r.error is None, "request %d failed: %s" % (r.id, r.error))
        check(len(r.tokens) == m, "request %d returned %d of %d tokens"
              % (r.id, len(r.tokens), m))
    return [list(r.tokens) for r in reqs]


def _aot_counters():
    from mxnet_tpu import telemetry

    reg = telemetry.registry()
    return (reg.counter("serve.aot.compiles").value,
            reg.counter("serve.aot.frozen_compiles").value)


def phase_serve(geom, params, n_blocks=None, requests=REQUESTS):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.executor import AotCache
    from mxnet_tpu.serving import ServingEngine
    from mxnet_tpu.serving import engine as engine_mod

    ctx = mx.tpu(0)
    device = ctx.jax_device()
    require_tpu_device(device, "mx.tpu(0)")
    model = _kv_model(geom)
    prompts = _prompts(geom, requests)
    if n_blocks is None:
        # the oracle engine below takes the same pool after this one is
        # dropped, so half of what is free leaves room for the programs
        n_blocks = _pool_blocks(geom, device, share=0.5)
    aot = AotCache("serve.aot")
    kw = dict(ctx=ctx, prefill_buckets=list(PREFILL_BUCKETS),
              block_size=BLOCK_SIZE, n_blocks=n_blocks, aot=aot)
    say("serve: bf16, buckets cut for the smoke to prefill %s / decode %s "
        "(defaults %s / %s; one decode program, so that batched and "
        "one-at-a-time requests meet the same rounding)"
        % (list(PREFILL_BUCKETS), list(DECODE_BUCKETS),
           engine_mod._default_prefill_buckets(geom.seq_len),
           engine_mod._default_decode_buckets(MAX_BATCH)))

    t0 = time.perf_counter()
    engine = ServingEngine(model, params, max_batch=MAX_BATCH,
                           decode_buckets=list(DECODE_BUCKETS),
                           name="smoke", **kw)
    t_build = time.perf_counter() - t0
    info = engine.warmup()
    t_warm = time.perf_counter() - t0 - t_build
    _ready()
    compiles, _ = _aot_counters()
    say("serve: engine built in %.1fs, warmup compiled %d programs in "
        "%.1fs, pool (%s cache, %d blocks) on the device %.1fs after the "
        "start" % (t_build, compiles, t_warm, info["cache"],
                   info["n_blocks"], time.perf_counter() - t0))

    t0 = time.perf_counter()
    got = _drive(engine, prompts)
    dt = time.perf_counter() - t0
    n_new = sum(len(t) for t in got)
    say("serve: %d requests (prompts %d-%d tokens) -> %d new tokens in "
        "%.2fs, %d decode steps, %d prefill chunks, peak batch %d "
        "(%.0f tokens/s, un-benchmarked)"
        % (len(got), min(len(p) for p, _ in prompts),
           max(len(p) for p, _ in prompts), n_new, dt,
           engine.stats["decode_steps"], engine.stats["prefill_chunks"],
           engine.stats["max_concurrent"], n_new / dt))
    check(all(0 <= t < geom.vocab for toks in got for t in toks),
          "a generated token is outside the vocabulary")
    check(engine.stats["completed"] == len(prompts),
          "engine completed %d of %d requests"
          % (engine.stats["completed"], len(prompts)))
    check(engine.leaked_blocks() == 0, "%d cache blocks leaked"
          % engine.leaked_blocks())
    after, frozen = _aot_counters()
    check(after == compiles and frozen == 0,
          "%d program(s) compiled after warmup" % (after - compiles + frozen))

    # the oracle of the serving tests: the same requests, one at a time,
    # through a fresh engine of the same geometry — nothing batched, nothing
    # shared between requests.  It takes this engine's programs (one
    # AotCache, frozen above) and its pool's place on the device.
    del engine
    gc.collect()
    oracle = ServingEngine(model, params, max_batch=MAX_BATCH,
                           decode_buckets=list(DECODE_BUCKETS),
                           name="oracle", **kw)
    oracle.warmup()
    _ready()
    t0 = time.perf_counter()
    want = _drive(oracle, prompts, one_at_a_time=True)
    t_oracle = time.perf_counter() - t0
    after, frozen = _aot_counters()
    check(after == compiles and frozen == 0,
          "the oracle compiled %d program(s) of its own"
          % (after - compiles + frozen))
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    check(not bad, "batched greedy tokens differ from the one-at-a-time "
          "oracle for request(s) %s" % bad)
    say("serve: greedy tokens equal the one-request-at-a-time oracle "
        "(%.2fs) for all %d requests; 0 leaked blocks; 0 compiles after "
        "warmup" % (t_oracle, len(got)))


# -- four chips -----------------------------------------------------------


def _bytes_by_device():
    import jax

    held = collections.Counter()
    for arr in jax.live_arrays():
        for shard in arr.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return held


def multichip_train(geom, devices, n_steps=3):
    """(a) dp x tp trainer, vocab-sharded fused head, against one device."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh

    net = _lm(geom, fused_head=True)
    batch_np = _lm_batch(geom)
    # plain SGD, as `__graft_entry__.dryrun_multichip`'s oracle: Adam turns
    # reduction-order noise into O(lr) drift across device counts.  The
    # step sums the gradient over a sequence, so 2e-4 already moves the
    # loss by tenths; a larger rate amplifies bf16 rounding differences
    # between the two layouts past the tolerance within three steps.
    opt = dict(lr=2e-4, optimizer="sgd", momentum=0.9)

    def run(shape, n_dev, shard_head, what):
        prev = os.environ.get("MXNET_CE_SHARD")
        os.environ["MXNET_CE_SHARD"] = "1" if shard_head else "0"
        try:
            mesh = make_mesh(shape=shape, axis_names=("data", "model"),
                             devices=list(devices[:n_dev]))
            mx.random.seed(SEED)
            trainer = _trainer(geom, net, mesh, **opt)
            batch = trainer.shard_batch(batch_np)
            kernels = require_kernels(trainer, batch, what)
            t0 = time.perf_counter()
            losses = [float(jnp.mean(trainer.step(batch)[0]))
                      for _ in range(n_steps)]
            dt = time.perf_counter() - t0
            head = trainer.params["pred_weight"]
            spread = {s.device for s in head.addressable_shards}
            rows = {s.data.shape[0] for s in head.addressable_shards}
        finally:
            if prev is None:
                os.environ.pop("MXNET_CE_SHARD", None)
            else:
                os.environ["MXNET_CE_SHARD"] = prev
        say("%s: %d Pallas kernel calls in the lowered step; %d steps in "
            "%.1fs with the compile; losses %s; head weight on %d device(s) "
            "in row blocks %s"
            % (what, kernels, n_steps, dt,
               ", ".join("%.5f" % v for v in losses), len(spread),
               sorted(rows)))
        return losses, spread, rows

    sharded, spread, rows = run((2, 2), 4, True, "4-chip train (2 data x 2 "
                                "model, vocab-sharded fused head)")
    check(len(spread) == 4 and rows == {geom.vocab // 2},
          "the head is not split in two over the model axis of four devices")
    gc.collect()
    single, _, _ = run((1, 1), 1, False,
                       "1-chip train (replicated fused head)")
    gc.collect()
    diff = max(abs(a - b) for a, b in zip(sharded, single))
    # the tolerance `__graft_entry__.dryrun_multichip` holds dp x tp to
    tol = 5e-4
    say("4-chip train: max |loss difference| to the one-device run %.2e "
        "(tolerance %.0e)" % (diff, tol))
    check(all(math.isfinite(v) for v in sharded + single),
          "a loss is not finite")
    check(diff <= tol, "dp x tp losses differ from the one-device run by "
          "%.2e > %.0e" % (diff, tol))
    check(sharded[-1] < sharded[0], "loss does not fall: %s" % (sharded,))


def _engine_kw(n_blocks):
    # greedy-only programs: the in-graph sampler costs ~25 s of compile
    # for each program, and the four-chip phases build six engines
    return dict(max_batch=4, decode_buckets=[1, 4],
                prefill_buckets=[128, 512], block_size=BLOCK_SIZE,
                n_blocks=n_blocks, sampling=False)


def multichip_sharded_engine(geom, devices, n_blocks=512):
    """(b) one engine on a 4-device sub-mesh against a one-device engine.

    In float32 (the engine's default dtype) at the highest matmul
    precision: the two engines run different programs, and in bf16
    different programs round differently — on the chip the decode programs
    of two batch buckets already disagree in 27366 of 32768 logits by a bf16
    ulp (PERF.md, PR 21) — so token equality would test luck, not the
    sharding."""
    import jax

    from mxnet_tpu.parallel.mesh import submeshes
    from mxnet_tpu.serving import ServingEngine

    model = _kv_model(geom, dtype=np.float32)
    params = model.init_params(np.random.RandomState(SEED))
    prompts = _prompts(geom, MULTICHIP_REQUESTS)
    kw = _engine_kw(n_blocks)

    before = _bytes_by_device()
    mesh = submeshes(list(devices[:4]), 4)[0]
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        engine = ServingEngine(model, params, ctx=mesh, name="sharded",
                               **kw)
        engine.warmup()
    t_warm = time.perf_counter() - t0
    foot = engine.memory_footprint()
    held = _bytes_by_device()
    grew = {d: held[d] - before[d] for d in devices[:4]}
    say("sharded engine (float32, highest matmul precision): built and "
        "warmed in %.1fs; params + K/V pool %s in total, %s on the fullest "
        "of %d devices; bytes placed per device %s"
        % (t_warm, _gb(foot["total_bytes"]), _gb(foot["per_device_bytes"]),
           foot["devices"], sorted(grew.values())))
    check(foot["devices"] == 4, "the sharded engine's arrays sit on %d "
          "device(s), not 4" % foot["devices"])
    check(foot["per_device_bytes"] < 0.5 * foot["total_bytes"],
          "no device holds less than half of the sharded engine's bytes")
    check(all(g > 0 for g in grew.values()),
          "a device of the sub-mesh holds nothing: %s" % (grew,))
    got = _drive(engine, prompts)
    check(engine.leaked_blocks() == 0, "sharded engine leaked blocks")
    del engine
    gc.collect()

    with jax.default_matmul_precision("highest"):
        single = ServingEngine(model, params, ctx=devices[0], name="single",
                               **kw)
        single.warmup()
    want = _drive(single, prompts)
    del single
    gc.collect()
    bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    check(not bad, "sharded-engine greedy tokens differ from the "
          "one-device engine's for request(s) %s" % bad)
    say("sharded engine: greedy tokens equal the one-device engine's for "
        "all %d requests" % len(got))


def multichip_router(geom, devices, n_blocks=512):
    """(c) four one-chip replicas behind the router."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.serving import ReplicaRouter

    model = _kv_model(geom)
    params = model.init_params(np.random.RandomState(SEED))
    prompts = _prompts(geom, MULTICHIP_REQUESTS)
    before = _bytes_by_device()
    mesh = make_mesh(shape=(4,), axis_names=("data",),
                     devices=list(devices[:4]))
    router = ReplicaRouter.from_mesh(model, params, mesh=mesh,
                                     devices_per_replica=1,
                                     **_engine_kw(n_blocks))
    try:
        check(len(router.engines) == 4, "router built %d replicas, not 4"
              % len(router.engines))
        t0 = time.perf_counter()
        router.warmup()
        t_warm = time.perf_counter() - t0
        held = _bytes_by_device()
        grew = {d: held[d] - before[d] for d in devices[:4]}
        check(all(g > 0 for g in grew.values()),
              "a replica's device holds nothing: %s" % (grew,))
        reqs = [router.submit(p, max_new_tokens=m) for p, m in prompts]
        router.run_until_idle(timeout=600)
        for r, (p, m) in zip(reqs, prompts):
            check(r.done and r.error is None and len(r.tokens) == m,
                  "routed request %d did not complete: %s"
                  % (r.id, r.error))
        served = [e.stats["completed"] for e in router.engines]
        leaked = sum(e.leaked_blocks() for e in router.engines)
    finally:
        router.stop()
    say("router: 4 one-chip replicas warmed in %.1fs; bytes placed per "
        "device %s; requests completed per replica %s"
        % (t_warm, sorted(grew.values()), served))
    check(all(n >= 1 for n in served) and sum(served) == len(prompts),
          "not every replica answered: %s" % (served,))
    check(leaked == 0, "%d blocks leaked across the replicas" % leaked)


def phase_multichip(geom, devices):
    say("four-chip paths at full width, depth cut to %d layers"
        % geom.layers)
    multichip_train(geom, devices)
    multichip_sharded_engine(geom, devices)
    multichip_router(geom, devices)


# -- main -----------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the paths across four chips")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    devices = phase_device(args.chips)
    cache = chip_env.enable_compile_cache()
    entries = chip_env.cache_entries(cache)
    say("compile cache: %s holds %d entries (%s)"
        % (cache, entries, "warm" if entries else "cold"))

    if args.chips == 4:
        phase_multichip(FULL._replace(layers=CUT_LAYERS), devices)
    else:
        phase_legacy()
        params = phase_train(FULL)
        gc.collect()
        phase_serve(FULL, params)

    from mxnet_tpu import engine as host_engine

    # native/*.so is built on first use and git ignores it: a checkout has
    # none, so nothing above may have reached for it
    check("mxnet_tpu._native" not in sys.modules,
          "the run loaded mxnet_tpu._native (native/libmxtpu.so)")
    say("host engine: %s (pure Python; native/libmxtpu.so never loaded)"
        % type(host_engine.get()).__name__)
    now = chip_env.cache_entries(cache)
    say("compile cache: %d entries now (%d added); %.0fs in all"
        % (now, now - entries, time.perf_counter() - t_start))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
