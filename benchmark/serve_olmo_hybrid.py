"""Cells of kind "serve_olmo_hybrid": one `ServingEngine` over a
`HybridDeltaKVModel` (gated delta-rule linear attention layers whose state
lives in a per-sequence slot, beside full multi-head layers over the paged
pool) on one chip.

The load generator, the stamping of arrivals and tokens, the window, the
settling and the sample are `serve.py`'s, by import (`Driver`, `offer`,
`settle`, `pick_sample`, `warm_requests`, `reduce_window`), so `serve_tok_s`
and `itl_p95_ms` are computed by the same lines in every serving cell; the
two numbers `correct` compares and the notes on where the gaps lie are
`serve_latent_moe.py`'s (`compared`, `describe`, `where_gaps_lie`).  What
is this model's is here: the engine's builder, the weights' decays and the
comparison (`reference/olmo_hybrid.py`).

Run as a module from the checkout's root, this is `benchmark/sweep.py` for
this kind:

    python3 -m benchmark.serve_olmo_hybrid --workload <cell> --rates 1.0 ...
"""
from __future__ import annotations

import functools
import gc
import sys
import time

import numpy as np

from . import chip, serve, traffic, weights
from .serve_latent_moe import _NO_GPT2, compared, describe, where_gaps_lie


def build_model(cfg):
    import jax.numpy as jnp
    from mxnet_tpu.serving import HybridDeltaKVModel

    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers, num_hidden_layers %d"
                         % (len(cfg["layer_types"]),
                            cfg["num_hidden_layers"]))
    if cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("linear_num_key_heads %d is not "
                         "linear_num_value_heads %d: one key head a value "
                         "head serves here" % (cfg["linear_num_key_heads"],
                                               cfg["linear_num_value_heads"]))
    if not cfg["linear_allow_neg_eigval"]:
        raise ValueError("beta in (0, 1) is not served here: the program "
                         "doubles it (linear_allow_neg_eigval)")
    return HybridDeltaKVModel(
        cfg["vocab_size"], cfg["n_positions"], cfg["layer_types"],
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
        cfg["linear_conv_kernel_dim"], cfg["intermediate_size"],
        chunk_size=cfg["linear_chunk_size"], eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["dtype"]))


@functools.lru_cache(maxsize=None)
def _decays(shapes, dtype):
    """A jitted draw of the linear layers' decay parameters, as FLA and
    Mamba-2 start them: ``A`` uniform in [1, 16] (``a_log = log A``) and
    ``dt`` log-uniform in [0.001, 0.1] (``dt_bias`` its inverse
    softplus)."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        out = {}
        for i, (name, shape) in enumerate(shapes):
            k = jax.random.fold_in(key, i)
            if name.endswith("a_log"):
                w = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0,
                                               16.0))
            else:
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
                w = dt + jnp.log(-jnp.expm1(-dt))
            out[name] = w.astype(dtype)
        return out

    return jax.jit(draw)


def make_params(cfg, seed, device):
    """The model's parameters from the seed, under the names and shapes the
    model gives: `weights.make_params` draws gains (`_gamma`) as 1 + N(0,
    0.02) and every matrix, the convolutions' taps among them, as N(0,
    init_std); the decays' `a_log` and `dt_bias` are then drawn as
    `_decays` says, from the same seed."""
    import jax

    shapes = build_model(cfg).param_shapes()
    params = weights.make_params(seed, shapes, cfg["dtype"], cfg["init_std"],
                                 device=device)
    decay = tuple((n, s) for n, s in shapes.items()
                  if n.endswith(("a_log", "dt_bias")))
    key = jax.random.fold_in(weights.fold_seed(seed), 0xDECA)
    if device is not None:
        key = jax.device_put(key, device)
    params.update(_decays(decay, cfg["dtype"])(key))
    return params


def build_engine(cfg, cell, device, params):
    from mxnet_tpu.serving import ServingEngine

    e = cfg["engine"]
    return ServingEngine(
        build_model(cfg), params, ctx=device, max_batch=e["max_batch"],
        block_size=e["block_size"], n_blocks=cell["n_blocks"],
        prefill_buckets=list(cell["prefill_buckets"]),
        decode_buckets=list(cell["decode_buckets"]), name="bench")


def set_up(run):
    """The engine, started and warm, and its driver (`serve.set_up` with
    this kind's builder)."""
    import jax

    cell, cfg, device, seed = run.cell, run.cfg, run.devices[0], run.seed
    params = make_params(cfg, seed, device)
    t0 = time.perf_counter()
    engine = build_engine(cfg, cell, device, params)
    info = engine.warmup()
    jax.block_until_ready(jax.live_arrays())
    model = engine.model
    slots = cfg["engine"]["max_batch"] + 1
    run.note("engine built and %d programs ready in %.1fs; pool %d blocks "
             "(%.2f GB) and %d state slots (%.2f GB) beside %.2f GB of "
             "weights"
             % (len(info["prefill"]) + len(info["decode"]),
                time.perf_counter() - t0, cell["n_blocks"],
                cell["n_blocks"] * model.block_bytes(
                    cfg["engine"]["block_size"]) / 1e9,
                slots, slots * model.state_slot_bytes() / 1e9,
                sum(v.nbytes for v in params.values()) / 1e9))
    del params
    engine.start()
    drv = serve.Driver(engine)
    try:
        t0 = time.perf_counter()
        warm = [drv.submit(-1, s, time.perf_counter())
                for s in serve.warm_requests(cell, cfg["vocab_size"], seed)]
        drv.wait_all(warm, 900.0)
        bad = [r for r in warm if r.req is None or r.req.error is not None
               or len(r.times) != r.max_new]
        if bad:
            raise RuntimeError("warm-up: %d of %d requests failed (%s)"
                               % (len(bad), len(warm),
                                  bad[0].error or bad[0].req.error))
    except BaseException:
        engine.stop()
        raise
    run.note("warm-up: %d requests through every bucket in %.1fs"
             % (len(warm), time.perf_counter() - t0))
    return engine, drv


def measure(run, engine, drv):
    """The ramp, the window and what follows its close (`serve.measure`
    with this kind's counters).  Returns the requests due in the window and
    those of them that were lost."""
    cell, cfg = run.cell, run.cfg
    mix = cell["traffic_mix"]
    drv.recs.clear()
    while not drv.finished.empty():
        drv.finished.get()
    specs = traffic.generate(mix, run.seed, run.seconds, cfg["vocab_size"])
    stats0, stats1 = {}, {}

    def on_open():
        stats0.update(engine.stats)

    def on_close():
        stats1.update(engine.stats)
        run.counters["queue_depth_at_close"] = engine.depth()

    window = serve.offer(run, drv, specs, mix, on_open, on_close)
    run.memory_peak = max(chip.memory_peak_bytes(run.devices),
                          chip.live_bytes(run.devices[0]))
    lost = serve.settle(drv, window, cell.get("settle_s", 60.0))
    serve.reduce_window(run, drv, window, _NO_GPT2, stats0, stats1)
    run.note("token gaps: " + where_gaps_lie(run, drv))
    return window, lost


def served_gaps(sample, seed, cfg, device, control=None, alter=None,
                q_block=128):
    """The gap by which each served token's logit lies below the reference's
    best, over the sample, one number a token; with ``control`` ("fp8" or a
    fault of `olmo_hybrid.FAULTS`), the same for the tokens that arithmetic
    puts first at the same positions, in the served tokens' place.  The
    reference reads the same bf16-rounded draw the engine served from and
    raises each weight to float32 where it is used."""
    from .reference import olmo_hybrid

    params = make_params(cfg, seed, device)
    gaps = []
    for prompt, out in sample:
        if alter is not None:
            out = alter(out)
        # every row padded to the deepest table: one shape, one compile of
        # each of the reference's layers a run
        served, other = olmo_hybrid.served_gaps(
            params, prompt, out, cfg, cfg["n_positions"], control=control,
            q_block=q_block)
        gaps.append(served if control is None else other)
    return np.concatenate(gaps) if gaps else np.zeros((0,), np.float32)


def run(run, keep_sample=None):
    """One run of a cell of this kind.  ``run`` is the harness's `Run`."""
    cell, cfg, device, seed = run.cell, run.cfg, run.devices[0], run.seed
    engine, drv = set_up(run)
    try:
        window, lost = measure(run, engine, drv)
    finally:
        engine.stop()
    run.attempted, run.failed = len(window), len(lost)
    run.checks["requests_lost"] = {"value": len(lost), "limit": 0}
    for r in lost[:3]:
        run.note("lost: request %d (prompt %d, %d of %d tokens): %s"
                 % (r.i, len(r.prompt), len(r.times), r.max_new,
                    r.error if r.req is None else r.req.error))
    finished = [r for r in drv.recs
                if r.req is not None and r.req.error is None
                and len(r.times) == r.max_new
                and run.t_open <= r.times[-1] <= run.t_close]
    sample = serve.pick_sample(finished, seed, cell["check_requests"])
    if keep_sample is not None:
        keep_sample.extend(sample)

    # -- the comparison, with the engine freed ------------------------------
    del engine, drv, window, lost, finished
    gc.collect()
    t0 = time.perf_counter()
    gaps = served_gaps(sample, seed, cfg, device)
    run.note("reference read %d served tokens of %d requests in %.1fs; "
             "their gaps: %s"
             % (gaps.size, len(sample), time.perf_counter() - t0,
                describe(gaps)))
    run.checks.update(compared(gaps, cell["limits"]))


# -- the readings the limits are set from ------------------------------------


def readings(cell, cfg, devices, args):
    """Rows for `benchmark/readings.py`: a short window at the cell's own
    load for every seed and the program's gap; for the control seeds the gap
    of the reference computed in fp8 in the program's place; for the fault
    seeds the gap with a served token altered, and with each of the
    reference's planted faults in the program's place."""
    from . import harness
    from .reference import olmo_hybrid

    for seed in args.seeds:
        t0 = time.perf_counter()
        r = harness.Run(dict(cell), cfg, seed, args.seconds, False, devices,
                        chip.peaks(devices[0], tiny=args.tiny),
                        time.perf_counter(), tiny=args.tiny)
        sample = []
        run(r, keep_sample=sample)
        row = {"seed": seed, "what": "program",
               "values": {k: c["value"] for k, c in r.checks.items()},
               "e2e": r.e2e}

        def reading(**how):
            gaps = served_gaps(sample, seed, cfg, devices[0], **how)
            return {k: c["value"]
                    for k, c in compared(gaps, cell["limits"]).items()}

        if seed in args.control_seeds:
            row["control_fp8"] = reading(control="fp8")
        if seed in args.fault_seeds:
            def alter(out):
                out = list(out)
                out[len(out) // 2] = (out[len(out) // 2] + 1) \
                    % cfg["vocab_size"]
                return out

            row["fault_token_altered"] = reading(alter=alter)
            for fault in olmo_hybrid.FAULTS:
                row["fault_" + fault] = reading(control=fault)
        row["seconds"] = time.perf_counter() - t0
        yield row


def sweep(argv=None):
    """`benchmark/sweep.py` over a cell of this kind.  The tool reaches its
    driver as `benchmark.serve`; for the length of its `main` that name is
    this module (which has the `set_up` and `measure` it calls)."""
    import benchmark
    from benchmark import sweep as tool

    benchmark.serve = sys.modules[__name__]
    try:
        return tool.main(argv)
    finally:
        benchmark.serve = serve


if __name__ == "__main__":
    sys.exit(sweep())
