"""Cells of kind "serve_lfm2_moe": one `ServingEngine` over a
`ShortConvMoEKVModel` (gated short convolutions with a per-sequence state
beside a grouped-query paged K/V pool, a sparse expert layer with every
expert held here) on one chip.

The load generator, the stamping of arrivals and tokens, the window, the
settling and the sample are `serve.py`'s, by import (`Driver`, `offer`,
`settle`, `pick_sample`, `warm_requests`, `reduce_window`), so `serve_tok_s`
and `itl_p95_ms` are computed by the same lines in every serving cell; the
two numbers `correct` compares and the notes on where the gaps lie are
`serve_latent_moe.py`'s (`compared`, `describe`, `where_gaps_lie`).  What
is this model's is here: the engine's builder, the FLOPs (`flops_lfm2_moe`,
with the (row, expert) pairs the engine counted) and the comparison
(`reference/lfm2_moe.py`).

Run as a module from the checkout's root, this is `benchmark/sweep.py` for
this kind:

    python3 -m benchmark.serve_lfm2_moe --workload <cell> --rates 4 5 6 ...
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from . import chip, flops_lfm2_moe, serve, traffic, weights
from .serve_latent_moe import _NO_GPT2, compared, describe, where_gaps_lie

#: positions the reference's rows are padded to a multiple of (few shapes)
_PAD = 1536


def build_model(cfg):
    import jax.numpy as jnp
    from mxnet_tpu.serving import ShortConvMoEKVModel

    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers, num_hidden_layers %d"
                         % (len(cfg["layer_types"]),
                            cfg["num_hidden_layers"]))
    return ShortConvMoEKVModel(
        cfg["vocab_size"], cfg["n_positions"], cfg["layer_types"],
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["conv_L_cache"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["num_experts"], cfg["num_experts_per_tok"],
        num_dense_layers=cfg["num_dense_layers"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        eps=cfg["norm_eps"], rope_theta=cfg["rope_theta"],
        dtype=jnp.dtype(cfg["dtype"]))


def make_params(cfg, seed, device):
    """The model's parameters from the seed, under the names and shapes the
    model gives: `weights.make_params` draws gains (`_gamma`) as 1 + N(0,
    0.02), the router's selection bias (`_bias`) as N(0, 0.02) and every
    matrix, the convolutions' taps among them, as N(0, init_std).  The head
    is the embedding: no `pred_weight` is drawn."""
    return weights.make_params(seed, build_model(cfg).param_shapes(),
                               cfg["dtype"], cfg["init_std"], device=device)


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
    run.note("engine built and %d programs ready in %.1fs; pool %d blocks "
             "(%.2f GB) and %d state slots (%.1f MB) beside %.2f GB of "
             "weights"
             % (len(info["prefill"]) + len(info["decode"]),
                time.perf_counter() - t0, cell["n_blocks"],
                cell["n_blocks"] * model.block_bytes(
                    cfg["engine"]["block_size"]) / 1e9,
                cfg["engine"]["max_batch"] + 1,
                (cfg["engine"]["max_batch"] + 1)
                * model.state_slot_bytes() / 1e6,
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


def model_flops(run, drv, pairs):
    """Forward FLOPs of the tokens delivered in the window, as
    `serve.reduce_window` charges them: a prompt whole at the instant of its
    first token, a decode token at its own."""
    t0, t1 = run.t_open, run.t_close
    tokens = context = head_rows = 0
    for r in drv.recs:
        p = len(r.prompt)
        for j, t in enumerate(r.times):
            if not t0 <= t <= t1:
                continue
            head_rows += 1
            if j == 0:
                tokens += p
                context += p * (p + 1) // 2
            else:
                tokens += 1
                context += p + j
    return flops_lfm2_moe.serve_flops(run.cfg, tokens, context, pairs,
                                      head_rows)


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
    pairs = stats1["moe_pairs_held"] - stats0["moe_pairs_held"]
    run.counters.update(moe_pairs=pairs,
                        model_flops=model_flops(run, drv, pairs))
    run.note("experts: %d (row, expert) pairs routed, all to experts held "
             "here" % pairs)
    run.note("token gaps: " + where_gaps_lie(run, drv))
    return window, lost


def served_gaps(sample, seed, cfg, device, control=None, alter=None,
                chunk=None, q_block=256):
    """The gap by which each served token's logit lies below the reference's
    best, over the sample, one number a token; with ``control`` ("fp8" or a
    fault of `lfm2_moe.FAULTS`), the same for the tokens that arithmetic
    puts first at the same positions, in the served tokens' place.  The
    reference reads the same bf16-rounded draw the engine served from and
    raises each weight to float32 where it is used."""
    from .reference import lfm2_moe

    params = make_params(cfg, seed, device)
    gaps = []
    for prompt, out in sample:
        if alter is not None:
            out = alter(out)
        pad_to = -(-(len(prompt) + len(out)) // _PAD) * _PAD
        served, other = lfm2_moe.served_gaps(
            params, prompt, out, cfg, min(pad_to, cfg["n_positions"]),
            control=control, q_block=q_block, chunk=chunk)
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
    from .reference import lfm2_moe

    chunk = max(cell["prefill_buckets"])
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
            gaps = served_gaps(sample, seed, cfg, devices[0], chunk=chunk,
                               **how)
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
            for fault in lfm2_moe.FAULTS:
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
