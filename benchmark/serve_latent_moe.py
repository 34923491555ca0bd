"""Cells of kind "serve_latent_moe": one `ServingEngine` over a
`LatentMoEKVModel` (latent attention over a paged latent cache, a share of a
sparse expert layer) on one chip.

The load generator, the stamping of arrivals and tokens, the window, the
settling and the sample are `serve.py`'s, by import: `Driver`, `offer`,
`settle`, `pick_sample`, `warm_requests`, and `reduce_window` for the
end-to-end metrics, so `serve_tok_s` and `itl_p95_ms` are computed by the
same lines in every serving cell.  What reads GPT-2's keys there is brought
here instead: the engine's builder, the parameter shapes and the pool's
bytes (both asked of the model), the FLOPs (`flops_latent_moe`, with the
held (row, expert) pairs the engine counted) and the comparison
(`reference/kimi_k2.py`).  `serve.reduce_window` computes GPT-2's model FLOPs
from its configuration; it is handed `_NO_GPT2`, under which they are 0, and
`model_flops` is then set from this module's own count.

Run as a module from the checkout's root, this is `benchmark/sweep.py` for
this kind (that tool names `serve.set_up` and `serve.measure`; this module
has the same two):

    python3 -m benchmark.serve_latent_moe --workload <cell> --rates 0.4 ...
"""
from __future__ import annotations

import gc
import sys
import time

import numpy as np

from . import chip, flops_latent_moe, serve, traffic, weights

#: `flops.serve_flops` of this reads 0: GPT-2's count is not this model's
_NO_GPT2 = {"n_embd": 0, "n_inner": 0, "n_layer": 0, "vocab_size": 0}

#: positions the reference's rows are padded to a multiple of (few shapes)
_PAD = 4096


def build_model(cfg):
    import jax.numpy as jnp
    from mxnet_tpu.serving import LatentMoEKVModel

    return LatentMoEKVModel(
        cfg["vocab_size"], cfg["n_positions"],
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["num_attention_heads"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["n_routed_experts"], cfg["experts_held"],
        cfg["num_experts_per_tok"], first_dense=cfg["first_k_dense_replace"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"], dtype=jnp.dtype(cfg["dtype"]))


def make_params(cfg, seed, device):
    """The model's parameters from the seed, under the names and shapes the
    model gives: `weights.make_params` draws gains (`_gamma`) as 1 + N(0,
    0.02), the router's correction bias (`_bias`) as N(0, 0.02) and every
    matrix as N(0, init_std)."""
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


def pool_bytes(cfg, cell):
    return cell["n_blocks"] * build_model(cfg).block_bytes(
        cfg["engine"]["block_size"])


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
    run.note("engine built and %d programs ready in %.1fs; pool %d blocks "
             "(%.2f GB) beside %.2f GB of weights"
             % (len(info["prefill"]) + len(info["decode"]),
                time.perf_counter() - t0, cell["n_blocks"],
                pool_bytes(cfg, cell) / 1e9,
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


def model_flops(run, drv, held_pairs):
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
    return flops_latent_moe.serve_flops(run.cfg, tokens, context, held_pairs,
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
    pairs = {k: stats1[k] - stats0[k]
             for k in ("moe_pairs_held", "moe_pairs_routed")}
    run.counters.update(
        pairs, model_flops=model_flops(run, drv, pairs["moe_pairs_held"]))
    run.note("experts: %d of %d routed (row, expert) pairs fell on the "
             "experts held here" % (pairs["moe_pairs_held"],
                                    pairs["moe_pairs_routed"]))
    run.note("token gaps: " + where_gaps_lie(run, drv))
    return window, lost


def where_gaps_lie(run, drv):
    """Where the window's token gaps lie (the gaps `serve.reduce_window`
    takes `itl_p95_ms` from): whether the 95th percentile is steady is
    decided by the slope of their quantile around it, and by how many of
    them held a prefill chunk (a plain iteration is ~10 ms here, a chunk
    40-100 ms more by its prefix)."""
    gaps = np.asarray([1e3 * (t - r.times[j - 1])
                       for r in drv.recs for j, t in enumerate(r.times)
                       if j and run.t_open <= t <= run.t_close], np.float64)
    if not gaps.size:
        return "none"
    at = (50, 80, 90, 93, 94, 95, 96, 97, 98, 99)
    return ("%d; ms at %s; %s" % (
        gaps.size,
        ", ".join("p%d %.2f" % (q, v)
                  for q, v in zip(at, np.percentile(gaps, at))),
        ", ".join("%.2f %% over %d ms" % (100.0 * (gaps > ms).mean(), ms)
                  for ms in (30, 60, 90, 120, 150))))


def served_gaps(sample, seed, cfg, device, control=None, alter=None,
                q_block=256):
    """The gap by which each served token's logit lies below the reference's
    best, over the sample, one number a token; with ``control`` ("fp8" or a
    fault of `kimi_k2.FAULTS`), the same for the tokens that arithmetic puts
    first at the same positions, in the served tokens' place.  The reference
    reads the same bf16-rounded draw the engine served from and raises each
    weight to float32 where it is used."""
    from .reference import kimi_k2

    params = make_params(cfg, seed, device)
    gaps = []
    for prompt, out in sample:
        if alter is not None:
            out = alter(out)
        pad_to = -(-(len(prompt) + len(out)) // _PAD) * _PAD
        served, other = kimi_k2.served_gaps(
            params, prompt, out, cfg, min(pad_to, cfg["n_positions"]),
            control=control, q_block=q_block)
        gaps.append(served if control is None else other)
    return np.concatenate(gaps) if gaps else np.zeros((0,), np.float32)


def compared(gaps, limits):
    """The two numbers `correct` compares, beside their limits.  A served
    token's gap is 0 wherever the program and the reference agree on the
    best token.  The widest gap catches a single wrong token; but a bf16
    activation can flip a near-tie at the router's cut, which moves that one
    position's logits as much as a dropped expert moves every position's, so
    the widest sound gap lies near the faults' and it is the MEAN gap that
    tells a program computed in too low a precision, or with part of the
    mathematics left out, from a sound one."""
    return {
        "served_tokens_compared": {
            "value": int(gaps.size), "limit": limits["min_tokens_compared"],
            "at_least": True},
        "served_logit_gap": {
            "value": float(gaps.max()) if gaps.size else 0.0,
            "limit": limits["served_logit_gap"]},
        "served_logit_gap_mean": {
            "value": float(gaps.mean()) if gaps.size else 0.0,
            "limit": limits["served_logit_gap_mean"]}}


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


def describe(gaps):
    """Where a run's gaps lie, for the notes the limits are judged from."""
    if not gaps.size:
        return "none"
    q = np.percentile(gaps, [50, 90, 99])
    return ("mean %.4f, p50 %.4f, p90 %.4f, p99 %.4f, max %.4f, %d over 0.1"
            % (gaps.mean(), q[0], q[1], q[2], gaps.max(),
               int((gaps > 0.1).sum())))


# -- the readings the limits are set from ------------------------------------


def readings(cell, cfg, devices, args):
    """Rows for `benchmark/readings.py`: a short window at the cell's own
    load for every seed and the program's gap; for the control seeds the gap
    of the reference computed in fp8 in the program's place; for the fault
    seeds the gap with a served token altered, and with each of the
    reference's planted faults in the program's place."""
    from . import harness
    from .reference import kimi_k2

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
            for fault in kimi_k2.FAULTS:
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
