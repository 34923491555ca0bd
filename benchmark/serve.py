"""Cells of kind "serve": one `ServingEngine` on one chip, driven through
`submit()` while its own scheduler thread (`engine.start()`) runs.

Set-up makes the weights on the device from the seed, builds the engine as
`chip_smoke.py` builds it (paged pool, the cell's buckets), really launches
every program the traffic can reach, and runs the mix's ramp.  The window
offers the mix's load from this one thread: on a schedule (open loop) or as
each client's reply comes (closed loop).  Arrivals and tokens are stamped
here, by the host's clock, not by the engine.  After the window the engine is
freed and the plain reference reads a seeded sample of the finished requests.
"""
from __future__ import annotations

import gc
import queue
import time

import numpy as np

from . import chip, flops, traffic, weights


class Rec:
    """One request as the benchmark saw it."""

    __slots__ = ("i", "due", "t_sub", "prompt", "max_new", "times", "req",
                 "error", "client")

    def __init__(self, i, due, spec, client=None):
        self.i, self.due, self.client = i, due, client
        self.prompt, self.max_new = spec["prompt"], spec["max_new"]
        self.times, self.req, self.error, self.t_sub = [], None, None, None


def build_engine(cfg, cell, device, params):
    from mxnet_tpu.serving import ServingEngine, TransformerKVModel
    import jax.numpy as jnp

    e = cfg["engine"]
    model = TransformerKVModel(
        cfg["vocab_size"], cfg["n_positions"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], num_embed=cfg["n_embd"],
        num_ffn_hidden=cfg["n_inner"], use_bias=e["use_bias"],
        eps=cfg["layer_norm_epsilon"], dtype=jnp.dtype(cfg["dtype"]))
    want = {n: tuple(s) for n, s in model.param_shapes().items()}
    have = {n: tuple(v.shape) for n, v in params.items()}
    if want != have:
        raise RuntimeError("the engine's parameters are not the "
                           "configuration's: %s"
                           % (sorted(set(want.items())
                                     ^ set(have.items()))[:6],))
    return ServingEngine(
        model, params, ctx=device, max_batch=e["max_batch"],
        block_size=e["block_size"], n_blocks=cell["n_blocks"],
        prefill_buckets=list(cell["prefill_buckets"]),
        decode_buckets=list(cell["decode_buckets"]), name="bench")


def pool_bytes(cfg, cell):
    itemsize = 2 if cfg["dtype"] == "bfloat16" else 4
    return (cfg["n_layer"] * 2 * cell["n_blocks"] * cfg["engine"]["block_size"]
            * cfg["n_embd"] * itemsize)


def warm_requests(cell, vocab, seed):
    """Requests that really launch every program of the cell: a prompt of
    each prefill bucket's length (and one of the mix's longest, for the
    chunked path), and output lengths under which the active set falls
    through every decode bucket."""
    rng = traffic.seed_rng(seed, 0x3A93)
    lens = list(cell["prefill_buckets"])
    longest = cell["traffic_mix"]["prompt_len"].get(
        "max", cell["traffic_mix"]["prompt_len"].get("value"))
    n = max(cell["decode_buckets"])
    out = []
    for i in range(n):
        length = longest if i == 0 else lens[i % len(lens)]
        out.append({"prompt": rng.integers(0, vocab, size=int(length))
                    .astype(np.int32).tolist(),
                    "max_new": 2 + int(np.log2(n / (i + 1.0)))})
    return out


class Driver:
    """Submits, stamps and collects.  One instance per run."""

    def __init__(self, engine):
        self.engine = engine
        self.finished = queue.SimpleQueue()
        self.recs = []

    def submit(self, i, spec, due, client=None):
        rec = Rec(i, due, spec, client)

        def on_token(_tok, rec=rec, put=self.finished.put):
            rec.times.append(time.perf_counter())
            if len(rec.times) == rec.max_new:
                put(rec)

        rec.t_sub = time.perf_counter()
        try:
            rec.req = self.engine.submit(rec.prompt,
                                         max_new_tokens=rec.max_new,
                                         on_token=on_token)
        except Exception as e:  # noqa: BLE001 - a refusal is a failed request
            rec.error = e
            if client is not None:
                self.finished.put(rec)
        self.recs.append(rec)
        return rec

    def wait_all(self, recs, timeout):
        """Wait until each has ended, well or badly."""
        from mxnet_tpu.base import MXNetError

        deadline = time.perf_counter() + timeout
        for r in recs:
            if r.req is None:
                continue
            try:
                r.req.result(max(0.001, deadline - time.perf_counter()))
            except MXNetError:
                pass


def offer(run, drv, specs, mix, on_open, on_close):
    """The ramp and the window.  Returns the requests due in the window."""
    arr = mix["arrivals"]
    ramp = float(arr.get("ramp_s", 0.0))
    t_start = time.perf_counter()
    t_open = t_start + ramp
    opened = False
    nxt = 0

    def open_if_due(now):
        nonlocal opened, t_open
        if not opened and now >= t_open:
            on_open()
            run.open_window()
            t_open, opened = run.t_open, True

    # a closed loop's clients start one by one, evenly over the ramp: started
    # together they stay in lockstep (all prefill, then all decode) for ever
    starts = []
    if arr["process"] == "closed":
        n = arr["clients"]
        starts = [t_start + ramp * c / n for c in range(n)]
    while True:
        now = time.perf_counter()
        if starts and starts[0] <= now:
            starts.pop(0)
            drv.submit(nxt, specs[nxt % len(specs)], now,
                       arr["clients"] - len(starts) - 1)
            nxt += 1
            continue
        open_if_due(now)
        if opened:
            run.tick()
            if now >= run.deadline:
                break
        limit = run.deadline if opened else t_open
        if arr["process"] == "poisson":
            due = t_open + specs[nxt]["due"] if nxt < len(specs) else None
            if due is not None and due <= now:
                drv.submit(nxt, specs[nxt], due)
                nxt += 1
                continue
            wake = limit if due is None else min(limit, due)
            time.sleep(max(0.0, min(wake - now, 0.05)))
        else:
            wake = min([limit] + starts[:1])
            try:
                rec = drv.finished.get(
                    timeout=max(0.0, min(wake - now, 0.05)))
            except queue.Empty:
                continue
            now = time.perf_counter()
            drv.submit(nxt, specs[nxt % len(specs)], now, rec.client)
            nxt += 1
    run.close_window(at_close=on_close)
    t_close = run.t_close
    window = [r for r in drv.recs if run.t_open <= r.due < t_close]
    return window


def p95(values):
    return float(np.percentile(np.asarray(values, np.float64), 95))


def reduce_window(run, drv, window, cfg, stats0, stats1):
    """End-to-end metrics and the counters the per-layer readers read."""
    t0, t1 = run.t_open, run.t_close
    tokens_out = 0
    gaps = []
    prompt_tokens = prompt_ctx = decode_tokens = decode_ctx = 0
    for r in drv.recs:
        p = len(r.prompt)
        for j, t in enumerate(r.times):
            if not t0 <= t <= t1:
                continue
            tokens_out += 1
            if j == 0:
                # the whole prompt is charged to the instant it produced
                # its first token
                prompt_tokens += p
                prompt_ctx += p * (p + 1) // 2
            else:
                decode_tokens += 1
                decode_ctx += p + j
                gaps.append(t - r.times[j - 1])
    run.e2e["serve_tok_s"] = tokens_out / run.window_s
    if gaps:
        run.e2e["itl_p95_ms"] = 1e3 * p95(gaps)
    give_up = time.perf_counter()
    ttft = [((r.times[0] if r.times else give_up) - r.due) for r in window]
    late = [r.t_sub - r.due for r in window]
    d = {k: stats1[k] - stats0[k] for k in
         ("decode_steps", "decode_rows", "decode_padded", "prefill_chunks",
          "prefill_tokens", "host_s", "wall_s", "fetch_wait_s",
          "preemptions", "alloc_denied")}
    run.counters.update(
        d, tokens_out=tokens_out, ttft_n=len(ttft), itl_n=len(gaps),
        loadgen_late_s=late, ttft_s=ttft,
        model_flops=flops.serve_flops(cfg, prompt_tokens, prompt_ctx,
                                      decode_tokens, decode_ctx, tokens_out))
    run.note("window %.2fs: %d requests due, %d tokens out, %d token gaps; "
             "%d decode launches of %.1f rows, %d prefill chunks; host %.2fs "
             "exposed, %.2fs waiting for the device; %d preemptions"
             % (run.window_s, len(window), tokens_out, len(gaps),
                d["decode_steps"],
                d["decode_rows"] / max(1, d["decode_steps"]),
                d["prefill_chunks"], d["host_s"], d["fetch_wait_s"],
                d["preemptions"]))


def settle(drv, window, wait_s):
    """After the close: wait for the first token of every request that was
    due in the window (its latency counts the wait), then cut what still
    runs.  Returns the requests that never answered."""
    from mxnet_tpu.serving.engine import ServeCancelled

    deadline = time.perf_counter() + wait_s
    for r in window:
        while r.req is not None and not r.times and not r.req.done \
                and time.perf_counter() < deadline:
            time.sleep(0.005)
    cut = [r for r in drv.recs if r.req is not None and not r.req.done]
    for r in cut:
        r.req.cancel()
    drv.wait_all(cut, 30.0)
    lost = []
    for r in window:
        err = r.error if r.req is None else r.req.error
        if isinstance(err, ServeCancelled) and r.times:
            err = None      # cut by the harness after its first token
        if err is not None or not r.times:
            lost.append(r)
    return lost


def pick_sample(finished, seed, n):
    """A seeded sample of the finished requests, the longest in it."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r.prompt) + len(r.times))
    rest = [r for r in finished if r is not longest]
    rng = traffic.seed_rng(seed, 0x5A3F1E)
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    # plain lists: a request object keeps its engine, and so the pool, alive
    return [(list(r.prompt), [int(t) for t in r.req.tokens])
            for r in [longest] + [rest[i] for i in take]]


def served_gap(sample, seed, cfg, device, control=None, alter=None):
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sample; with ``control``, also the widest
    for the tokens that arithmetic puts first at the same positions."""
    from .reference import gpt2

    shapes = weights.lm_param_shapes(cfg)
    params = weights.make_params(seed, shapes, cfg["dtype"], cfg["init_std"],
                                 device=device)
    tree = gpt2.stack(params, cfg["n_layer"])
    del params
    worst, worst_c, n_tokens = 0.0, 0.0, 0
    for prompt, out in sample:
        if alter is not None:
            out = alter(out)
        g, c = gpt2.served_gaps(tree, prompt, out, cfg,
                                cfg["n_positions"], control=control)
        worst, worst_c = max(worst, g), max(worst_c, c)
        n_tokens += len(out)
    return worst, worst_c, n_tokens


def set_up(run):
    """The engine, started and warm, and its driver."""
    import jax

    cell, cfg, device, seed = run.cell, run.cfg, run.devices[0], run.seed
    shapes = weights.lm_param_shapes(cfg)
    params = weights.make_params(seed, shapes, cfg["dtype"], cfg["init_std"],
                                 device=device)
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
    drv = Driver(engine)
    try:
        t0 = time.perf_counter()
        warm = [drv.submit(-1, s, time.perf_counter())
                for s in warm_requests(cell, cfg["vocab_size"], seed)]
        drv.wait_all(warm, 600.0)
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
    """The ramp, the window and what follows its close.  Returns the
    requests due in the window and those of them that were lost."""
    cell, cfg = run.cell, run.cfg
    mix = cell["traffic_mix"]
    drv.recs.clear()
    while not drv.finished.empty():
        drv.finished.get()
    specs = traffic.generate(mix, run.seed, run.seconds, cfg["vocab_size"])
    stats0, stats1 = {}, {}

    def on_open():
        # the engine's counters are read, never written, and differenced
        # over the window
        stats0.update(engine.stats)

    def on_close():
        stats1.update(engine.stats)
        run.counters["queue_depth_at_close"] = engine.depth()

    window = offer(run, drv, specs, mix, on_open, on_close)
    run.memory_peak = max(chip.memory_peak_bytes(run.devices),
                          chip.live_bytes(run.devices[0]))
    lost = settle(drv, window, cell.get("settle_s", 60.0))
    reduce_window(run, drv, window, cfg, stats0, stats1)
    return window, lost


def run(run, keep_sample=None):
    """One run of a serving cell.  ``run`` is the harness's `Run`."""
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
    sample = pick_sample(finished, seed, cell["check_requests"])
    if keep_sample is not None:
        keep_sample.extend(sample)

    # -- the comparison, with the engine freed ------------------------------
    del engine, drv, window, lost, finished
    gc.collect()
    t0 = time.perf_counter()
    gap, _, n_tokens = served_gap(sample, seed, cfg, device)
    run.note("reference read %d served tokens of %d requests in %.1fs"
             % (n_tokens, len(sample), time.perf_counter() - t0))
    run.checks["served_tokens_compared"] = {
        "value": n_tokens, "limit": cell["limits"]["min_tokens_compared"],
        "at_least": True}
    run.checks["served_logit_gap"] = {
        "value": gap, "limit": cell["limits"]["served_logit_gap"]}


# -- the readings the limits are set from ------------------------------------


def readings(cell, cfg, devices, args):
    """Rows for `benchmark/readings.py`: a short window at the cell's own
    load for every seed, the program's gap and, for the control seeds, the
    gap of the reference computed in fp8 in the program's place."""
    from . import harness

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
        if seed in args.control_seeds:
            _, ctrl, n = served_gap(sample, seed, cfg, devices[0],
                                    control="fp8")
            row["control_fp8"] = {"served_logit_gap": ctrl, "tokens": n}
        if seed in args.fault_seeds:
            def alter(out):
                out = list(out)
                out[len(out) // 2] = (out[len(out) // 2] + 1) \
                    % cfg["vocab_size"]
                return out

            bad, _, _ = served_gap(sample, seed, cfg, devices[0],
                                   alter=alter)
            row["fault_token_altered"] = {"served_logit_gap": bad}
        row["seconds"] = time.perf_counter() - t0
        yield row
