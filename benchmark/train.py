"""Cells of kind "train": `SPMDTrainer.step()` on a one-device mesh.

Set-up builds ONE trainer, gives it seeded parameters made on the device,
drives it through its first steps (which also warm the one program the window
uses) while keeping what the comparison needs, and hands that same object to
the window.  After the window the trainer is freed and the plain reference
follows the same first steps from the same seed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from . import chip, flops, weights


def _build_trainer(cfg, cell, device):
    """The trainer as `chip_smoke._trainer` builds it, at the configuration's
    arguments — but ``abstract=True``: the constructor then places nothing
    (no host-side initialiser at ~0.25 GB/s to the chip, no host zeros
    uploaded) and leaves `params`, `momenta` and `aux` as shapes that carry
    their shardings.  Returns the trainer and that layout, which
    `_fill_state` fills with arrays made on the device."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    t = cfg["trainer"]
    rows, seq = cell["batch_rows"], cell["seq_len"]
    net = models.get_transformer_lm(
        vocab_size=cfg["vocab_size"], seq_len=seq,
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        num_embed=cfg["n_embd"], num_ffn_hidden=cfg["n_inner"],
        use_bias=t["use_bias"], fused_head=t["fused_head"],
        attn_layout=t["attn_layout"])
    mesh = make_mesh(shape=(1,), axis_names=("data",), devices=[device])
    mx.random.seed(20260930)
    shape = (rows, seq)
    trainer = SPMDTrainer(
        net, mesh, data_shapes={"data": shape, "softmax_label": shape},
        lr=t["lr"], optimizer=t["optimizer"], wd=t["wd"], beta1=t["beta1"],
        beta2=t["beta2"], epsilon=t["epsilon"],
        adam_v_dtype=t["adam_v_dtype"], dtype=jnp.dtype(cfg["dtype"]),
        abstract=True)
    like = {"params": dict(trainer.params), "momenta": dict(trainer.momenta)}
    # what `correct` reads of the optimizer's state: Adam's first moment is
    # the first of each parameter's pair (PERF.md, section 7)
    odd = [n for n in trainer.param_names
           if not (isinstance(like["momenta"].get(n), tuple)
                   and len(like["momenta"][n]) == 2)]
    if odd or trainer.aux:
        raise RuntimeError(
            "the trainer's state is not {name: (m, v)} with no auxiliary "
            "states, which the comparison reads: %s %s"
            % (odd[:4], sorted(trainer.aux)[:4]))
    return trainer, like


def _fill_state(trainer, like, params):
    """Seeded parameters and zero optimizer state, all made on the device,
    in the shapes, types and shardings the constructor laid out."""
    import jax
    import jax.numpy as jnp

    want = {n: tuple(s.shape) for n, s in like["params"].items()}
    have = {n: tuple(v.shape) for n, v in params.items()}
    if want != have:
        diff = sorted(set(want.items()) ^ set(have.items()))[:6]
        raise RuntimeError("the trainer's parameters are not the "
                           "configuration's: %s" % (diff,))
    tree_map = jax.tree_util.tree_map
    zeros = jax.jit(
        lambda: tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                         like["momenta"]),
        out_shardings=tree_map(lambda s: s.sharding, like["momenta"]))
    trainer.params = {n: jax.device_put(params[n], s.sharding)
                      for n, s in like["params"].items()}
    trainer.momenta = zeros()


def _leaf_programs(names):
    import jax
    import jax.numpy as jnp

    def norm(a):
        return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))

    @jax.jit
    def first_moment_norms(momenta):
        return jnp.stack([norm(momenta[n][0]) for n in names])

    @jax.jit
    def change_norms(now, start):
        return jnp.stack([norm(now[n] - start[n]) for n in names])

    @jax.jit
    def mean(x):
        return jnp.mean(x.astype(jnp.float32))

    return first_moment_norms, change_norms, mean


def worst_leaf_gap(prog, ref, keep=None):
    """The worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  Returns (gap, index)."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = float(np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(ref, floor)
    if keep is not None:
        gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def compare(prog, ref, limits):
    """The numbers that decide `correct`, each beside its limit.

    Leaves whose gradient is nought to rounding in the reference (under a
    thousandth of the median leaf's) move under Adam by round-off alone and
    are left out of the change."""
    order = [ref["names"].index(n) for n in prog["names"]]
    ref_grad = np.asarray(ref["grad_norms"])[order]
    ref_change = np.asarray(ref["change_norms"])[order]
    moved = ref_grad >= 1e-3 * float(np.median(ref_grad))
    checks = {}
    for i, (a, b) in enumerate(zip(prog["losses"], ref["losses"])):
        # a step's loss is compared where the cell's file gives it a limit:
        # only a number with an upper reading has one (PERF.md, section 6)
        name = "loss%d_gap" % (i + 1)
        checks[name] = {"value": abs(a - b) / abs(b),
                        "limit": limits.get(name), "program": a,
                        "reference": b}
    g, gi = worst_leaf_gap(prog["grad_norms"], ref_grad)
    checks["grad_norm_gap"] = {"value": g, "limit": limits["grad_norm_gap"],
                               "leaf": prog["names"][gi]}
    c, ci = worst_leaf_gap(prog["change_norms"], ref_change, keep=moved)
    checks["change_norm_gap"] = {"value": c,
                                 "limit": limits["change_norm_gap"],
                                 "leaf": prog["names"][ci],
                                 "leaves_left_out": int((~moved).sum())}
    return checks


def compared(checks):
    """(the checks that have a limit, the readings that have none)."""
    held = {k: c for k, c in checks.items() if c["limit"] is not None}
    rest = {k: c["value"] for k, c in checks.items() if c["limit"] is None}
    return held, rest


def program_readings(trainer, ring, start_params, cfg, cell):
    """Drive the trainer through the first steps by the window's own call
    and feed, keeping each step's loss, the first gradient's per-leaf norm
    as the optimizer got it (from Adam's first moment after one step:
    m1 = (1 - beta1) g) and the per-leaf norm of the parameters' change."""
    names = list(trainer.param_names)
    first_moment_norms, change_norms, mean = _leaf_programs(names)
    b1 = cfg["trainer"]["beta1"]
    losses, grad_norms = [], None
    for i in range(cell["check_steps"]):
        outs = trainer.step(ring[i % len(ring)])
        losses.append(float(mean(outs[0])))
        if i == 0:
            grad_norms = np.asarray(first_moment_norms(trainer.momenta)) \
                / (1.0 - b1)
    change = np.asarray(change_norms(trainer.params, start_params()))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "names": names}, mean


def reference_readings(seed, cfg, cell, device, mode="f32"):
    from .reference import gpt2

    shapes = weights.lm_param_shapes(cfg)
    params = weights.make_params(seed, shapes, "float32", cfg["init_std"],
                                   device=device)
    n = cell["check_steps"]
    tokens, labels = weights.make_batches(
        seed, cell["ring_batches"], cell["batch_rows"], cell["seq_len"],
        cfg["vocab_size"], device=device)
    opt = cfg["trainer"]
    return gpt2.train_readings(
        params, (tokens[:n], labels[:n]), cfg, opt, steps=n,
        micro_rows=cell["reference_micro_rows"], mode=mode)


def make_ring(trainer, seed, cfg, cell, device):
    import jax

    tokens, labels = weights.make_batches(
        seed, cell["ring_batches"], cell["batch_rows"], cell["seq_len"],
        cfg["vocab_size"], device=device)
    ring = [trainer.shard_batch({"data": tokens[i],
                                 "softmax_label": labels[i]})
            for i in range(cell["ring_batches"])]
    jax.block_until_ready(ring)
    return ring


def run(run):
    """One run of a training cell.  ``run`` is the harness's `Run`."""
    import jax

    cell, cfg, device = run.cell, run.cfg, run.devices[0]
    seed = run.seed
    shapes = weights.lm_param_shapes(cfg)

    run.note("imports done")
    trainer, like = _build_trainer(cfg, cell, device)

    def start_params():
        return weights.make_params(seed, shapes, "float32", cfg["init_std"],
                                   device=device)

    _fill_state(trainer, like, start_params())
    ring = make_ring(trainer, seed, cfg, cell, device)
    jax.block_until_ready(ring)
    run.note("trainer built; weights, Adam state and %d batches made on the "
             "device" % len(ring))
    prog, mean = program_readings(trainer, ring, start_params, cfg, cell)
    jax.block_until_ready(trainer.params)
    run.note("first %d steps driven and read" % cell["check_steps"])
    # the step `step()` has just compiled, asked for its memory: the trace
    # and the executable are in jit's caches by now, so this costs nothing
    compiled = trainer._step.lower(
        trainer.params, trainer.momenta, trainer.aux,
        trainer.shard_batch(ring[0]), jax.random.PRNGKey(0),
        jax.numpy.float32(trainer.lr)).compile()
    mem = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    run.note("compiled step: %d Pallas kernel calls; arguments %.2f GB, "
             "temporaries %.2f GB, outputs %.2f GB, aliased %.2f GB"
             % (kernels, mem.argument_size_in_bytes / 1e9,
                mem.temp_size_in_bytes / 1e9, mem.output_size_in_bytes / 1e9,
                mem.alias_size_in_bytes / 1e9))
    if not run.tiny and not kernels:
        raise RuntimeError("the compiled step holds no Pallas kernel: the "
                           "kernel gates chose the jax.numpy bodies")
    del compiled
    program_bytes = (chip.live_bytes(device) + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)

    # -- the window ------------------------------------------------------
    every = cell["loss_every"]
    tokens_per_step = cell["batch_rows"] * cell["seq_len"]
    steps, bad, fetched = 0, 0, []
    run.open_window()
    while time.perf_counter() < run.deadline:
        outs = trainer.step(ring[(cell["check_steps"] + steps) % len(ring)])
        steps += 1
        if steps % every == 0:
            loss = float(mean(outs[0]))
            fetched.append(loss)
            bad += not np.isfinite(loss)
        run.tick()
    run.close_window(sync=lambda: jax.block_until_ready(trainer.params))

    run.attempted, run.failed = steps, bad
    run.counters.update(
        steps=steps, tokens=steps * tokens_per_step,
        tokens_per_step=tokens_per_step, losses=fetched,
        flops_per_token=flops.train_flops_token(cfg, cell["seq_len"]))
    run.e2e["train_tok_s"] = steps * tokens_per_step / run.window_s
    run.memory_peak = chip.memory_peak_bytes(run.devices, program_bytes)

    # -- the comparison, with the program's state freed ---------------------
    del trainer, ring, outs
    gc.collect()
    t0 = time.perf_counter()
    ref = reference_readings(seed, cfg, cell, device)
    run.note("reference followed %d steps in %.1fs"
             % (cell["check_steps"], time.perf_counter() - t0))
    held, rest = compared(compare(prog, ref, cell["limits"]))
    run.checks.update(held)
    run.note("read and not compared: %s"
             % ", ".join("%s = %.3g" % kv for kv in rest.items()))


# -- the readings the limits are set from ------------------------------------


def _values(checks):
    return {k: c["value"] for k, c in checks.items()}


def _halved(ring):
    """The fault "half of the batch left out, the mean taken over the rest":
    the second half of every batch repeats the first."""
    import jax.numpy as jnp

    def dup(a):
        h = a.shape[0] // 2
        return jnp.concatenate([a[:h], a[:h]], axis=0)

    return [{k: dup(v) for k, v in b.items()} for b in ring]


def readings(cell, cfg, devices, args):
    """Rows for `benchmark/readings.py`: one trainer, many seeds."""
    import jax

    device = devices[0]
    shapes = weights.lm_param_shapes(cfg)
    trainer, like = _build_trainer(cfg, cell, device)
    limits = cell["limits"]

    def program(seed, fault=None):
        def start_params():
            return weights.make_params(seed, shapes, "float32",
                                       cfg["init_std"], device=device)

        _fill_state(trainer, like, start_params())
        ring = make_ring(trainer, seed, cfg, cell, device)
        if fault == "half_batch":
            ring = _halved(ring)
        prog, _ = program_readings(trainer, ring, start_params, cfg, cell)
        jax.block_until_ready(trainer.params)
        trainer.params = trainer.momenta = None
        del ring
        gc.collect()
        return prog

    for seed in args.seeds:
        t0 = time.perf_counter()
        prog = program(seed)
        ref = reference_readings(seed, cfg, cell, device)
        row = {"seed": seed, "what": "program",
               "values": _values(compare(prog, ref, limits)),
               "losses": prog["losses"], "ref_losses": ref["losses"]}
        if seed in args.control_seeds:
            ctrl = reference_readings(seed, cfg, cell, device, mode="fp8")
            ctrl["names"] = ref["names"]
            row["control_fp8"] = _values(compare(ctrl, ref, limits))
        if seed in args.fault_seeds:
            bad = program(seed, fault="half_batch")
            row["fault_half_batch"] = _values(compare(bad, ref, limits))
        row["seconds"] = time.perf_counter() - t0
        yield row
