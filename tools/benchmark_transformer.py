#!/usr/bin/env python
"""Transformer-LM training MFU on one chip.

The ResNet-50 north star is HBM-bound at ~30% MFU on v5e
(docs/mfu_roofline.md); transformers are where TPU MFU headroom actually
lives — matmul-dominated, flash attention (ops/pallas_kernels) keeping the
sequence dimension out of HBM.  This benchmark trains the decoder-only LM
from models/transformer.py with the fused SPMD step and reports tokens/sec
and MFU.

MFU accounting (2 ops per MAC, PaLM convention): per token
  6 * n_params_active  (fwd+bwd matmul flops, params minus embeddings)
+ 12 * L * H * S       (attention scores+values, causal halves it)
Prints ONE JSON line.

Env: TBENCH_LAYERS/EMBED/HEADS/SEQ/BATCH/STEPS/DTYPE.  The peak comes
from `tools/chip_env.py`, keyed by the device found.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_env  # noqa: E402  (tools/ is on the path: script dir, or bench.py)


DEFAULT_HEADS = 12  # GPT-2-small parity; bench.py reads this for dedupe


def run():
    """Measure and return the result dict (importable by bench.py: one
    process holds the chip, so bench.py runs this in-process)."""
    import jax

    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    L = int(os.environ.get("TBENCH_LAYERS", "12"))
    D = int(os.environ.get("TBENCH_EMBED", "768"))
    H = int(os.environ.get("TBENCH_HEADS", str(DEFAULT_HEADS)))
    S = int(os.environ.get("TBENCH_SEQ", "1024"))
    B = int(os.environ.get("TBENCH_BATCH", "32"))
    V = int(os.environ.get("TBENCH_VOCAB", "32768"))
    steps = int(os.environ.get("TBENCH_STEPS", "15"))
    reps = int(os.environ.get("TBENCH_REPS", "3"))
    # fused head: measures ~= dense at this shape (the head is compute-
    # bound, so the logits traffic the fused kernel saves hides under the
    # matmuls — round-4 A/B in docs/mfu_roofline.md); its value is the
    # HBM it frees at larger batches, so dense stays the timed default
    fused = os.environ.get("TBENCH_FUSED_HEAD", "0").lower() in (
        "1", "true", "yes")
    dtype = os.environ.get("TBENCH_DTYPE", "bfloat16")
    if dtype == "bfloat16":
        from mxnet_tpu.base import bfloat16 as dtype

    use_bias = os.environ.get("TBENCH_USE_BIAS", "1") != "0"
    # deliberately pinned to 'bhsd' (NOT the library's 'auto' default):
    # the recorded parity/geometry configs must stay byte-comparable
    # across rounds, and the unit string discloses the layout either way
    # — the bsd path is measured by the explicit tpu_geom_fast_ config
    attn_layout = os.environ.get("TBENCH_ATTN_LAYOUT", "bhsd")
    net = models.get_transformer_lm(
        vocab_size=V, seq_len=S, num_layers=L, num_heads=H, num_embed=D,
        fused_head=fused, use_bias=use_bias, attn_layout=attn_layout)
    n_dev = len(jax.devices())
    n_dev = next(k for k in range(n_dev, 0, -1) if B % k == 0)
    mesh = make_mesh(shape=(n_dev,), axis_names=("data",))
    # bf16 Adam second moments are the benchmark default (stochastic
    # rounding, tests/test_adam_vdtype.py) — halves the optimizer-table
    # HBM stream; TBENCH_ADAM_V_DTYPE=float32 opts out.  Disclosed in the
    # unit string so configs stay comparable across rounds.
    adam_v = os.environ.get("TBENCH_ADAM_V_DTYPE", "bfloat16") or None
    trainer = SPMDTrainer(
        net, mesh,
        data_shapes={"data": (B, S), "softmax_label": (B, S)},
        lr=1e-3, optimizer="adam", wd=0.0, dtype=dtype,
        adam_v_dtype=adam_v)
    rng = np.random.RandomState(0)
    batch = {
        "data": rng.randint(0, V, (B, S)).astype(np.int32),
        "softmax_label": rng.randint(0, V, (B, S)).astype(np.float32),
    }
    from mxnet_tpu import profiler

    dev_batch = trainer.shard_batch(batch)
    # two warm calls: the first compiles, the second runs on the donated
    # buffers' settled layouts
    trainer.run_steps(dev_batch, steps)
    profiler.device_sync(trainer.params)
    trainer.run_steps(dev_batch, steps)
    profiler.device_sync(trainer.params)
    # median-of-windows timing: robust to a one-off stall (a stall in a
    # differenced window once produced a fictitious 3.8x speedup)
    dt = profiler.timed_median(
        lambda: trainer.run_steps(dev_batch, steps),
        lambda: trainer.params, reps=max(1, reps // 2),
        windows=3) / steps

    tokens_per_sec = B * S / dt
    # active params: matmul-participating weights (incl. the tied-size
    # output head; embedding table lookups are gathers, not matmuls)
    n_matmul_params = (L * (4 * D * D + 2 * D * 4 * D)) + D * V
    flops_token = 6 * n_matmul_params + 12 * L * D * S // 2  # causal
    peak = chip_env.peak_flops(jax.devices()[0]) * n_dev
    mfu = flops_token * B * S / dt / peak

    result = {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / n_dev, 1),
        "unit": "tokens/sec/chip (mfu=%.3f, L=%d D=%d H=%d S=%d B=%d, %s, "
                "%s head, adam_v=%s, bias=%s, attn=%s)"
                % (mfu, L, D, H, S, B, np.dtype(dtype).name,
                   "fused" if fused else "dense", adam_v or "float32",
                   int(use_bias), attn_layout),
        "vs_baseline": None,
        "mfu": round(mfu, 4),
    }
    # release the model state before the caller reuses the chip
    del trainer, dev_batch
    return result


def main():
    chip_env.require_tpu()
    chip_env.enable_compile_cache()
    result = run()
    result.pop("mfu", None)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
