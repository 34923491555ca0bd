#!/usr/bin/env python
"""Benchmark the model zoo's training throughput (SPMD fused step, bf16).

Prints one line per model: images-or-tokens/sec/chip on the current
device, measured with the same staged-batch + fused-multi-step method as
bench.py.  `python tools/benchmark_zoo.py [--models resnet50,lenet,...]`
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_env  # noqa: E402  (tools/ is on the path: script dir, or bench.py)


CONFIGS = {
    # name: (builder kwargs, data shapes builder, unit)
    "mlp": (lambda m: m.get_mlp(),
            lambda b: {"data": (b, 784), "softmax_label": (b,)}, 512),
    "lenet": (lambda m: m.get_lenet(),
              lambda b: {"data": (b, 1, 28, 28), "softmax_label": (b,)}, 512),
    "alexnet": (lambda m: m.get_alexnet(),
                lambda b: {"data": (b, 3, 224, 224), "softmax_label": (b,)},
                256),
    "inception-bn": (
        lambda m: m.get_inception_bn(num_classes=1000,
                                     image_shape=(3, 224, 224)),
        lambda b: {"data": (b, 3, 224, 224), "softmax_label": (b,)}, 128),
    "resnet50": (lambda m: m.get_resnet(num_classes=1000, num_layers=50),
                 lambda b: {"data": (b, 3, 224, 224), "softmax_label": (b,)},
                 256),
    "resnet101": (lambda m: m.get_resnet(num_classes=1000, num_layers=101),
                  lambda b: {"data": (b, 3, 224, 224),
                             "softmax_label": (b,)}, 128),
    "vgg": (lambda m: m.get_vgg(),
            lambda b: {"data": (b, 3, 224, 224), "softmax_label": (b,)}, 64),
}


def bench_model(name, batch, steps, reps):
    import jax

    from mxnet_tpu import models
    from mxnet_tpu.base import bfloat16 as bf16
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    build, shapes_fn, _ = CONFIGS[name]
    net = build(models)
    n_dev = next(k for k in range(len(jax.devices()), 0, -1)
                 if batch % k == 0)
    mesh = make_mesh(shape=(n_dev,), axis_names=("data",))
    shapes = shapes_fn(batch)
    trainer = SPMDTrainer(net, mesh, data_shapes=shapes, lr=0.1,
                          momentum=0.9, wd=1e-4, dtype=bf16)
    rng = np.random.RandomState(0)
    batch_np = {}
    for k, s in shapes.items():
        if "label" in k:
            batch_np[k] = rng.randint(0, 10, s).astype(np.float32)
        else:
            batch_np[k] = rng.randn(*s).astype(np.float32).astype(bf16)
    dev = trainer.shard_batch(batch_np)
    trainer.run_steps(dev, steps)
    jax.block_until_ready(trainer.params)
    t0 = time.time()
    for _ in range(reps):
        trainer.run_steps(dev, steps)
    jax.block_until_ready(trainer.params)
    dt = (time.time() - t0) / (steps * reps)
    return batch / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default=",".join(CONFIGS))
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--reps", type=int, default=2)
    args = ap.parse_args()
    devices = chip_env.require_tpu()
    chip_env.enable_compile_cache()
    print("device: %d x %s (%s)" % (len(devices), devices[0].device_kind,
                                   devices[0].platform))
    print("%-14s %10s %14s" % ("model", "batch", "images/sec/chip"))
    for name in args.models.split(","):
        name = name.strip()
        if name not in CONFIGS:
            print("%-14s unknown" % name)
            continue
        batch = CONFIGS[name][2]
        try:
            ips = bench_model(name, batch, args.steps, args.reps)
            print("%-14s %10d %14.1f" % (name, batch, ips))
        except Exception as e:  # keep the table going
            print("%-14s %10d   ERROR: %s" % (name, batch, str(e)[:60]))


if __name__ == "__main__":
    main()
