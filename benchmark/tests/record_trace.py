#!/usr/bin/env python3
"""Record the small trace the reducer is checked against (run on the chip):

    python3 benchmark/tests/record_trace.py <out_dir>

A few calls of one jitted program that holds the repo's flash-attention
forward and backward kernels and a matmul, with a pause between two of them so
that the trace has one known idle gap.  Writes `<out_dir>/small.xplane.pb` and
prints the trace's structure."""
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(out_dir):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels.flash_attention import flash_attention

    def loss(q, k, v, w):
        o = flash_attention(q, k, v, causal=True)
        return jnp.sum(jnp.dot(o.reshape(-1, o.shape[-1]), w)
                       .astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (2, 4, 512, 64), jnp.bfloat16)
               for i in range(3))
    w = jax.random.normal(key, (64, 256), jnp.bfloat16)
    jax.block_until_ready(step(q, k, v, w))
    tmp = os.path.join(out_dir, "_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    t0 = time.perf_counter()
    for i in range(4):
        jax.block_until_ready(step(q, k, v, w))
        if i == 1:
            time.sleep(0.05)
    window = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    dst = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    print("window_s %.6f, %d bytes -> %s" % (window, os.path.getsize(dst),
                                             dst))
    from jax.profiler import ProfileData

    data = ProfileData.from_file(dst)
    for plane in data.planes:
        print("plane %r: %d lines" % (plane.name, len(list(plane.lines))))
        for line in plane.lines:
            events = list(line.events)
            print("  line %r: %d events" % (line.name, len(events)))
            for ev in events[:6]:
                print("    %r start %d dur %d stats %s"
                      % (ev.name, ev.start_ns, ev.duration_ns,
                         [(k, str(x)[:80]) for k, x in ev.stats][:8]))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out")
