#!/usr/bin/env python3
"""Record the small serving trace the new readers are checked against (run on
the chip):

    python3 benchmark/tests/record_serve_trace.py <out_dir>

A tiny `ServingEngine` (1 layer, 64 wide, a 64-block pool, one prefill and
one decode program) serves three requests on its own scheduler thread while
the profiler runs with the Python tracer off and the host tracer at level 1
(the program's own spans and the runtime's coarsest), and the planes no
reader reads (`/host:metadata` holds every program's whole HLO) are left out
of the copy, so the file stays small.  It holds `sched.*` spans on the
scheduler's thread, `jit_serve_prefill_s32` and `jit_serve_decode_b4` module
events and operations under the model's scopes.  Writes
`<out_dir>/serve_small.xplane.pb` and prints what `tests/test_xplane_raw.py`
checks by hand."""
import glob
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def copy_planes(src, dst, keep):
    """The `XSpace` of ``src`` with only the planes ``keep`` names."""
    from benchmark import xplane_raw

    def varint(n):
        out = bytearray()
        while n > 0x7F:
            out.append(0x80 | (n & 0x7F))
            n >>= 7
        out.append(n)
        return bytes(out)

    with open(src, "rb") as f:
        space = memoryview(f.read())
    with open(dst, "wb") as f:
        for field, plane in xplane_raw._fields(space):
            if field != 1:
                continue
            name = next(xplane_raw._text(v)
                        for pf, v in xplane_raw._fields(plane) if pf == 2)
            if name in keep:
                f.write(b"\x0a" + varint(len(plane)) + bytes(plane))


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.serving import ServingEngine, TransformerKVModel

    model = TransformerKVModel(256, 128, num_layers=1, num_heads=2,
                               num_embed=64, num_ffn_hidden=128,
                               dtype=jnp.bfloat16)
    params = {k: jnp.asarray(v, jnp.bfloat16)
              for k, v in model.init_params().items()}
    engine = ServingEngine(model, params, max_batch=4, block_size=16,
                           n_blocks=64, prefill_buckets=[32],
                           decode_buckets=[4], name="small")
    engine.warmup()
    engine.start()
    rng = np.random.default_rng(0)

    def serve(n):
        reqs = [engine.submit(rng.integers(0, 256, size=int(p)).tolist(),
                              max_new_tokens=6)
                for p in rng.integers(5, 30, size=n)]
        for r in reqs:
            r.result(60.0)

    serve(4)                      # every shape once, outside the trace
    tmp = os.path.join(out_dir, "_trace")
    shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=options)
    serve(3)
    jax.profiler.stop_trace()
    engine.stop()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    dst = os.path.join(out_dir, "serve_small.xplane.pb")
    copy_planes(path, dst, ("/device:TPU:0", "/host:CPU"))
    shutil.rmtree(tmp, ignore_errors=True)
    print("%d bytes -> %s" % (os.path.getsize(dst), dst))

    from benchmark import xplane_raw
    from benchmark.readers import idle_in_spans, scope_ms

    raw = xplane_raw.load(dst)
    if raw is None:
        print("no device plane (not recorded on the chip)")
        return
    names = {}
    for _, d, name in raw["modules"]:
        head = name.split("(")[0]
        n, total = names.get(head, (0, 0))
        names[head] = (n + 1, total + d)
    print("modules:", names)
    spans = {}
    for _, d, name, line in raw["spans"]:
        n, total = spans.get((name, line), (0, 0))
        spans[(name, line)] = (n + 1, total + d)
    print("spans:", spans)
    print("idle table:", idle_in_spans.table(raw))
    decode = xplane_raw.programs_of(raw, ["serve_decode_"])
    launches = scope_ms.launches_of(raw["ops"], decode)
    ops = [op for launch in launches for op in launch]
    print("decode ops: %d, %d ns; by scope: %s"
          % (len(ops), sum(d for _, d, _ in ops), scope_ms.by_scope(ops)))
    want = {"kv_gather", "decode_attention"}
    print("by launch, ns under kv_gather or decode_attention: %s; under no "
          "scope: %s"
          % ([sum(d for _, d, m in launch
                  if want & set(xplane_raw.scopes_of(m.get("tf_op"))))
              for launch in launches],
             [sum(d for _, d, m in launch
                  if not xplane_raw.scopes_of(m.get("tf_op"))[1:-1])
              for launch in launches]))


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out")
