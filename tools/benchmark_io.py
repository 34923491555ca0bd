#!/usr/bin/env python
"""Input-pipeline benchmark (BASELINE.md row 2: the reference sustains
~3,000 img/s packed-RecordIO read+decode on a 2015 multi-core box via OMP
threads, `docs/tutorials/imagenet_full.md:37`, decode pool
`iter_image_recordio.cc:184-194`).

Measures, on THIS host, images/sec for:
  * jpeg_read_decode        — RecordIO read + JPEG decode (ImageRecordIter)
  * jpeg_decode_augment     — + random crop/mirror (device-side augmenter)
  * npy_native_loader       — raw float payloads through native/loader.cc
  * overlapped_train        — decode overlapped against device train steps
                              via PrefetchingIter (the `iter_prefetcher.h`
                              role): epoch img/s for a small conv net
  * serial_train            — same workload without the prefetcher

Also reports cores and per-core decode rate: the reference's 3,000 img/s
used OMP across many cores (~375 img/s/core on 2015 hardware); this
pipeline's per-core decode rate is the comparable number on single-core
hosts.

Prints ONE JSON line.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_env  # noqa: E402  (tools/ is on the path: script dir, or bench.py)


def _build_pack(path, n, shape=(256, 256, 3), fmt=".jpg"):
    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    w = recordio.MXRecordIO(path, "w")
    for i in range(n):
        if fmt == ".npy":
            img = rng.randn(shape[2], shape[0], shape[1]).astype(np.float32)
        else:
            img = rng.randint(0, 255, shape, np.uint8)
        w.write(recordio.pack_img(
            recordio.IRHeader(0, float(i % 10), i, 0), img,
            quality=90, img_fmt=fmt))
    w.close()


def _drain(it):
    t0 = time.time()
    n = 0
    last = None
    for b in it:
        n += b.data[0].shape[0] - b.pad
        last = b
    last.data[0].asnumpy()  # sync any device-side tail
    return n / (time.time() - t0)


def main():
    import mxnet_tpu as mx

    n_imgs = int(os.environ.get("IOBENCH_IMAGES", "1200"))
    batch = int(os.environ.get("IOBENCH_BATCH", "64"))
    tmp = tempfile.mkdtemp(prefix="iobench")
    jpg = os.path.join(tmp, "jpg.rec")
    npy = os.path.join(tmp, "npy.rec")
    _build_pack(jpg, n_imgs)
    _build_pack(npy, max(n_imgs // 2, batch), shape=(224, 224, 3),
                fmt=".npy")

    out = {}

    # host-only read+decode (no device staging): the framework-owned part
    # of the pipeline.  Device staging overlaps training in steady state.
    from mxnet_tpu import recordio as _rio

    r = _rio.MXRecordIO(jpg, "r")
    t0 = time.time()
    n = 0
    while True:
        rec = r.read()
        if rec is None:
            break
        _, img = _rio.unpack_img(rec, iscolor=1)
        n += 1
    r.close()
    out["jpeg_host_read_decode"] = round(n / (time.time() - t0), 1)

    it = mx.io.ImageRecordIter(path_imgrec=jpg, data_shape=(3, 256, 256),
                               batch_size=batch, use_native=False)
    next(it)
    it.reset()  # jit warm
    out["jpeg_read_decode"] = round(_drain(it), 1)

    # C++ libjpeg decode in the threaded loader: uint8 HWC batches, no
    # Python in the decode loop (scales with preprocess_threads on
    # multi-core hosts; bit-identical to the PIL path)
    from mxnet_tpu import _native

    if _native.has_u8_loader():
        # raw C++ loader throughput, no JAX staging: the framework-owned
        # decode rate (the iterator numbers below add device staging and,
        # on a CPU backend, fight the decoder for the same cores)
        import ctypes

        lib = _native.LIB

        def raw_decode_rate(threads):
            hnd = lib.mxtpu_loader_open_u8(
                jpg.encode(), 0, 1, batch, 3 * 256 * 256, threads, 4)
            if not hnd:
                return None
            dbuf = np.empty((batch, 256, 256, 3), np.uint8)
            lbuf = np.empty((batch,), np.float32)
            t0 = time.time()
            got = 0
            while True:
                m = lib.mxtpu_loader_next_u8(
                    hnd,
                    dbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                    lbuf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
                if m <= 0:
                    break
                got += m
            lib.mxtpu_loader_close(hnd)
            return round(got / (time.time() - t0), 1)

        # io_cores sweep (round-4 verdict task 4): 1 thread and all-cores
        # (plus IOBENCH_THREADS override) — on a single-core host the two
        # coincide and the per-core rate is the scaling story
        ncores = int(os.environ.get("IOBENCH_THREADS", "0")) \
            or (os.cpu_count() or 1)
        r1 = raw_decode_rate(1)
        if r1 is not None:
            out["jpeg_native_raw_decode_1thread"] = r1
        rn = raw_decode_rate(ncores) if ncores != 1 else None
        if rn is not None:
            out["jpeg_native_raw_decode"] = rn
            out["io_threads"] = ncores
        elif r1 is not None:
            # the 1-thread rate is still a valid native measurement; the
            # headline must not fall back to the slower python decode
            out["jpeg_native_raw_decode"] = r1
            out["io_threads"] = 1

        it = mx.io.ImageRecordIter(
            path_imgrec=jpg, data_shape=(3, 256, 256), batch_size=batch,
            use_native=True, preprocess_threads=os.cpu_count() or 1)
        next(it)
        it.reset()
        out["jpeg_native_u8_decode"] = round(_drain(it), 1)
        it.close()

    it = mx.io.ImageRecordIter(path_imgrec=jpg, data_shape=(3, 224, 224),
                               record_shape=(3, 256, 256), rand_crop=True,
                               rand_mirror=True, batch_size=batch,
                               use_native=False)
    next(it)
    it.reset()
    out["jpeg_decode_augment"] = round(_drain(it), 1)

    it = mx.io.ImageRecordIter(path_imgrec=npy, data_shape=(3, 224, 224),
                               batch_size=batch)
    out["npy_native_loader"] = round(_drain(it), 1)

    if os.environ.get("IOBENCH_SKIP_TRAIN", "0") == "1":
        # decode-only mode: the host-side numbers need no device at all
        _finish(out)
        return

    # -- overlap: decode thread feeding device train steps ----------------
    # IOBENCH_TRAIN_IMAGE sizes the train model/pack: 224 (resnet18) on a
    # real chip, small (resnet-28 CIFAR stem) for CPU smoke runs
    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh

    chip_env.enable_compile_cache()
    timg = int(os.environ.get("IOBENCH_TRAIN_IMAGE", "224"))
    rec = timg + 32
    tjpg = os.path.join(tmp, "train.rec")
    _build_pack(tjpg, int(os.environ.get("IOBENCH_TRAIN_IMAGES", "768")),
                shape=(rec, rec, 3))
    layers = 18 if timg >= 64 else 28
    net = models.get_resnet(num_classes=10, num_layers=layers,
                            image_shape=(3, timg, timg))
    mesh = make_mesh(shape=(1,), axis_names=("data",))
    trainer = SPMDTrainer(
        net, mesh, data_shapes={"data": (batch, 3, timg, timg),
                                "softmax_label": (batch,)},
        lr=0.1, momentum=0.9)

    def run_epoch(prefetch):
        src = mx.io.ImageRecordIter(
            path_imgrec=tjpg, data_shape=(3, timg, timg),
            record_shape=(3, rec, rec), rand_crop=True, rand_mirror=True,
            batch_size=batch, use_native=False)
        it = mx.io.PrefetchingIter(src) if prefetch else src
        # warm the step compile outside the timed region
        warm = next(iter(it))
        if warm.pad == 0:
            trainer.step({"data": warm.data[0],
                          "softmax_label": warm.label[0]})
        it.reset()
        t0 = time.time()
        n = 0
        for b in it:
            if b.pad:
                continue
            trainer.step({"data": b.data[0],
                          "softmax_label": b.label[0]})
            n += batch
        from mxnet_tpu import profiler

        profiler.device_sync(trainer.params)
        return n / (time.time() - t0)

    out["serial_train"] = round(run_epoch(False), 1)
    out["overlapped_train"] = round(run_epoch(True), 1)
    _finish(out)


def _finish(out):
    ncores = os.cpu_count() or 1
    out["cores"] = ncores
    out["jpeg_host_decode_per_core"] = round(
        out["jpeg_host_read_decode"] / ncores, 1)
    if "jpeg_native_raw_decode" in out:
        # divide by the threads that actually ran the sweep (IOBENCH_THREADS
        # may differ from the host's core count), not os.cpu_count()
        out["jpeg_native_raw_decode_per_core"] = round(
            out["jpeg_native_raw_decode"]
            / out.get("io_threads", ncores), 1)
        best = out["jpeg_native_raw_decode"]
    else:
        best = out["jpeg_host_read_decode"]
    # the reference's ~3000 img/s rode OMP decode over many 2015 cores
    # (~375 img/s/core); per-core decode is the comparable number on
    # core-starved hosts
    out["vs_reference_3000"] = round(best / 3000.0, 3)
    # persist as a bench_results/ artifact: bench.py surfaces the newest
    # one beside its own record
    try:
        import bench_store

        bench_store.record(
            {"metric": "recordio_decode_img_per_sec", "value": best,
             "unit": "img/s (host decode, %d core(s))" % ncores,
             "vs_baseline": out["vs_reference_3000"], "extra": dict(out)},
            kind="io")
    except Exception as e:  # pragma: no cover
        print("bench_store.record failed: %s" % e, file=sys.stderr)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
