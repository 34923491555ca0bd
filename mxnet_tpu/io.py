"""Data iterators.

Reference: `include/mxnet/io.h` (`IIterator<DataBatch>`), `src/io/`
(MNIST/CSV/ImageRecord iters, batch loader, prefetcher) and
`python/mxnet/io.py` (DataIter, NDArrayIter, MXDataIter, ResizeIter,
PrefetchingIter).

TPU-first notes: iterators produce host numpy batches; the training loop (or
sharded executor) device-puts them — for multi-chip data parallelism the batch
is laid out over the mesh's data axis, which replaces the reference's
per-GPU slice copies (`executor_manager.py:76-91`).  `part_index/num_parts`
sharded reading is kept on every iterator (the reference got it from
`dmlc::InputSplit`, `iter_image_recordio.cc:215-217`), because multi-host
training shards input files the same way.
"""
from __future__ import annotations

import gzip
import logging
import os
import struct
import threading
import time
import queue as _queue

import numpy as np

from . import telemetry
from .base import MXNetError, check_shape
from .ndarray import NDArray, array


class DataBatch:
    """One batch (reference `DataBatch`, `io.h:60-69`)."""

    def __init__(self, data, label, pad=0, index=None, bucket_key=None,
                 provide_data=None, provide_label=None):
        self.data = data  # list of NDArray
        self.label = label  # list of NDArray
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Iterator base (reference `python/mxnet/io.py:35`)."""

    def __init__(self):
        self.batch_size = 0

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        raise NotImplementedError()

    def __next__(self):
        return self.next()

    # convenience accessors used by older loops
    def iter_next(self):
        try:
            self._next_batch = self.next()
            return True
        except StopIteration:
            self._next_batch = None
            return False

    def getdata(self):
        return self._next_batch.data[0]

    def getlabel(self):
        return self._next_batch.label[0]

    def getindex(self):
        return self._next_batch.index

    def getpad(self):
        return self._next_batch.pad

    @property
    def provide_data(self):
        """[(name, shape)] of data (`io.py` provide_data)."""
        raise NotImplementedError()

    @property
    def provide_label(self):
        raise NotImplementedError()


class NDArrayIter(DataIter):
    """In-memory iterator (`python/mxnet/io.py:319` NDArrayIter): shuffle,
    pad/discard/roll_over last-batch handling."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data", label_name="softmax_label"):
        super().__init__()
        self.data = self._init_data(data, data_name)
        self.label = self._init_data(label, label_name) if label is not None else []
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.last_batch_handle = last_batch_handle
        self.num_data = self.data[0][1].shape[0]
        if self.num_data < batch_size:
            raise MXNetError("batch_size larger than dataset")
        self.cursor = -batch_size
        self._order = np.arange(self.num_data)
        if shuffle:
            np.random.shuffle(self._order)

    @staticmethod
    def _init_data(data, default_name):
        if data is None:
            return []
        if isinstance(data, (np.ndarray, NDArray)):
            data = {default_name: data}
        elif isinstance(data, (list, tuple)):
            data = {("%s_%d" % (default_name, i) if i else default_name): d
                    for i, d in enumerate(data)}
        out = []
        for k, v in data.items():
            if isinstance(v, NDArray):
                v = v.asnumpy()
            out.append((k, np.asarray(v)))
        return out

    @property
    def provide_data(self):
        return [(k, (self.batch_size,) + v.shape[1:]) for k, v in self.data]

    @property
    def provide_label(self):
        return [(k, (self.batch_size,) + v.shape[1:]) for k, v in self.label]

    def reset(self):
        if self.shuffle:
            # re-derive the permutation from scratch: the epoch's order
            # must be a pure function of the RNG state at reset time (an
            # in-place shuffle composes with every PREVIOUS epoch's), so
            # auto-resume can replay one epoch's order from one saved RNG
            # snapshot (checkpoint.save_auto / docs/fault_tolerance.md)
            self._order = np.arange(self.num_data)
            np.random.shuffle(self._order)
        if self.last_batch_handle == "roll_over" and self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor - self.num_data)
        else:
            self.cursor = -self.batch_size

    def _getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0

    def _take(self, arrs):
        end = self.cursor + self.batch_size
        if end <= self.num_data:
            idx = self._order[self.cursor:end]
        else:  # pad by wrapping
            idx = np.concatenate(
                [self._order[self.cursor:], self._order[:end - self.num_data]]
            )
        return [array(v[idx]) for _, v in arrs]

    def next(self):
        self.cursor += self.batch_size
        if self.cursor >= self.num_data:
            raise StopIteration
        if self.cursor + self.batch_size > self.num_data and \
                self.last_batch_handle == "discard":
            raise StopIteration
        return DataBatch(
            data=self._take(self.data),
            label=self._take(self.label),
            pad=self._getpad(),
            index=None,
            provide_data=self.provide_data,
            provide_label=self.provide_label,
        )


class CSVIter(DataIter):
    """CSV reader (`src/io/iter_csv.cc`): data_csv + optional label_csv,
    fixed row shapes, part_index/num_parts sharding."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, part_index=0, num_parts=1,
                 label_name="label"):
        super().__init__()
        data = np.loadtxt(data_csv, delimiter=",", ndmin=2, dtype=np.float32)
        data = data.reshape((-1,) + check_shape(data_shape))
        label = None
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", ndmin=2, dtype=np.float32)
            label = label.reshape((-1,) + check_shape(label_shape))
            if label.shape[-1] == 1:
                label = label[..., 0]
        else:
            label = np.zeros((data.shape[0],), dtype=np.float32)
        if num_parts > 1:
            data = data[part_index::num_parts]
            label = label[part_index::num_parts]
        handle = "pad" if round_batch else "discard"
        self._inner = NDArrayIter(
            data, label, batch_size=batch_size, last_batch_handle=handle,
            label_name=label_name,
        )
        self.batch_size = batch_size

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


def _read_idx_images(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        magic, num, rows, cols = struct.unpack(">IIII", f.read(16))
        if magic != 2051:
            raise MXNetError("%s is not an MNIST image file" % path)
        data = np.frombuffer(f.read(), dtype=np.uint8).reshape(num, rows, cols)
    return data


def _read_idx_labels(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        magic, num = struct.unpack(">II", f.read(8))
        if magic != 2049:
            raise MXNetError("%s is not an MNIST label file" % path)
        return np.frombuffer(f.read(), dtype=np.uint8)


class MNISTIter(DataIter):
    """idx-format MNIST reader (`src/io/iter_mnist.cc`): flat or (1,28,28)
    layout, shuffle, silent, part_index/num_parts distributed sharding."""

    def __init__(self, image, label, batch_size=128, shuffle=True, flat=False,
                 silent=False, seed=0, part_index=0, num_parts=1,
                 input_shape=None):
        super().__init__()
        imgs = _read_idx_images(image).astype(np.float32) / 255.0
        lbls = _read_idx_labels(label).astype(np.float32)
        if num_parts > 1:
            imgs = imgs[part_index::num_parts]
            lbls = lbls[part_index::num_parts]
        if flat:
            imgs = imgs.reshape(len(imgs), -1)
        else:
            imgs = imgs.reshape(len(imgs), 1, imgs.shape[1], imgs.shape[2])
            if input_shape is not None:
                imgs = imgs.reshape((len(imgs),) + check_shape(input_shape))
        if shuffle:
            rng = np.random.RandomState(seed)
            order = rng.permutation(len(imgs))
            imgs, lbls = imgs[order], lbls[order]
        self._inner = NDArrayIter(imgs, lbls, batch_size=batch_size,
                                  shuffle=False, last_batch_handle="pad")
        self.batch_size = batch_size

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


class ResizeIter(DataIter):
    """Resize an iterator to a fixed number of batches per epoch
    (`python/mxnet/io.py` ResizeIter)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.batch_size = data_iter.batch_size

    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def next(self):
        if self.cur == self.size:
            raise StopIteration
        try:
            batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            batch = self.data_iter.next()
        self.cur += 1
        return batch


class PrefetchingIter(DataIter):
    """Double-buffered prefetch over one or more iterators
    (`python/mxnet/io.py` PrefetchingIter; C++ `src/io/iter_prefetcher.h`
    used `dmlc::ThreadedIter` — here a worker thread + bounded queue gives
    the same pipeline overlap with host decode).

    The worker is started lazily (first `next()`), joined by the
    idempotent `close()` — called from `reset`, `__del__` and the training
    loops' finally blocks, so an early loop exit or in-loop exception no
    longer leaks the daemon thread and its queued batches.  A closed
    iterator revives on the next `reset()`/`next()` call."""

    def __init__(self, iters, rename_data=None, rename_label=None, capacity=2):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.iters = iters
        self.batch_size = iters[0].batch_size
        self._capacity = capacity
        self._queue = None
        self._thread = None
        self._stop = [False]   # per-generation cell, see _start
        self._exhausted = False

    def _start(self):
        # a revival (reset() or a post-close next()) must never run a new
        # worker concurrently with a zombie a past close() abandoned
        # inside the inner iterator
        self._stale = _require_workers_dead(
            getattr(self, "_stale", []), "PrefetchingIter")
        self._queue = _queue.Queue(self._capacity)
        # per-GENERATION stop cell, captured by the worker closure: if a
        # previous close() gave up on a worker stuck in a long next(), a
        # restart must not un-stop that zombie — only its own generation's
        # cell ever goes back to False
        stop = self._stop = [False]
        queue = self._queue

        def worker():
            while not stop[0]:
                try:
                    batches = [it.next() for it in self.iters]
                except StopIteration:
                    queue.put(None)
                    return
                except BaseException as e:
                    # forward errors to the consumer: a dead worker with
                    # no sentinel would leave next() blocked forever
                    queue.put(_WorkerError(e))
                    return
                queue.put(batches)

        self._thread = threading.Thread(target=worker, daemon=True,
                                        name="mx-prefetch")
        self._thread.start()

    def close(self):
        """Stop and join the worker, draining queued batches (idempotent).
        The drain is what lets a worker blocked on a full queue observe the
        stop flag; undelivered batches are discarded — callers that need
        the stream position use `reset()` right after."""
        thread, self._thread = self._thread, None
        if thread is None:
            return
        self._stop[0] = True
        self._stale = _drain_and_join((thread,), (self._queue,)) + \
            [t for t in getattr(self, "_stale", []) if t.is_alive()]

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    @property
    def provide_data(self):
        return sum([it.provide_data for it in self.iters], [])

    @property
    def provide_label(self):
        return sum([it.provide_label for it in self.iters], [])

    def reset(self):
        self.close()
        self._stale = _require_workers_dead(
            getattr(self, "_stale", []), "PrefetchingIter")
        self._exhausted = False
        for it in self.iters:
            it.reset()

    def next(self):
        if self._exhausted:
            raise StopIteration
        if self._thread is None:
            self._start()
        # data-iterator wait time: how long the training loop blocked on
        # the prefetch queue.  Near-zero means the pipeline keeps up; a
        # step-sized wait means the loop is input-bound — the telemetry
        # stream's "io.wait_ms" histogram separates the two without a
        # trace viewer.
        t0 = time.perf_counter()
        batches = self._queue.get()
        telemetry.observe("io.wait_ms", 1e3 * (time.perf_counter() - t0))
        if batches is None or isinstance(batches, _WorkerError):
            self._exhausted = True
            self.close()
            if batches is not None:
                raise batches.error
            raise StopIteration
        if len(batches) == 1:
            return batches[0]
        return DataBatch(
            data=sum([b.data for b in batches], []),
            label=sum([b.label for b in batches], []),
            pad=batches[0].pad,
        )


class _WorkerError:
    """Queue marker carrying a prefetch-worker exception to the consumer
    thread (where it is re-raised)."""

    __slots__ = ("error",)

    def __init__(self, error):
        self.error = error


def _drain_and_join(threads, queues, deadline_s=5.0):
    """Shared shutdown protocol of the prefetch iterators: repeatedly
    drain the queues (so a worker blocked on a full `put` can observe its
    stop flag) while joining, giving up after the deadline — the workers
    are daemon threads, teardown must never hang on one.  Returns the
    threads still alive at the deadline (stuck inside the inner
    iterator's `next()`); callers stash them so `reset()` can refuse to
    hand the inner iterator to a new generation while an old one might
    still be touching it."""
    deadline = time.perf_counter() + deadline_s
    while any(t.is_alive() for t in threads):
        for q in queues:
            try:
                while True:
                    q.get_nowait()
            except _queue.Empty:
                pass
        for t in threads:
            t.join(timeout=0.05)
        if time.perf_counter() > deadline:
            break
    return [t for t in threads if t.is_alive()]


def _require_workers_dead(stale, what):
    """Before a reset re-enters the inner iterator: wait out any worker a
    past close() abandoned mid-`next()` (two threads in one iterator
    would corrupt its cursor); a worker that still won't die is an
    error, not a silent race."""
    alive = [t for t in stale if t.is_alive()]
    for t in alive:
        t.join(timeout=30)
    alive = [t for t in alive if t.is_alive()]
    if alive:
        raise MXNetError(
            "%s.reset(): a prefetch worker is still blocked inside the "
            "inner iterator's next(); cannot safely reset" % what)
    return []


# ---------------------------------------------------------------------------
# Device-staging prefetch (zero-host-sync training input path)
# ---------------------------------------------------------------------------


def device_prefetch_depth():
    """MXNET_DEVICE_PREFETCH: queue depth of the device-staging prefetch
    layer the training loops wrap around their data iterator (default 2;
    `0` kill-switches back to the synchronous in-step host->device copy).
    Read per fit() call, like the other kill-switches."""
    raw = os.environ.get("MXNET_DEVICE_PREFETCH", "2")
    try:
        depth = int(raw or 0)
    except ValueError:
        raise MXNetError(
            "MXNET_DEVICE_PREFETCH must be an integer queue depth, got %r"
            % raw)
    return max(depth, 0)


class PrefetchPlan:
    """Where a staged batch's per-device slices go: the executor group's
    batch slices and jax devices.  `key` is structural — a staged batch is
    only fast-path loaded by a group whose own key matches, so a stale
    plan (rebound group, different ctx list) degrades to the normal copy
    path instead of mis-placing data."""

    def __init__(self, slices, devices):
        self.slices = list(slices)
        self.devices = list(devices)
        self.key = self.make_key(self.slices, self.devices)

    @staticmethod
    def make_key(slices, devices):
        return (tuple((s.start, s.stop) for s in slices),
                tuple(str(d) for d in devices))


class DevicePrefetchIter(DataIter):
    """Pipeline host batches into per-device HBM while the previous step
    computes.

    The reference hid input latency with `dmlc::ThreadedIter` feeding its
    async dependency engine; the JAX rebuild's steady-state loop still
    paid a synchronous host->device copy inside every step
    (`load_data_batch`).  This layer's worker thread pulls batch N+1 from
    the inner iterator, shards it with the executor group's `PrefetchPlan`
    (per-device slices) and `jax.device_put`s each slice, so by the time
    the training loop asks for the batch its buffers are already
    device-resident — `DataParallelExecutorGroup.load_data_batch`
    pointer-shares them into the bound args with no second copy.

    Without a plan it degrades to plain threaded prefetch (the batches
    still carry host-produced arrays).  Queue depth is bounded
    (`MXNET_DEVICE_PREFETCH`); `close()` is idempotent and joins the
    worker; `reset()`/`next()` revive a closed iterator.

    Telemetry: `io.device_wait_ms` (time the loop blocked on the queue),
    `io.prefetch_depth` (queue occupancy at fetch), `io.input_wait_frac`
    (blocked fraction of the inter-batch interval — ~0 when compute-bound,
    ~1 when input-bound)."""

    def __init__(self, data_iter, plan=None, depth=None):
        super().__init__()
        self.data_iter = data_iter
        self.plan = plan
        self.batch_size = data_iter.batch_size
        if depth is None:
            depth = device_prefetch_depth()
        if depth <= 0:
            # the synchronous path is the UNWRAPPED iterator (the loops
            # gate on the depth before constructing one of these) — a
            # direct construction under MXNET_DEVICE_PREFETCH=0 is
            # rejected loudly rather than silently spawning threads the
            # kill-switch promised away
            raise MXNetError(
                "DevicePrefetchIter needs depth >= 1; use the plain "
                "iterator (MXNET_DEVICE_PREFETCH=0) for the synchronous "
                "path")
        self._depth = depth
        self._host_queue = None
        self._queue = None
        self._threads = ()
        self._stop = [False]   # per-generation cell, see _start
        self._exhausted = False
        self._last_return = None
        self._skip_stage = [0]  # see set_skip_staging

    @property
    def provide_data(self):
        return self.data_iter.provide_data

    @property
    def provide_label(self):
        return self.data_iter.provide_label

    def _stage(self, batch):
        """Shard + device-put one batch per the plan (runs on the worker
        thread, overlapping step N's compute).  The original full-batch
        arrays stay on the DataBatch — legacy paths (host metrics, resume
        skip, callbacks reading labels) keep working — and the staged
        slices ride along in `device_parts`."""
        plan = self.plan
        if plan is None:
            return batch
        import jax

        whole = len(plan.slices) == 1

        def shard(arrs):
            out = []
            for arr in arrs:
                src = arr.data if isinstance(arr, NDArray) else arr
                parts = []
                for s, dev in zip(plan.slices, plan.devices):
                    piece = src if whole and s.start == 0 \
                        and s.stop == src.shape[0] else src[s.start:s.stop]
                    # already resident (single-device CPU runs): skip the
                    # no-op device_put dispatch — the staging thread's CPU
                    # time matters on small hosts
                    if getattr(piece, "device", None) != dev:
                        piece = jax.device_put(piece, dev)
                    parts.append(NDArray(piece))
                out.append(parts)
            return out

        batch.device_parts = {
            "key": plan.key,
            "data": shard(batch.data),
            "label": shard(batch.label),
        }
        return batch

    def _start(self):
        # two-stage pipeline: the producer pulls host batches (decode /
        # synthetic input time), the stager shards + device-puts them —
        # so input latency and staging overlap each other AND the compute,
        # and steady-state step time approaches max(compute, input, stage)
        self._stale = _require_workers_dead(
            getattr(self, "_stale", []), "DevicePrefetchIter")
        self._host_queue = _queue.Queue(self._depth)
        self._queue = _queue.Queue(self._depth)
        # per-generation stop cell (see PrefetchingIter._start): a restart
        # must never revive a zombie worker close() gave up on
        stop = self._stop = [False]
        host_queue, queue = self._host_queue, self._queue

        def producer():
            while not stop[0]:
                try:
                    batch = self.data_iter.next()
                except StopIteration:
                    host_queue.put((None, None))
                    return
                except BaseException as e:  # surfaced on the main thread
                    host_queue.put((e, None))
                    return
                host_queue.put((None, batch))

        skip_stage = self._skip_stage

        def stager():
            while not stop[0]:
                try:
                    err, batch = host_queue.get(timeout=0.05)
                except _queue.Empty:
                    continue  # poll the stop flag; steady state never waits
                if err is not None or batch is None:
                    queue.put((err, None))
                    return
                if skip_stage[0] > 0:
                    # resume fast-forward: the consumer will discard this
                    # batch unprocessed — don't pay the shard+device_put
                    skip_stage[0] -= 1
                    queue.put((None, batch))
                    continue
                try:
                    staged = self._stage(batch)
                except BaseException as e:
                    queue.put((e, None))
                    return
                queue.put((None, staged))

        self._threads = (
            threading.Thread(target=producer, daemon=True,
                             name="mx-device-prefetch-in"),
            threading.Thread(target=stager, daemon=True,
                             name="mx-device-prefetch-stage"),
        )
        for t in self._threads:
            t.start()

    def close(self):
        """Idempotent worker join + queue drain (see PrefetchingIter.close);
        queued staged batches are discarded."""
        threads, self._threads = self._threads, ()
        if not threads:
            return
        self._stop[0] = True
        self._stale = _drain_and_join(
            threads, (self._host_queue, self._queue)) + \
            [t for t in getattr(self, "_stale", []) if t.is_alive()]

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def set_skip_staging(self, n):
        """The next `n` batches will be consumed-and-discarded (auto-resume
        fast-forward): deliver them unstaged so the replay does not pay a
        shard+device_put per skipped batch.  Call before iteration starts
        (the workers spawn lazily at the first `next()`)."""
        self._skip_stage[0] = int(n)

    def reset(self):
        self.close()
        self._stale = _require_workers_dead(
            getattr(self, "_stale", []), "DevicePrefetchIter")
        self._exhausted = False
        self._last_return = None
        self._skip_stage[0] = 0
        self.data_iter.reset()

    def next(self):
        if self._exhausted:
            raise StopIteration
        if not self._threads:
            self._start()
        t0 = time.perf_counter()
        err, batch = self._queue.get()
        now = time.perf_counter()
        wait = now - t0
        telemetry.observe("io.device_wait_ms", 1e3 * wait)
        telemetry.set_gauge("io.prefetch_depth", self._queue.qsize())
        if self._last_return is not None:
            interval = now - self._last_return
            telemetry.set_gauge(
                "io.input_wait_frac",
                wait / interval if interval > 0 else 0.0)
        self._last_return = now
        if err is not None:
            self._exhausted = True
            self.close()
            raise err
        if batch is None:
            self._exhausted = True
            self.close()
            raise StopIteration
        return batch


def close_iter(data_iter):
    """Best-effort close of a (possibly wrapped) prefetching iterator —
    the training loops call this from their finally blocks so an aborted
    fit never leaks a worker thread.  Only prefetch-layer iterators are
    touched (they revive on reset); resource-owning iterators like
    ImageRecordIter are left alone."""
    if isinstance(data_iter, (PrefetchingIter, DevicePrefetchIter)):
        try:
            data_iter.close()
        except Exception:
            logging.exception("close of %r failed", data_iter)


class ImageRecordIter(DataIter):
    """Batches from a recordio pack (reference `ImageRecordIter`,
    `src/io/iter_image_recordio.cc`): sharded reading via
    part_index/num_parts, multi-threaded decode, prefetching.

    Records are IRHeader + raw .npy payloads (`recordio.pack_img`).  When
    `native/libmxtpu.so` is built the C++ threaded loader
    (`native/loader.cc`) does read+decode+batch off the Python thread; the
    pure-Python fallback decodes inline.  Augmentations (crop/mirror) of
    the reference run on-device in this build — random crops/flips vectorize
    far better as jax ops inside the input pipeline than per-image host
    loops.
    """

    def __init__(self, path_imgrec, data_shape, batch_size,
                 part_index=0, num_parts=1, preprocess_threads=4,
                 prefetch_buffer=4, data_name="data",
                 label_name="softmax_label", use_native=None,
                 rand_crop=False, rand_mirror=False, mean_img=None,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0, scale=1.0,
                 max_random_contrast=0.0, max_random_illumination=0.0,
                 record_shape=None,
                 # full ImageAugmentParam set (image_augmenter.h:29-54),
                 # handled by the on-device ImageAugmenter
                 max_rotate_angle=0, rotate=-1, max_shear_ratio=0.0,
                 max_random_scale=1.0, min_random_scale=1.0,
                 max_aspect_ratio=0.0, max_img_size=1e10, min_img_size=0.0,
                 random_h=0, random_s=0, random_l=0, fill_value=255,
                 crop_y_start=-1, crop_x_start=-1, max_crop_size=-1,
                 min_crop_size=-1, inter_method=1):
        super().__init__()
        from . import _native
        from . import recordio as _recordio

        # remote URIs (s3://... via a registered fetch hook, file://)
        # resolve to a local file first — the dmlc::InputSplit remote-read
        # role (`iter_image_recordio.cc:105-126`), see
        # recordio.register_fetch_hook
        path_imgrec = _recordio.resolve_uri(path_imgrec)
        self.batch_size = batch_size
        self._data_shape = tuple(int(x) for x in check_shape(data_shape))
        # on-device augmentation (image.py): records may be stored larger
        # than data_shape (record_shape) so random crops have margin,
        # mirroring the reference's decode-then-crop flow
        self._record_shape = tuple(int(x) for x in check_shape(record_shape)) \
            if record_shape else self._data_shape
        self._augmenter = None
        aug_extra = dict(
            max_rotate_angle=max_rotate_angle, rotate=rotate,
            max_shear_ratio=max_shear_ratio,
            max_random_scale=max_random_scale,
            min_random_scale=min_random_scale,
            max_aspect_ratio=max_aspect_ratio, max_img_size=max_img_size,
            min_img_size=min_img_size, random_h=random_h,
            random_s=random_s, random_l=random_l, fill_value=fill_value,
            crop_y_start=crop_y_start, crop_x_start=crop_x_start,
            max_crop_size=max_crop_size, min_crop_size=min_crop_size,
            inter_method=inter_method)
        defaults = dict(
            max_rotate_angle=0, rotate=-1, max_shear_ratio=0.0,
            max_random_scale=1.0, min_random_scale=1.0,
            max_aspect_ratio=0.0, max_img_size=1e10, min_img_size=0.0,
            random_h=0, random_s=0, random_l=0, fill_value=255,
            crop_y_start=-1, crop_x_start=-1, max_crop_size=-1,
            min_crop_size=-1, inter_method=1)
        if (rand_crop or rand_mirror or mean_img is not None
                or any((mean_r, mean_g, mean_b))
                or scale != 1.0 or max_random_contrast
                or max_random_illumination
                or self._record_shape != self._data_shape
                or any(aug_extra[k] != defaults[k] for k in defaults)):
            from .image import ImageAugmenter

            mean_rgb = [mean_r, mean_g, mean_b] \
                if any((mean_r, mean_g, mean_b)) else None
            self._augmenter = ImageAugmenter(
                data_shape=self._data_shape, rand_crop=rand_crop,
                rand_mirror=rand_mirror,
                max_random_contrast=max_random_contrast,
                max_random_illumination=max_random_illumination,
                mean_img=mean_img, mean_rgb=mean_rgb, scale=scale,
                **aug_extra)
        self._sample_len = int(np.prod(self._record_shape))
        self._path = path_imgrec
        self._part_index = part_index
        self._num_parts = num_parts
        self._data_name = data_name
        self._label_name = label_name
        kind = self._payload_kind()
        # decode failures (zero-filled samples) observed so far; surfaced
        # from the native loader's per-batch count so mixed/corrupt .rec
        # files don't silently train on zeros
        self.decode_failures = 0
        self._warned_decode_fail = False
        if use_native is None:
            use_native = _native.available() and kind in ("npy", "jpeg")
        self._native = bool(use_native) and _native.available()
        # JPEG fast path: the C++ loader keeps batches uint8 HWC (no host
        # deinterleave/float widening, 4x smaller copies); the device does
        # layout+convert in _finish_hwc_u8
        self._native_u8 = (self._native and kind == "jpeg"
                           and _native.has_u8_loader()
                           and self._record_shape[0] in (1, 3))
        if self._native:
            import ctypes
            self._lib = _native.LIB
            opener = (self._lib.mxtpu_loader_open_u8 if self._native_u8
                      else self._lib.mxtpu_loader_open)
            self._handle = opener(
                path_imgrec.encode(), part_index, num_parts, batch_size,
                self._sample_len, preprocess_threads, prefetch_buffer)
            _native.check(self._handle != 0, "loader_open")
            if self._native_u8:
                c, h, w = self._record_shape
                self._data_buf = np.zeros((batch_size, h, w, c), np.uint8)
                self._data_ptr = self._data_buf.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_uint8))
            else:
                self._data_buf = np.zeros(
                    (batch_size,) + self._record_shape, np.float32)
                self._data_ptr = self._data_buf.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_float))
            self._label_buf = np.zeros((batch_size,), np.float32)
            self._label_ptr = self._label_buf.ctypes.data_as(
                ctypes.POINTER(ctypes.c_float))
        else:
            self._recordio_mod = _recordio
            self._f = open(path_imgrec, "rb")
            self._f.seek(0, 2)
            fsize = self._f.tell()
            chunk = fsize // num_parts
            raw_begin = chunk * part_index
            self._end = fsize if part_index == num_parts - 1 \
                else chunk * (part_index + 1)
            self._begin = 0 if part_index == 0 \
                else self._resync(raw_begin, fsize)
            self._f.seek(self._begin)

    def _payload_kind(self, sample=8):
        """Sniff the payload kind ('npy' / 'jpeg' / 'other') of the first
        few records — not just the first, so a mixed-payload .rec (JPEG
        head, PNG tail) is caught up front.  The C++ loader handles .npy
        and JPEG (in float mode, per record); anything else (PNG) must
        take the Python/PIL path rather than silently zero-filling
        samples.  A mixed jpeg/npy file routes to the native float path
        ('npy'), which dispatches per record; any 'other' forces Python.
        Deeper mixing is caught at runtime by the loader's per-batch
        decode-failure count (`mxtpu_loader_last_failed`)."""
        kinds = set()
        try:
            with open(self._path, "rb") as f:
                for _ in range(sample):
                    head = f.read(8)
                    if len(head) < 8:
                        break
                    magic, lrec = struct.unpack("<II", head)
                    if magic != 0xCED7230A:
                        return "other"
                    ln = lrec & ((1 << 29) - 1)
                    payload = f.read(min(ln, 32))
                    body = payload[24:24 + 6]
                    if body[:6] == b"\x93NUMPY":
                        kinds.add("npy")
                    elif body[:3] == b"\xff\xd8\xff":
                        kinds.add("jpeg")
                    else:
                        return "other"
                    skip = ln - len(payload)
                    skip += (4 - ln % 4) % 4
                    f.seek(skip, 1)
        except OSError:
            return "other"
        if kinds == {"jpeg"}:
            return "jpeg"
        if kinds:
            return "npy"  # npy, or mixed npy+jpeg: native float path
        return "other"

    @property
    def provide_data(self):
        return [(self._data_name, (self.batch_size,) + self._data_shape)]

    @property
    def provide_label(self):
        return [(self._label_name, (self.batch_size,))]

    def _resync(self, pos, fsize):
        """Scan to the next record magic at 4-byte alignment (the byte-range
        shard boundary rule shared with `native/recordio.cc` Resync)."""
        magic = struct.pack("<I", 0xCED7230A)
        pos = (pos + 3) & ~3
        while pos + 8 <= fsize:
            self._f.seek(pos)
            head = self._f.read(8)
            if head[:4] == magic:
                ln = struct.unpack("<I", head[4:])[0] & ((1 << 29) - 1)
                if pos + 8 + ln <= fsize:
                    return pos
            pos += 4
        return fsize

    def _read_record(self):
        pos = self._f.tell()
        if pos >= self._end:
            return None
        head = self._f.read(8)
        if len(head) < 8:
            return None
        magic, lrec = struct.unpack("<II", head)
        if magic != 0xCED7230A:
            raise MXNetError("bad record magic in %s" % self._path)
        ln = lrec & ((1 << 29) - 1)
        buf = self._f.read(ln)
        pad = (4 - ln % 4) % 4
        if pad:
            self._f.read(pad)
        return buf

    def reset(self):
        if self._native:
            self._lib.mxtpu_loader_reset(self._handle)
        else:
            self._f.seek(self._begin)

    def next(self):
        self._ensure_mean()  # before any record is consumed for this batch
        if self._native:
            nextfn = (self._lib.mxtpu_loader_next_u8 if self._native_u8
                      else self._lib.mxtpu_loader_next)
            n = nextfn(self._handle, self._data_ptr, self._label_ptr)
            if n <= 0:
                raise StopIteration
            if hasattr(self._lib, "mxtpu_loader_last_failed"):
                failed = self._lib.mxtpu_loader_last_failed(self._handle)
                if failed > 0:
                    from . import _native
                    self.decode_failures += failed
                    if not self._warned_decode_fail:
                        self._warned_decode_fail = True
                        logging.warning(
                            "ImageRecordIter: %d sample(s) in this batch "
                            "failed to decode and were zero-filled (%s); "
                            "cumulative count in .decode_failures",
                            failed, _native.last_error())
            # the loader writes every batch into the one `_data_buf`, and
            # JAX may read a host array it was handed after the call
            # returns (it may even alias it): hand it a copy, or the next
            # `next()` (a prefetch thread calls it back to back) rewrites
            # this batch's images under this batch's labels
            data = self._data_buf.copy()
            out = (self._finish_hwc_u8(data) if self._native_u8
                   else self._finish(data))
            return DataBatch(
                data=[out],
                label=[array(self._label_buf.copy())],
                pad=self.batch_size - n,
                provide_data=self.provide_data,
                provide_label=self.provide_label,
            )
        # ---- pure-python fallback ----
        # Host does the minimum (JPEG/PNG decode to uint8 HWC); float
        # conversion, NCHW layout and augmentation run ON DEVICE in
        # `_finish` — per-record numpy astype/transpose was half the cost
        # of the decode loop, and staging uint8 moves 4x fewer bytes over
        # the host->device link than f32.
        rs = self._record_shape
        rows, labels = [], []
        fast_u8 = True
        while len(rows) < self.batch_size:
            buf = self._read_record()
            if buf is None:
                break
            # force the channel count at decode (grayscale JPEGs in a color
            # dataset and vice versa, like the reference's cv2 iscolor)
            iscolor = 1 if rs[0] == 3 else (0 if rs[0] == 1 else -1)
            header, img = self._recordio_mod.unpack_img(buf, iscolor=iscolor)
            img = np.asarray(img)
            if img.ndim == 2 and rs[0] == 1:
                img = img[:, :, None]  # grayscale HW -> HW1
            if img.dtype != np.uint8 or img.shape != (rs[1], rs[2], rs[0]):
                fast_u8 = False  # .npy float/CHW payload
            rows.append(img)
            labels.append(header.label)
        n = len(rows)
        if n == 0:
            raise StopIteration
        label = np.zeros((self.batch_size,), np.float32)
        label[:n] = labels
        if fast_u8:
            data = np.zeros((self.batch_size, rs[1], rs[2], rs[0]), np.uint8)
            for i, img in enumerate(rows):
                data[i] = img
            out = self._finish_hwc_u8(data)
        else:
            data = np.zeros((self.batch_size,) + rs, np.float32)
            for i, img in enumerate(rows):
                img = np.asarray(img, np.float32)
                if img.shape == (rs[1], rs[2], rs[0]) and img.shape != rs:
                    img = img.transpose(2, 0, 1)  # HWC -> CHW
                data[i] = img.reshape(rs)
            out = self._finish(data)
        return DataBatch(
            data=[out], label=[array(label)],
            pad=self.batch_size - n,
            provide_data=self.provide_data,
            provide_label=self.provide_label,
        )

    def _ensure_mean(self):
        """`iter_normalize.h` flow: mean_img named a file that doesn't
        exist — compute it with one raw pass over this iterator (augmenter
        suspended), cache to the file, then normalize with it."""
        if self._augmenter is None or not self._augmenter.needs_mean:
            return
        from .image import compute_mean_image

        aug, self._augmenter = self._augmenter, None
        try:
            mean = compute_mean_image(self)
        finally:
            self._augmenter = aug
        aug.set_mean(mean)

    def _finish(self, data):
        """Apply the on-device augmentation pipeline (or plain wrap).
        The augmented batch stays a device array inside the NDArray — no
        host round-trip; it overlaps the train step under async dispatch."""
        if self._augmenter is None:
            return array(data)
        return NDArray(self._augmenter(data))

    def _finish_hwc_u8(self, data_u8):
        """Device-side tail of the fast decode path: stage the uint8 HWC
        batch (4x smaller transfer than f32), then transpose to NCHW and
        convert to float on device before the augmenter."""
        if not hasattr(self, "_hwc_jit"):
            import jax
            import jax.numpy as jnp

            self._hwc_jit = jax.jit(
                lambda u8: jnp.transpose(u8, (0, 3, 1, 2)).astype(
                    jnp.float32))
        x = self._hwc_jit(data_u8)
        if self._augmenter is None:
            return NDArray(x)
        return NDArray(self._augmenter(x))

    def close(self):
        if self._native and self._handle:
            self._lib.mxtpu_loader_close(self._handle)
            self._handle = 0

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
