"""Port of `tests/python/unittest/test_io.py`: iterators + recordio."""
import gzip
import os
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.io import (CSVIter, MNISTIter, NDArrayIter, PrefetchingIter,
                          ResizeIter)
from mxnet_tpu import recordio


def test_ndarray_iter_basic():
    X = np.arange(100 * 4).reshape(100, 4).astype(np.float32)
    y = np.arange(100).astype(np.float32)
    it = NDArrayIter(X, y, batch_size=10)
    batches = list(it)
    assert len(batches) == 10
    assert batches[0].data[0].shape == (10, 4)
    np.testing.assert_allclose(batches[0].data[0].asnumpy(), X[:10])
    np.testing.assert_allclose(batches[3].label[0].asnumpy(), y[30:40])
    it.reset()
    assert len(list(it)) == 10


def test_ndarray_iter_pad():
    X = np.arange(25 * 2).reshape(25, 2).astype(np.float32)
    it = NDArrayIter(X, np.zeros(25), batch_size=10, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 3
    assert batches[-1].pad == 5
    it2 = NDArrayIter(X, np.zeros(25), batch_size=10,
                      last_batch_handle="discard")
    assert len(list(it2)) == 2


def test_ndarray_iter_shuffle_covers_all():
    X = np.arange(40).reshape(40, 1).astype(np.float32)
    it = NDArrayIter(X, np.zeros(40), batch_size=10, shuffle=True)
    seen = np.concatenate([b.data[0].asnumpy().ravel() for b in it])
    assert sorted(seen.tolist()) == list(range(40))


def test_csv_iter(tmp_path):
    data = np.random.rand(20, 3).astype(np.float32)
    labels = np.arange(20).astype(np.float32)
    dpath, lpath = str(tmp_path / "d.csv"), str(tmp_path / "l.csv")
    np.savetxt(dpath, data, delimiter=",")
    np.savetxt(lpath, labels, delimiter=",")
    it = CSVIter(data_csv=dpath, data_shape=(3,), label_csv=lpath,
                 batch_size=5)
    b = next(iter(it))
    np.testing.assert_allclose(b.data[0].asnumpy(), data[:5], rtol=1e-5)
    np.testing.assert_allclose(b.label[0].asnumpy(), labels[:5])


def _write_mnist(tmp_path, n=50):
    rng = np.random.RandomState(0)
    imgs = (rng.rand(n, 28, 28) * 255).astype(np.uint8)
    lbls = (np.arange(n) % 10).astype(np.uint8)
    ipath, lpath = str(tmp_path / "imgs"), str(tmp_path / "lbls")
    with open(ipath, "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(imgs.tobytes())
    with open(lpath, "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(lbls.tobytes())
    return ipath, lpath, imgs, lbls


def test_mnist_iter(tmp_path):
    ipath, lpath, imgs, lbls = _write_mnist(tmp_path)
    it = MNISTIter(image=ipath, label=lpath, batch_size=10, shuffle=False,
                   flat=True)
    b = next(iter(it))
    assert b.data[0].shape == (10, 784)
    np.testing.assert_allclose(b.data[0].asnumpy(),
                               imgs[:10].reshape(10, -1) / 255.0, rtol=1e-5)
    it2 = MNISTIter(image=ipath, label=lpath, batch_size=10, shuffle=False)
    assert next(iter(it2)).data[0].shape == (10, 1, 28, 28)


def test_mnist_iter_sharded(tmp_path):
    """part_index/num_parts distributed sharding
    (`iter_image_recordio.cc:215-217` behavior)."""
    ipath, lpath, imgs, lbls = _write_mnist(tmp_path, n=40)
    parts = []
    for p in range(2):
        it = MNISTIter(image=ipath, label=lpath, batch_size=10, shuffle=False,
                       flat=True, part_index=p, num_parts=2)
        parts.append(np.concatenate([b.label[0].asnumpy() for b in it]))
    all_labels = np.sort(np.concatenate(parts))
    np.testing.assert_allclose(all_labels, np.sort(lbls.astype(np.float32)))


def test_resize_iter():
    X = np.zeros((30, 2), np.float32)
    base = NDArrayIter(X, np.zeros(30), batch_size=10)
    it = ResizeIter(base, size=7)
    assert len(list(it)) == 7  # wraps around the 3-batch base iter
    it.reset()
    assert len(list(it)) == 7


def test_prefetching_iter():
    X = np.arange(60).reshape(60, 1).astype(np.float32)
    base = NDArrayIter(X, np.zeros(60), batch_size=10)
    it = PrefetchingIter(base)
    batches = list(it)
    assert len(batches) == 6
    np.testing.assert_allclose(batches[0].data[0].asnumpy(), X[:10])
    it.reset()
    assert len(list(it)) == 6


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "x.rec")
    w = recordio.MXRecordIO(path, "w")
    for i in range(5):
        w.write(b"record-%d" % i)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    for i in range(5):
        assert r.read() == b"record-%d" % i
    assert r.read() is None


def test_indexed_recordio(tmp_path):
    path, idx = str(tmp_path / "x.rec"), str(tmp_path / "x.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(5):
        w.write_idx(i, b"rec-%d" % i)
    w.close()
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    assert r.read_idx(3) == b"rec-3"
    assert r.read_idx(0) == b"rec-0"
    assert sorted(r.keys) == list(range(5))


def test_recordio_pack_unpack_img(tmp_path):
    header = recordio.IRHeader(0, 3.0, 7, 0)
    img = (np.random.rand(4, 4, 3) * 255).astype(np.uint8)
    s = recordio.pack_img(header, img)
    h2, img2 = recordio.unpack_img(s)
    assert h2.label == 3.0 and h2.id == 7
    np.testing.assert_array_equal(img, img2)


def test_recordio_jpeg_png_roundtrip(tmp_path):
    """pack_img/unpack_img with real JPEG and PNG payloads (the reference
    packed JPEGs via cv2; PIL here)."""
    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    img = (rng.rand(10, 12, 3) * 255).astype(np.uint8)
    # PNG: lossless roundtrip
    s = recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img,
                          img_fmt=".png")
    hdr, out = recordio.unpack_img(s)
    assert hdr.label == 1.0
    np.testing.assert_array_equal(out, img)
    # JPEG: lossy but close on smooth content (noise is JPEG's worst case)
    yy, xx = np.mgrid[0:32, 0:32]
    smooth = np.stack([yy * 8, xx * 8, (yy + xx) * 4], -1).astype(np.uint8)
    s = recordio.pack_img(recordio.IRHeader(0, 2.0, 0, 0), smooth,
                          img_fmt=".jpg", quality=95)
    _, out = recordio.unpack_img(s)
    assert out.shape == smooth.shape
    assert np.abs(out.astype(int) - smooth.astype(int)).mean() < 8
    # CHW input auto-transposes for encoding
    s = recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0),
                          img.transpose(2, 0, 1), img_fmt=".png")
    _, out = recordio.unpack_img(s)
    np.testing.assert_array_equal(out, img)


def test_image_record_iter_jpeg_payloads(tmp_path):
    """ImageRecordIter over a pack of real JPEGs: HWC decode lands in the
    NCHW record layout."""
    from mxnet_tpu import recordio
    import mxnet_tpu as mx

    path = str(tmp_path / "jpegs.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(1)
    for i in range(6):
        img = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
        rec.write(recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, img_fmt=".png"))
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                               batch_size=3, use_native=False)
    b = next(it)
    assert b.data[0].shape == (3, 3, 8, 8)
    assert b.label[0].asnumpy().tolist() == [0.0, 1.0, 2.0]


def test_pack_img_rejects_normalized_floats():
    from mxnet_tpu import recordio
    from mxnet_tpu.base import MXNetError

    img = np.random.RandomState(0).rand(8, 8, 3)  # 0..1 float
    with pytest.raises(MXNetError):
        recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0), img,
                          img_fmt=".png")
    # 0..255 floats clip+round fine
    s = recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0), img * 255,
                          img_fmt=".png")
    _, out = recordio.unpack_img(s)
    np.testing.assert_array_equal(out, np.clip(np.round(img * 255), 0, 255))


def test_image_record_iter_grayscale_in_color_dataset(tmp_path):
    """A grayscale-mode image inside a 3-channel dataset decodes to 3
    channels instead of crashing the reshape."""
    from mxnet_tpu import recordio
    import mxnet_tpu as mx
    from PIL import Image
    import io as _io

    path = str(tmp_path / "mixed.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    color = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
    rec.write(recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0), color,
                                img_fmt=".png"))
    # hand-craft a grayscale-mode PNG record
    buf = _io.BytesIO()
    Image.fromarray((rng.rand(8, 8) * 255).astype(np.uint8), "L").save(
        buf, format="PNG")
    rec.write(recordio.pack(recordio.IRHeader(0, 1.0, 1, 0),
                            buf.getvalue()))
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                               batch_size=2, use_native=False)
    b = next(it)
    assert b.data[0].shape == (2, 3, 8, 8)
    arr = b.data[0].asnumpy()[1]
    np.testing.assert_allclose(arr[0], arr[1])  # gray replicated to RGB


def test_image_record_iter_u8_fast_path_matches_decode():
    """The uint8-HWC fast path (device-side transpose/float) must produce
    exactly the decoded pixel values as float32 NCHW."""
    import tempfile

    from mxnet_tpu import recordio

    path = os.path.join(tempfile.mkdtemp(), "u8.rec")
    rng = np.random.RandomState(7)
    imgs = []
    w = recordio.MXRecordIO(path, "w")
    for i in range(5):
        img = rng.randint(0, 255, (8, 8, 3), np.uint8)
        # PNG is lossless: decoded values equal packed values
        w.write(recordio.pack_img(recordio.IRHeader(0, float(i), i, 0),
                                  img, img_fmt=".png"))
        imgs.append(img)
    w.close()
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                               batch_size=5, use_native=False)
    b = next(it)
    got = b.data[0].asnumpy()
    expect = np.stack(imgs).transpose(0, 3, 1, 2).astype(np.float32)
    np.testing.assert_array_equal(got, expect)
    assert b.label[0].asnumpy().tolist() == [0.0, 1.0, 2.0, 3.0, 4.0]


def test_payload_kind_mixed_sniff(tmp_path):
    """_payload_kind samples several records: a mixed JPEG+PNG .rec must
    NOT route to the native loader (which would zero-fill the PNGs)."""
    from mxnet_tpu import recordio

    path = str(tmp_path / "mixed.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(3)
    img = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
    rec.write(recordio.pack_img(recordio.IRHeader(0, 0.0, 0, 0), img,
                                img_fmt=".jpg", quality=95))
    rec.write(recordio.pack_img(recordio.IRHeader(0, 1.0, 1, 0), img,
                                img_fmt=".png"))
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                               batch_size=2)
    assert not it._native  # PNG in the sample forces the Python/PIL path
    b = next(it)
    assert b.data[0].shape == (2, 3, 8, 8)


def test_native_loader_decode_failure_count(tmp_path):
    """A corrupt record past the sniff window is zero-filled by the native
    loader; the per-batch failure count must surface on the iterator."""
    from mxnet_tpu import _native, recordio

    if not _native.available():
        pytest.skip("native lib not built")
    path = str(tmp_path / "corrupt.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(5)
    hdr = struct.Struct("<IfQQ")
    for i in range(10):
        img = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
        rec.write(recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, img_fmt=".jpg",
            quality=95))
    # record 11: valid header, JPEG SOI magic, garbage body -> decode fails
    rec.write(hdr.pack(0, 10.0, 10, 0) + b"\xff\xd8\xff" + b"\x00" * 64)
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 8, 8),
                               batch_size=11, use_native=True)
    assert it._native
    b = next(it)
    assert b.pad == 0
    assert it.decode_failures == 1
    # the corrupt sample (slot 10) is zero-filled, good ones are not
    d = b.data[0].asnumpy()
    assert float(np.abs(d[10]).sum()) == 0.0
    assert float(np.abs(d[0]).sum()) > 0.0


def test_native_loader_batch_survives_the_next_write(tmp_path):
    """The native loader writes every batch into one buffer, and where that
    buffer happens to lie on a 64-byte boundary JAX's CPU backend aliases a
    host array it is handed instead of copying it: a batch whose device work
    is still queued must not change when the loader's buffer is written
    again (a prefetch thread calls `next()` back to back).  The buffer is
    put on such a boundary here; numpy's own allocation lands on one in
    some processes and not in others."""
    import ctypes

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import _native, recordio

    if not _native.available():
        pytest.skip("native lib not built")
    path = str(tmp_path / "gray.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(9)
    for i in range(32):
        rec.write(recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0),
            (rng.rand(24, 24) * 255).astype(np.uint8), img_fmt=".jpg"))
    rec.close()
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(1, 24, 24),
                               batch_size=32, scale=1.0 / 255,
                               use_native=True)
    assert it._native_u8
    raw = np.zeros(it._data_buf.nbytes + 64, np.uint8)
    off = (-raw.ctypes.data) % 64
    it._data_buf = raw[off:off + it._data_buf.nbytes].reshape(
        it._data_buf.shape)
    it._data_ptr = it._data_buf.ctypes.data_as(
        ctypes.POINTER(ctypes.c_uint8))
    want = next(it).data[0].asnumpy().copy()
    busy = jax.jit(lambda a: jnp.linalg.matrix_power(a, 64))
    big = jnp.full((512, 512), 1e-3, jnp.float32)
    for _ in range(8):
        it.reset()
        busy(big)                    # the device is busy: what follows queues
        batch = next(it)
        it._data_buf[:] = 255        # the loader's next write
        np.testing.assert_array_equal(batch.data[0].asnumpy(), want)


def test_recordio_remote_fetch_hooks(tmp_path):
    """Remote-read hooks (the dmlc::InputSplit role,
    `iter_image_recordio.cc:105-126`): file:// built in, custom schemes
    pluggable, unknown schemes raise with guidance."""
    from mxnet_tpu import recordio

    path = str(tmp_path / "imgs.rec")
    rec = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(9)
    for i in range(4):
        img = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
        rec.write(recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, img_fmt=".jpg",
            quality=95))
    rec.close()

    # file:// through both the raw reader and the image iterator
    r = recordio.MXRecordIO("file://" + path, "r")
    assert r.read() is not None
    r.close()
    it = mx.io.ImageRecordIter(path_imgrec="file://" + path,
                               data_shape=(3, 8, 8), batch_size=2)
    assert next(it).data[0].shape == (2, 3, 8, 8)

    # custom scheme: hook materializes the local file (e.g. object-store
    # download); records each fetch so we can assert it ran
    fetched = []

    def fake_s3(uri):
        fetched.append(uri)
        return path

    prev = recordio.register_fetch_hook("fakes3", fake_s3)
    try:
        it2 = mx.io.ImageRecordIter(path_imgrec="fakes3://bucket/imgs.rec",
                                    data_shape=(3, 8, 8), batch_size=2)
        assert next(it2).data[0].shape == (2, 3, 8, 8)
        assert fetched == ["fakes3://bucket/imgs.rec"]
    finally:
        recordio._FETCH_HOOKS.pop("fakes3", None)
        if prev is not None:
            recordio.register_fetch_hook("fakes3", prev)

    with pytest.raises(mx.base.MXNetError, match="no fetch hook"):
        mx.io.ImageRecordIter(path_imgrec="s3://bucket/x.rec",
                              data_shape=(3, 8, 8), batch_size=2)
