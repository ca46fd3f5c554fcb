"""IO tests (reference ``tests/python/unittest/test_io.py``)."""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io, recordio


def test_NDArrayIter():
    data = np.ones([1000, 2, 2])
    label = np.ones([1000, 1])
    for i in range(1000):
        data[i] = i / 100
        label[i] = i / 100
    dataiter = io.NDArrayIter(data, label, 128, True,
                              last_batch_handle="pad")
    batchidx = 0
    for batch in dataiter:
        batchidx += 1
    assert batchidx == 8
    dataiter = io.NDArrayIter(data, label, 128, False,
                              last_batch_handle="pad")
    batchidx = 0
    labelcount = [0] * 10
    for batch in dataiter:
        label = batch.label[0].asnumpy().flatten()
        assert (batch.data[0].asnumpy()[:, 0, 0] == label).all()
        for i in range(label.shape[0]):
            labelcount[int(label[i])] += 1
    for i in range(10):
        if i == 0:
            # pad duplicated the first entries
            assert labelcount[i] == 124
        else:
            assert labelcount[i] == 100


def test_NDArrayIter_discard():
    data = np.arange(100).reshape(100, 1)
    it = io.NDArrayIter(data, np.zeros(100), 32,
                        last_batch_handle="discard")
    n = sum(1 for _ in it)
    assert n == 3


def test_resize_iter():
    data = np.random.rand(30, 2)
    it = io.NDArrayIter(data, np.zeros(30), batch_size=10)
    r = io.ResizeIter(it, 7)
    assert sum(1 for _ in r) == 7
    r.reset()
    assert sum(1 for _ in r) == 7


def test_prefetching_iter():
    data = np.random.rand(40, 3)
    base = io.NDArrayIter(data, np.zeros(40), batch_size=10)
    pf = io.PrefetchingIter(base)
    seen = [b.data[0].asnumpy() for b in pf]
    assert len(seen) == 4
    pf.reset()
    assert sum(1 for _ in pf) == 4


def test_prefetch_overlap():
    """The engine-scheduled producer really overlaps the consumer: while
    the consumer works on batch k the producer is already at work on
    batch k+1 (the double-buffering contract of the reference's
    ``iter_prefetcher.h``).  Asserted on the event itself: a margin on the
    wall clock (under 0.8 of the serial time) broke on a loaded machine."""
    import threading
    import time

    P, nbatch = 0.05, 8
    at_work = [threading.Event() for _ in range(nbatch + 1)]

    class SlowIter(io.DataIter):
        def __init__(self):
            super().__init__(4)
            self.i = 0

        @property
        def provide_data(self):
            return [io.DataDesc("data", (4, 2))]

        @property
        def provide_label(self):
            return [io.DataDesc("softmax_label", (4,))]

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= nbatch:
                raise StopIteration
            self.i += 1
            at_work[self.i].set()
            time.sleep(P)                      # simulated decode/IO cost
            return io.DataBatch(data=[mx.nd.zeros((4, 2))],
                                label=[mx.nd.zeros((4,))], pad=0)

    pf = io.PrefetchingIter(SlowIter())
    if pf._engine is None or pf._engine.engine_type == "NaiveEngine":
        import pytest
        pytest.skip("async native engine unavailable (naive/sync mode)")
    n = 0
    for _ in pf:
        n += 1
        # the train step's time: a serial pipeline would not start the
        # next batch before this wait is over
        assert n == nbatch or at_work[n + 1].wait(20), \
            "no overlap: batch %d not begun while %d was consumed" % (n + 1, n)
    assert n == nbatch


def test_csv_iter(tmp_path):
    data = np.random.rand(24, 6).astype("f")
    label = np.arange(24).astype("f")
    dpath = str(tmp_path / "d.csv")
    lpath = str(tmp_path / "l.csv")
    np.savetxt(dpath, data, delimiter=",")
    np.savetxt(lpath, label, delimiter=",")
    it = io.CSVIter(data_csv=dpath, data_shape=(6,), label_csv=lpath,
                    batch_size=8)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (8, 6)
    got = np.concatenate([b.data[0].asnumpy() for b in batches])
    assert np.allclose(got, data, atol=1e-5)


def test_mnist_iter(tmp_path):
    """Synthesize an MNIST-format file pair and read it back."""
    import gzip
    import struct
    n = 50
    images = np.random.randint(0, 255, (n, 28, 28), dtype=np.uint8)
    labels = np.random.randint(0, 10, (n,), dtype=np.uint8)
    img_path = str(tmp_path / "img-idx3-ubyte")
    lbl_path = str(tmp_path / "lbl-idx1-ubyte")
    with open(img_path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, n, 28, 28))
        f.write(images.tobytes())
    with open(lbl_path, "wb") as f:
        f.write(struct.pack(">II", 2049, n))
        f.write(labels.tobytes())
    it = io.MNISTIter(image=img_path, label=lbl_path, batch_size=10,
                      shuffle=False, silent=True)
    batches = list(it)
    assert len(batches) == 5
    assert batches[0].data[0].shape == (10, 1, 28, 28)
    got = np.concatenate([b.label[0].asnumpy() for b in batches])
    assert np.allclose(got, labels)
    # flat mode
    it = io.MNISTIter(image=img_path, label=lbl_path, batch_size=10,
                      flat=True, shuffle=False, silent=True)
    assert next(iter(it)).data[0].shape == (10, 784)


def test_image_record_iter(tmp_path):
    rec = str(tmp_path / "img.rec")
    idx = str(tmp_path / "img.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rng = np.random.RandomState(0)
    for i in range(32):
        img = rng.randint(0, 255, (36, 36, 3), dtype=np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 4), i, 0), img))
    w.close()
    it = io.ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                            data_shape=(3, 32, 32), batch_size=8,
                            shuffle=True, rand_crop=True, rand_mirror=True,
                            preprocess_threads=2)
    count = 0
    labels = []
    for b in it:
        count += 1
        assert b.data[0].shape == (8, 3, 32, 32)
        labels.extend(b.label[0].asnumpy().tolist())
    assert count == 4
    assert sorted(set(labels)) == [0.0, 1.0, 2.0, 3.0]
    # sharding
    it_half = io.ImageRecordIter(path_imgrec=rec, path_imgidx=idx,
                                 data_shape=(3, 32, 32), batch_size=8,
                                 num_parts=2, part_index=0,
                                 preprocess_threads=2)
    assert sum(1 for _ in it_half) == 2


def test_DataBatch_str():
    batch = io.DataBatch(data=[mx.nd.ones((2, 3))],
                         label=[mx.nd.ones((2,))])
    assert "(2, 3)" in str(batch)


def test_native_image_record_iter(tmp_path):
    """Native C++ loader: same records, labels, augment contract as the
    python iterator (decode equivalence + pad/reset/shuffle semantics)."""
    from mxnet_tpu.io import NativeImageRecordIter, PyImageRecordIter
    from mxnet_tpu import recordio
    from mxnet_tpu._native import dataloader_lib
    if dataloader_lib() is None:
        import pytest
        pytest.skip("native data loader not built")
    from PIL import Image
    import io as pio
    rec_path = str(tmp_path / "d.rec")
    rng = np.random.RandomState(3)
    rec = recordio.MXRecordIO(rec_path, "w")
    for i in range(10):
        img = Image.fromarray(rng.randint(0, 255, (40, 36, 3),
                                          dtype=np.uint8))
        buf = pio.BytesIO()
        img.save(buf, format="JPEG", quality=95)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                buf.getvalue()))
    rec.close()
    common = dict(path_imgrec=rec_path, data_shape=(3, 32, 32),
                  batch_size=4, shuffle=False)
    nat = NativeImageRecordIter(**common)
    py = PyImageRecordIter(**common)
    assert nat.num_samples == 10
    nb, pb = list(nat), list(py)
    assert len(nb) == len(pb) == 3
    assert nb[-1].pad == 2                       # 10 samples, batch 4
    for a, b in zip(nb, pb):
        np.testing.assert_allclose(a.label[0].asnumpy(),
                                   b.label[0].asnumpy())
        d1, d2 = a.data[0].asnumpy(), b.data[0].asnumpy()
        # center-crop of identical libjpeg decodes: tiny tolerance
        assert np.abs(d1 - d2).mean() < 2.0
    # reset replays the epoch
    nat.reset()
    again = next(iter(nat)).data[0].asnumpy()
    np.testing.assert_allclose(again, nb[0].data[0].asnumpy())
    # shuffled epochs differ
    sh = NativeImageRecordIter(shuffle=True, seed=1, **{
        k: v for k, v in common.items() if k != "shuffle"})
    l1 = np.concatenate([b.label[0].asnumpy() for b in sh])
    sh.reset()
    l2 = np.concatenate([b.label[0].asnumpy() for b in sh])
    assert set(l1[:10]) == set(range(10))
    assert not np.array_equal(l1, l2)


def test_native_loader_multipart_record(tmp_path):
    """A payload containing the aligned RecordIO magic word is written as
    a multi-part record; the native loader must re-insert the escaped
    magic when rejoining (parity with recordio.py read())."""
    from mxnet_tpu.io import NativeImageRecordIter
    from mxnet_tpu import recordio
    from mxnet_tpu._native import dataloader_lib
    if dataloader_lib() is None:
        import pytest
        pytest.skip("native data loader not built")
    from PIL import Image
    import io as pio
    magic_label = np.frombuffer(
        np.uint32(0xced7230a).tobytes(), np.float32)[0]
    rec_path = str(tmp_path / "m.rec")
    rec = recordio.MXRecordIO(rec_path, "w")
    img = Image.fromarray(np.full((16, 16, 3), 128, np.uint8))
    buf = pio.BytesIO()
    img.save(buf, format="JPEG", quality=95)
    # labels sit at aligned payload offset 24 -> the magic-valued label
    # forces a record split right through the label block
    rec.write(recordio.pack(
        recordio.IRHeader(2, np.array([magic_label, 7.0], np.float32),
                          0, 0), buf.getvalue()))
    rec.close()
    # sanity: the writer really did produce a multi-part record
    with open(rec_path, "rb") as f:
        raw = f.read()
    assert raw[4:8] != b"" and len(raw) > 0
    import struct as _struct
    first_lrec = _struct.unpack("<I", raw[4:8])[0]
    assert first_lrec >> 29 == 1, "expected a multi-part record"
    it = NativeImageRecordIter(path_imgrec=rec_path, data_shape=(3, 12, 12),
                               batch_size=1, label_width=2)
    b = next(iter(it))
    labels = b.label[0].asnumpy()
    assert labels.view(np.uint32)[0, 0] == 0xced7230a
    assert labels[0, 1] == 7.0
    # image decoded successfully (not the zero-filled failure path)
    assert it._lib.mxt_loader_failures(it._handle) == 0
    assert abs(float(b.data[0].asnumpy().mean()) - 128.0) < 3.0


def test_image_det_record_iter(tmp_path):
    """Detection iterator: variable-object labels pad to a fixed
    (batch, num_obj, width) block (reference iter_image_det_recordio)."""
    from mxnet_tpu import recordio
    from mxnet_tpu.io import ImageDetRecordIter
    from PIL import Image
    import io as pio
    rec_path = str(tmp_path / "det.rec")
    rng = np.random.RandomState(0)
    rec = recordio.MXRecordIO(rec_path, "w")
    objs_per_img = [1, 3, 2, 0]
    for i, n_obj in enumerate(objs_per_img):
        img = Image.fromarray(rng.randint(0, 255, (24, 24, 3),
                                          dtype=np.uint8))
        buf = pio.BytesIO()
        img.save(buf, format="JPEG")
        label = []
        for j in range(n_obj):
            label += [float(j), 0.1, 0.1, 0.5, 0.5]
        label = np.array(label, np.float32)   # empty => flag 0 record
        rec.write(recordio.pack(
            recordio.IRHeader(len(label), label, i, 0), buf.getvalue()))
    rec.close()
    it = ImageDetRecordIter(path_imgrec=rec_path, data_shape=(3, 20, 20),
                            batch_size=4, label_pad_width=15)
    b = next(iter(it))
    lab = b.label[0].asnumpy()
    assert lab.shape == (4, 3, 5)
    assert lab[1, 2, 0] == 2.0          # third object of image 1
    assert lab[0, 1, 0] == -1.0         # padding
    assert (lab[3] == -1.0).all()       # zero-object image: all padding
    assert b.data[0].shape == (4, 3, 20, 20)
    # over-capacity records must error, not silently truncate
    import pytest
    rec2 = str(tmp_path / "big.rec")
    w = recordio.MXRecordIO(rec2, "w")
    big = np.arange(20, dtype=np.float32)
    img = Image.fromarray(np.zeros((8, 8, 3), np.uint8))
    buf2 = pio.BytesIO()
    img.save(buf2, format="JPEG")
    w.write(recordio.pack(recordio.IRHeader(len(big), big, 0, 0),
                          buf2.getvalue()))
    w.close()
    it2 = ImageDetRecordIter(path_imgrec=rec2, data_shape=(3, 8, 8),
                             batch_size=1, label_pad_width=15)
    with pytest.raises(Exception, match="label_pad_width"):
        next(iter(it2))
    # malformed ground truth: not a multiple of object_width
    rec3 = str(tmp_path / "odd.rec")
    w = recordio.MXRecordIO(rec3, "w")
    odd = np.arange(7, dtype=np.float32)
    w.write(recordio.pack(recordio.IRHeader(len(odd), odd, 0, 0),
                          buf2.getvalue()))
    w.close()
    it3 = ImageDetRecordIter(path_imgrec=rec3, data_shape=(3, 8, 8),
                             batch_size=1, label_pad_width=15)
    with pytest.raises(Exception, match="object_width"):
        next(iter(it3))


def test_c_iter_getters_require_current_batch():
    """io_iter_data/label/pad raise a contract MXNetError before the
    first MXDataIterNext and after end-of-stream, instead of an opaque
    AttributeError (C callers read it via MXGetLastError)."""
    import numpy as np
    import pytest
    from mxnet_tpu import c_api_support as cs
    from mxnet_tpu.base import MXNetError
    it = io.NDArrayIter(np.zeros((4, 2), "f"), np.zeros((4,), "f"),
                        batch_size=2)
    with pytest.raises(MXNetError, match="no current batch"):
        cs.io_iter_data(it)
    while cs.io_iter_next(it):
        pass
    with pytest.raises(MXNetError, match="no current batch"):
        cs.io_iter_label(it)


def test_native_loader_nhwc_layout(tmp_path):
    """layout='NHWC' decodes channels-last in C++ — bit-identical to the
    CHW output transposed — and output='numpy' keeps batches host-side
    (one H2D crossing for the consumer, none here)."""
    import pytest
    from mxnet_tpu.io import NativeImageRecordIter
    from mxnet_tpu import recordio
    from mxnet_tpu._native import dataloader_lib
    if dataloader_lib() is None:
        pytest.skip("native data loader not built")
    from PIL import Image
    import io as pio
    rec_path = str(tmp_path / "n.rec")
    rng = np.random.RandomState(7)
    rec = recordio.MXRecordIO(rec_path, "w")
    for i in range(6):
        img = Image.fromarray(rng.randint(0, 255, (40, 36, 3),
                                          dtype=np.uint8))
        buf = pio.BytesIO()
        img.save(buf, format="JPEG", quality=95)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                buf.getvalue()))
    rec.close()
    common = dict(path_imgrec=rec_path, data_shape=(3, 32, 32),
                  batch_size=3, shuffle=False, rand_crop=True,
                  rand_mirror=True, seed=5)
    chw = NativeImageRecordIter(layout="NCHW", **common)
    nhwc = NativeImageRecordIter(layout="NHWC", output="numpy", **common)
    assert nhwc.provide_data[0].shape == (3, 32, 32, 3)
    for a, b in zip(chw, nhwc):
        assert isinstance(b.data[0], np.ndarray)     # stays host-side
        assert isinstance(b.label[0], np.ndarray)
        np.testing.assert_array_equal(
            a.data[0].asnumpy().transpose(0, 2, 3, 1), b.data[0])
        np.testing.assert_array_equal(a.label[0].asnumpy(), b.label[0])
    with pytest.raises(Exception):
        NativeImageRecordIter(layout="HWCN", **common)


def test_native_nhwc_numpy_feeds_module_fit(tmp_path):
    """The bench pipeline contract in miniature: NativeImageRecordIter
    with layout='NHWC', output='numpy' feeds Module.fit directly —
    host-side batches, ONE device transfer per batch inside the
    trainer — and the model trains on it."""
    import pytest
    import mxnet_tpu as mx
    from mxnet_tpu.io import NativeImageRecordIter, PrefetchingIter
    from mxnet_tpu import recordio
    from mxnet_tpu._native import dataloader_lib
    if dataloader_lib() is None:
        pytest.skip("native data loader not built")
    from PIL import Image
    import io as pio
    rec_path = str(tmp_path / "m.rec")
    rng = np.random.RandomState(0)
    rec = recordio.MXRecordIO(rec_path, "w")
    for i in range(32):
        # class = bright vs dark image: learnable from pixels
        base = 40 if i % 2 == 0 else 200
        img = Image.fromarray(rng.randint(base, base + 40, (24, 24, 3),
                                          dtype=np.uint8))
        buf = pio.BytesIO()
        img.save(buf, format="JPEG", quality=95)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i % 2), i, 0),
                                buf.getvalue()))
    rec.close()
    it = PrefetchingIter(NativeImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, 20, 20), batch_size=8,
        layout="NHWC", output="numpy", scale=1.0 / 255,
        preprocess_threads=2))
    net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=4,
                             kernel=(3, 3), layout="NHWC", name="c")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=6, optimizer="adam",
            optimizer_params={"learning_rate": 0.01},
            initializer=mx.init.Xavier())
    it.reset()
    assert mod.score(it, "acc")[0][1] > 0.9


def _write_jpeg_rec(tmp_path, name, n, hw=(40, 36), seed=7):
    from PIL import Image
    import io as pio
    rec_path = str(tmp_path / name)
    rng = np.random.RandomState(seed)
    rec = recordio.MXRecordIO(rec_path, "w")
    for i in range(n):
        img = Image.fromarray(rng.randint(0, 255, hw + (3,),
                                          dtype=np.uint8))
        buf = pio.BytesIO()
        img.save(buf, format="JPEG", quality=95)
        rec.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                                buf.getvalue()))
    rec.close()
    return rec_path


def test_native_loader_uint8_output(tmp_path):
    """dtype='uint8' ships raw decoded bytes (quarter the H2D traffic);
    with identity normalization it is value-identical to the float
    path, and it refuses non-identity normalization rather than
    silently changing the math."""
    import pytest
    from mxnet_tpu.io import NativeImageRecordIter
    from mxnet_tpu._native import dataloader_lib
    if dataloader_lib() is None:
        pytest.skip("native data loader not built")
    rec_path = _write_jpeg_rec(tmp_path, "u8.rec", 6)
    common = dict(path_imgrec=rec_path, data_shape=(3, 32, 32),
                  batch_size=3, rand_crop=True, rand_mirror=True,
                  layout="NHWC", output="numpy", seed=5)
    f32 = NativeImageRecordIter(dtype="float32", **common)
    u8 = NativeImageRecordIter(dtype="uint8", **common)
    assert u8.provide_data[0].dtype == np.uint8
    for a, b in zip(f32, u8):
        assert b.data[0].dtype == np.uint8
        np.testing.assert_array_equal(a.data[0],
                                      b.data[0].astype(np.float32))
        np.testing.assert_array_equal(a.label[0], b.label[0])
    with pytest.raises(mx.base.MXNetError):
        NativeImageRecordIter(dtype="uint8", mean_r=123.0, **common)
    with pytest.raises(mx.base.MXNetError):
        NativeImageRecordIter(dtype="uint8", scale=1 / 255., **common)


def test_device_upload_iter(tmp_path):
    """DeviceUploadIter stages device-resident batches ahead of the
    consumer (the H2D half of the reference prefetcher contract,
    iter_prefetcher.h:28-129): arrays arrive as NDArray, epoch length
    and order are preserved, reset restarts cleanly, and the staging
    genuinely runs ahead of consumption."""
    import time
    x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
    y = np.arange(16, dtype=np.float32)
    up = io.DeviceUploadIter(io.NDArrayIter(x, y, batch_size=4), depth=2)
    seen = []
    for b in up:
        assert isinstance(b.data[0], mx.nd.NDArray)
        seen.append(b.data[0].asnumpy())
    assert len(seen) == 4
    np.testing.assert_array_equal(np.concatenate(seen, 0), x)
    up.reset()
    assert sum(1 for _ in up) == 4

    # run-ahead property: with a slow consumer, the worker has the next
    # batch staged by the time the consumer asks (queue non-empty)
    class Slow(io.DataIter):
        def __init__(self):
            super().__init__(2)
            self.n = 0
            self.provide_data = [io.DataDesc("data", (2, 3))]
            self.provide_label = [io.DataDesc("softmax_label", (2,))]
        def next(self):
            if self.n >= 6:
                raise StopIteration
            self.n += 1
            return io.DataBatch([np.ones((2, 3), np.float32)],
                                [np.zeros(2, np.float32)], pad=0)
        def reset(self):
            self.n = 0
    up2 = io.DeviceUploadIter(Slow(), depth=2)
    up2.next()
    deadline = time.time() + 5.0
    while up2._q.qsize() == 0 and time.time() < deadline:
        time.sleep(0.01)
    assert up2._q.qsize() >= 1       # staged ahead while consumer idle
    up2._shutdown_worker()


def test_fit_wraps_upload_overlap():
    """Module.fit on the fused path auto-wraps host-side train data in
    DeviceUploadIter (and tears the worker down afterwards)."""
    import mxnet_tpu.module.base_module as bm
    x = np.random.RandomState(0).randn(32, 6).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    it = io.NDArrayIter(x, y, batch_size=8, label_name="softmax_label")
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    os.environ["MXTPU_MODULE_FUSED"] = "always"
    os.environ["MXTPU_UPLOAD_OVERLAP"] = "1"   # force on (1-core CI host)
    try:
        mod = mx.mod.Module(net, context=mx.cpu())
        wrapped = {}
        orig = bm.BaseModule._maybe_overlap_uploads
        def spy(self, td):
            out = orig(self, td)
            wrapped["did"] = out is not td
            wrapped["iter"] = out
            return out
        bm.BaseModule._maybe_overlap_uploads = spy
        try:
            mod.fit(it, num_epoch=2, optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1},
                    initializer=mx.init.Uniform(0.1))
        finally:
            bm.BaseModule._maybe_overlap_uploads = orig
        assert wrapped["did"]
        assert not wrapped["iter"]._worker.is_alive()   # torn down
    finally:
        os.environ.pop("MXTPU_MODULE_FUSED", None)
        os.environ.pop("MXTPU_UPLOAD_OVERLAP", None)


class _FrameSource(io.DataIter):
    """Deterministic uint8 frames for DeviceCacheIter tests."""

    N, H, W = 20, 10, 12
    frames = np.arange(N * H * W * 3, dtype=np.uint8).reshape(N, H, W, 3)
    labels = np.arange(N, dtype=np.float32)

    def __init__(self):
        super().__init__(8)
        self.i = 0
        self.provide_data = [io.DataDesc("data", (8, self.H, self.W, 3),
                                         np.uint8)]
        self.provide_label = [io.DataDesc("softmax_label", (8,))]

    def next(self):
        if self.i >= self.N:
            raise StopIteration
        lo = self.i
        hi = min(self.N, lo + 8)
        self.i = hi
        sel = np.arange(lo, lo + 8) % self.N
        return io.DataBatch([self.frames[sel]], [self.labels[sel]],
                            pad=8 - (hi - lo))

    def reset(self):
        self.i = 0


def test_device_cache_iter_center_crop():
    """The cache reproduces the source rows exactly under a center crop
    (one upload at build, per-batch work all on device)."""
    src = _FrameSource()
    it = io.DeviceCacheIter(src, data_shape=(6, 8))
    assert it.num_data == src.N
    bs = list(it)
    assert len(bs) == 3 and bs[-1].pad == 4
    got = np.concatenate([b.data[0].asnumpy() for b in bs], 0)
    y0, x0 = (src.H - 6) // 2, (src.W - 8) // 2
    want = src.frames[np.arange(24) % src.N][:, y0:y0 + 6, x0:x0 + 8, :]
    np.testing.assert_array_equal(got, want)
    lbl = np.concatenate([b.label[0].asnumpy() for b in bs])
    np.testing.assert_array_equal(lbl, src.labels[np.arange(24) % src.N])
    it.reset()
    assert sum(1 for _ in it) == 3


def test_device_cache_iter_random_aug_provenance():
    """Every random crop/mirror emitted is literally a window of its
    labeled source frame, and epochs differ under shuffle."""
    src = _FrameSource()
    it = io.DeviceCacheIter(src, data_shape=(6, 8), rand_crop=True,
                            rand_mirror=True, shuffle=True, seed=3)
    b = it.next()
    for img, lab in zip(b.data[0].asnumpy(),
                        b.label[0].asnumpy().astype(int)):
        frame = src.frames[lab]
        windows = []
        for cand in (frame, frame[:, ::-1, :]):
            windows += [cand[y:y + 6, x:x + 8]
                        for y in range(src.H - 6 + 1)
                        for x in range(src.W - 8 + 1)]
        assert any(np.array_equal(img, w) for w in windows)
    a1 = it.next().data[0].asnumpy()
    it.reset()
    it.next()
    a2 = it.next().data[0].asnumpy()
    assert not np.array_equal(a1, a2)


def test_device_cache_iter_feeds_fit():
    net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=4,
                             kernel=(3, 3), layout="NHWC", name="c")
    net = mx.sym.Flatten(mx.sym.Activation(net, act_type="relu"))
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    os.environ["MXTPU_MODULE_FUSED"] = "always"
    try:
        mod = mx.mod.Module(net, context=mx.cpu())
        it = io.DeviceCacheIter(_FrameSource(), data_shape=(6, 8),
                                rand_crop=True)
        mod.fit(it, num_epoch=2, optimizer="sgd",
                initializer=mx.init.Xavier())
    finally:
        os.environ.pop("MXTPU_MODULE_FUSED", None)


def test_device_cache_iter_on_device_normalization():
    """mean/std fold into the on-device program: emitted batches are
    f32 and value-equal to (u8 - mean) / std of the center crop."""
    src = _FrameSource()
    mean = (10.0, 20.0, 30.0)
    std = (2.0, 4.0, 5.0)
    it = io.DeviceCacheIter(src, data_shape=(6, 8), mean=mean, std=std)
    assert it.provide_data[0].dtype == np.float32
    b = it.next()
    got = b.data[0].asnumpy()
    assert got.dtype == np.float32
    y0, x0 = (src.H - 6) // 2, (src.W - 8) // 2
    raw = src.frames[:8, y0:y0 + 6, x0:x0 + 8, :].astype(np.float32)
    want = (raw - np.asarray(mean, np.float32)) / np.asarray(std,
                                                             np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_device_cache_iter_legacy_protocol():
    """The legacy split DataIter protocol (``iter_next()`` then
    ``getdata()``/``getlabel()``/``getpad()``) observes the SAME batch
    sequence as ``next()``: ``iter_next`` stages ``current_batch`` like
    ``DeviceUploadIter`` does.  (Round-5 advisory: previously only the
    cursor advanced, so the accessors returned the PREVIOUS batch.)"""
    legacy = io.DeviceCacheIter(_FrameSource(), data_shape=(6, 8),
                                rand_crop=True, rand_mirror=True,
                                shuffle=True, seed=5)
    modern = io.DeviceCacheIter(_FrameSource(), data_shape=(6, 8),
                                rand_crop=True, rand_mirror=True,
                                shuffle=True, seed=5)
    n = 0
    while legacy.iter_next():
        want = modern.next()
        np.testing.assert_array_equal(legacy.getdata()[0].asnumpy(),
                                      want.data[0].asnumpy())
        np.testing.assert_array_equal(legacy.getlabel()[0].asnumpy(),
                                      want.label[0].asnumpy())
        assert legacy.getpad() == want.pad
        n += 1
    with pytest.raises(StopIteration):
        modern.next()
    assert n == 3
    # reset restores both protocols
    legacy.reset()
    assert legacy.iter_next()
    assert legacy.getdata()[0].shape == (8, 6, 8, 3)


def test_device_upload_iter_callable_shardings():
    """Callable shardings resolve lazily, once per staged batch — the
    hook Module.fit uses so shardings that appear after the wrapper is
    built (fused-trainer bind) still route uploads (round-5 advisory:
    a None snapshot staged to the default device and the trainer paid
    a second device_put per batch)."""
    resolved = []

    def data_sh():
        resolved.append(1)
        return [None]

    x = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
    y = np.arange(16, dtype=np.float32)
    up = io.DeviceUploadIter(io.NDArrayIter(x, y, batch_size=4),
                             data_shardings=data_sh,
                             label_shardings=lambda: [None])
    seen = [b.data[0].asnumpy() for b in up]
    np.testing.assert_array_equal(np.concatenate(seen, 0), x)
    assert len(resolved) == 4          # one resolution per staged batch
    up._shutdown_worker()


def test_device_cache_iter_shards_with_num_parts(tmp_path):
    """The docs' pod recipe: each worker caches only ITS num_parts
    shard — two part caches are disjoint and together cover the set."""
    from mxnet_tpu.io import DeviceCacheIter, NativeImageRecordIter
    from mxnet_tpu._native import dataloader_lib
    if dataloader_lib() is None:
        pytest.skip("native data loader not built")
    rec_path = _write_jpeg_rec(tmp_path, "shard.rec", 12, hw=(20, 20))
    seen = []
    for part in (0, 1):
        loader = NativeImageRecordIter(
            path_imgrec=rec_path, data_shape=(3, 16, 16), batch_size=3,
            layout="NHWC", output="numpy", dtype="uint8",
            num_parts=2, part_index=part, preprocess_threads=1)
        it = DeviceCacheIter(loader, data_shape=(12, 12))
        assert it.num_data == 6
        labels = np.concatenate([b.label[0].asnumpy() for b in it])
        seen.append(set(labels.astype(int).tolist()))
    assert seen[0].isdisjoint(seen[1])
    assert seen[0] | seen[1] == set(range(12))


class _ExplodingSource(io.DataIter):
    """Source whose next() dies mid-epoch (resilience satellite: the
    prefetcher must hand the producer's error to the consumer instead of
    stalling or ending the epoch silently)."""

    def __init__(self, blow_at=2):
        super().__init__(4)
        self.n = 0
        self.blow_at = blow_at
        self.provide_data = [io.DataDesc("data", (4, 3))]
        self.provide_label = [io.DataDesc("softmax_label", (4,))]

    def next(self):
        self.n += 1
        if self.n == self.blow_at:
            raise RuntimeError("decoder died on batch %d" % self.n)
        if self.n > 5:
            raise StopIteration
        return io.DataBatch([mx.nd.array(np.full((4, 3), self.n, "f"))],
                            [mx.nd.array(np.zeros(4, "f"))], pad=0)

    def reset(self):
        self.n = 0


def test_prefetching_iter_producer_error_reaches_consumer():
    pf = io.PrefetchingIter(_ExplodingSource(blow_at=2))
    first = pf.next()                       # batch 1 was already staged
    assert first.data[0].asnumpy()[0, 0] == 1
    with pytest.raises(RuntimeError, match="decoder died on batch 2"):
        pf.next()
    # the error is a one-shot latch: reset rearms the stream
    pf.reset()
    assert pf.next().data[0].asnumpy()[0, 0] == 1


def test_prefetching_iter_error_not_confused_with_epoch_end():
    """An error at the FIRST production must raise, not read as an empty
    epoch (next_batch[0] is None in both cases)."""
    pf = io.PrefetchingIter(_ExplodingSource(blow_at=1))
    with pytest.raises(RuntimeError, match="decoder died on batch 1"):
        pf.next()
