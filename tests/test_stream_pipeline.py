"""Overlapped streaming input pipeline (docs/how_to/perf.md "Input
pipeline"): multi-process decode ring, chunked async H2D staging,
on-device stream augmentation — plus the sharding/offset satellites.

Runs fully under ``JAX_PLATFORMS=cpu``; ``ci/run_tests.sh`` drives this
file as its own fast-tier stage under a HARD timeout so a deadlocked
ring/queue fails the gate instead of hanging it.
"""
import io as pio
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io, recordio

N_WORKERS = 2           # the CI stage contract: 2 decode processes
N_THREADS = 2           # ... and preprocess_threads=2 for thread mode


@pytest.fixture(scope="module")
def rec_with_idx(tmp_path_factory):
    """10 JPEG records + .idx sidecar (40x36 frames, label=i)."""
    from PIL import Image
    d = tmp_path_factory.mktemp("stream_rec")
    rec, idx = str(d / "img.rec"), str(d / "img.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rng = np.random.RandomState(3)
    for i in range(10):
        img = Image.fromarray(rng.randint(0, 255, (40, 36, 3),
                                          dtype=np.uint8))
        buf = pio.BytesIO()
        img.save(buf, format="JPEG", quality=95)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
    w.close()
    return rec, idx


@pytest.fixture(scope="module")
def process_iter(rec_with_idx):
    """ONE shared process-mode iterator (spawning workers costs a
    package import each; tests that only read batches share it)."""
    rec, idx = rec_with_idx
    it = io.PyImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=4, shuffle=False, preprocess_mode="process",
        decode_workers=N_WORKERS, output="numpy")
    yield it
    it.close()


# ---------------------------------------------------------------- decode ring
def test_process_decode_matches_thread(rec_with_idx, process_iter):
    """Process workers emit uint8 NHWC batches value-identical to the
    thread path's float CHW output (identity normalization), with the
    same labels, pad, and epoch length."""
    rec, idx = rec_with_idx
    th = io.PyImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=4, shuffle=False, preprocess_threads=N_THREADS)
    process_iter.reset()
    tb, pb = list(th), list(process_iter)
    assert len(tb) == len(pb) == 3
    assert pb[-1].pad == 2                       # 10 records, batch 4
    assert pb[0].data[0].dtype == np.uint8
    assert pb[0].data[0].shape == (4, 32, 32, 3)
    assert process_iter.provide_data[0].dtype == np.uint8
    for a, b in zip(tb, pb):
        np.testing.assert_array_equal(a.label[0].asnumpy(), b.label[0])
        np.testing.assert_array_equal(
            a.data[0].asnumpy(),
            b.data[0].transpose(0, 3, 1, 2).astype(np.float32))


def test_process_decode_reset_midepoch_no_leaks(rec_with_idx):
    """A mid-epoch reset() invalidates in-flight work without teardown
    (same workers, full replay), and close() leaves no worker process
    and no shared-memory slab behind."""
    from multiprocessing import shared_memory
    rec, idx = rec_with_idx
    it = io.PyImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=4, shuffle=False, preprocess_mode="process",
        decode_workers=N_WORKERS, output="numpy")
    first = it.next()                            # mid-epoch
    procs_before = [w["proc"].pid for w in it._ring._workers]
    it.reset()
    assert [w["proc"].pid for w in it._ring._workers] == procs_before, \
        "reset must reuse the ring, not respawn it"
    replay = list(it)
    assert len(replay) == 3
    np.testing.assert_array_equal(first.data[0], replay[0].data[0])
    ring = it._ring
    procs = [w["proc"] for w in ring._workers]
    shm_names = [w["shm"].name for w in ring._workers]
    it.close()
    assert it._ring is None
    for p in procs:
        assert not p.is_alive()
    for name in shm_names:
        with pytest.raises(FileNotFoundError):
            shared_memory.SharedMemory(name=name)
    it.close()                                   # idempotent


def test_process_decode_worker_crash_propagates(rec_with_idx):
    """An exception inside a decode WORKER PROCESS (driven by the
    MXTPU_FAULTS io_error directive at the decode_worker site) reaches
    the consumer as the original exception type with the worker-side
    traceback chained — and the stream continues past the bad batch."""
    rec, idx = rec_with_idx
    os.environ["MXTPU_FAULTS"] = "io_error@decode_worker"
    it = None
    try:
        # env must be set BEFORE spawn so the children inherit the spec
        it = io.PyImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
            batch_size=4, shuffle=False, preprocess_mode="process",
            decode_workers=1, output="numpy")
        with pytest.raises(OSError, match="injected io_error") as ei:
            it.next()
        cause = ei.value.__cause__
        assert cause is not None
        assert "decode worker traceback" in str(cause)
        assert "worker_main" in str(cause)       # the child-side stack
        # the ring delivers the NEXT batch after the poisoned one
        b2 = it.next()
        np.testing.assert_array_equal(b2.label[0],
                                      np.arange(4, 8, dtype=np.float32))
    finally:
        os.environ.pop("MXTPU_FAULTS", None)
        from mxnet_tpu import faults
        faults.configure("")
        if it is not None:
            it.close()


def test_process_mode_refuses_normalization(rec_with_idx):
    rec, idx = rec_with_idx
    with pytest.raises(mx.base.MXNetError, match="uint8"):
        io.PyImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
            batch_size=4, preprocess_mode="process", mean_r=123.0)
    with pytest.raises(mx.base.MXNetError, match="uint8"):
        io.PyImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
            batch_size=4, preprocess_mode="process", scale=1 / 255.)


# ---------------------------------------------------------------- satellites
def test_idx_sidecar_skips_offset_scan(rec_with_idx, monkeypatch,
                                       tmp_path):
    """With an .idx sidecar the offset table comes from the index, not
    a sequential re-read of the whole .rec (the scan still backs
    index-less files)."""
    rec, idx = rec_with_idx

    def boom(path):
        raise AssertionError("offset scan ran despite .idx sidecar")

    monkeypatch.setattr(io.PyImageRecordIter, "_scan_offsets",
                        staticmethod(boom))
    it = io.PyImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=5, shuffle=False, preprocess_threads=N_THREADS)
    labels = np.concatenate([b.label[0].asnumpy() for b in it])
    np.testing.assert_array_equal(labels, np.arange(10, dtype=np.float32))
    monkeypatch.undo()
    # index-less file (no sidecar anywhere): the scan fallback is the
    # path actually taken and yields the same table
    import shutil
    bare = str(tmp_path / "noidx.rec")
    shutil.copyfile(rec, bare)
    rec_only = io.PyImageRecordIter(
        path_imgrec=bare, data_shape=(3, 32, 32),
        batch_size=5, shuffle=False, preprocess_threads=N_THREADS)
    assert rec_only._offsets == io.PyImageRecordIter._scan_offsets(rec)


def test_num_parts_sharding_drops_no_records(rec_with_idx):
    """Contiguous sharding with the remainder spread over the first
    parts: 10 records over 3 parts = 4+3+3, disjoint, covering — the
    old ``len // num_parts`` truncation lost 10 - 3*3 = 1 record."""
    rec, idx = rec_with_idx
    seen, sizes = set(), []
    for part in range(3):
        it = io.PyImageRecordIter(
            path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
            batch_size=2, shuffle=False, num_parts=3, part_index=part,
            preprocess_threads=N_THREADS)
        labels = [int(l) for b in it
                  for l in b.label[0].asnumpy()[:len(b.label[0]) -
                                                (b.pad or 0)]]
        sizes.append(len(set(labels)))
        assert seen.isdisjoint(set(labels))
        seen |= set(labels)
    assert sizes == [4, 3, 3]
    assert seen == set(range(10))
    # helper-level contract incl. bounds check
    assert io._shard_contiguous(list(range(10)), 3, 0) == [0, 1, 2, 3]
    with pytest.raises(mx.base.MXNetError):
        io._shard_contiguous(list(range(10)), 3, 3)


def test_chunk_threshold_spares_small_arrays():
    """Below CHUNK_MIN_BYTES the upload stays ONE device_put per
    member even with chunks>1 (a 1 KB label split K ways costs
    dispatches for zero wire win); values are unchanged either way."""
    import jax
    x = np.arange(8 * 4, dtype=np.float32).reshape(8, 4)   # 128 B
    y = np.arange(8, dtype=np.float32)
    up = io.DeviceUploadIter(_NumpySource(x, y), chunks=4)  # default floor
    calls = []
    real_put = jax.device_put
    jax.device_put = lambda v, *a, **kw: calls.append(1) or \
        real_put(v, *a, **kw)
    try:
        b = up.next()
    finally:
        jax.device_put = real_put
    np.testing.assert_array_equal(b.data[0].asnumpy(), x)
    assert len(calls) == 2                                 # data + label
    up._shutdown_worker()


def test_short_dataset_wrap_fills_whole_batch(rec_with_idx):
    """A dataset smaller than the pad still fills every batch slot
    (modular wrap): 10 records at batch 16 -> one batch, pad 6, the
    tail repeating labels 0..5."""
    rec, idx = rec_with_idx
    it = io.PyImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=16, shuffle=False, preprocess_threads=N_THREADS)
    b = it.next()
    assert b.pad == 6
    assert b.data[0].shape[0] == 16
    np.testing.assert_array_equal(
        b.label[0].asnumpy(),
        np.concatenate([np.arange(10), np.arange(6)]).astype(np.float32))


def test_round_batch_false_drops_ragged_tail(rec_with_idx):
    rec, idx = rec_with_idx
    it = io.PyImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=4, shuffle=False, round_batch=False,
        preprocess_threads=N_THREADS)
    batches = list(it)
    assert len(batches) == 2                     # 10 // 4, tail dropped
    assert all((b.pad or 0) == 0 for b in batches)
    it.reset()
    assert sum(1 for _ in it) == 2


# ---------------------------------------------------------- chunked staging
class _NumpySource(io.DataIter):
    """One HOST-side numpy batch (NDArrayIter would hand the uploader
    already-device-resident NDArray slices, bypassing device_put)."""

    def __init__(self, x, y):
        super().__init__(x.shape[0])
        self.x, self.y = x, y
        self.done = False
        self.provide_data = [io.DataDesc("data", x.shape, x.dtype)]
        self.provide_label = [io.DataDesc("softmax_label", y.shape)]

    def next(self):
        if self.done:
            raise StopIteration
        self.done = True
        return io.DataBatch([self.x], [self.y], pad=0)

    def reset(self):
        self.done = False


def test_chunked_upload_bit_identical():
    """chunks=K uploads reassemble bit-identically to the single
    device_put for u8 and f32, odd and even splits — and really take
    the chunked path (K device_puts for the data member)."""
    import jax
    rng = np.random.RandomState(0)
    for dtype, k in ((np.uint8, 4), (np.float32, 3)):
        x = rng.randint(0, 255, (10, 5, 3)).astype(dtype)
        y = np.arange(10, dtype=np.float32)
        up = io.DeviceUploadIter(_NumpySource(x, y), chunks=k,
                                 chunk_min_bytes=0)
        calls = []
        real_put = jax.device_put
        jax.device_put = lambda v, *a, **kw: calls.append(1) or \
            real_put(v, *a, **kw)
        try:
            b = up.next()
        finally:
            jax.device_put = real_put
        assert len(calls) == 2 * k               # K chunks each member
        got = b.data[0].asnumpy()
        want = np.asarray(jax.device_put(x))
        assert got.dtype == want.dtype == dtype
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(b.label[0].asnumpy(), y)
        up._shutdown_worker()


def test_upload_iter_stays_depth_ahead():
    """With a fast producer and a slow consumer the staging queue holds
    depth-D batches by the time the consumer asks — and stats()
    attributes the stages (ready_ahead_frac ~1 for all but the first
    ask; consumer_wait ~0 after the pipeline fill)."""

    class Fast(io.DataIter):
        def __init__(self):
            super().__init__(2)
            self.n = 0
            self.provide_data = [io.DataDesc("data", (2, 3))]
            self.provide_label = [io.DataDesc("softmax_label", (2,))]

        def next(self):
            if self.n >= 12:
                raise StopIteration
            self.n += 1
            return io.DataBatch([np.full((2, 3), self.n, np.float32)],
                                [np.zeros(2, np.float32)], pad=0)

        def reset(self):
            self.n = 0

    depth = 3
    up = io.DeviceUploadIter(Fast(), depth=depth, chunks=2)
    up.next()                                    # starts the worker
    deadline = time.time() + 10.0
    while up._q.qsize() < depth and time.time() < deadline:
        time.sleep(0.01)
    assert up._q.qsize() == depth, "staging did not run depth ahead"
    n = 1
    while True:
        try:
            time.sleep(0.02)                     # slow consumer
            up.next()
            n += 1
        except StopIteration:
            break
    assert n == 12
    st = up.stats()
    assert st["batches_staged"] == 12
    assert st["depth"] == depth and st["chunks"] == 2
    assert st["ready_ahead_frac"] >= 0.75        # all but the fill asks
    for key in ("upload_s", "decode_wait_s", "consumer_wait_s"):
        assert st[key] >= 0.0
    up._shutdown_worker()


# ------------------------------------------------------- on-device augment
def test_stream_augment_matches_device_cache_semantics():
    """StreamAugmentIter's crops/mirrors are literal windows of the
    labeled source frame (the DeviceCacheIter provenance contract, via
    the shared _make_device_augment kernel), and mean/std emit f32."""

    class Frames(io.DataIter):
        H, W = 10, 12
        frames = np.arange(8 * H * W * 3, dtype=np.uint8).reshape(
            8, H, W, 3)

        def __init__(self):
            super().__init__(8)
            self.done = False
            self.provide_data = [io.DataDesc("data", (8, self.H, self.W, 3),
                                             np.uint8)]
            self.provide_label = [io.DataDesc("softmax_label", (8,))]

        def next(self):
            if self.done:
                raise StopIteration
            self.done = True
            return io.DataBatch([self.frames],
                                [np.arange(8, dtype=np.float32)], pad=0)

        def reset(self):
            self.done = False

    src = Frames()
    it = io.StreamAugmentIter(src, data_shape=(6, 8), rand_crop=True,
                              rand_mirror=True, seed=3)
    assert it.provide_data[0].shape == (8, 6, 8, 3)
    assert it.provide_data[0].dtype == np.uint8
    b = it.next()
    assert b.data[0].shape == (8, 6, 8, 3)
    for img, lab in zip(b.data[0].asnumpy(),
                        b.label[0].asnumpy().astype(int)):
        frame = Frames.frames[lab]
        windows = []
        for cand in (frame, frame[:, ::-1, :]):
            windows += [cand[y:y + 6, x:x + 8]
                        for y in range(Frames.H - 6 + 1)
                        for x in range(Frames.W - 8 + 1)]
        assert any(np.array_equal(img, w) for w in windows)
    # normalization folds in on device and emits float32
    src.reset()
    itn = io.StreamAugmentIter(src, data_shape=(6, 8),
                               mean=(10., 20., 30.), std=(2., 4., 5.))
    assert itn.provide_data[0].dtype == np.float32
    got = itn.next().data[0].asnumpy()
    y0, x0 = (Frames.H - 6) // 2, (Frames.W - 8) // 2
    raw = Frames.frames[:, y0:y0 + 6, x0:x0 + 8, :].astype(np.float32)
    want = (raw - np.asarray((10., 20., 30.), np.float32)) \
        / np.asarray((2., 4., 5.), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    with pytest.raises(mx.base.MXNetError, match="exceeds"):
        io.StreamAugmentIter(src, data_shape=(11, 8))


def test_composed_pipeline_process_to_device(rec_with_idx, process_iter):
    """The bench's stream wiring in miniature: process decode ring ->
    chunked DeviceUploadIter -> StreamAugmentIter -> device batches
    that a fused step could consume, value-equal to the thread-path
    reference under a center crop."""
    rec, idx = rec_with_idx
    process_iter.reset()
    up = io.DeviceUploadIter(process_iter, depth=2, chunks=2)
    it = io.StreamAugmentIter(up, data_shape=(28, 28))
    got, labels = [], []
    for b in it:
        assert isinstance(b.data[0], mx.nd.NDArray)
        fresh = b.data[0].shape[0] - (b.pad or 0)
        got.append(b.data[0].asnumpy()[:fresh])
        labels.extend(b.label[0].asnumpy()[:fresh].tolist())
    got = np.concatenate(got, axis=0)
    assert got.shape == (10, 28, 28, 3) and got.dtype == np.uint8
    assert labels == list(range(10))
    th = io.PyImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=(3, 32, 32),
        batch_size=4, shuffle=False, preprocess_threads=N_THREADS)
    ref = np.concatenate(
        [b.data[0].asnumpy()[:b.data[0].shape[0] - (b.pad or 0)]
         for b in th], axis=0).transpose(0, 2, 3, 1)[:, 2:30, 2:30, :]
    np.testing.assert_array_equal(got.astype(np.float32), ref)
    up._shutdown_worker()


# ------------------------------------------------- trainer donation/overlap
def test_trainer_donate_batch_steps_on_fresh_batches():
    """donate_batch=True: the fused step donates the staged batch
    buffers (freeing staging HBM after the on-device cast); feeding a
    FRESH batch every step — the staging pipeline's contract — trains
    normally."""
    import jax
    from mxnet_tpu.parallel import Trainer
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    t = Trainer(net, mx.optimizer.SGD(learning_rate=0.1),
                donate_batch=True)
    t.bind(data_shapes={"data": (4, 6)},
           label_shapes={"softmax_label": (4,)})
    t.init_params(mx.init.Xavier())
    rng = np.random.RandomState(0)
    for _ in range(3):
        batch = {"data": jax.device_put(
                     rng.randn(4, 6).astype(np.float32)),
                 "softmax_label": jax.device_put(
                     rng.randint(0, 2, (4,)).astype(np.float32))}
        outs = t.step(batch)
    assert np.isfinite(outs[0].asnumpy()).all()


def test_fit_upload_chunks_env(monkeypatch):
    """MXTPU_UPLOAD_CHUNKS/DEPTH thread through Module.fit's auto
    wrapper."""
    import mxnet_tpu.module.base_module as bm
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "always")
    monkeypatch.setenv("MXTPU_UPLOAD_OVERLAP", "1")
    monkeypatch.setenv("MXTPU_UPLOAD_CHUNKS", "3")
    monkeypatch.setenv("MXTPU_UPLOAD_DEPTH", "4")
    x = np.random.RandomState(0).randn(32, 6).astype(np.float32)
    y = (x.sum(axis=1) > 0).astype(np.float32)
    it = io.NDArrayIter(x, y, batch_size=8, label_name="softmax_label")
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                                name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    seen = {}
    orig = bm.BaseModule._maybe_overlap_uploads

    def spy(self, td):
        out = orig(self, td)
        seen["iter"] = out
        return out

    monkeypatch.setattr(bm.BaseModule, "_maybe_overlap_uploads", spy)
    mod.fit(it, num_epoch=1, optimizer="sgd",
            initializer=mx.init.Uniform(0.1))
    assert isinstance(seen["iter"], io.DeviceUploadIter)
    assert seen["iter"]._chunks == 3
    assert seen["iter"]._depth == 4
