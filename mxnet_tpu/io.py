"""Data iterators (reference ``python/mxnet/io.py`` + the C++ iterators in
``src/io/``).

The reference composes C++ stages ``Prefetcher(BatchLoader(Normalize(
Parser)))`` behind ``MXDataIterCreateIter``; here the same contract
(``provide_data``/``provide_label``, ``DataBatch{data,label,pad,index}``,
``reset/iter_next``) is met by Python iterators that stage host numpy
batches and hand the device transfer to JAX — double-buffered by
``PrefetchingIter`` (the analog of ``iter_prefetcher.h:28-129``'s
``ThreadedIter``) so input decode overlaps TPU compute.

Included C++-iterator equivalents: ``MNISTIter`` (``src/io/iter_mnist.cc``),
``CSVIter`` (``iter_csv.cc``), ``ImageRecordIter``
(``iter_image_recordio_2.cc`` incl. OMP-style threaded JPEG decode via a
thread pool, shuffle, part_index/num_parts sharding, and the default
augmenters of ``image_aug_default.cc``).
"""
from __future__ import annotations

import io as _pyio
import logging
import os
import queue
import struct
import threading
from collections import namedtuple
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .base import MXNetError, mx_real_t, _dtype
from .ndarray import NDArray, array
from . import _tsan
from . import faults as _faults
from . import obs as _obs
from . import ndarray as nd
from . import recordio as _recordio
from . import random as _random


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """Name/shape/dtype/layout descriptor (reference ``io.py:19-79``)."""

    def __new__(cls, name, shape, dtype=mx_real_t, layout="NCHW"):
        desc = super().__new__(cls, name, shape)
        desc.dtype = dtype
        desc.layout = layout
        return desc

    def __repr__(self):
        return "DataDesc[%s,%s,%s,%s]" % (self.name, self.shape,
                                          self.dtype, self.layout)

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict[x[0]]) for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


class DataBatch(object):
    """One batch: data/label lists of NDArray + padding info
    (reference ``io.py:82-123``)."""

    def __init__(self, data, label, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label

    def __str__(self):
        return "%s: data shapes: %s label shapes: %s" % (
            type(self).__name__, [d.shape for d in self.data],
            [l.shape for l in self.label] if self.label else [])


class DataIter(object):
    """Base iterator (reference ``io.py:126-213``)."""

    batch_size = 0

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def __next__(self):
        # fault-injection point (docs/how_to/resilience.md): ``batch``
        # counts batches this iterator DELIVERED over its lifetime, so a
        # failed fetch keeps the same index and a bounded retry loop
        # (resilience.retry_io around the fit inner loop) re-asks for
        # the batch the consumer never got
        fetched = getattr(self, "_faults_delivered", 0)
        if _faults.hit("io_error", site="iter_next", batch=fetched):
            raise OSError("injected io_error at %s batch %d"
                          % (type(self).__name__, fetched))
        batch = self.next()
        self._faults_delivered = fetched + 1
        return batch

    def reset(self):
        pass

    def next(self):
        if not self.iter_next():
            raise StopIteration
        return DataBatch(data=self.getdata(), label=self.getlabel(),
                         pad=self.getpad(), index=self.getindex())

    def iter_next(self):
        pass

    def getdata(self):
        pass

    def getlabel(self):
        pass

    def getindex(self):
        return None

    def getpad(self):
        pass


class _CurrentBatchAccessors(object):
    """The legacy DataIter getter protocol over ``self.current_batch``
    (shared by every wrapper iterator that stages whole batches)."""

    current_batch = None

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class ResizeIter(_CurrentBatchAccessors, DataIter):
    """Clamp (or stretch) another iterator to exactly ``size`` batches
    per epoch, wrapping the inner iterator's epochs as needed
    (reference contract ``io.py:216-278``).

    Contract note (intentional hardening vs the reference): an inner
    iterator that yields NO batches even after a reset raises
    ``MXNetError`` from ``iter_next`` instead of silently propagating
    ``StopIteration`` — a resized-to-N epoch over an empty source is a
    configuration error (the caller asked for ``size`` batches that can
    never exist), not an empty epoch."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        bucket_key = getattr(data_iter, "default_bucket_key", None)
        if bucket_key is not None:
            self.default_bucket_key = bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur >= self.size:
            return False
        self.cur += 1
        for _ in range(2):
            try:
                self.current_batch = self.data_iter.next()
                return True
            except StopIteration:  # inner epoch ended: wrap and retry
                self.data_iter.reset()
        raise MXNetError("inner iterator yields no batches")


class PrefetchingIter(_CurrentBatchAccessors, DataIter):
    """Double-buffering prefetcher over one or more iterators
    (reference ``io.py:281-423``; C++ analog ``iter_prefetcher.h``).

    Producer work is scheduled through the native dependency engine
    (``mxnet_tpu.engine`` over ``native/mxtpu_runtime.cc``): each wrapped
    iterator owns an engine variable; producing its next batch is an
    engine op that *writes* that variable, and the consumer waits on the
    variable before taking the batch — the same read/write dependency
    protocol the reference engine applies to its IO pipeline
    (``iter_prefetcher.h`` over ``dmlc::ThreadedIter``).  Under
    ``MXNET_ENGINE_TYPE=NaiveEngine`` production runs synchronously at
    push time (the serial debugging mode, ``src/engine/engine.cc:13-39``);
    the default threaded engine overlaps host decode with device compute.
    """

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        if self.n_iter < 1:
            raise MXNetError("PrefetchingIter needs at least one iterator")
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0].shape[0]
        try:
            from . import engine as _engine
            self._engine = _engine.get()
        except RuntimeError:
            # no native runtime on this host: degrade to synchronous
            # production (the NaiveEngine behavior)
            self._engine = None
        self._vars = [self._engine.new_variable()
                      for _ in range(self.n_iter)] if self._engine else []
        self.current_batch = [None] * self.n_iter
        self.next_batch = [None] * self.n_iter
        self._scheduled = [False] * self.n_iter
        self._errors = [None] * self.n_iter
        for i in range(self.n_iter):
            self._schedule(i)

    def _schedule(self, i):
        """Push production of iterator ``i``'s next batch as an engine op
        writing var ``i``."""

        def produce():
            # a producer failure is captured HERE (with its traceback
            # still attached to the exception object) and re-raised by
            # the consumer's next ``next()`` — NOT left to poison the
            # engine-global error slot, where it would surface at some
            # unrelated wait_all (an async checkpoint flush, GC)
            try:
                self.next_batch[i] = self.iters[i].next()
            except StopIteration:
                self.next_batch[i] = None
            except BaseException as e:              # noqa: BLE001
                self._errors[i] = e
                self.next_batch[i] = None

        if self._engine is None:
            produce()
            return
        self._scheduled[i] = True
        self._engine.push(produce, mutable_vars=[self._vars[i]])

    def _drain(self, reraise=True):
        """Wait out in-flight productions (before reset/teardown)."""
        for i in range(self.n_iter):
            if self._scheduled[i]:
                self._engine.wait_for_var(self._vars[i], reraise=reraise)
                self._scheduled[i] = False

    def __del__(self):
        # bounded: a stuck producer (blocking source) must not hang GC —
        # drain on a daemon thread with the old 1s-join patience.  With
        # nothing in flight (sync/NaiveEngine production, or already
        # drained) skip the thread entirely: Thread.start() during
        # interpreter finalization deadlocks CPython 3.10, turning a
        # clean exit into a hang
        try:
            if self._engine is None or not any(self._scheduled):
                return
            t = threading.Thread(target=lambda: self._drain(reraise=False),
                                 daemon=True, name="mxtpu-prefetch-drain")
            t.start()
            t.join(timeout=1.0)
        except Exception:
            pass

    @staticmethod
    def _renamed(rename_maps, per_iter_descs):
        """Flatten descriptors over wrapped iterators, applying the
        optional per-iterator name remapping."""
        if rename_maps is None:
            return [d for descs in per_iter_descs for d in descs]
        out = []
        for names, descs in zip(rename_maps, per_iter_descs):
            for d in descs:
                # only full descriptors participate in renaming; plain
                # (name, shape) tuples pass through untouched
                out.append(DataDesc(names[d.name], d.shape, d.dtype)
                           if isinstance(d, DataDesc) else DataDesc(*d))
        return out

    @property
    def provide_data(self):
        return self._renamed(self.rename_data,
                             [i.provide_data for i in self.iters])

    @property
    def provide_label(self):
        return self._renamed(self.rename_label,
                             [i.provide_label for i in self.iters])

    def reset(self):
        self._drain()
        for it in self.iters:
            it.reset()
        self._errors = [None] * self.n_iter
        for i in range(self.n_iter):
            self._schedule(i)

    def iter_next(self):
        for i in range(self.n_iter):
            if self._scheduled[i]:
                self._engine.wait_for_var(self._vars[i])
                self._scheduled[i] = False
        for i in range(self.n_iter):
            if self._errors[i] is not None:
                err, self._errors[i] = self._errors[i], None
                # REARM the slot before raising: a consumer that treats
                # the error as transient (fit's retry_io loop) continues
                # the stream on its next next(); without this the
                # errored slot would read as a silent end-of-epoch
                self._schedule(i)
                # re-raising the captured instance keeps the producer
                # thread's original traceback on the chain
                raise err
        if self.next_batch[0] is None:
            for b in self.next_batch:
                assert b is None, "Number of entry mismatches between iterators"
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, \
                "Different pad number in the data batches"
        lead = self.next_batch[0]
        self.current_batch = DataBatch(
            [a for b in self.next_batch for a in b.data],
            [a for b in self.next_batch for a in b.label],
            lead.pad, lead.index,
            provide_data=self.provide_data,
            provide_label=self.provide_label)
        for i in range(self.n_iter):
            self._schedule(i)
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration


class DeviceUploadIter(_CurrentBatchAccessors, DataIter):
    """Stages each batch on the accelerator AHEAD of consumption.

    ``PrefetchingIter`` overlaps host decode with device compute; this is
    the other half of the reference prefetcher contract
    (``src/io/iter_prefetcher.h:28-129``: the next batch is staged through
    pinned memory while the current one computes): a background thread
    pulls host batches from ``it`` and runs their ``jax.device_put`` —
    so the H2D crossing of batch N+1 rides under the compute of batch
    N.  The consumer
    receives batches whose arrays are already device-resident; the fused
    trainer then pays ZERO upload wait inside ``step()``.

    ``depth`` bounds device-side staging memory (depth x batch bytes).
    ``chunks`` splits each host batch into K row-chunks uploaded as K
    separate ``device_put``\\ s into COMMITTED staging buffers and
    reassembled on device (one concatenate — bit-identical to the
    single-put result): on transports that pace uploads at the wire,
    the serializer starts shipping chunk 0 while chunk 1 is still being
    pinned, and the consumer-side reassembly runs on the accelerator.
    ``stats()`` reports where the worker's wall went — ``upload_s`` vs
    ``source_s``/``decode_wait_s`` (inner-iterator wait) — plus the
    consumer's view (``consumer_wait_s``, ``ready_ahead_frac``), so a
    pipeline benchmark can attribute per-batch time to named stages.

    ``data_shardings`` / ``label_shardings`` may be lists of shardings
    OR zero-argument callables returning such lists: a callable is
    resolved PER BATCH, so a wrapper built before the consumer's
    shardings exist (``Module.fit`` wraps before the fused trainer's
    first-step compile) stages every batch onto the right devices once
    they do — instead of snapshotting ``None`` and paying a second
    ``device_put`` per batch on a data-parallel mesh.
    """

    _END = object()

    # arrays below this size ship as ONE device_put even when chunking
    # is on: splitting a 1 KB label vector into K dispatches plus an
    # on-device concatenate costs latency for zero wire win
    CHUNK_MIN_BYTES = 1 << 20

    def __init__(self, it, device=None, depth=2,
                 data_shardings=None, label_shardings=None, chunks=1,
                 chunk_min_bytes=None):
        super().__init__()
        self.it = it
        self.batch_size = getattr(it, "batch_size", 0)
        self._device = device
        self._data_shardings = data_shardings
        self._label_shardings = label_shardings
        self._depth = max(1, int(depth))
        self._chunks = max(1, int(chunks or 1))
        self._chunk_min_bytes = self.CHUNK_MIN_BYTES \
            if chunk_min_bytes is None else int(chunk_min_bytes)
        self._q = queue.Queue(self._depth)
        self._stop = threading.Event()
        self._err = None
        # stage-attribution counters are written by BOTH sides of the
        # pipeline (worker: upload/source wall; consumer: wait/hit
        # tallies) and read whole by stats() — one lock, one snapshot,
        # no mid-update reads (the lockset checker gates this).  The
        # VALUES live in the process-wide metrics registry under this
        # iterator's scope, so one obs.snapshot() sees every stage; the
        # _stats_lock stays the outer GROUP guard (registry mutex nests
        # inside it, one direction only).
        self._stats_lock = _tsan.lock("io.DeviceUploadIter._stats_lock")
        self._obs_scope = _obs.REGISTRY.scope("io.upload")
        self._c = {k: _obs.REGISTRY.counter(
            "%s.%s" % (self._obs_scope, k), initial=z)
            for k, z in (("upload_s", 0.0), ("source_s", 0.0),
                         ("consumer_wait_s", 0.0), ("batches_staged", 0),
                         ("ready_hits", 0), ("next_calls", 0))}
        self._worker = None
        self._ended = False
        # the worker starts LAZILY on the first next(): a reset (or
        # construction) must not advance the wrapped iterator before the
        # consumer actually asks for data — fit() resets after its final
        # epoch and the caller's iterator must stay at a fresh start

    @property
    def provide_data(self):
        return self.it.provide_data

    @property
    def provide_label(self):
        return self.it.provide_label

    # ------------------------------------------------------------------
    def _start_worker(self):
        self._stop.clear()
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name="mxtpu-upload")
        self._worker.start()

    def _run(self):
        import time as _time
        import jax
        nbatch = 0
        try:
            while not self._stop.is_set():
                # span per staged batch (MXTPU_OBS=1): io.source =
                # blocked on the inner iterator (decode), io.upload =
                # device_put + readiness — the uploader's rows on the
                # unified trace timeline.  corr is only FORMATTED when
                # recording (the off contract: no per-batch allocation)
                corr = ("io%d" % nbatch) if _obs.OBS else None
                t0 = _time.perf_counter()
                try:
                    with _obs.span("io.source", corr=corr, parent=None):
                        b = self.it.next()
                except StopIteration:
                    self._put(self._END)
                    return
                dt_src = _time.perf_counter() - t0
                t0 = _time.perf_counter()
                with _obs.span("io.upload", corr=corr, parent=None):
                    # resolve callable shardings lazily, once per batch
                    data_sh = self._data_shardings() \
                        if callable(self._data_shardings) \
                        else self._data_shardings
                    label_sh = self._label_shardings() \
                        if callable(self._label_shardings) \
                        else self._label_shardings
                    data = [self._upload(a, data_sh, i)
                            for i, a in enumerate(b.data)]
                    label = [self._upload(a, label_sh, i)
                             for i, a in enumerate(b.label or [])]
                    jax.block_until_ready([a.data for a in data + label])
                nbatch += 1
                with self._stats_lock:
                    if _tsan.TSAN:
                        _tsan.note_write("io.DeviceUploadIter.stats")
                    self._c["source_s"].inc(dt_src)
                    self._c["upload_s"].inc(_time.perf_counter() - t0)
                    self._c["batches_staged"].inc()
                staged = DataBatch(data=data, label=label, pad=b.pad,
                                   index=b.index,
                                   provide_data=b.provide_data,
                                   provide_label=b.provide_label)
                if not self._put(staged):
                    return
        except Exception as e:              # surface in the consumer
            self._err = e   # tsan: ok — published BEFORE the _END
            #                 sentinel; the consumer reads it only after
            #                 draining the queue (a happens-before edge
            #                 through queue.Queue's internal lock)
            self._put(self._END)

    def _upload(self, a, shardings, i):
        import jax
        if isinstance(a, NDArray):
            return a                       # already device-resident
        placement = shardings[i] if shardings else self._device
        arr = np.asarray(a)
        if self._chunks > 1 and arr.ndim > 0 \
                and arr.shape[0] >= self._chunks \
                and arr.nbytes >= self._chunk_min_bytes \
                and self._chunkable(placement):
            import jax.numpy as jnp
            if placement is None:
                # commit the staging buffers: an uncommitted chunk may
                # be re-placed by the consumer, voiding the pipelining
                placement = jax.devices()[0]
            parts = [jax.device_put(p, placement)
                     for p in np.array_split(arr, self._chunks, axis=0)]
            return NDArray(jnp.concatenate(parts, axis=0))
        return NDArray(jax.device_put(arr, placement))

    @staticmethod
    def _chunkable(placement):
        """Chunk only single-device placements: row-splitting a batch
        bound for a multi-device sharding would need per-chunk shard
        arithmetic for no wire win (each device's shard already ships
        as its own transfer)."""
        import jax
        if placement is None or isinstance(placement, jax.Device):
            return True
        try:
            return len(placement.device_set) == 1
        except Exception:                   # noqa: BLE001
            return False

    def _put(self, item):
        if _tsan.TSAN:
            _tsan.note_write("io.DeviceUploadIter.staging", lockfree=True,
                             reason="queue.Queue handoff (internal lock)")
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                pass
        return False

    def _shutdown_worker(self):
        self._stop.set()
        while self._worker is not None and self._worker.is_alive():
            try:                            # unblock a full-queue put
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._worker.join(timeout=0.05)
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    def __del__(self):
        try:
            self._shutdown_worker()
        except Exception:
            pass

    # ------------------------------------------------------------------
    def reset(self):
        self._shutdown_worker()
        self.it.reset()
        self._ended = False
        self._err = None      # a stale worker error must not resurface

    def next(self):
        import time as _time
        if self._ended:                 # exhausted: repeatable, no hang
            raise StopIteration
        if self._worker is None or not (self._worker.is_alive()
                                        or self._q.qsize()):
            self._start_worker()
        ready = bool(self._q.qsize())   # staged ahead of the ask
        t0 = _time.perf_counter()
        if _tsan.TSAN:
            _tsan.note_read("io.DeviceUploadIter.staging", lockfree=True,
                            reason="queue.Queue handoff (internal lock)")
        with _obs.span("io.wait",
                       attrs={"ready": ready} if _obs.OBS else None):
            # consumer side of the pipeline: nests under fit.fetch when
            # the fit loop is the consumer (thread-local span stack)
            item = self._q.get()
        dt_wait = _time.perf_counter() - t0
        with self._stats_lock:
            if _tsan.TSAN:
                _tsan.note_write("io.DeviceUploadIter.stats")
            self._c["next_calls"].inc()
            if ready:
                self._c["ready_hits"].inc()
            self._c["consumer_wait_s"].inc(dt_wait)
        if item is self._END:
            self._ended = True
            if self._err is not None:
                err, self._err = self._err, None
                raise err
            raise StopIteration
        self.current_batch = item
        return item

    def iter_next(self):
        try:
            self.next()
            return True
        except StopIteration:
            return False

    def stats(self):
        """Per-stage wall attribution.  Worker side: ``upload_s``
        (device_put + readiness wait) vs ``source_s`` (aliased
        ``decode_wait_s`` — blocked on the inner iterator).  Consumer
        side: ``consumer_wait_s`` (blocked on the staging queue) and
        ``ready_ahead_frac`` (fraction of ``next()`` calls served from
        an already-staged batch — 1.0 means the pipeline ran fully
        ahead of consumption).

        One atomic snapshot under the stats lock: the worker updates
        these counters mid-flight, and an unlocked read could pair a
        fresh ``upload_s`` with a stale ``batches_staged`` (the race
        the concurrency sanitizer flags).  The counters themselves are
        registry-backed (scope ``io.upload<N>``), so ``obs.snapshot()``
        reports the same numbers process-wide."""
        with self._stats_lock:
            if _tsan.TSAN:
                _tsan.note_read("io.DeviceUploadIter.stats")
            upload_s = self._c["upload_s"].value
            source_s = self._c["source_s"].value
            consumer_wait_s = self._c["consumer_wait_s"].value
            staged = self._c["batches_staged"].value
            hits = self._c["ready_hits"].value
            calls = self._c["next_calls"].value
        return {"upload_s": round(upload_s, 3),
                "source_s": round(source_s, 3),
                "decode_wait_s": round(source_s, 3),
                "consumer_wait_s": round(consumer_wait_s, 3),
                "ready_ahead_frac": round(hits / calls, 3)
                if calls else None,
                "batches_staged": staged,
                "chunks": self._chunks,
                "depth": self._depth}

    # raw-counter views kept for callers that read the old attributes
    @property
    def upload_s(self):
        return self._c["upload_s"].value

    @property
    def source_s(self):
        return self._c["source_s"].value

    @property
    def consumer_wait_s(self):
        return self._c["consumer_wait_s"].value

    @property
    def batches_staged(self):
        return self._c["batches_staged"].value


def _make_device_augment(crop, chans, rand_crop, rand_mirror, mean, std,
                         gather):
    """The jitted on-device augmentation program shared by
    ``DeviceCacheIter`` (``gather=True``: batches are gathered out of
    the HBM-resident cache by index) and ``StreamAugmentIter``
    (``gather=False``: batches arrive whole from the upload stage):
    random-or-center crop, random mirror, optional mean/std
    normalization (emitting float32), all on the accelerator."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    ch, cw = crop

    def _core(imgs, key):
        B, H, W = imgs.shape[0], imgs.shape[1], imgs.shape[2]
        kc, km = jax.random.split(key)
        if rand_crop and (H > ch or W > cw):
            oy = jax.random.randint(kc, (B,), 0, H - ch + 1)
            ox = jax.random.randint(jax.random.fold_in(kc, 1),
                                    (B,), 0, W - cw + 1)
        else:
            oy = jnp.full((B,), (H - ch) // 2)
            ox = jnp.full((B,), (W - cw) // 2)
        out = jax.vmap(
            lambda im, y, x: lax.dynamic_slice(
                im, (y, x, 0), (ch, cw, chans)))(imgs, oy, ox)
        if rand_mirror:
            flip = jax.random.bernoulli(km, 0.5, (B,))
            out = jnp.where(flip[:, None, None, None],
                            out[:, :, ::-1, :], out)
        if mean is not None or std is not None:
            out = out.astype(jnp.float32)
            if mean is not None:
                out = out - mean
            if std is not None:
                out = out / std
        return out

    if gather:
        def augment(data, labels, idx, key):
            return (_core(jnp.take(data, idx, axis=0), key),
                    jnp.take(labels, idx, axis=0))
    else:
        def augment(imgs, labels, key):
            return _core(imgs, key), labels
    return jax.jit(augment)


class StreamAugmentIter(_CurrentBatchAccessors, DataIter):
    """On-device augmentation for the STREAMING input path: wraps an
    iterator yielding uint8 NHWC frame batches (host numpy or already
    device-resident, e.g. staged by :class:`DeviceUploadIter`) and runs
    crop / mirror / normalize inside one jitted program on the
    accelerator — the streaming sibling of ``DeviceCacheIter``'s
    per-batch program (same ``_make_device_augment`` kernel).

    Division of labor with the host decode stage (docs/how_to/perf.md
    "Input pipeline"): augmentations that SHRINK the batch (crop)
    belong before the wire — they reduce the bytes shipped — while
    byte-neutral or byte-growing work (mirror, normalize, the float
    cast) belongs here, after the wire, where it costs microseconds of
    idle accelerator time instead of host CPU.  With ``data_shape``
    smaller than the incoming frames this iterator also does the crop
    (for hosts that want zero spatial work in the decode workers).
    """

    def __init__(self, inner, data_shape=None, rand_crop=False,
                 rand_mirror=False, mean=None, std=None, seed=0,
                 device=None):
        import jax
        super().__init__(getattr(inner, "batch_size", 0))
        self.it = inner
        self._device = device
        desc = inner.provide_data[0]
        if len(desc.shape) != 4:
            raise MXNetError(
                "StreamAugmentIter expects NHWC frame batches, got "
                "shape %s from %s" % (desc.shape, type(inner).__name__))
        _, H, W, C = desc.shape
        if data_shape is None:
            ch, cw = int(H), int(W)
        else:
            ch, cw = int(data_shape[-2]), int(data_shape[-1])
        if ch > H or cw > W:
            raise MXNetError("crop %s exceeds incoming frames %s"
                             % ((ch, cw), (H, W)))
        for what, v in (("mean", mean), ("std", std)):
            if v is not None and np.asarray(v).size not in (1, int(C)):
                raise MXNetError(
                    "%s has %d entries but frames have %d channels"
                    % (what, np.asarray(v).size, C))
        self._crop = (ch, cw)
        self._chans = int(C)
        self._in_dtype = desc.dtype
        self._mean = None if mean is None else np.asarray(mean, np.float32)
        self._std = None if std is None else np.asarray(std, np.float32)
        self._aug = _make_device_augment(
            self._crop, self._chans, bool(rand_crop), bool(rand_mirror),
            self._mean, self._std, gather=False)
        self._key = jax.random.key(seed)

    @property
    def provide_data(self):
        desc = self.it.provide_data[0]
        out_t = np.float32 if (self._mean is not None
                               or self._std is not None) else desc.dtype
        ch, cw = self._crop
        return [DataDesc(desc.name, (desc.shape[0], ch, cw, self._chans),
                         out_t)]

    @property
    def provide_label(self):
        return self.it.provide_label

    def reset(self):
        self.it.reset()

    def stats(self):
        inner = getattr(self.it, "stats", None)
        return inner() if callable(inner) else {}

    def next(self):
        import jax
        b = self.it.next()
        imgs = b.data[0]
        imgs = imgs.data if isinstance(imgs, NDArray) \
            else jax.device_put(np.asarray(imgs), self._device)
        lbl = b.label[0] if b.label else None
        if isinstance(lbl, NDArray):
            lbl = lbl.data
        self._key, sub = jax.random.split(self._key)
        out, lbl_out = self._aug(imgs, lbl, sub)
        self.current_batch = DataBatch(
            data=[NDArray(out)],
            label=[NDArray(lbl_out)] if lbl is not None else [],
            pad=b.pad, index=b.index,
            provide_data=self.provide_data,
            provide_label=self.provide_label)
        return self.current_batch

    def iter_next(self):
        try:
            self.next()
            return True
        except StopIteration:
            return False


class DeviceCacheIter(_CurrentBatchAccessors, DataIter):
    """Device-resident dataset cache: decode + upload the WHOLE dataset
    once, then run the per-batch pipeline — gather, random crop, random
    mirror — on the accelerator.  Per-batch host->device traffic drops
    from the image batch to one index vector (~1 KB).

    This is the TPU-native steady-state input pipeline for datasets
    that fit in HBM (a 16 GB chip holds ~80k 256x256 RGB uint8
    storage frames alongside the model; a data-parallel pod shards num_parts-fashion far beyond
    that), and the answer to a slow or serialized host link: epoch 1
    pays decode + wire once, every later batch costs an on-chip gather
    (microseconds).  The reference has no analog — its prefetcher can
    only hide, never remove, the per-batch PCIe crossing
    (``src/io/iter_prefetcher.h``).

    ``inner`` is any iterator yielding host-side batches at the STORAGE
    size (e.g. ``NativeImageRecordIter(..., output="numpy",
    dtype="uint8", layout="NHWC")`` decoding to 256x256); ``data_shape``
    (h, w) is the on-device crop emitted per batch — random when
    ``rand_crop`` else center, plus ``rand_mirror``, matching the
    standard ImageNet augmentation split (host: resize/decode; device:
    crop + flip).  ``mean``/``std`` (per-channel, in the inner
    iterator's channel order) fold the normalization into the on-device
    program too — batches then emerge float32; without them uint8
    frames stay uint8 (the fused trainer casts on device)."""

    def __init__(self, inner, data_shape=None, rand_crop=False,
                 rand_mirror=False, shuffle=False, seed=0,
                 batch_size=None, device=None, mean=None, std=None):
        import jax
        super().__init__(int(batch_size or inner.batch_size))
        self.rand_crop = bool(rand_crop)
        self.rand_mirror = bool(rand_mirror)
        self.shuffle = bool(shuffle)
        self._epoch = 0
        self._rng = np.random.RandomState(seed)
        self._key = jax.random.key(seed)
        self.data_name = inner.provide_data[0].name
        self.label_name = inner.provide_label[0].name

        # build the cache: stream the inner iterator once, uploading
        # each host batch as it arrives (bounded host memory), then
        # concatenate ON DEVICE
        dparts, lparts, n = [], [], 0
        for b in inner:
            fresh = b.data[0].shape[0] - (b.pad or 0)
            d = np.asarray(b.data[0])[:fresh]
            l = np.asarray(b.label[0])[:fresh]
            dparts.append(jax.device_put(d, device))
            lparts.append(jax.device_put(l.astype(np.float32), device))
            n += fresh
        if not n:
            raise MXNetError("DeviceCacheIter: inner iterator is empty")
        import jax.numpy as jnp
        self._data = jnp.concatenate(dparts, axis=0)
        self._label = jnp.concatenate(lparts, axis=0)
        self.num_data = n
        sh, sw = self._data.shape[1], self._data.shape[2]
        if data_shape is None:
            ch, cw = sh, sw
        else:
            ch, cw = (data_shape[-2], data_shape[-1])
        if ch > sh or cw > sw:
            raise MXNetError("crop %s exceeds cached frames %s"
                             % ((ch, cw), (sh, sw)))
        self._crop = (int(ch), int(cw))
        chans = int(self._data.shape[-1])
        for what, v in (("mean", mean), ("std", std)):
            if v is not None and np.asarray(v).size not in (1, chans):
                raise MXNetError(
                    "%s has %d entries but cached frames have %d "
                    "channels" % (what, np.asarray(v).size, chans))
        self._mean = None if mean is None else np.asarray(mean, np.float32)
        self._std = None if std is None else np.asarray(std, np.float32)
        self._order = np.arange(n)
        self.cursor = -self.batch_size
        self._aug = self._build_augment()
        if self.shuffle:
            self._rng.shuffle(self._order)

    def _build_augment(self):
        return _make_device_augment(
            self._crop, int(self._data.shape[-1]), self.rand_crop,
            self.rand_mirror, self._mean, self._std, gather=True)

    @property
    def provide_data(self):
        ch, cw = self._crop
        shape = (self.batch_size, ch, cw, int(self._data.shape[-1]))
        out_t = np.float32 if (self._mean is not None
                               or self._std is not None) \
            else self._data.dtype
        return [DataDesc(self.data_name, shape, out_t)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) + tuple(self._label.shape[1:])
        return [DataDesc(self.label_name, shape, np.float32)]

    def cache_nbytes(self):
        return int(self._data.nbytes + self._label.nbytes)

    def reset(self):
        self.cursor = -self.batch_size
        self._epoch += 1
        if self.shuffle:
            self._rng.shuffle(self._order)

    def iter_next(self):
        """Advance the cursor AND stage ``current_batch``, so the
        legacy split protocol (``iter_next()`` then ``getdata()`` /
        ``getlabel()``) observes the batch just advanced to — the same
        contract ``DeviceUploadIter.iter_next`` keeps (previously only
        the cursor moved and the accessors returned the PREVIOUS
        batch)."""
        import jax
        self.cursor += self.batch_size
        if self.cursor >= self.num_data:
            return False
        lo = self.cursor
        hi = lo + self.batch_size
        pad = max(0, hi - self.num_data)
        rows = np.take(self._order, np.arange(lo, hi), mode="wrap")
        self._key, sub = jax.random.split(self._key)
        imgs, lbls = self._aug(self._data, self._label,
                               jax.device_put(rows.astype(np.int32)), sub)
        self.current_batch = DataBatch(
            data=[NDArray(imgs)], label=[NDArray(lbls)], pad=pad,
            provide_data=self.provide_data,
            provide_label=self.provide_label)
        return True

    def next(self):
        if not self.iter_next():
            raise StopIteration
        return self.current_batch


def _init_data(data, allow_empty, default_name):
    """Normalize data into a list of (name, numpy) pairs
    (reference ``io.py:424-452``)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of them "
                        "or dict with them as values")
    for k, v in data.items():
        if not isinstance(v, NDArray):
            try:
                data[k] = array(v)
            except Exception:
                raise TypeError("Invalid type '%s' for %s, should be NDArray "
                                "or numpy.ndarray" % (type(v), k))
    return list(sorted(data.items()))


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (reference ``io.py:453-610``)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)

        self.idx = np.arange(self.data[0][1].shape[0])
        if shuffle:
            _random.np_rng().shuffle(self.idx)

            def _reorder(pairs):
                return [(k, array(v.asnumpy()[self.idx], dtype=v.dtype))
                        for k, v in pairs]

            self.data, self.label = _reorder(self.data), _reorder(self.label)

        if last_batch_handle == "discard":
            # trim to whole batches up front; the cursor then never runs
            # past a ragged tail
            keep = self.data[0][1].shape[0] // batch_size * batch_size
            self.data = [(k, v[:keep]) for k, v in self.data]
            self.label = [(k, v[:keep]) for k, v in self.label]

        self.data_list = [v for _, v in self.data] + \
            [v for _, v in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.data_list[0].shape[0]
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.last_batch_handle = last_batch_handle

    def _batch_descs(self, pairs):
        """Per-source descriptors with the batch dim swapped in."""
        return [DataDesc(k, (self.batch_size,) + tuple(v.shape[1:]),
                         v.dtype) for k, v in pairs]

    @property
    def provide_data(self):
        return self._batch_descs(self.data)

    @property
    def provide_label(self):
        return self._batch_descs(self.label)

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        # roll_over carries the unconsumed tail rows into the next
        # epoch: start the cursor early by exactly that remainder
        leftover = 0
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            leftover = (self.cursor % self.num_data) % self.batch_size
        self.cursor = leftover - self.batch_size

    def iter_next(self):
        nxt = self.cursor + self.batch_size
        self.cursor = nxt
        return nxt < self.num_data

    def next(self):
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=None)
        raise StopIteration

    def _getdata(self, source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        lo, hi = self.cursor, self.cursor + self.batch_size
        if hi <= self.num_data:
            return [v[lo:hi] for _, v in source]
        # final short batch: wrap the pad rows around to the epoch start
        wrap = hi - self.num_data
        return [array(np.concatenate([v.asnumpy()[lo:],
                                      v.asnumpy()[:wrap]], axis=0),
                      dtype=v.dtype)
                for _, v in source]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        overrun = self.cursor + self.batch_size - self.num_data
        return overrun if (self.last_batch_handle == "pad"
                           and overrun > 0) else 0


# ----------------------------------------------------------------------
# C++-iterator equivalents (registered iterators in the reference)
class MNISTIter(DataIter):
    """MNIST idx-ubyte reader (reference ``src/io/iter_mnist.cc``)."""

    def __init__(self, image="train-images-idx3-ubyte",
                 label="train-labels-idx1-ubyte", batch_size=128, shuffle=True,
                 flat=False, silent=False, seed=0, part_index=0, num_parts=1,
                 **kwargs):
        super().__init__(int(batch_size))
        img = self._read_images(image)
        lbl = self._read_labels(label)
        assert img.shape[0] == lbl.shape[0]
        if int(num_parts) > 1:
            n = img.shape[0] // int(num_parts)
            s = int(part_index) * n
            img, lbl = img[s:s + n], lbl[s:s + n]
        if _parse_bool(shuffle):
            rng = np.random.RandomState(int(seed))
            perm = rng.permutation(img.shape[0])
            img, lbl = img[perm], lbl[perm]
        img = img.astype(np.float32) / 255.0
        if _parse_bool(flat):
            img = img.reshape(img.shape[0], -1)
        else:
            img = img.reshape(img.shape[0], 1, 28, 28)
        self._iter = NDArrayIter(img, lbl.astype(np.float32),
                                 batch_size=int(batch_size),
                                 data_name="data", label_name="softmax_label")
        if not _parse_bool(silent):
            logging.info("MNISTIter: load %d images", img.shape[0])

    @staticmethod
    def _read_images(path):
        with _maybe_gzip(path) as f:
            magic, num, rows, cols = struct.unpack(">IIII", f.read(16))
            if magic != 2051:
                raise MXNetError("invalid MNIST image file %s" % path)
            return np.frombuffer(f.read(num * rows * cols),
                                 dtype=np.uint8).reshape(num, rows, cols)

    @staticmethod
    def _read_labels(path):
        with _maybe_gzip(path) as f:
            magic, num = struct.unpack(">II", f.read(8))
            if magic != 2049:
                raise MXNetError("invalid MNIST label file %s" % path)
            return np.frombuffer(f.read(num), dtype=np.uint8)

    @property
    def provide_data(self):
        return self._iter.provide_data

    @property
    def provide_label(self):
        return self._iter.provide_label

    def reset(self):
        self._iter.reset()

    def next(self):
        return self._iter.next()

    def iter_next(self):
        return self._iter.iter_next()

    def getdata(self):
        return self._iter.getdata()

    def getlabel(self):
        return self._iter.getlabel()

    def getpad(self):
        return self._iter.getpad()


def _maybe_gzip(path):
    if path.endswith(".gz"):
        import gzip
        return gzip.open(path, "rb")
    return open(path, "rb")


def _parse_bool(v):
    if isinstance(v, str):
        return v.lower() in ("true", "1", "yes")
    return bool(v)


class CSVIter(DataIter):
    """CSV reader (reference ``src/io/iter_csv.cc``)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=128, round_batch=True, **kwargs):
        super().__init__(int(batch_size))
        data_shape = _as_shape(data_shape)
        label_shape = _as_shape(label_shape)
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32, ndmin=2)
        data = data.reshape((-1,) + data_shape)
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32,
                               ndmin=2)
            label = label.reshape((-1,) + label_shape)
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros((data.shape[0],), dtype=np.float32)
        self._iter = NDArrayIter(data, label, batch_size=int(batch_size),
                                 last_batch_handle="pad" if _parse_bool(round_batch) else "discard",
                                 data_name="data", label_name="label")

    provide_data = property(lambda self: self._iter.provide_data)
    provide_label = property(lambda self: self._iter.provide_label)

    def reset(self):
        self._iter.reset()

    def next(self):
        return self._iter.next()

    def iter_next(self):
        return self._iter.iter_next()

    def getdata(self):
        return self._iter.getdata()

    def getlabel(self):
        return self._iter.getlabel()

    def getpad(self):
        return self._iter.getpad()


def _as_shape(s):
    if isinstance(s, str):
        import ast
        s = ast.literal_eval(s)
    if isinstance(s, int):
        return (s,)
    return tuple(int(x) for x in s)


def _shard_contiguous(items, num_parts, part_index):
    """Contiguous ``num_parts`` sharding with the remainder spread over
    the first parts — every record lands in exactly one part.  (The old
    ``len // num_parts`` truncation silently dropped the remainder
    records from every worker's epoch.)"""
    if num_parts <= 1:
        return list(items)
    if not 0 <= part_index < num_parts:
        raise MXNetError("part_index %d out of range for num_parts %d"
                         % (part_index, num_parts))
    base, rem = divmod(len(items), num_parts)
    start = part_index * base + min(part_index, rem)
    stop = start + base + (1 if part_index < rem else 0)
    return list(items[start:stop])


class _RemoteDecodeTraceback(Exception):
    """Carries a decode worker's formatted traceback as the
    ``__cause__`` of the re-raised original exception (the
    ``multiprocessing.pool`` RemoteTraceback pattern): the consumer
    sees the worker-side stack, not just the parent's re-raise site."""

    def __init__(self, tb):
        super().__init__("\n--- decode worker traceback ---\n%s" % tb)


class _ProcessDecodeRing:
    """Parent-side controller of the multi-process decode ring
    (``_decode_worker.worker_main`` holds the child-side protocol
    spec).  Each worker owns a ``depth``-slot shared-memory slab ring;
    batches are assigned round-robin (worker ``w`` decodes batches
    ``w, w+W, ...``), the parent reassembles global batch order from
    the tagged results, copies each slab out the moment it arrives
    (so workers run ahead regardless of consumer cadence), and bounds
    host memory at ``workers x depth`` batch slabs.

    ``submit_epoch`` invalidates in-flight work by bumping the shared
    epoch value — a mid-epoch ``reset()`` needs no teardown, no
    respawn, and cannot deadlock (workers parked on a full ring
    re-check the epoch).  ``close`` joins the workers and unlinks every
    shared-memory slab."""

    def __init__(self, rec_path, slab_shape, label_width, workers, depth,
                 resize, rand_crop, rand_mirror, seed, crop,
                 start_method=None):
        import multiprocessing as mp
        from multiprocessing import shared_memory
        from . import _decode_worker
        start_method = start_method or os.environ.get(
            "MXTPU_DECODE_START_METHOD", "spawn")
        self._ctx = mp.get_context(start_method)
        self._closed = False
        self._workers = []
        self._stash = {}
        self._expected = 0
        self._next_seq = 0
        self._delivered = 0
        self._epoch = 0
        self._depth = max(1, int(depth))
        self._slab_shape = tuple(int(s) for s in slab_shape)
        self._result_q = self._ctx.Queue()
        self._epoch_val = self._ctx.Value("i", 0)
        nbytes = int(np.prod(self._slab_shape)) * self._depth
        try:
            for wid in range(max(1, int(workers))):
                shm = shared_memory.SharedMemory(create=True, size=nbytes)
                try:
                    task_q = self._ctx.Queue()
                    sem = self._ctx.Semaphore(self._depth)
                    cfg = {"wid": wid, "rec_path": rec_path,
                           "shm_name": shm.name, "depth": self._depth,
                           "slab_shape": self._slab_shape,
                           "label_width": int(label_width),
                           "resize": int(resize), "crop": tuple(crop),
                           "rand_crop": bool(rand_crop),
                           "rand_mirror": bool(rand_mirror),
                           "seed": int(seed)}
                    proc = self._ctx.Process(
                        target=_decode_worker.worker_main,
                        args=(cfg, task_q, self._result_q, sem,
                              self._epoch_val),
                        daemon=True, name="mxtpu-decode-%d" % wid)
                    proc.start()
                    view = np.ndarray((self._depth,) + self._slab_shape,
                                      dtype=np.uint8, buffer=shm.buf)
                except BaseException:
                    # this wid's segment is in no _workers entry yet —
                    # close() below would never reach it
                    shm.close()
                    shm.unlink()
                    raise
                self._workers.append({"proc": proc, "shm": shm,
                                      "task_q": task_q, "sem": sem,
                                      "view": view})
        except BaseException:
            self.close()
            raise

    # ------------------------------------------------------------------
    def submit_epoch(self, batches):
        """Assign one epoch of ``(offsets, pad, indices)`` batch tasks
        round-robin over the workers.  Implicitly invalidates any
        in-flight work from the previous epoch."""
        self._epoch += 1
        with self._epoch_val.get_lock():
            self._epoch_val.value = self._epoch
        # stale in-flight results are drained lazily by next_batch
        # (each releases its ring slot there)
        self._stash.clear()
        self._expected = len(batches)
        self._next_seq = 0
        self._delivered = 0
        W = len(self._workers)
        for seq, (offsets, pad, idxs) in enumerate(batches):
            self._workers[seq % W]["task_q"].put(
                (self._epoch, seq, list(offsets), int(pad),
                 np.asarray(idxs)))

    def _receive(self, deadline, timeout):
        import time as _time
        while True:
            try:
                return self._result_q.get(timeout=0.2)
            except queue.Empty:
                dead = [w["proc"].name for w in self._workers
                        if not w["proc"].is_alive()]
                if dead:
                    raise MXNetError(
                        "decode worker(s) %s died without reporting — "
                        "ring aborted" % ", ".join(dead))
                if _time.monotonic() > deadline:
                    raise MXNetError(
                        "decode ring stalled: no batch within %.0f s "
                        "(epoch %d, waiting for batch %d of %d)"
                        % (timeout, self._epoch, self._next_seq,
                           self._expected))

    def next_batch(self, timeout=300.0):
        """The next in-order decoded batch as ``(uint8 NHWC data,
        labels, pad, indices)``, or ``None`` at epoch end.  A batch
        whose decode failed re-raises the worker's ORIGINAL exception,
        its child-side formatted traceback attached as ``__cause__``;
        the stream continues past it on the following call."""
        import time as _time
        if self._delivered >= self._expected:
            return None
        deadline = _time.monotonic() + timeout
        while self._next_seq not in self._stash:
            msg = self._receive(deadline, timeout)
            kind, wid, epoch, seq = msg[0], msg[1], msg[2], msg[3]
            w = self._workers[wid]
            if kind == "ok":
                slot, labels, pad, idxs = msg[4], msg[5], msg[6], msg[7]
                if epoch != self._epoch:
                    w["sem"].release()      # stale: just recycle the slot
                    continue
                # copy the slab out IMMEDIATELY and free the slot — the
                # worker runs ahead regardless of consumer cadence
                data = np.array(w["view"][slot])
                w["sem"].release()
                self._stash[seq] = ("ok", (data, labels, pad, idxs))
            else:
                exc, tb = msg[4], msg[5]
                if epoch != self._epoch:
                    continue               # slot was returned worker-side
                self._stash[seq] = ("err", (exc, tb))
        kind, payload = self._stash.pop(self._next_seq)
        self._next_seq += 1
        self._delivered += 1
        if kind == "err":
            exc, tb = payload
            raise exc from _RemoteDecodeTraceback(tb)
        return payload

    def close(self):
        if self._closed:
            return
        self._closed = True
        try:
            with self._epoch_val.get_lock():
                self._epoch_val.value = -1  # parked workers bail out
        except Exception:                   # noqa: BLE001
            pass
        for w in self._workers:
            try:
                w["task_q"].put(None)
            except Exception:               # noqa: BLE001
                pass
        for w in self._workers:
            w["proc"].join(timeout=5.0)
            if w["proc"].is_alive():
                w["proc"].terminate()
                w["proc"].join(timeout=2.0)
        try:                # free the feeder thread before closing
            while True:
                self._result_q.get_nowait()
        except (queue.Empty, OSError, ValueError):
            pass
        self._result_q.close()
        for w in self._workers:
            try:
                w["task_q"].close()
            except Exception:               # noqa: BLE001
                pass
            w["view"] = None               # release the exported buffer
            w["shm"].close()
            try:
                w["shm"].unlink()
            except FileNotFoundError:
                pass
        self._workers = []

    def __del__(self):
        try:
            self.close()
        except Exception:                   # noqa: BLE001
            pass


class PyImageRecordIter(DataIter):
    """RecordIO image iterator with threaded OR multi-process decode.

    Python-native equivalent of ``src/io/iter_image_recordio_2.cc:28-120``
    (parser with OMP decode threads) + ``image_aug_default.cc`` (resize,
    random/center crop, mirror, HSL jitter) + normalize/batch/prefetch
    stages.

    ``preprocess_mode`` selects the decode engine:

    * ``"thread"`` (default, the ``preprocess_threads``-compatible
      path): a ``ThreadPoolExecutor`` decode pool + a producer thread
      double-buffering ready batches.  GIL-bound — PIL decode releases
      the GIL only partially and the float normalize/transpose never
      does — but works everywhere and keeps the reference float-CHW
      output contract.
    * ``"process"``: ``decode_workers`` (default ``preprocess_threads``)
      spawned worker processes (``_decode_worker.worker_main``), each
      seeking its own slice of the RecordIO by byte offset and decoding
      JPEG → **uint8 NHWC** into a ``multiprocessing.shared_memory``
      ring of ``prefetch_buffer`` batch slabs — true decode
      parallelism, no GIL.  Color math (normalize/scale) is refused
      here by design: raw bytes cross the wire and the jitted consumer
      (``StreamAugmentIter`` / the fused trainer's on-device cast)
      finishes the pipeline on the accelerator.  Falls back to spawn's
      semantics everywhere; on spawn-hostile platforms use
      ``"thread"``.

    ``output="numpy"`` keeps batches host-side (the staging pipeline's
    contract: exactly one H2D crossing, owned by ``DeviceUploadIter``).
    """

    def __init__(self, path_imgrec, data_shape, batch_size,
                 path_imgidx=None, label_width=1, shuffle=False,
                 rand_crop=False, rand_mirror=False, mean_img=None,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0, std_r=1.0, std_g=1.0,
                 std_b=1.0, scale=1.0, resize=-1, max_random_scale=1.0,
                 min_random_scale=1.0, max_rotate_angle=0,
                 max_aspect_ratio=0.0, random_h=0, random_s=0, random_l=0,
                 preprocess_threads=4, prefetch_buffer=4, part_index=0,
                 num_parts=1, round_batch=True, seed=0, data_name="data",
                 label_name="softmax_label", preprocess_mode="thread",
                 decode_workers=None, output="ndarray", **kwargs):
        super().__init__(int(batch_size))
        self.data_shape = _as_shape(data_shape)
        assert len(self.data_shape) == 3, "data_shape must be (c, h, w)"
        if preprocess_mode not in ("thread", "process"):
            raise MXNetError("preprocess_mode must be thread or process, "
                             "got %r" % (preprocess_mode,))
        if output not in ("ndarray", "numpy"):
            raise MXNetError("output must be ndarray or numpy, got %r"
                             % (output,))
        self.preprocess_mode = preprocess_mode
        self.output = output
        self.label_width = int(label_width)
        self.shuffle = _parse_bool(shuffle)
        self.rand_crop = _parse_bool(rand_crop)
        self.rand_mirror = _parse_bool(rand_mirror)
        self.round_batch = _parse_bool(round_batch)
        self.scale = float(scale)
        self.resize = int(resize)
        self.mean = None
        if mean_img is not None and os.path.isfile(str(mean_img)):
            m = nd.load(str(mean_img))
            self.mean = list(m.values())[0].asnumpy() if isinstance(m, dict) \
                else m[0].asnumpy()
        elif float(mean_r) or float(mean_g) or float(mean_b):
            self.mean = np.array([float(mean_b), float(mean_g),
                                  float(mean_r)]).reshape(3, 1, 1)
        self.std = np.array([float(std_b), float(std_g),
                             float(std_r)]).reshape(3, 1, 1)
        if self.preprocess_mode == "process":
            if type(self) is not PyImageRecordIter:
                raise MXNetError(
                    "preprocess_mode='process' supports plain image "
                    "records only (%s overrides the decode hook; use "
                    "thread mode)" % type(self).__name__)
            if self.mean is not None or self.scale != 1.0 or \
                    not np.all(self.std == 1.0):
                raise MXNetError(
                    "preprocess_mode='process' ships raw uint8 NHWC: "
                    "mean/std/scale must be identity — normalize on "
                    "device instead (StreamAugmentIter or the fused "
                    "trainer's cast)")
        self.data_name = data_name
        self.label_name = label_name
        self._seed = int(seed)
        self.rng = np.random.RandomState(self._seed)

        self._rec_path = path_imgrec
        self._record = _recordio.MXIndexedRecordIO(
            path_imgidx or os.path.splitext(path_imgrec)[0] + ".idx",
            path_imgrec, "r") if (path_imgidx or os.path.isfile(
                os.path.splitext(path_imgrec)[0] + ".idx")) \
            else _recordio.MXRecordIO(path_imgrec, "r")
        if isinstance(self._record, _recordio.MXIndexedRecordIO) \
                and self._record.keys:
            # the .idx sidecar already maps every record to its byte
            # offset — no sequential re-read of the whole .rec
            self._offsets = self._record.offsets()
        else:
            self._offsets = self._scan_offsets(path_imgrec)
        self._offsets = _shard_contiguous(self._offsets, int(num_parts),
                                          int(part_index))
        self._order = np.arange(len(self._offsets))
        self._ring = None
        self._ring_depth = max(2, int(prefetch_buffer))
        self._decode_workers = max(1, int(decode_workers
                                          or preprocess_threads or 1))
        self._pool = None
        if self.preprocess_mode == "thread":
            self._pool = ThreadPoolExecutor(
                max_workers=int(preprocess_threads))
        self._queue: "queue.Queue" = queue.Queue(maxsize=int(prefetch_buffer))
        self._producer = None
        self._stop = threading.Event()
        self._epoch_done = False
        self.reset()

    @staticmethod
    def _scan_offsets(path):
        """Sequential full-file scan — the fallback when no ``.idx``
        sidecar exists (the indexed path reads the offsets straight
        from ``MXIndexedRecordIO.offsets()``)."""
        from . import _decode_worker
        return _decode_worker.scan_offsets(path)

    @property
    def provide_data(self):
        if self.preprocess_mode == "process":
            c, h, w = self.data_shape
            return [DataDesc(self.data_name,
                             (self.batch_size, h, w, c), np.uint8)]
        return [DataDesc(self.data_name,
                         (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self.label_width == 1 \
            else (self.batch_size, self.label_width)
        return [DataDesc(self.label_name, shape)]

    # -- producer pipeline ---------------------------------------------
    def _epoch_batches(self):
        """The epoch's batch plan: ``(record_indices, pad)`` per batch.
        ``round_batch=True`` wraps the ragged tail from the epoch start
        (reporting ``pad``); ``False`` drops it — the same mapping
        ``CSVIter`` applies (pad vs discard)."""
        bs = self.batch_size
        out = []
        for i in range(0, len(self._order), bs):
            idxs = self._order[i:i + bs]
            pad = bs - len(idxs)
            if pad > 0:
                if not self.round_batch:
                    break
                # modular wrap: a dataset smaller than the pad still
                # fills every slot (plain self._order[:pad] came up
                # short and underfilled the batch)
                idxs = np.concatenate([
                    idxs, np.take(self._order, np.arange(pad),
                                  mode="wrap")])
            out.append((idxs, pad))
        return out

    def reset(self):
        if self.shuffle:
            self.rng.shuffle(self._order)
        self._epoch_done = False
        if self.preprocess_mode == "process":
            if self._ring is None:
                c, h, w = self.data_shape
                self._ring = _ProcessDecodeRing(
                    rec_path=self._rec_path,
                    slab_shape=(self.batch_size, h, w, c),
                    label_width=self.label_width,
                    workers=self._decode_workers,
                    depth=self._ring_depth, resize=self.resize,
                    rand_crop=self.rand_crop,
                    rand_mirror=self.rand_mirror, seed=self._seed,
                    crop=(h, w))
            self._ring.submit_epoch(
                [([self._offsets[j] for j in idxs], pad, idxs.copy())
                 for idxs, pad in self._epoch_batches()])
            return
        self._drain()
        self._stop.clear()
        self._producer = threading.Thread(target=self._produce, daemon=True,
                                          name="mxtpu-decode")
        self._producer.start()

    def close(self):
        """Tear down the decode pipeline: the process-mode ring (worker
        processes + shared-memory slabs) AND the thread-mode producer.
        Idempotent; also runs at GC for the ring.  The thread producer
        is stopped here because a mid-epoch abandon used to leave it
        parked in its bounded-put loop until process exit — the
        ``mxtpu-decode`` thread held a reference to this iterator (its
        bound ``_produce``), so GC never fired and the thread leaked
        (the conftest ``mxtpu-*`` leak check catches exactly this)."""
        if self._ring is not None:
            self._ring.close()
            self._ring = None
        if self._producer is not None and \
                self._producer is not threading.current_thread():
            self._drain()

    def __del__(self):
        try:
            self.close()
        except Exception:                   # noqa: BLE001
            pass

    def _drain(self):
        if self._producer is not None:
            self._stop.set()
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._producer.join(timeout=5.0)
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._producer = None

    def _read_record(self, offset):
        self._record.seek_to(offset)
        return self._record.read()

    def _decode_one(self, raw):
        header, img = _recordio.unpack_img(raw)
        label = np.asarray(header.label, dtype=np.float32) \
            if header.flag > 0 else np.float32(header.label)
        return self._augment(img), label

    def _augment(self, img):
        """resize -> crop -> mirror (the shared spatial stage) ->
        normalize; CHW float out."""
        from ._decode_worker import spatial_augment
        c, h, w = self.data_shape
        img = spatial_augment(img, h, w, self.resize, self.rand_crop,
                              self.rand_mirror, self.rng)
        chw = img.transpose(2, 0, 1).astype(np.float32)
        if self.mean is not None:
            chw = chw - self.mean
        chw = chw / self.std
        return chw * self.scale

    def _produce(self):
        try:
            self._produce_impl()
        except BaseException as e:  # surfaced in next(); never deadlock
            self._queue.put(e)
            self._queue.put(None)  # later next() calls see end-of-epoch

    def _produce_impl(self):
        bs = self.batch_size
        for idxs, pad in self._epoch_batches():
            if self._stop.is_set():
                return
            raws = [self._read_record(self._offsets[j]) for j in idxs]
            decoded = list(self._pool.map(self._decode_one, raws))
            data = np.stack([d for d, _ in decoded])
            labels = np.stack([l for _, l in decoded])
            if self.label_width == 1:
                labels = labels.reshape(bs)
            item = (data, labels, pad, idxs.copy())
            while not self._stop.is_set():  # never drop a decoded batch
                try:
                    self._queue.put(item, timeout=0.5)
                    break
                except queue.Full:
                    continue
            if self._stop.is_set():
                return
        self._queue.put(None)

    def next(self):
        if self.preprocess_mode == "process":
            return self._next_process()
        item = self._queue.get()
        if item is None:
            self._epoch_done = True
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        data, labels, pad, idxs = item
        if self.output == "numpy":
            return DataBatch(data=[data], label=[labels],
                             pad=pad, index=idxs)
        return DataBatch(data=[array(data)], label=[array(labels)],
                         pad=pad, index=idxs)

    def _next_process(self):
        if self._epoch_done:
            raise StopIteration
        item = self._ring.next_batch()
        if item is None:
            self._epoch_done = True
            raise StopIteration
        data, labels, pad, idxs = item
        if self.label_width == 1:
            labels = labels.reshape(self.batch_size)
        if self.output == "numpy":
            return DataBatch(data=[data], label=[labels],
                             pad=pad, index=idxs)
        return DataBatch(data=[array(data)], label=[array(labels)],
                         pad=pad, index=idxs)

    def iter_next(self):
        try:
            self._next_batch = self.next()
            return True
        except StopIteration:
            return False


def _decode_lrec_mod(lrec):
    return lrec >> 29, lrec & ((1 << 29) - 1)


# Factory parity with the registered C++ iterators


class NativeImageRecordIter(DataIter):
    """RecordIO image iterator backed by the native C++ loader
    (``native/mxtpu_dataloader.cc``): libjpeg/libpng decode + augment on
    a C++ thread pool — true decode parallelism, no GIL (the analog of
    the reference's OMP ``ImageRecordIOParser2``,
    ``iter_image_recordio_2.cc:104-120``).  Same record bytes, same
    augmentations (resize-short, random/center crop, mirror, mean/std),
    same BGR/CHW float output as the python path."""

    def __init__(self, path_imgrec, data_shape, batch_size, label_width=1,
                 shuffle=False, rand_crop=False, rand_mirror=False,
                 mean_r=0.0, mean_g=0.0, mean_b=0.0, std_r=1.0, std_g=1.0,
                 std_b=1.0, scale=1.0, resize=-1, preprocess_threads=4,
                 part_index=0, num_parts=1, seed=0, data_name="data",
                 label_name="softmax_label", layout="NCHW",
                 output="ndarray", dtype="float32", **kwargs):
        super().__init__(int(batch_size))
        from ._native import dataloader_lib
        import ctypes
        self._lib = dataloader_lib()
        assert self._lib is not None, "native data loader unavailable"
        self.data_shape = _as_shape(data_shape)
        assert len(self.data_shape) == 3
        # layout: "NCHW" (reference default) or "NHWC" (TPU-native; the
        # C++ loop decodes channels-innermost, no host transpose).
        # data_shape stays (C, H, W) in BOTH cases, like the reference's
        # parameter contract; only the emitted batch layout changes.
        if layout not in ("NCHW", "NHWC"):
            raise MXNetError("layout must be NCHW or NHWC, got %r" % layout)
        self.layout = layout
        # output: "ndarray" uploads each batch to the default device;
        # "numpy" keeps batches host-side so a host-feeding consumer
        # (e.g. a sharded trainer doing its own device_put) pays exactly
        # one H2D crossing per batch
        if output not in ("ndarray", "numpy"):
            raise MXNetError("output must be ndarray or numpy, got %r"
                             % output)
        self.output = output
        # dtype: "float32" (normalized, reference semantics) or "uint8"
        # (raw decoded bytes, quarter the host->device traffic; the
        # trainer casts + normalizes on device).  u8 is only exact when
        # the loader-side normalization is identity, so refuse otherwise
        # rather than silently changing the math.
        if dtype not in ("float32", "uint8"):
            raise MXNetError("dtype must be float32 or uint8, got %r"
                             % dtype)
        if dtype == "uint8" and not (
                mean_r == mean_g == mean_b == 0.0
                and std_r == std_g == std_b == 1.0 and scale == 1.0):
            raise MXNetError(
                "dtype='uint8' emits raw bytes: mean/std/scale must be "
                "identity (normalize on device instead)")
        self.dtype = np.dtype(dtype)
        self.label_width = int(label_width)
        if self.label_width < 1:
            raise MXNetError("label_width must be >= 1")
        self.data_name = data_name
        self.label_name = label_name
        c, h, w = self.data_shape
        mean = (ctypes.c_float * 3)(float(mean_b), float(mean_g),
                                    float(mean_r))     # BGR plane order
        std = (ctypes.c_float * 3)(float(std_b), float(std_g),
                                   float(std_r))
        self._handle = self._lib.mxt_loader_create(
            str(path_imgrec).encode(), int(batch_size), int(c), int(h),
            int(w), int(label_width), int(_parse_bool(shuffle)),
            int(_parse_bool(rand_crop)), int(_parse_bool(rand_mirror)),
            int(resize), float(scale), mean, std,
            int(preprocess_threads), int(seed) & 0xffffffff,
            int(part_index), int(num_parts))
        if not self._handle:
            raise MXNetError("cannot open record file %s" % path_imgrec)
        if self.layout == "NHWC":
            self._lib.mxt_loader_set_layout(self._handle, 1)
        self.num_samples = int(self._lib.mxt_loader_count(self._handle))

    @property
    def _batch_data_shape(self):
        c, h, w = self.data_shape
        if self.layout == "NHWC":
            return (self.batch_size, h, w, c)
        return (self.batch_size, c, h, w)

    @property
    def provide_data(self):
        return [DataDesc(self.data_name, self._batch_data_shape,
                         self.dtype)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self.label_width == 1 \
            else (self.batch_size, self.label_width)
        return [DataDesc(self.label_name, shape)]

    def reset(self):
        self._lib.mxt_loader_reset(self._handle)

    def next(self):
        import ctypes
        data = np.empty(self._batch_data_shape, self.dtype)
        label = np.empty((self.batch_size, self.label_width), np.float32)
        if self.dtype == np.uint8:
            fresh = self._lib.mxt_loader_next_u8(
                self._handle,
                data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                label.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        else:
            fresh = self._lib.mxt_loader_next(
                self._handle,
                data.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                label.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if fresh <= 0:
            raise StopIteration
        if self.label_width == 1:
            label = label.reshape(self.batch_size)
        if self.output == "numpy":
            return DataBatch(data=[data], label=[label],
                             pad=self.batch_size - fresh)
        return DataBatch(data=[array(data)], label=[array(label)],
                         pad=self.batch_size - fresh)

    # legacy DataIter protocol (iter_next/getdata/... loop)
    def iter_next(self):
        try:
            self._current = self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self._current.data

    def getlabel(self):
        return self._current.label

    def getpad(self):
        return self._current.pad

    def getindex(self):
        return None

    def __del__(self):
        try:
            if getattr(self, "_handle", None):
                self._lib.mxt_loader_free(self._handle)
                self._handle = None
        except Exception:
            pass


# python-path-only options and their defaults; passing one at a
# NON-default value selects the python iterator (the native loader does
# not implement these augmentations)
_PY_ONLY_DEFAULTS = {"mean_img": None, "max_random_scale": 1.0,
                     "min_random_scale": 1.0, "max_rotate_angle": 0,
                     "max_aspect_ratio": 0.0, "random_h": 0,
                     "random_s": 0, "random_l": 0, "round_batch": True,
                     "preprocess_mode": "thread", "decode_workers": None}


# leading positional parameters (the python class's order) — normalized
# to kwargs so both backends see identical named arguments
_IRI_POSITIONAL = ("path_imgrec", "data_shape", "batch_size", "path_imgidx",
                   "label_width", "shuffle")


def ImageRecordIter(*args, **kwargs):
    """Factory: native C++ loader when available and sufficient, python
    fallback otherwise (same signature, reference
    ``MXNET_REGISTER_IO_ITER(ImageRecordIter)``).  Force a backend with
    ``backend='native'|'python'``."""
    backend = kwargs.pop("backend", "auto")
    for name_, value in zip(_IRI_POSITIONAL, args):
        if name_ in kwargs:
            raise TypeError("ImageRecordIter got multiple values for %r"
                            % name_)
        kwargs[name_] = value
    if len(args) > len(_IRI_POSITIONAL):
        raise TypeError("too many positional arguments")
    args = ()
    if backend != "python":
        from ._native import dataloader_lib

        def _non_default(k):
            if k not in kwargs:
                return False
            v, d = kwargs[k], _PY_ONLY_DEFAULTS[k]
            try:
                return float(v) != float(d)
            except (TypeError, ValueError):
                return v != d

        uses_py_only = any(_non_default(k) for k in _PY_ONLY_DEFAULTS)
        if dataloader_lib() is not None and not uses_py_only:
            try:
                return NativeImageRecordIter(*args, **kwargs)
            except (MXNetError, AssertionError):
                if backend == "native":
                    raise
    if backend == "native":
        raise MXNetError("native data loader unavailable")
    return PyImageRecordIter(*args, **kwargs)


ImageRecordIter_v1 = ImageRecordIter


class ImageDetRecordIter(PyImageRecordIter):
    """Detection variant: variable-length ground-truth labels per image
    (reference ``src/io/iter_image_det_recordio.cc``): each record's
    label block holds N objects × ``object_width`` floats; the iterator
    pads every sample to ``label_pad_width`` floats with
    ``label_pad_value`` and yields labels shaped
    ``(batch, label_pad_width // object_width, object_width)`` — the
    layout ``MultiBoxTarget`` consumes."""

    def __init__(self, *args, label_pad_width=0, label_pad_value=-1.0,
                 object_width=5, **kwargs):
        self.label_pad_width = int(label_pad_width)
        self.label_pad_value = float(label_pad_value)
        self.object_width = int(object_width)
        if self.label_pad_width <= 0:
            raise MXNetError("label_pad_width (total floats, a multiple "
                             "of object_width) is required")
        if self.label_pad_width % self.object_width:
            raise MXNetError("label_pad_width must be a multiple of "
                             "object_width")
        kwargs.setdefault("label_width", self.label_pad_width)
        super().__init__(*args, **kwargs)

    def _decode_one(self, raw):
        header, img = _recordio.unpack_img(raw)
        lab = np.full((self.label_pad_width,), self.label_pad_value,
                      np.float32)
        if header.flag > 0:
            src = np.asarray(header.label, np.float32).ravel()
            if len(src) > self.label_pad_width:
                raise MXNetError(
                    "record %s carries %d label floats > label_pad_width="
                    "%d; raise label_pad_width to the dataset's max "
                    "object count" % (header.id, len(src),
                                      self.label_pad_width))
            if len(src) % self.object_width:
                raise MXNetError(
                    "record %s carries %d label floats, not a multiple "
                    "of object_width=%d — malformed ground truth"
                    % (header.id, len(src), self.object_width))
            lab[:len(src)] = src
        # flag == 0 (scalar label / empty list): a background-only image —
        # every slot stays at label_pad_value, no phantom object
        return self._augment(img), lab

    @property
    def provide_label(self):
        w = self.object_width
        return [DataDesc(self.label_name,
                         (self.batch_size, self.label_pad_width // w, w))]

    def next(self):
        batch = super().next()
        w = self.object_width
        lab = batch.label[0]
        batch.label = [lab.reshape((self.batch_size,
                                    self.label_pad_width // w, w))]
        return batch
