"""BaseModule: the abstract train/eval/predict surface.

API parity with the reference module layer (``python/mxnet/module/
base_module.py``: ``fit``/``score``/``predict``/``iter_predict``, the
bind → init_params → init_optimizer lifecycle, ``arg:``/``aux:`` param
files), restructured around two shared drivers: ``_evaluation_pass``
feeds every inference-style entry point, and ``fit`` delegates the inner
loop to ``_train_epoch``.  Subclasses (Module, BucketingModule,
SequentialModule) provide the computation primitives.
"""
from __future__ import annotations

import logging
import os
import time
from collections import namedtuple

from .. import metric as metric_mod
from .. import ndarray
from .. import obs as _obs
from ..ndarray import NDArray

BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])


def _as_list(obj):
    return obj if isinstance(obj, list) else [obj]


def _invoke(callbacks, param):
    for cb in _as_list(callbacks):
        cb(param)


def _check_input_names(symbol, names, typename, throw):
    """Validate that every requested input exists among the symbol's
    arguments; suggest likely input names (non-parameter args) if not."""
    args = symbol.list_arguments()
    missing = [n for n in names if n not in args]
    if not missing:
        return
    param_suffixes = ("_weight", "_bias", "_gamma", "_beta")
    suggestions = [a for a in args if not a.endswith(param_suffixes)]
    for name in missing:
        msg = "\033[91mYou created Module with Module(..., %s_names=%s) but " \
              "input with name '%s' is not found in symbol.list_arguments(). " \
              "Did you mean one of:\n\t%s\033[0m" % (
                  typename, str(names), name, "\n\t".join(suggestions))
        if throw:
            raise ValueError(msg)
        logging.warning(msg)


class BaseModule(object):
    """Abstract module: subclasses implement the computation primitives
    (forward/backward/update/...) and inherit the high-level drivers."""

    def __init__(self, logger=logging):
        self.logger = logger
        # lifecycle flags, flipped by bind/init_params/init_optimizer
        self.binded = self.params_initialized = False
        self.for_training = self.inputs_need_grad = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # ==================================================================
    # high-level drivers
    def forward_backward(self, data_batch):
        """Forward then backward in one call."""
        self.forward(data_batch, is_train=True)
        self.backward()

    def _evaluation_pass(self, eval_data, num_batch, reset):
        """Generator driving forward(is_train=False) over an iterator,
        yielding ``(nbatch, batch, pad_stripped_outputs)``."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch >= num_batch:
                return
            self.forward(batch, is_train=False)
            keep = None if not batch.pad else -batch.pad
            yield nbatch, batch, [NDArray(o.data[:keep])
                                  for o in self.get_outputs()]

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate ``eval_metric`` over an iterator."""
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        seen = 0
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, batch in enumerate(eval_data):
            if num_batch is not None and nbatch >= num_batch:
                break
            self.forward(batch, is_train=False)
            self.update_metric(eval_metric, batch.label)
            if batch_end_callback is not None:
                _invoke(batch_end_callback,
                        BatchEndParam(epoch=epoch, nbatch=nbatch,
                                      eval_metric=eval_metric,
                                      locals=locals()))
            seen = nbatch + 1
        if score_end_callback:
            _invoke(score_end_callback,
                    BatchEndParam(epoch=epoch, nbatch=seen,
                                  eval_metric=eval_metric, locals=locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        """Yield ``(outputs, nbatch, batch)`` per evaluation batch."""
        for nbatch, batch, outputs in self._evaluation_pass(
                eval_data, num_batch, reset):
            yield outputs, nbatch, batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Collect predictions; with ``merge_batches`` the per-batch
        outputs are concatenated (and a single output unwrapped)."""
        collected = [outputs for _, _, outputs in self._evaluation_pass(
            eval_data, num_batch, reset)]
        if not collected or not merge_batches:
            return collected
        width = len(collected[0])
        if any(len(outs) != width for outs in collected):
            raise AssertionError(
                "Cannot merge batches: the number of outputs varies "
                "across mini-batches. Maybe bucketing is used?")
        merged = [ndarray.concatenate([outs[i] for outs in collected])
                  for i in range(width)]
        if width == 1 and not always_output_list:
            return merged[0]
        return merged

    # ------------------------------------------------------------------
    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint=None, checkpoint_period=1,
            resume=False, elastic=None):
        """The training driver: bind, init, then epochs of
        forward_backward/update/update_metric with callbacks.

        ``checkpoint`` (a prefix string or a
        :class:`~mxnet_tpu.resilience.CheckpointManager`) saves a
        CRC-manifested checkpoint — params + optimizer states + cursor —
        every ``checkpoint_period`` epochs; with ``resume=True`` a
        killed run re-launched with the same arguments continues from
        the newest INTACT checkpoint (torn or corrupt saves are skipped
        by the scan) and, given a deterministic iterator, reproduces the
        uninterrupted run bit-for-bit (docs/how_to/resilience.md).

        ``elastic`` (an :class:`~mxnet_tpu.elastic.ElasticCoordinator`)
        guards every batch with the collective-entry barrier: a dead
        peer raises :class:`~mxnet_tpu.elastic.ElasticShrink` at the
        next batch boundary instead of wedging the step's collectives —
        the caller exits with ``elastic.SHRINK_EXIT_CODE`` and the
        launcher relaunches the shrunk world, which resumes via
        ``checkpoint``/``resume`` (docs/how_to/multi_host.md "Elastic
        training")."""
        assert num_epoch is not None, "please specify number of epochs"
        if initializer is None:
            from ..initializer import Uniform
            initializer = Uniform(0.01)

        ckpt_mgr = None
        if checkpoint is not None:
            from .. import resilience
            ckpt_mgr = checkpoint \
                if isinstance(checkpoint, resilience.CheckpointManager) \
                else resilience.CheckpointManager(checkpoint)
        resumed = None
        if resume:
            assert ckpt_mgr is not None, \
                "fit(resume=True) needs checkpoint=<prefix or manager>"
            resumed = ckpt_mgr.latest()
            if resumed is not None:
                _, arg_params, aux_params = resumed.load_params()
                begin_epoch = resumed.epoch
                self.logger.info(
                    "auto-resume: continuing from checkpoint epoch %d "
                    "(step %s)", resumed.epoch, resumed.step)

        self.bind(train_data.provide_data, train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, force_init=force_init,
                         allow_missing=allow_missing,
                         arg_params=arg_params, aux_params=aux_params)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        if resumed is not None and resumed.states_path:
            # optimizer state (momentum, the fused trainer's update
            # cursor + sentinel counters) must land AFTER init_optimizer
            # built the structures it restores into
            self.load_optimizer_states(resumed.states_path)

        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        validation_metric = validation_metric or eval_metric

        # fused-trainer path: stage batch N+1's H2D upload while step N
        # computes (the reference prefetcher's pinned-memory staging,
        # iter_prefetcher.h:28-129) — see io.DeviceUploadIter
        staged = self._maybe_overlap_uploads(train_data)
        wrapped = staged is not train_data
        train_data = staged

        # silent-data-corruption recovery (docs/how_to/resilience.md
        # "Silent data corruption"): the trainer's in-step integrity
        # check raises IntegrityError on a fingerprint divergence; the
        # loop below rolls back to the newest checkpoint whose reloaded
        # state re-hashes to its manifest fingerprint and re-steps (a
        # deterministic iterator reproduces the lost updates bit-for-
        # bit, and the agreeing re-check attributes blame).  A
        # consecutive-divergence cap turns a persistently corrupt
        # device into a loud MXNetError instead of a rollback loop;
        # with an elastic coordinator attached, a blamed replica is
        # quarantined through the membership-shrink path.
        from ..base import MXNetError
        from ..integrity import IntegrityError
        raw_cap = os.environ.get("MXTPU_INTEGRITY_MAX_ROLLBACKS", "3") or 3
        try:
            max_rollbacks = int(raw_cap)
        except (TypeError, ValueError):
            raise MXNetError(
                "max_rollbacks (MXTPU_INTEGRITY_MAX_ROLLBACKS)=%r is "
                "not an integer" % (raw_cap,)) from None
        trainer = getattr(self, "_trainer", None)
        if trainer is not None and (
                getattr(trainer, "on_integrity_blame", None) is None or
                getattr(trainer.on_integrity_blame, "_fit_wired", False)):
            # blame can resolve AFTER the rollback (the replay's
            # agreeing re-check exonerates the honest replicas on a
            # 1-vs-1 split): quarantine from the callback too.  Rewire
            # on EVERY fit — a wrapper left by a previous fit() holds
            # that call's (possibly closed) coordinator — but never
            # clobber a user-installed callback.
            if elastic is None:
                trainer.on_integrity_blame = None
            else:
                def _blame_cb(record, _elastic=elastic):
                    self._quarantine_blamed(record, _elastic)
                _blame_cb._fit_wired = True
                trainer.on_integrity_blame = _blame_cb
        # cross-rank comm-plan parity (docs/how_to/static_analysis.md
        # "Communication analysis"): stamp this rank's static comm-plan
        # digest into the elastic shared dir BEFORE the first step; the
        # coordinator's first guard refuses to enter the step
        # collectives until every member's digest matches, so a
        # rank-divergent program fails loudly pre-step instead of
        # wedging inside XLA.  MXTPU_COMM_PARITY=0 disarms.
        if elastic is not None and trainer is not None and \
                os.environ.get("MXTPU_COMM_PARITY", "1") != "0":
            try:
                elastic.publish_comm_plan(trainer.comm_plan())
            except Exception as e:                  # noqa: BLE001
                # an untraceable plan downgrades parity to UNVERIFIED —
                # publish the sentinel so peers log a warning instead of
                # dying on this rank's missing stamp; never kill a
                # training run over a lint trace
                self.logger.warning(
                    "comm-plan parity unverifiable: tracing this rank's "
                    "comm plan failed (%s)", e)
                from ..elastic import COMM_PLAN_UNTRACED
                try:
                    elastic.publish_comm_plan(
                        [], digest=COMM_PLAN_UNTRACED)
                except Exception:                   # noqa: BLE001
                    pass                # shared-dir I/O: peers time out
        rollbacks = 0
        try:
            epoch = begin_epoch
            while epoch < num_epoch:
                try:
                    elapsed = self._train_epoch(epoch, train_data,
                                                eval_metric,
                                                batch_end_callback,
                                                monitor, elastic=elastic)
                except IntegrityError as err:
                    rollbacks += 1
                    epoch = self._integrity_rollback(
                        err, ckpt_mgr, elastic, rollbacks, max_rollbacks)
                    train_data.reset()
                    continue
                rollbacks = 0       # verified forward progress
                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f",
                                     epoch, name, val)
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, elapsed)

                # pull trained values off the devices and refresh mirrors
                arg_snap, aux_snap = self.get_params()
                self.set_params(arg_snap, aux_snap)
                trainer = getattr(self, "_trainer", None)
                if trainer is not None and trainer.sentinel != "off":
                    skips = trainer.sentinel_skips
                    if skips:
                        self.logger.warning(
                            "Epoch[%d] sentinel skipped %d non-finite "
                            "step(s) so far", epoch, skips)
                if ckpt_mgr is not None and \
                        (epoch + 1) % checkpoint_period == 0:
                    with _obs.span("fit.checkpoint",
                                   corr="e%d" % (epoch + 1),
                                   parent=None,
                                   attrs={"epoch": epoch + 1}):
                        ckpt_mgr.save(self, epoch + 1,
                                      arg_params=arg_snap,
                                      aux_params=aux_snap)
                if epoch_end_callback is not None:
                    for cb in _as_list(epoch_end_callback):
                        cb(epoch, self.symbol, arg_snap, aux_snap)

                if eval_data:
                    for name, val in self.score(
                            eval_data, validation_metric,
                            score_end_callback=eval_end_callback,
                            batch_end_callback=eval_batch_end_callback,
                            epoch=epoch):
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)
                train_data.reset()
                epoch += 1
        finally:
            if wrapped:
                train_data._shutdown_worker()

    def _quarantine_blamed(self, record, elastic):
        """Shrink the process hosting every blamed replica out of the
        elastic membership (docs/how_to/resilience.md "Silent data
        corruption").  The outvoted rank is alive and heartbeating —
        that is the point: policy, not a lapsed lease, removes it, so
        the launcher relaunches the shrunk world instead of handing the
        flaky chip more updates to corrupt.

        Membership is per-PROCESS while blame is per data-axis REPLICA:
        on a multi-process mesh each blamed replica maps to the process
        owning its device (rank-major global meshes — a host with two
        chips holds replicas 2h and 2h+1), so the flaky chip evicts its
        host and never a neighbor.  On a single-process mesh (tests,
        simulation) there is no device→process signal and the replica
        index is used as the elastic rank directly."""
        blamed = sorted({int(r) for r in record.get("blamed") or []})
        trainer = getattr(self, "_trainer", None)
        mesh = getattr(trainer, "mesh", None)
        if mesh is not None and tuple(mesh.axis_names) == ("data",):
            devs = list(mesh.devices.reshape(-1))
            if len({d.process_index for d in devs}) > 1:
                blamed = sorted({int(devs[r].process_index)
                                 for r in blamed if r < len(devs)})
        for rank in blamed:
            try:
                elastic.quarantine(rank)
            except Exception as e:                  # noqa: BLE001
                self.logger.warning(
                    "integrity: quarantine of blamed rank %s failed: %s",
                    rank, e)

    def _integrity_rollback(self, err, ckpt_mgr, elastic, rollbacks,
                            max_rollbacks):
        """One round of the rollback-to-last-verified protocol; returns
        the epoch index the fit loop re-enters at.  Escalates to
        MXNetError when there is nothing trustworthy to restore or the
        consecutive-divergence cap is hit — silent corruption must
        never fail silently."""
        from ..base import MXNetError
        record = getattr(err, "record", None) or {}
        if rollbacks > max_rollbacks:
            raise MXNetError(
                "integrity: %d consecutive divergences without verified "
                "progress (MXTPU_INTEGRITY_MAX_ROLLBACKS=%d) — the "
                "corruption recurs faster than checkpoints verify; "
                "refusing to rollback-loop. Last divergence: %s"
                % (rollbacks, max_rollbacks, err)) from err
        cb = getattr(getattr(self, "_trainer", None),
                     "on_integrity_blame", None)
        if elastic is not None and record.get("blamed") and \
                not getattr(cb, "_fit_wired", False):
            # only when the fit-wired blame callback is NOT installed:
            # that callback already quarantined this record when the
            # trainer resolved the blame at detection time
            self._quarantine_blamed(record, elastic)
        if ckpt_mgr is None:
            raise MXNetError(
                "integrity divergence at update %s but fit() has no "
                "checkpoint line to roll back to — pass "
                "checkpoint=<prefix> to enable recovery: %s"
                % (record.get("step"), err)) from err
        ck = ckpt_mgr.latest_verified()
        if ck is None:
            raise MXNetError(
                "integrity divergence at update %s and NO checkpoint "
                "re-hashes to its manifest fingerprint — the corruption "
                "predates the whole retained checkpoint line: %s"
                % (record.get("step"), err)) from err
        self.logger.warning(
            "integrity: divergence at update %s (mode=%s, blamed=%s) — "
            "rolling back to verified checkpoint epoch %d (step %s) and "
            "re-stepping [rollback %d/%d]",
            record.get("step"), record.get("mode"), record.get("blamed"),
            ck.epoch, ck.step, rollbacks, max_rollbacks)
        # counted HERE, once the rollback actually happens — a refusal
        # (cap hit, no verified checkpoint) must not inflate the figure
        _obs.counter("integrity.rollbacks").inc()
        _, arg_params, aux_params = ck.load_params()
        self.set_params(arg_params, aux_params)
        if ck.states_path and getattr(self, "optimizer_initialized",
                                      False):
            self.load_optimizer_states(ck.states_path)
        return ck.epoch

    def _maybe_overlap_uploads(self, train_data):
        """Wrap ``train_data`` in :class:`~mxnet_tpu.io.DeviceUploadIter`
        when the fused trainer consumes host-side batches, so each
        batch's device upload overlaps the previous step's compute.
        Multi-host feeding stays synchronous
        (``make_array_from_process_local_data`` is a collective); opt
        out with ``MXTPU_UPLOAD_OVERLAP=0`` (or force on with ``=1``).
        ``MXTPU_UPLOAD_DEPTH`` (default 2) bounds the device staging
        buffers; ``MXTPU_UPLOAD_CHUNKS`` (default 1) splits each host
        batch into K chunked async device_puts (perf.md "Input
        pipeline").  Defaults OFF on single-core hosts: there the
        decode pool, the staging thread, and the transport's serializer
        fight for the one core — the bench's streaming config enables
        it explicitly because its wire wait releases the GIL."""
        import os
        from ..io import DeviceUploadIter
        tr = getattr(self, "_trainer", None)
        knob = os.environ.get("MXTPU_UPLOAD_OVERLAP", "")
        enabled = knob == "1" or (knob != "0"
                                  and (os.cpu_count() or 1) > 1)
        if (tr is None or tr.multihost or not enabled
                or isinstance(train_data, DeviceUploadIter)):
            return train_data

        # LAZY sharding resolution (resolved by the upload worker per
        # batch): tr._batch_shardings is populated by the trainer's
        # bind/compile, which may happen after this wrapper is built —
        # snapshotting it here staged every batch to the default device
        # and Trainer._device_batch paid a SECOND device_put per batch
        # on a data-parallel mesh
        def _sh(names):
            def resolve():
                bs = tr._batch_shardings
                return [bs.get(n) for n in names] if bs is not None \
                    else None
            return resolve

        # env beats the trainer's applied tune-plan entries beats the
        # built-in defaults (docs/how_to/autotune.md)
        from .. import envknobs as _envknobs
        pk = getattr(tr, "plan_knobs", None) or {}
        return DeviceUploadIter(
            train_data,
            depth=_envknobs.get_int("MXTPU_UPLOAD_DEPTH",
                                    pk.get("upload_depth", 2)),
            chunks=_envknobs.get_int("MXTPU_UPLOAD_CHUNKS",
                                     pk.get("upload_chunks", 1)),
            data_shardings=_sh(self._data_names),
            label_shardings=_sh(self._label_names))

    def _train_epoch(self, epoch, train_data, eval_metric,
                     batch_end_callback, monitor, elastic=None):
        """One pass over ``train_data``; returns the wall time.

        Batch fetches ride :func:`~mxnet_tpu.resilience.retry_io`: a
        transient ``OSError`` from the input pipeline (flaky NFS read,
        preempted record fetch — or an injected ``io_error`` fault) is
        retried with backoff instead of killing the epoch; a persistent
        one still propagates after the attempts run out.

        With ``elastic``, every batch is preceded by the coordinator's
        collective-entry guard: no rank enters the fused step until all
        members commit to it, and a lapsed member surfaces as
        ``ElasticShrink`` HERE — at the batch boundary, with the device
        state still coherent — instead of inside a hung collective."""
        from ..resilience import retry_io
        eval_metric.reset()
        tic = time.time()
        data_iter = iter(train_data)
        trainer = getattr(self, "_trainer", None)
        nbatch = 0
        while True:
            # the step's correlation ID: the update counter the fused
            # trainer is ABOUT to take (spans recorded inside
            # Trainer.step carry the same "s<n>", so fetch/guard/h2d/
            # dispatch/sync join into one per-step breakdown).  The
            # classic-executor fallback counts cumulatively on the
            # module — a per-epoch nbatch would alias epoch 0's step 1
            # with epoch 1's and the report would fold them into one
            # row.  Only formatted when recording — off mode pays no
            # per-step allocation at these sites
            on = _obs.OBS
            self._obs_steps = getattr(self, "_obs_steps", 0) + 1
            ncorr = ("s%d" % (trainer.num_update + 1
                              if trainer is not None
                              else self._obs_steps)) if on else None
            try:
                with _obs.phase("fit.fetch", corr=ncorr, parent=None):
                    data_batch = retry_io(lambda: next(data_iter),
                                          what="train batch fetch",
                                          logger=self.logger)
            except StopIteration:
                break
            with _obs.span("train.step", corr=ncorr, parent=None,
                           attrs={"epoch": epoch, "nbatch": nbatch}
                           if on else None):
                if elastic is not None:
                    elastic.guard(trainer.num_update + 1
                                  if trainer is not None else None)
                if monitor is not None:
                    monitor.tic()
                self.forward_backward(data_batch)
                self.update()
                self.update_metric(eval_metric, data_batch.label)
            if monitor is not None:
                monitor.toc_print()
            if batch_end_callback is not None:
                _invoke(batch_end_callback,
                        BatchEndParam(epoch=epoch, nbatch=nbatch,
                                      eval_metric=eval_metric,
                                      locals=locals()))
            nbatch += 1
        return time.time() - tic

    # ==================================================================
    # symbol / params
    @property
    def symbol(self):
        return self._symbol

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, force_init=force_init,
                         allow_missing=allow_missing,
                         arg_params=arg_params, aux_params=aux_params)

    def save_params(self, fname):
        """Write params with the reference's ``arg:``/``aux:`` key
        prefixes (wire-compatible with ``ndarray.save``)."""
        arg_params, aux_params = self.get_params()
        blob = {"arg:" + k: v for k, v in arg_params.items()}
        blob.update(("aux:" + k, v) for k, v in aux_params.items())
        ndarray.save(fname, blob)

    def load_params(self, fname):
        """Inverse of :meth:`save_params`."""
        arg_params, aux_params = {}, {}
        bins = {"arg": arg_params, "aux": aux_params}
        for key, value in ndarray.load(fname).items():
            kind, _, name = key.partition(":")
            if kind not in bins or not name:
                raise ValueError("Invalid param file " + fname)
            bins[kind][name] = value
        self.set_params(arg_params, aux_params)

    # ==================================================================
    # computation primitives (subclass responsibility)
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    # ==================================================================
    # introspection (all subclass responsibility)
    def _abstract_property(self):
        raise NotImplementedError()

    data_names = property(_abstract_property)
    output_names = property(_abstract_property)
    data_shapes = property(_abstract_property)
    label_shapes = property(_abstract_property)
    output_shapes = property(_abstract_property)

    def install_monitor(self, mon):
        raise NotImplementedError()
