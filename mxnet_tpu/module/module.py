"""Module: the primary training surface.

Reference: ``python/mxnet/module/module.py:323-565``.  Two execution paths:

* **classic** (``context`` = Context or list): one Executor per context via
  :class:`DataParallelExecutorGroup`, gradients synced through KVStore /
  local Updater — semantics identical to the reference, used by the parity
  tests.
* **fused** (``context`` = a ``jax.sharding.Mesh``): forward+backward+
  allreduce+update compile into ONE XLA computation
  (:class:`mxnet_tpu.parallel.Trainer`), batch sharded over the mesh's
  ``data`` axis.  This is the TPU-performance path (BASELINE north star:
  the whole train step is a single pjit'd program).  ``forward(is_train=
  True)`` stages the batch; ``update()`` executes the fused step; outputs
  seen by metrics are the pre-update forward outputs, matching reference
  timing.
"""
from __future__ import annotations

import logging
import os
import warnings

import numpy as np

from .. import ndarray
from .. import optimizer as opt
from ..base import Context, MXNetError, current_context
from ..initializer import Uniform, InitDesc
from ..io import DataDesc
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint,
                     save_checkpoint)
from ..ndarray import NDArray, zeros
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

try:
    from jax.sharding import Mesh as _JaxMesh
except Exception:  # pragma: no cover
    _JaxMesh = ()


class Module(BaseModule):
    """Module over a Symbol (reference ``module.py:31-90``)."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 compute_dtype=None):
        super().__init__(logger=logger)
        # fused-path compute dtype (e.g. "bfloat16" for MXU-rate matmuls
        # with fp32 master weights); default from MXTPU_COMPUTE_DTYPE
        self._compute_dtype = compute_dtype or \
            os.environ.get("MXTPU_COMPUTE_DTYPE") or None
        if context is None:
            context = current_context()
        self._mesh = context if isinstance(context, _JaxMesh) else None
        if self._mesh is not None:
            self._context = [current_context()]
        elif isinstance(context, Context):
            self._context = [context]
        else:
            self._context = list(context)
        # fused-path policy: "auto" fuses a single tpu Context onto an
        # auto-built 1-host mesh (the north-star path: whole train step =
        # one XLA computation), "always" fuses any single context (used
        # by the CPU tests), "never" forces the classic executor group
        self._fused_mode = os.environ.get("MXTPU_MODULE_FUSED", "auto")
        n_dev = len(self._context)
        if work_load_list is None:
            work_load_list = [1] * n_dev
        assert len(work_load_list) == n_dev
        self._work_load_list = work_load_list

        self._symbol = symbol
        data_names = list(data_names) if data_names is not None else []
        label_names = list(label_names) if label_names is not None else []
        arg_names = symbol.list_arguments()
        input_names = data_names + label_names
        self._param_names = [x for x in arg_names if x not in input_names]
        self._fixed_param_names = list(fixed_param_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._data_names = data_names
        self._label_names = label_names
        self._state_names = []
        self._output_names = symbol.list_outputs()
        _check_input_names(symbol, data_names, "data", True)
        _check_input_names(symbol, label_names, "label", False)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param", True)

        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        # fused path state
        self._trainer = None
        self._staged_batch = None
        self._fused_outputs = None
        self._auto_fused = False

    # ------------------------------------------------------------------
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Create a Module from a checkpoint (reference ``module.py:104``)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params, mod._aux_params = args, auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Checkpoint symbol + params (+ optimizer states)
        (reference ``module.py:129``)."""
        self._symbol.save("%s-symbol.json" % prefix)
        param_name = "%s-%04d.params" % (prefix, epoch)
        self.save_params(param_name)
        logging.info("Saved checkpoint to \"%s\"", param_name)
        if save_optimizer_states:
            state_name = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(state_name)
            logging.info("Saved optimizer state to \"%s\"", state_name)

    # ------------------------------------------------------------------
    def _reset_bind(self):
        self.binded = False
        self._exec_group = None
        self._data_shapes = None
        self._label_shapes = None
        self._trainer = None
        if self._auto_fused:
            self._mesh = None
            self._auto_fused = False

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        if self._exec_group is not None:
            return self._exec_group.get_output_shapes()
        shapes = {n: s.shape for n, s in
                  (self._data_shapes + (self._label_shapes or []))}
        _, out_shapes, _ = self._symbol.infer_shape(**shapes)
        return list(zip(self._output_names, out_shapes))

    # ------------------------------------------------------------------
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:      # trained values still on device
            self._sync_params_from_devices()
        return self._arg_params, self._aux_params

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        """Initialize parameters (reference ``module.py:173-235``)."""
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "init_params call ignored.", stacklevel=2)
            return
        assert self.binded, "call bind before initializing the parameters"

        def _seed_one(desc, arr, given):
            # a caller-supplied dict wins; absent entries fall back to
            # the initializer only when allow_missing permits
            if given is None:
                initializer(desc, arr)
                return
            src = given.get(desc)
            if src is not None:
                if src is not arr:
                    src.copyto(arr)
                return
            if not allow_missing:
                raise RuntimeError("%s is not presented" % desc)
            if initializer is not None:
                initializer(desc, arr)

        attrs = self._symbol.attr_dict()
        for params, given in ((self._arg_params, arg_params),
                              (self._aux_params, aux_params)):
            for name, arr in sorted(params.items()):
                _seed_one(InitDesc(name, attrs.get(name, None)), arr,
                          given)

        self.params_initialized = True
        self._params_dirty = False
        if self._trainer is not None:
            self._trainer.init_params(arg_params=self._arg_params,
                                      aux_params=self._aux_params,
                                      force_init=True)
        elif self._exec_group is not None:
            self._exec_group.set_params(self._arg_params, self._aux_params)
        # else: fused path before init_optimizer — host mirrors are pushed
        # into the Trainer when it is created

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        if not allow_missing:
            # complete assignment routes through init_params so the
            # trainer/executor mirrors stay coherent
            self.init_params(initializer=None, force_init=force_init,
                             allow_missing=allow_missing,
                             arg_params=arg_params, aux_params=aux_params)
            return
        if self.params_initialized and not force_init:
            warnings.warn("Parameters already initialized and force_init=False. "
                          "set_params call ignored.", stacklevel=2)
            return
        if self._trainer is not None:
            self._trainer.set_params(arg_params, aux_params)
        else:
            self._exec_group.set_params(arg_params, aux_params)
        self._params_dirty = True
        self.params_initialized = True

    # ------------------------------------------------------------------
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        """Bind executors (reference ``module.py:323-431``)."""
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Already binded, ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True

        if not for_training:
            assert not inputs_need_grad

        self._data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                             for x in data_shapes]
        if label_shapes is not None and len(label_shapes):
            self._label_shapes = [x if isinstance(x, DataDesc)
                                  else DataDesc(*x) for x in label_shapes]
        else:
            self._label_shapes = None

        if shared_module is not None:
            assert isinstance(shared_module, Module) and \
                shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group
        else:
            shared_group = None

        fused_ok = (for_training and not inputs_need_grad and
                    shared_module is None and grad_req == "write" and
                    not self._fixed_param_names and
                    self._fused_mode != "never")
        if self._mesh is None and fused_ok and (
                self._fused_mode == "always" or
                (len(self._context) == 1 and
                 self._context[0].device_type == "tpu")):
            self._mesh = self._auto_mesh()
            self._auto_fused = True
        if self._mesh is not None and fused_ok:
            # fused path defers compilation until init_optimizer; here we
            # only infer shapes and allocate host-visible param mirrors
            self._build_param_mirrors()
            return

        self._bind_exec_group(shared_group=shared_group, grad_req=grad_req)
        if shared_module is not None:
            # adopt the host mirrors wholesale: shared modules train one
            # parameter set
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
            self.params_initialized = True
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)
        else:
            assert self._arg_params is None and self._aux_params is None
            param_arrays = [zeros(x[0].shape, dtype=x[0].dtype)
                            for x in self._exec_group.param_arrays]
            self._arg_params = dict(zip(self._param_names, param_arrays))
            aux_arrays = [zeros(x[0].shape, dtype=x[0].dtype)
                          for x in self._exec_group.aux_arrays]
            self._aux_params = dict(zip(self._aux_names, aux_arrays))
        if shared_module is not None and shared_module.optimizer_initialized:
            self.borrow_optimizer(shared_module)

    def _bind_exec_group(self, shared_group=None, grad_req="write"):
        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            self.for_training, self.inputs_need_grad, shared_group,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req)

    def _auto_mesh(self):
        """Build a single-host data-parallel mesh over the default
        backend's local devices (the TPU analog of the reference's
        context-list data parallelism): as many devices as evenly divide
        the batch, 1 on a lone chip."""
        import jax
        from ..parallel import make_mesh
        devs = jax.local_devices()
        batch = self._data_shapes[0].shape[0]
        n = len(devs)
        while n > 1 and batch % n != 0:
            n -= 1
        return make_mesh({"data": n}, devs[:n])

    def _auto_global_mesh(self):
        """Widen the auto mesh to all processes' devices for multi-host
        fused training (``parallel.global_data_parallel_mesh``: data
        axis spans hosts, rank-major, per-process device count capped to
        divide the local batch — k=1 always qualifies, so with >1
        process this succeeds).  Returns None only when there is just
        one process — the caller then falls back to the classic executor
        path so cross-host sync is never silently skipped."""
        from ..parallel import global_data_parallel_mesh
        return global_data_parallel_mesh(
            local_batch=self._data_shapes[0].shape[0])

    def _build_param_mirrors(self):
        shapes = {d.name: d.shape for d in self._data_shapes}
        if self._label_shapes:
            shapes.update({d.name: d.shape for d in self._label_shapes})
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shapes)
        arg_types, _, aux_types = self._symbol.infer_type()
        arg_map = dict(zip(self._symbol.list_arguments(), arg_shapes))
        aux_map = dict(zip(self._aux_names, aux_shapes))
        if self._arg_params is None:
            self._arg_params = {n: zeros(arg_map[n]) for n in self._param_names}
            self._aux_params = {n: zeros(aux_map[n]) for n in self._aux_names}

    def reshape(self, data_shapes, label_shapes=None):
        """Reshape the module for new batch shapes
        (reference ``module.py:433``)."""
        assert self.binded
        self._data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                             for x in data_shapes]
        if label_shapes is not None and len(label_shapes):
            self._label_shapes = [x if isinstance(x, DataDesc)
                                  else DataDesc(*x) for x in label_shapes]
        else:
            self._label_shapes = None
        if self._exec_group is not None:
            self._exec_group.reshape(self._data_shapes, self._label_shapes)

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Install optimizer + kvstore (reference ``module.py:432-530``)."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return

        # Resolve a dist kvstore FIRST: the default rescale_grad must be
        # computed over the GLOBAL batch (reference module.py:460-486 does
        # ``batch_size *= kvstore.num_workers`` for dist_sync).  Both sync
        # paths here sum gradients across hosts (the fused step psums; the
        # classic kvstore _merge sums), so a local-batch default would
        # scale the effective LR by num_workers on multi-host runs.
        from ..kvstore import KVStore as _KVStore
        from ..kvstore import create as _kv_create
        if isinstance(kvstore, _KVStore):
            kv = kvstore
        elif isinstance(kvstore, str) and "dist" in kvstore:
            kv = _kv_create(kvstore)
        else:
            kv = None
        kvstore = kv if kv is not None else kvstore

        batch_size = self._data_shapes[0].shape[0]
        if kv is not None and "dist" in kv.type and "_async" not in kv.type:
            batch_size *= kv.num_workers

        if isinstance(optimizer, str):
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer_params = dict(optimizer_params)
            if "rescale_grad" not in optimizer_params:
                optimizer_params["rescale_grad"] = 1.0 / batch_size
            optimizer = opt.create(optimizer, sym=self.symbol,
                                   param_idx2name=idx2name, **optimizer_params)
        else:
            assert isinstance(optimizer, opt.Optimizer)
            if optimizer.rescale_grad != 1.0 / batch_size:
                self.logger.warning(
                    "optimizer.rescale_grad is %g but 1/(global batch) is "
                    "%g; gradients are summed over the global batch of %d "
                    "— make sure this is intended",
                    optimizer.rescale_grad, 1.0 / batch_size, batch_size)
            if not optimizer.idx2name:
                optimizer.idx2name = {i: n for i, n in
                                      enumerate(self._param_names)}

        self._optimizer = optimizer

        if self._mesh is not None and self._exec_group is None:
            from ..parallel.optim import _supports_fusion
            fallback = None
            if (kv is not None and "dist" in kv.type and
                    kv.num_workers > 1 and self._auto_fused):
                # multi-host with an auto-built single-host mesh: widen it
                # to the GLOBAL mesh over every process's devices, so the
                # cross-host gradient psum compiles into the fused step
                # (the reference's dist_sync exactness via allreduce,
                # kvstore_dist_server.h:164-210, now at ICI/DCN speed)
                gmesh = self._auto_global_mesh()
                if gmesh is not None:
                    self._mesh = gmesh
                else:
                    # never train multi-host on a local-only fused step:
                    # it would silently skip cross-host gradient sync
                    fallback = ("could not build a global mesh; using the "
                                "classic executor path with kvstore sync")
            if fallback is None and not _supports_fusion(optimizer):
                # optimizer without a pure fused-step rule (SGLD,
                # user-defined subclasses)
                fallback = ("optimizer %s has no fused-step rule; using "
                            "the classic executor path"
                            % type(optimizer).__name__)
            if fallback is not None:
                self.logger.warning(fallback)
                self._mesh = None
                self._trainer = None
                self._bind_exec_group()
                self._exec_group.set_params(self._arg_params,
                                            self._aux_params)
            else:
                from ..parallel.trainer import Trainer
                self._trainer = Trainer(
                    self._symbol, optimizer, data_names=self._data_names,
                    label_names=self._label_names, mesh=self._mesh,
                    compute_dtype=self._compute_dtype)
                self._trainer.bind(
                    data_shapes={d.name: d.shape for d in self._data_shapes},
                    label_shapes={d.name: d.shape
                                  for d in (self._label_shapes or [])})
                if kv is not None and "dist" in kv.type \
                        and kv.num_workers > 1:
                    # explicit global mesh: psum rides inside the fused
                    # step; make the starting params identical by
                    # broadcasting rank 0's init (kvstore_dist.h:63-80)
                    for name in self._param_names:
                        kv.init(name, self._arg_params[name])
                        kv.pull(name, out=self._arg_params[name])
                    for name in self._aux_names:
                        kv.init("aux:" + name, self._aux_params[name])
                        kv.pull("aux:" + name, out=self._aux_params[name])
                self._trainer.init_params(arg_params=self._arg_params,
                                          aux_params=self._aux_params,
                                          force_init=True)
                self._kvstore = None
                self._update_on_kvstore = False
                self._finish_optimizer_init()
                return

        self._kvstore, self._update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), self._arg_params)

        if self._kvstore:
            # seed the store with the host init values (one key per
            # parameter; store-side optimizers pull them back to devices)
            _initialize_kvstore(self._kvstore,
                                self._exec_group.param_arrays,
                                self._arg_params, self._param_names,
                                self._update_on_kvstore)
        if self._update_on_kvstore:
            self._updater = None
            self._kvstore.set_optimizer(self._optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
        self._finish_optimizer_init()

    def _finish_optimizer_init(self):
        """Mark ready + replay any optimizer state queued by a resume
        (set_params-time preload, reference ``module.py:525-529``)."""
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            preload, self._preload_opt_states = \
                self._preload_opt_states, None
            self.load_optimizer_states(preload)

    def borrow_optimizer(self, shared_module):
        """Share another module's optimizer state wholesale
        (reference contract ``module.py:531``)."""
        assert shared_module.optimizer_initialized
        for attr in ("_optimizer", "_kvstore", "_update_on_kvstore",
                     "_updater"):
            setattr(self, attr, getattr(shared_module, attr))
        self.optimizer_initialized = True

    # ------------------------------------------------------------------
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if self._trainer is not None or (self._mesh is not None and
                                         self._exec_group is None):
            if is_train is None:
                is_train = self.for_training
            batch = self._fused_batch_dict(data_batch)
            if is_train:
                self._staged_batch = batch
                self._fused_outputs = None
            else:
                self._ensure_trainer()
                self._fused_outputs = self._trainer.forward(batch)
            return
        self._exec_group.forward(data_batch, is_train)

    def _ensure_trainer(self):
        """Fused-path forward before init_optimizer (e.g. ``score`` on a
        freshly bound module): compile a trainer with a placeholder
        optimizer; init_optimizer replaces it."""
        if self._trainer is None:
            from ..parallel.trainer import Trainer
            self._trainer = Trainer(
                self._symbol, opt.SGD(), data_names=self._data_names,
                label_names=self._label_names, mesh=self._mesh,
                compute_dtype=self._compute_dtype)
            self._trainer.bind(
                data_shapes={d.name: d.shape for d in self._data_shapes},
                label_shapes={d.name: d.shape
                              for d in (self._label_shapes or [])})
            self._trainer.init_params(arg_params=self._arg_params,
                                      aux_params=self._aux_params,
                                      force_init=True)

    def _fused_batch_dict(self, data_batch):
        batch = {}
        for name, arr in zip(self._data_names, data_batch.data):
            batch[name] = arr
        if data_batch.label is not None:
            for name, arr in zip(self._label_names, data_batch.label):
                batch[name] = arr
        return batch

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        if self._trainer is not None or (self._mesh is not None and
                                         self._exec_group is None):
            assert out_grads is None, \
                "fused mesh path computes gradients internally"
            return
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Apply the optimizer (reference ``module.py:553``)."""
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if self._trainer is not None:
            assert self._staged_batch is not None, \
                "call forward(is_train=True) before update() on the fused path"
            self._fused_outputs = self._trainer.step(self._staged_batch)
            self._staged_batch = None
            return
        weights = self._exec_group.param_arrays
        grads = self._exec_group.grad_arrays
        if self._update_on_kvstore:
            _update_params_on_kvstore(weights, grads, self._kvstore)
        else:
            _update_params(weights, grads, updater=self._updater,
                           num_device=len(self._context),
                           kvstore=self._kvstore)

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        if self._trainer is not None or (self._mesh is not None and
                                         self._exec_group is None):
            if self._fused_outputs is None and self._staged_batch is not None:
                # outputs read between forward(is_train=True) and update():
                # run a training-mode forward without the update
                self._ensure_trainer()
                self._fused_outputs = self._trainer.forward_train(
                    self._staged_batch)
            assert self._fused_outputs is not None, \
                "no outputs yet: run forward() or update()"
            return self._fused_outputs
        return self._exec_group.get_outputs(merge_multi_context=merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and self.inputs_need_grad
        return self._exec_group.get_input_grads(
            merge_multi_context=merge_multi_context)

    def update_metric(self, eval_metric, labels):
        if self._trainer is not None or (self._mesh is not None and
                                         self._exec_group is None):
            if self._fused_outputs is None and self._staged_batch is not None:
                # metric before update(): run a train-mode forward (the
                # fit loop's update-then-metric order avoids this cost)
                self.get_outputs()
            if self._fused_outputs is not None:
                eval_metric.update(labels, self._labelled(
                    self._fused_outputs, len(labels)))
            return
        self._exec_group.update_metric(eval_metric, labels)

    def _labelled(self, outputs, n_labels):
        """The outputs a metric is shown.  A graph may have more loss
        heads than labels (a second head fed from the same label, as a
        multi-token-prediction module is): the metric then sees, label
        by label, the output named for it (``<name>_output`` for
        ``<name>_label``), else the first ``n_labels`` outputs."""
        if n_labels >= len(outputs) or n_labels != len(self._label_names):
            return outputs
        by_name = dict(zip(self._output_names, outputs))
        wanted = [n[:-len("label")] + "output" for n in self._label_names]
        if all(n in by_name for n in wanted):
            return [by_name[n] for n in wanted]
        return outputs[:n_labels]

    # ------------------------------------------------------------------
    def _sync_params_from_devices(self):
        if self._trainer is not None:
            arg, aux = self._trainer.get_params()
            for n, v in arg.items():
                self._arg_params[n]._set_data(v.data)
            for n, v in aux.items():
                self._aux_params[n]._set_data(v.data)
        else:
            self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._trainer is not None:
            with open(fname, "wb") as fout:
                fout.write(self._trainer.get_opt_states())
        elif self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updater.get_states())

    @property
    def sentinel_skips(self):
        """Fused-path step-sentinel skip count (0 on the classic path —
        its per-op executors have no fused finiteness watch)."""
        if self._trainer is not None:
            return self._trainer.sentinel_skips
        return 0

    def state_fingerprint(self):
        """Integrity record of the training state for the checkpoint
        manifest (docs/how_to/resilience.md "Silent data corruption").
        Fused path: the DEVICE-computed fingerprint over params + aux +
        optimizer state — hashed before the host/disk path could touch
        the values.  Classic path: a host-side hash of the param
        mirrors (arg/aux only; the per-op executors have no device
        fingerprint program)."""
        if self._trainer is not None:
            return self._trainer.state_fingerprint()
        from .. import integrity
        assert self.binded and self.params_initialized
        arg_params, aux_params = self.get_params()
        named = integrity.named_state_leaves(
            {n: v.asnumpy() for n, v in arg_params.items()},
            {n: v.asnumpy() for n, v in aux_params.items()})
        global_fp, leaves = integrity.host_fingerprint(named)
        return integrity.manifest_record(global_fp, leaves, mode="host")

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._trainer is not None:
            with open(fname, "rb") as fin:
                blob = fin.read()
            try:
                self._trainer.set_opt_states(blob)
            except MXNetError as e:
                raise MXNetError("optimizer states file %r: %s"
                                 % (fname, e)) from e
        elif self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fin:
                self._updater.set_states(fin.read())

    def install_monitor(self, mon):
        assert self.binded
        if self._exec_group is not None:
            self._exec_group.install_monitor(mon)
        else:
            self.logger.warning(
                "Monitor requires the classic executor path; the fused "
                "mesh path has no per-op taps (the whole step is one XLA "
                "computation). Set MXTPU_MODULE_FUSED=never to monitor.")
