"""The fused train step: forward + backward + gradient sync + optimizer
update as ONE jitted XLA computation.

This is the TPU-native collapse of the reference's whole hot path —
``GraphExecutor::RunOps`` per-node engine pushes (``graph_executor.cc:
781-831``) + ``KVStore::Push/Pull`` comm-tree reduce (``comm.h``) + python
``Updater`` per weight (``optimizer.py:722``) — and the requirement behind
the BASELINE north star: with the step compiled whole, XLA overlaps the
gradient all-reduce with backward compute and buffer-donates weights, so
updates are true in-place HBM writes.

Data parallelism: batch dim sharded over the mesh ``data`` axis; params
replicated; XLA's SPMD partitioner inserts the psum.  Tensor/model
parallelism: pass ``param_specs={name: PartitionSpec(...)}`` to shard
weights; the compiler places the matching collectives.  bf16: pass
``compute_dtype='bfloat16'`` for MXU-rate matmuls with fp32 master weights.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..base import MXNetError, _dtype
from ..ndarray import NDArray
from ..executor import _GraphProgram
from ..initializer import InitDesc
from .. import initializer as _init_mod
from .. import envknobs as _envknobs
from .. import faults as _faults
from .. import obs as _obs
from .. import program as _program
from .. import tuneplan as _tuneplan
from .mesh import batch_sharding, replicated
from .moe import held_chunk_rows, row_chunks
from .optim import make_update_fn

from .collectives import _process_index

__all__ = ["Trainer", "remat_policy"]

# dynamic loss-scale schedule (the standard GradScaler constants): halve
# on a non-finite step, double after GROWTH_INTERVAL consecutive clean
# steps, clamp to [1, 2**24]
_LS_INIT = 2.0 ** 15
_LS_MAX = 2.0 ** 24
_LS_GROWTH_INTERVAL = 200

# the device scopes of the fused step's own work outside the graph walk:
# the compute-dtype casts and their float32 way back, the cotangent
# seeds, and the in-step state fingerprint.  Like the optimizer's
# ``optimizer_update`` they never enclose a node's scope: a device trace
# gives an operation to its outermost scope
_CAST_SCOPE = "trainer_cast"
_INTEGRITY_SCOPE = "trainer_integrity"
# ``train.host_ms_p50`` is the median of this many steps' host times
_HOST_STEPS = 64

# MXNet-style output ops whose custom vjp INJECTS the loss gradient and
# (with out_grad left False) discards the upstream cotangent — seed-side
# loss scaling cannot reach a backward that starts at one of these, so
# the trainer refuses to silently mis-scale and runs with scaling inert
_FIXED_LOSS_OPS = frozenset((
    "SoftmaxOutput", "Softmax", "SVMOutput", "MakeLoss",
    "LinearRegressionOutput", "LogisticRegressionOutput",
    "MAERegressionOutput",
))


def _seeds_reach_grads(symbol) -> bool:
    """True when every graph output propagates its cotangent seed (the
    graph is linear in the seeds), i.e. no fixed-loss output op without
    ``out_grad=True`` sits at a head."""
    import json as _json
    try:
        graph = _json.loads(symbol.tojson())
    except Exception:      # noqa: BLE001 — unparseable: assume linear
        return True
    nodes = graph.get("nodes", [])
    for head in graph.get("heads", []):
        node = nodes[head[0]] if head and head[0] < len(nodes) else None
        if node is None:
            continue
        if node.get("op") in _FIXED_LOSS_OPS:
            attrs = node.get("attrs") or node.get("param") or {}
            if str(attrs.get("out_grad", "False")) not in ("True", "true",
                                                           "1"):
                return False
    return True


# where a graph reads an input as indices, and the ops that hand values on
# unchanged (shape, slicing, and arithmetic with a scalar)
_INDEX_SLOTS = {"Embedding": ("data",), "SoftmaxOutput": ("label",),
                "_contrib_RowCrossEntropy": ("label",)}
_VALUE_KEEPING = frozenset((
    "Reshape", "Flatten", "expand_dims", "transpose", "slice_axis", "slice",
    "Concat", "SliceChannel", "BlockGrad", "_mul_scalar", "_plus_scalar",
    "_minus_scalar"))


def _index_inputs(nodes) -> frozenset:
    """Names of the variables a graph (its ``nodes``) consumes as
    indices: those that reach ``Embedding``'s data or ``SoftmaxOutput``'s
    label directly or through value-keeping ops.  A class or token id
    does not survive the cast to bfloat16 (exact to 256), so
    ``_forward`` leaves these as they were fed, float32 by MXNet's
    convention included."""
    found = set()
    stack = []
    for n in nodes:
        if n.is_variable:
            continue
        slots = _INDEX_SLOTS.get(n.op.name, ())
        for slot, (src, _) in zip(n.op.list_inputs(n.params), n.inputs):
            if slot in slots:
                stack.append(src)
    seen = set()
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        if n.is_variable:
            found.add(n.name)
        elif n.op.name in _VALUE_KEEPING:
            stack.extend(src for src, _ in n.inputs)
    return frozenset(found)


def remat_policy(name):
    """Resolve a rematerialization policy for the fused step.

    The step is usually HBM-bandwidth-bound, not MXU-bound (see
    PERF.md and docs/how_to/perf.md): rematerialization trades the
    idle MXU's free flops for scarce HBM bytes by storing fewer
    residuals and recomputing the rest inside backward.  Policies:

    - ``"none"``: save every residual (jax default; most HBM traffic).
    - ``"convs_dots"``: save only conv / matmul outputs — the cheap
      epilogues (BatchNorm, ReLU, adds) are recomputed in backward, so
      their activations are never round-tripped through HBM.
    - ``"dots"``: save only matmul outputs (``dots_saveable``) — for
      transformer-shaped models; on conv nets this recomputes convs too.
    - ``"nothing"``: full remat — backward recomputes the entire
      forward (least memory, most recompute flops).
    """
    import jax.ad_checkpoint as adc
    if name in (None, "", "none"):
        return None
    if name == "convs_dots":
        def save_convs_dots(prim, *_, **__):
            return prim.name in ("conv_general_dilated", "dot_general")
        return save_convs_dots
    if name == "dots":
        return adc.checkpoint_policies.dots_saveable
    if name == "nothing":
        return adc.checkpoint_policies.nothing_saveable
    raise MXNetError("unknown remat policy %r (none|convs_dots|dots|"
                     "nothing)" % (name,))


class Trainer:
    """Compiled data-parallel trainer for a Symbol.

    Usage::

        t = Trainer(softmax, optimizer, mesh=mesh)
        t.bind(data_shapes={"data": (256, 3, 224, 224)},
               label_shapes={"softmax_label": (256,)})
        t.init_params(mx.init.Xavier())
        outs = t.step({"data": x, "softmax_label": y})
    """

    def __init__(self, symbol, optimizer, data_names: Sequence[str] = ("data",),
                 label_names: Sequence[str] = ("softmax_label",),
                 mesh=None, compute_dtype=None,
                 param_specs: Optional[Dict[str, PartitionSpec]] = None,
                 remat: Optional[str] = None,
                 dtype_policy: Optional[str] = None,
                 sentinel: Optional[str] = None,
                 loss_scale=None,
                 sentinel_max_skips: Optional[int] = None,
                 ls_growth_interval: Optional[int] = None,
                 donate_batch: Optional[bool] = None,
                 zero: Optional[int] = None,
                 grad_accum: Optional[int] = None,
                 grad_dtype: Optional[str] = None,
                 integrity: Optional[str] = None,
                 integrity_period: Optional[int] = None,
                 plan=None):
        self.symbol = symbol
        self.optimizer = optimizer
        self.prog = _GraphProgram(symbol)
        self.prog.platform = mesh.devices.flat[0].platform \
            if mesh is not None else jax.default_backend()
        self.data_names = list(data_names)
        self.label_names = [n for n in label_names
                            if n in self.prog.arg_names]
        self.mesh = mesh
        # multi-host mesh: some devices belong to other processes.  The
        # caller binds LOCAL batch shapes; the compiled program sees the
        # GLOBAL batch, each process contributing its shard
        # (make_array_from_process_local_data), and reads back only its
        # addressable output rows — the jax.distributed analog of the
        # reference's per-worker DataBatch under dist_sync.
        self.multihost = mesh is not None and any(
            d.process_index != jax.process_index()
            for d in mesh.devices.flat)
        self.compute_dtype = _dtype(compute_dtype) if compute_dtype else None
        import os as _os
        # --- persisted autotune plan (docs/how_to/autotune.md):
        # ``plan=`` (a dict, a path, or None -> MXTPU_TUNE_PLAN) sits
        # BELOW every explicit constructor argument and set env var —
        # resolution is ctor > env > plan > default — and applies only
        # when its key matches this (symbol, mesh, jax, platform); a
        # foreign plan is a loud COUNTED fallback to defaults
        # (``tune.plan_foreign``), never silent misconfiguration.
        self.tune_plan = _tuneplan.resolve(plan)
        tplan = {}
        if self.tune_plan is not None:
            tplan = _tuneplan.train_section(
                self.tune_plan, _program.symbol_digest(symbol),
                mesh=mesh, platform=self.prog.platform)
        self.plan_knobs = tplan      # what actually applied (tests/obs)

        def _knob(ctor, env_name, plan_key, default):
            if ctor is not None:
                return ctor
            if _envknobs.is_set(env_name):
                return _os.environ[env_name]
            if plan_key is not None and plan_key in tplan:
                return tplan[plan_key]
            return default

        self.remat = _knob(remat, "MXTPU_REMAT", "remat", "none")
        # residual/intermediate dtype policy (op/bytediet.py): the fused
        # step seeds bf16 cotangents (see ``step``) and the byte-diet
        # backward formulations keep elementwise math in that dtype with
        # f32-accumulated reductions; ``"legacy"`` restores the plain
        # autodiff backwards (A/B and bisection knob,
        # ``MXTPU_DTYPE_POLICY`` for the process default).
        self.dtype_policy = _knob(dtype_policy, "MXTPU_DTYPE_POLICY",
                                  "dtype_policy", None)
        self.prog.dtype_policy = self.dtype_policy
        # --- step sentinel (docs/how_to/resilience.md): watch the f32
        # grads' global finiteness INSIDE the jitted step and lax-select
        # the old (params, aux, opt_state) on a non-finite batch — skip
        # semantics with no host round-trip.  "off" keeps the step
        # program byte-identical to the pre-sentinel build.
        self.sentinel = sentinel if sentinel is not None \
            else _os.environ.get("MXTPU_SENTINEL", "off")
        if self.sentinel not in ("off", "skip", "abort"):
            raise MXNetError("unknown sentinel mode %r (off|skip|abort)"
                             % (self.sentinel,))
        self.sentinel_max_skips = int(
            sentinel_max_skips if sentinel_max_skips is not None
            else _os.environ.get("MXTPU_SENTINEL_MAX_SKIPS", "3"))
        # loss scale: None/off, "dynamic", or a fixed float.  Scales the
        # cotangent seeds so a bf16 backward keeps small grads out of
        # the flush-to-zero range; grads are unscaled in f32 before the
        # finiteness check and the update, so the optimizer math never
        # sees the scale.
        if loss_scale is None:
            loss_scale = _os.environ.get("MXTPU_LOSS_SCALE", "") or None
        if loss_scale in ("off", "none", "0"):
            loss_scale = None
        if loss_scale is not None and loss_scale != "dynamic":
            loss_scale = float(loss_scale)
        self.loss_scale = loss_scale
        self._ls_applies = True
        if loss_scale is not None and not _seeds_reach_grads(symbol):
            import logging as _logging
            _logging.getLogger("mxtpu.trainer").warning(
                "loss scale requested, but an output op of this graph "
                "injects its loss gradient and discards upstream "
                "cotangents (SoftmaxOutput-style, out_grad=False): the "
                "seed-side scale cannot reach the backward; running "
                "with scaling INERT (skip/abort sentinel unaffected)")
            self._ls_applies = False
        self.ls_growth_interval = int(
            ls_growth_interval if ls_growth_interval is not None
            else _os.environ.get("MXTPU_LS_GROWTH_INTERVAL",
                                 str(_LS_GROWTH_INTERVAL)))
        self._sent = None          # device sentinel state, see _init_sentinel
        # staging-buffer donation (docs/how_to/perf.md "Input
        # pipeline"): donate the batch argument so the uint8 staging
        # buffers a DeviceUploadIter parked in HBM are freed the moment
        # the step's on-device cast consumes them — device-side input
        # memory stays bounded at depth x batch bytes instead of
        # depth + in-flight.  OPT-IN: a caller that re-feeds the same
        # device arrays every step (synthetic benches) or reads batch
        # members after the step (Module.update_metric reads labels)
        # must keep it off.
        if donate_batch is None:
            if _envknobs.is_set("MXTPU_DONATE_BATCH"):
                donate_batch = _envknobs.get_bool("MXTPU_DONATE_BATCH")
            else:
                donate_batch = bool(tplan.get("donate_batch", False))
        self.donate_batch = bool(donate_batch)
        self.param_specs = param_specs or {}
        # --- ZeRO-1 / gradient accumulation / reduced-precision grad
        # comm (docs/how_to/perf.md "Optimizer sharding").  The
        # reference's distributed kvstore ran the optimizer ON the
        # servers, each owning a slice of the keys — optimizer state was
        # naturally sharded across the cluster.  zero=1 recovers that on
        # the mesh: every state leaf shards along the ``data`` axis, the
        # update runs on the owned shard, updated params all-gather back.
        def _as_int(value, what):
            try:
                return int(value)
            except (TypeError, ValueError):
                raise MXNetError("%s=%r is not an integer" % (what, value)) \
                    from None

        zero = _knob(zero, "MXTPU_ZERO", "zero", "0")
        self.zero = _as_int(zero, "zero (MXTPU_ZERO)")
        if self.zero not in (0, 1):
            raise MXNetError("zero=%r: supported stages are 0 (replicated "
                             "optimizer state) and 1 (state sharded along "
                             "the data axis)" % (zero,))
        grad_accum = _knob(grad_accum, "MXTPU_GRAD_ACCUM", "grad_accum",
                           "1")
        self.grad_accum = _as_int(grad_accum, "grad_accum (MXTPU_GRAD_ACCUM)")
        if self.grad_accum < 1:
            raise MXNetError("grad_accum=%r: need a microbatch count >= 1"
                             % (grad_accum,))
        grad_dtype = _knob(grad_dtype, "MXTPU_GRAD_DTYPE", "grad_dtype",
                           "f32")
        _GD = {"f32": "f32", "float32": "f32",
               "bf16": "bf16", "bfloat16": "bf16"}
        if grad_dtype not in _GD:
            raise MXNetError("grad_dtype=%r: bf16 or f32 (the cross-chip "
                             "gradient wire dtype)" % (grad_dtype,))
        self.grad_dtype = _GD[grad_dtype]
        ndata = self._data_axis_size()
        self._zero_on = self.zero == 1 and ndata > 1
        self._lowp_on = self.grad_dtype == "bf16" and ndata > 1
        if self._lowp_on and any(any(e is not None for e in tuple(s))
                                 for s in self.param_specs.values()):
            raise MXNetError(
                "grad_dtype=bf16 runs the backward shard_map'd over the "
                "data axis and does not compose with tensor-parallel "
                "param_specs yet; keep f32 grad comm for sharded params")
        # --- silent-data-corruption defense (docs/how_to/resilience.md
        # "Silent data corruption"): an on-device state fingerprint
        # computed INSIDE the jitted step every `integrity_period`
        # updates (lax.cond, so off-period steps pay nothing), with a
        # cross-replica checksum vote on data-parallel meshes and a
        # deterministic replay audit on a single device.  Divergence
        # raises integrity.IntegrityError; the recovery protocol
        # (rollback to the last VERIFIED checkpoint + re-step) lives in
        # Module.fit / resilience.CheckpointManager.
        if integrity is None:
            integrity = _os.environ.get("MXTPU_INTEGRITY_MODE", "off")
        if integrity not in ("off", "fp", "vote", "audit"):
            raise MXNetError("unknown integrity mode %r (off|fp|vote|"
                             "audit)" % (integrity,))
        self.integrity = integrity
        integrity_period = _knob(integrity_period,
                                 "MXTPU_INTEGRITY_PERIOD",
                                 "integrity_period", "100")
        self.integrity_period = _as_int(
            integrity_period, "integrity_period (MXTPU_INTEGRITY_PERIOD)")
        if self.integrity != "off" and self.integrity_period < 1:
            raise MXNetError("integrity_period=%r: need >= 1"
                             % (integrity_period,))
        self._integ = None             # device integrity carry (fp/vote)
        self._integ_mode = "off"       # resolved at _build
        self._integ_paths = None       # state-leaf paths, vote column order
        self._integ_rep_mask = None    # which columns vote (replicated)
        self._integ_fused = False      # fingerprint rides the step program
        self._integ_external = False   # ZeRO-1: standalone vote program
        self._vote_fn = None           # compiled standalone vote (ZeRO-1)
        self._fp_fn = None             # standalone fingerprint program
        self.integrity_divergences = 0
        self.integrity_blamed = []     # resolved blame records
        self._integrity_pending = None  # divergence awaiting replay blame
        self.on_integrity_blame = None  # callback(record) on resolution
        self._opt_shardings = None     # per-leaf state shardings (mesh)
        self._grad_shardings = None    # zero-sharded grad specs
        input_set = set(self.data_names) | set(self.label_names)
        self.param_names = [n for n in self.prog.arg_names
                            if n not in input_set]
        self.aux_names = list(self.prog.aux_names)
        self.params = None
        self.aux = None
        self.opt_state = None
        self.num_update = optimizer.begin_num_update
        self._step_fn = None
        self._eval_fn = None
        self._batch_shardings = None
        self._lr_cache = None
        self._step_check_fn = None     # fingerprint-fused check program
        self._key = jax.random.key(0)
        # host time of each step but the first (which compiles), in ms
        self._host_ms = collections.deque(maxlen=_HOST_STEPS)
        self._stepped = False
        _obs.REGISTRY.pull(self._pull_host_gauge)

    def _data_axis_size(self) -> int:
        """Mesh ``data`` axis degree (1 without a mesh or data axis)."""
        if self.mesh is None:
            return 1
        return int(dict(self.mesh.shape).get("data", 1))

    def _program_key(self) -> Dict:
        """Identity fields of this trainer's compiled programs beyond
        the abstract call signature — everything that is BAKED into the
        traced step (optimizer hyperparameters become XLA constants;
        the config knobs choose which step variant is traced).  Two
        processes whose keys and signatures agree run the same program,
        so a persisted executable (``MXTPU_PROGRAM_CACHE``) is safe to
        reuse; anything volatile (lr — a runtime argument — and the
        host-side update counters) is deliberately excluded."""
        volatile = {"lr", "num_update", "begin_num_update"}

        def _jsonable(v):
            # scalars AND containers of scalars: lr_mult/wd_mult dicts
            # are baked per-param into the update math (optim.py
            # `scales`), so they MUST key the program — a filter that
            # kept only scalars would let two wd_mult configs share one
            # executable (silent wrong-update on a warm cache)
            if isinstance(v, (int, float, str, bool, type(None))):
                return v
            if isinstance(v, dict):
                return {str(k): _jsonable(x)
                        for k, x in sorted(v.items())}
            if isinstance(v, (list, tuple)):
                return [_jsonable(x) for x in v]
            if isinstance(v, (set, frozenset)):
                return sorted(str(x) for x in v)
            raise TypeError(type(v))

        opt, opaque = {}, []
        for k, v in sorted(vars(self.optimizer).items()):
            if k in volatile:
                continue
            try:
                opt[k] = _jsonable(v)
            except TypeError:
                # objects (lr_scheduler: host-side, lr arrives as a
                # runtime arg) — record the field NAME so presence
                # still keys, content doesn't churn the key with
                # per-process reprs
                opaque.append(k)
        if opaque:
            opt["_opaque_fields"] = opaque
        mesh_desc = None
        if self.mesh is not None:
            mesh_desc = {"axes": dict(self.mesh.shape),
                         "devices": int(self.mesh.size)}
        return {
            "symbol": _program.symbol_digest(self.symbol),
            "optimizer": [type(self.optimizer).__name__, opt],
            "compute_dtype": str(self.compute_dtype)
            if self.compute_dtype is not None else None,
            "dtype_policy": self.dtype_policy,
            "platform": self.prog.platform,
            "remat": self.remat,
            "sentinel": self.sentinel,
            "loss_scale": str(self.loss_scale),
            "ls_growth_interval": self.ls_growth_interval,
            "zero": self.zero,
            "grad_accum": self.grad_accum,
            "grad_dtype": self.grad_dtype,
            "integrity": [self._integ_mode, self.integrity_period],
            "donate_batch": self.donate_batch,
            "mesh": mesh_desc,
            "param_specs": sorted((n, str(s))
                                  for n, s in self.param_specs.items()),
            "multihost": self.multihost,
        }

    # ------------------------------------------------------------------
    def bind(self, data_shapes: Dict[str, tuple],
             label_shapes: Optional[Dict[str, tuple]] = None):
        shapes = dict(data_shapes)
        shapes.update(label_shapes or {})
        if self.multihost:
            # caller passed per-process (local) batch shapes; the program
            # is traced at the global batch
            scale = jax.process_count()
            shapes = {n: (s[0] * scale,) + tuple(s[1:])
                      for n, s in shapes.items()}
        arg_shapes, out_shapes, aux_shapes = self.symbol.infer_shape(**shapes)
        if arg_shapes is None:
            raise MXNetError("cannot infer shapes from %s" % shapes)
        if (self.grad_accum > 1 or self._lowp_on) and out_shapes:
            # both paths reassemble outputs along dim 0 (scan-stacked
            # microbatches / shard_map out_specs): a REDUCED head
            # (softmax_cross_entropy's (1,) loss, a scalar MakeLoss sum)
            # would be silently stitched into per-microbatch/per-shard
            # pieces instead of the big-batch value — refuse loudly
            bsz = shapes[self.data_names[0]][0] \
                if self.data_names and self.data_names[0] in shapes \
                else next(iter(shapes.values()))[0]
            for oname, oshape in zip(self.symbol.list_outputs(),
                                     out_shapes or []):
                if not oshape or oshape[0] != bsz:
                    raise MXNetError(
                        "grad_accum>1 / grad_dtype=bf16 need batch-major "
                        "graph outputs, but %r has shape %s (batch %d): "
                        "reduced-output heads are not supported on these "
                        "paths" % (oname, tuple(oshape or ()), bsz))
        self._arg_shapes = dict(zip(self.prog.arg_names, arg_shapes))
        self._aux_shapes = dict(zip(self.aux_names, aux_shapes))
        self._bind_moe_gauges(shapes)
        self._bind_op_gauges()
        self._input_shapes = {n: self._arg_shapes[n]
                              for n in self.data_names + self.label_names}
        if self.grad_accum > 1:
            ndata = self._data_axis_size()
            for n, s in self._input_shapes.items():
                if s[0] % self.grad_accum:
                    raise MXNetError(
                        "grad_accum=%d does not divide the %r batch dim %d"
                        % (self.grad_accum, n, s[0]))
                if ndata > 1 and (s[0] // self.grad_accum) % ndata:
                    raise MXNetError(
                        "microbatch %d (batch %d / grad_accum %d) is not "
                        "divisible by the data-axis size %d"
                        % (s[0] // self.grad_accum, s[0], self.grad_accum,
                           ndata))
        self._build()
        return self

    # ------------------------------------------------ expert-layer load
    def _bind_moe_gauges(self, shapes):
        """The ``moe.*`` obs gauges of a graph with ``MoEExperts`` nodes:
        what the chip holds and is sent, set here, and how evenly, read
        from the nodes' count state whenever a snapshot is taken (a
        pull: the step carries the counts as it carries BatchNorm's
        statistics and nothing reads them on its path)."""
        layers = [n for n in self.prog.nodes
                  if not n.is_variable and n.op.name == "MoEExperts"]
        self._moe_layers = []
        if not layers:
            return
        inner = self.symbol.get_internals()
        shape_of = dict(zip(inner.list_outputs(),
                            inner.infer_shape(**shapes)[1]))
        entries = 0
        for n in layers:
            src, i = n.inputs[1]              # the router's chosen experts
            mine = int(np.prod(shape_of[
                "%s_%s" % (src.name, src.op.list_outputs(src.params)[i])]))
            entries += mine
            lo, held = n.params["first_expert"], n.params["experts_held"]
            self._moe_layers.append(
                (n.name + "_count", lo, lo + held,
                 held_chunk_rows(mine, held, n.params["num_experts"])))
        _obs.gauge("moe.experts_held").set(
            max(hi - lo for _, lo, hi, _ in self._moe_layers))
        _obs.gauge("moe.entries_per_step").set(entries)
        _obs.REGISTRY.pull(self._pull_moe_gauges)

    def _pull_moe_gauges(self):
        """``moe.held_entries_share``: of the last step's routing
        entries, the share sent to experts this chip holds, over all
        expert layers.  ``moe.load_max_over_mean``: the fullest held
        expert's entries over the mean held expert's.
        ``moe.row_chunks_per_layer``: the mean over the expert layers of
        the chunks of sorted rows that held a live entry, which is the
        trips ``moe_apply_held`` made after its first chunk, plus one: 1
        at the expected load, entries over the chunk's rows when every
        entry goes to a held expert."""
        if self.aux is None:
            return
        counts = jax.device_get([self.aux[layer[0]]
                                 for layer in self._moe_layers])
        held = [np.asarray(c)[lo:hi] for c, (_, lo, hi, _)
                in zip(counts, self._moe_layers)]
        total = float(sum(np.sum(c) for c in counts))
        if total and sum(h.sum() for h in held):
            _obs.gauge("moe.row_chunks_per_layer").set(float(np.mean(
                [row_chunks(int(h.sum()), rows) for h, (_, _, _, rows)
                 in zip(held, self._moe_layers)])))
            held = np.concatenate(held)
            _obs.gauge("moe.held_entries_share").set(
                float(held.sum()) / total)
            _obs.gauge("moe.load_max_over_mean").set(
                float(held.max() / held.mean()))

    def _bind_op_gauges(self):
        """The gauges that ops declare for their auxiliary state
        (``Op.gauges``), read from the state whenever a snapshot is
        taken, as the ``moe.*`` ones are: a pull."""
        self._gauge_nodes = [n for n in self.prog.nodes
                             if not n.is_variable and n.op.gauges is not None]
        if self._gauge_nodes:
            _obs.REGISTRY.pull(self._pull_op_gauges)

    def _pull_op_gauges(self):
        if self.aux is None:
            return
        read = {}
        for n in self._gauge_nodes:
            names = n.aux_names()
            state = jax.device_get([self.aux["%s_%s" % (n.name, a)]
                                    for a in names])
            for gauge, value in n.op.gauges(
                    n.params, dict(zip(names, state))).items():
                read.setdefault(gauge, []).append(value)
        for gauge, values in read.items():
            _obs.gauge(gauge).set(float(np.mean(values)))

    def _pull_host_gauge(self):
        """``train.host_ms_p50``: the median host time of the last
        ``_HOST_STEPS`` steps, from :meth:`step`'s entry to its return
        (placement, integrity, dispatch, bookkeeping), the first step
        left out.  The device's time is not in it: a step returns once
        its program is dispatched."""
        if self._host_ms:
            _obs.gauge("train.host_ms_p50").set(
                float(np.median(self._host_ms)))

    def __del__(self):
        # a trainer that goes away leaves its last reading in the gauges
        try:
            if getattr(self, "_gauge_nodes", None):
                self._pull_op_gauges()
            if getattr(self, "_moe_layers", None):
                self._pull_moe_gauges()
            self._pull_host_gauge()
        except Exception:          # noqa: BLE001 — never raise from a finalizer
            pass

    def _param_sharding(self, name):
        if self.mesh is None:
            return None
        spec = self.param_specs.get(name, PartitionSpec())
        return NamedSharding(self.mesh, spec)

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    force_init=False):
        """Host-side init then a one-time placement into HBM."""
        if self.params is not None and not force_init:
            return
        initializer = initializer or _init_mod.Uniform(0.01)
        attrs = self.symbol.attr_dict()

        def _seed(n, shape, given):
            if given is not None and n in given:
                # a device-resident mirror needs no trip through the
                # host.  Adopt via an on-device COPY (jnp.copy): the
                # step fn donates params, so aliasing the caller's
                # buffer would delete it after the first step; only
                # true host arrays pay an upload.
                src = given[n]
                return jnp.copy(src.data) if isinstance(src, NDArray) \
                    else jnp.asarray(np.asarray(src))
            arr = NDArray(jnp.zeros(shape, jnp.float32))
            initializer(InitDesc(n, attrs.get(n, {})), arr)
            return arr.data

        params = {n: self._place(_seed(n, self._arg_shapes[n], arg_params),
                                 self._param_sharding(n))
                  for n in self.param_names}
        aux = {n: self._place(_seed(n, self._aux_shapes[n], aux_params),
                              self._param_sharding(n))
               for n in self.aux_names}
        self.params, self.aux = params, aux
        init_fn, self._update_fn = make_update_fn(
            self.optimizer, self.param_names)
        init_kw = {} if self._opt_shardings is None else \
            {"out_shardings": self._opt_shardings}
        # state is born on its PLANNED sharding (zeros are not
        # sharding-connected to the weights, so propagation alone
        # could commit them anywhere).  Under zero=1 that means born
        # SHARDED: each chip materializes only its owned slice —
        # peak HBM never holds the replicated copy a post-hoc
        # reshard would.  A CompiledProgram like the step itself, so a
        # warm program cache also skips the init compile.
        self.opt_state = _program.CompiledProgram(
            "trainer.opt_init", init_fn,
            key=dict(self._pkey, prog="opt_init"),
            jit_kwargs=init_kw)(params)
        if self.sentinel != "off" and self._sent is None:
            # created once per trainer, NOT per (re-)init: init_params
            # doesn't reset num_update, and Module.fit's epoch-end
            # set_params refresh routes through here with force_init —
            # recreating the state would silently zero the skip counters
            # and desync the effective update cursor every epoch
            self._sent = self._init_sentinel(self.num_update)
        if self._integ_mode in ("fp", "vote") and self._integ is None:
            self._integ = self._init_integ()
        return self

    def _init_sentinel(self, t, skips=0, scale=None):
        """Fresh device-side sentinel state.  ``t`` is the effective
        update counter (advanced only on CLEAN steps, so a skipped batch
        leaves the optimizer's time axis exactly where a dropped batch
        would); ``skips``/``consec``/``good`` are the total-skip,
        consecutive-skip, and clean-streak counters; ``scale`` the
        current loss scale."""
        if scale is None:
            scale = _LS_INIT if self.loss_scale == "dynamic" else \
                float(self.loss_scale or 1.0)
        return {"skips": jnp.int32(skips), "consec": jnp.int32(0),
                "good": jnp.int32(0), "t": jnp.int32(t),
                "scale": jnp.float32(scale)}

    # ----------------------------------------------------- integrity
    def _resolve_integrity(self) -> bool:
        """Resolve the requested integrity mode against this build's
        topology and precompute the fingerprint leaf walk.  Returns
        True when the step carries in-step fingerprint state (fp/vote);
        ``audit`` is host-driven (deterministic step replay) and adds
        nothing to the step program."""
        if self.integrity == "off":
            self._integ_mode = "off"
            return False
        mode = self.integrity
        ndata = self._data_axis_size()
        if mode == "vote" and (
                ndata <= 1 or self.mesh is None
                or tuple(self.mesh.axis_names) != ("data",)):
            # the documented single-device fallback: a deterministic
            # replay audit (also taken on model/pipe meshes, where a
            # data-axis replica vote has no meaning)
            import logging as _logging
            _logging.getLogger("mxtpu.integrity").info(
                "integrity=vote needs a >=2-way pure-data mesh; "
                "falling back to the deterministic replay audit")
            mode = "audit"
        self._integ_mode = mode
        if mode == "audit":
            self._integ_external = False
            return False
        from jax.sharding import PartitionSpec as _P
        from .. import integrity as _integrity
        from .optim import state_shapes as _state_shapes
        arg_sds = {n: jax.ShapeDtypeStruct(tuple(self._arg_shapes[n]),
                                           jnp.float32)
                   for n in self.param_names}
        aux_sds = {n: jax.ShapeDtypeStruct(tuple(self._aux_shapes[n]),
                                           jnp.float32)
                   for n in self.aux_names}
        opt_sds = _state_shapes(self.optimizer, self.param_names,
                                self._arg_shapes)
        named = _integrity.named_state_leaves(arg_sds, aux_sds, opt_sds)
        self._integ_paths = [p for p, _ in named]
        self._integ_specs = [self._state_leaf_spec(p) or _P()
                             for p in self._integ_paths]
        # only REPLICATED leaves vote: ZeRO-1 shards (and any
        # tensor-parallel leaf) hold legitimately different bits per
        # device — they are fingerprinted per-shard for the record but
        # sit out the agreement check
        self._integ_rep_mask = np.array(
            [all(e is None for e in tuple(s)) for s in self._integ_specs],
            bool)
        # ZeRO-1 vote runs as a STANDALONE per-period program: the
        # zero-sharded step's partitioner is entitled to materialize a
        # claimed-replicated operand from its shards (slice +
        # all-gather), which rebuilds every replica's copy from the
        # same bytes and launders a physically divergent replica into
        # agreement before the in-step fingerprint reads it.  A program
        # whose ONLY consumer is the manual-sharding fingerprint reads
        # each device's own copy (tests/test_integrity.py asserts the
        # detection).  Costs one extra dispatch per period, not per
        # step.
        self._integ_external = (mode == "vote" and self.zero == 1)
        self._vote_fn = None
        return not self._integ_external

    def _init_integ(self):
        """Fresh device-side integrity carry: the per-replica per-leaf
        fingerprint matrix from the last check, the global content
        fingerprint, the agreement flag, and the update counter the
        check ran at.  ``agree`` starts true — no check has failed."""
        rows = self._data_axis_size() if self._integ_mode == "vote" else 1
        cols = len(self._integ_paths)
        return {"leaf": jnp.zeros((rows, cols), jnp.uint32),
                "global": jnp.uint32(0),
                "agree": jnp.int32(1),
                "step": jnp.int32(0)}

    def _state_leaf_spec(self, path):
        """PartitionSpec of a state leaf by its integrity path (None
        without a mesh)."""
        from jax.sharding import PartitionSpec as _P
        if self.mesh is None:
            return None
        ns, _, rest = path.partition(":")
        if ns in ("arg", "aux"):
            return self.param_specs.get(rest, _P())
        if self._opt_shardings is not None:
            import jax.tree_util as jtu
            for name, tree in self._opt_shardings.items():
                for kp, sh in jtu.tree_flatten_with_path(tree)[0]:
                    if "opt:%s%s" % (name, jtu.keystr(kp)) == path:
                        return sh.spec
        return _P()

    def _make_integ_update(self):
        """The in-step fingerprint/vote closure (traced into the fused
        step under ``lax.cond`` on the check flag)."""
        from jax import lax
        from .. import integrity as _integrity
        paths = self._integ_paths
        salts = jnp.asarray(np.array([_integrity.path_salt(p)
                                      for p in paths], np.uint32))
        vote_on = self._integ_mode == "vote"
        mesh = self.mesh
        specs = tuple(self._integ_specs)
        rep_cols = np.where(self._integ_rep_mask)[0]

        def integ_update(params, aux, opt_state, integ, check, t):
            def compute(_):
                named = _integrity.named_state_leaves(params, aux,
                                                      opt_state)
                leaves = [v for _, v in named]
                lf = jnp.stack([_integrity.leaf_fingerprint(v)
                                for v in leaves])
                gfp = _integrity.fold_fingerprints(lf, salts)
                if vote_on:
                    def local(*vals):
                        return jnp.stack(
                            [_integrity.leaf_fingerprint(v)
                             for v in vals]).reshape(1, -1)

                    # each replica fingerprints ITS copy (shards: its
                    # shard); rows stack along the data axis.  check_vma
                    # off: divergent replicas are the signal, not a bug
                    mat = jax.shard_map(
                        local, mesh=mesh, in_specs=specs,
                        out_specs=PartitionSpec("data", None),
                        check_vma=False)(*leaves)
                    if len(rep_cols):
                        agree = jnp.all(mat[:, rep_cols]
                                        == mat[0:1, rep_cols])
                    else:
                        agree = jnp.bool_(True)
                else:
                    mat = lf.reshape(1, -1)
                    agree = jnp.bool_(True)
                return {"leaf": mat, "global": gfp,
                        "agree": agree.astype(jnp.int32),
                        "step": jnp.asarray(t, jnp.int32)}

            with jax.named_scope(_INTEGRITY_SCOPE):
                return lax.cond(check, compute, lambda _: integ, 0)

        return integ_update

    def _zero_keeps_shard(self, name: str) -> bool:
        """True when ``name``'s zero-sharded grad spec owns dim 0 along
        the data axis — the lowp reduce-scatter can then hand the update
        its f32 shard directly (no gather, no extra bf16 rounding)."""
        sh = (self._grad_shardings or {}).get(name)
        return bool(sh is not None and len(sh.spec)
                    and sh.spec[0] == "data")

    def opt_state_bytes_per_chip(self) -> int:
        """Optimizer-state bytes resident on ONE chip.  Replicated state
        counts at full size (every chip holds a copy); zero-sharded
        state at ~1/n — what ``tools/stepcost.cost_model`` reports as
        ``opt_state_bytes_per_chip``."""
        if self.opt_state is None:
            return 0
        total, dev = 0, None
        for leaf in jax.tree.leaves(self.opt_state):
            shards = getattr(leaf, "addressable_shards", None)
            if not shards:
                total += int(getattr(leaf, "nbytes", 0))
                continue
            if dev is None:
                dev = shards[0].device
            total += sum(int(s.data.nbytes) for s in shards
                         if s.device == dev)
        return int(total)

    def grad_comm_bytes_per_step(self) -> int:
        """Analytic per-chip gradient-comm wire bytes for one fused step
        (0 without a >1 data axis).  f32 SPMD path: ring all-reduce
        ``2*(n-1)/n`` of the f32 grad bytes, once per microbatch (the
        psum lives inside each scan iteration).  bf16 path: the two-phase
        reduce in ``collectives.lowp_allreduce`` — half the f32 bytes —
        fired once per step regardless of ``grad_accum``."""
        n = self._data_axis_size()
        if n <= 1:
            return 0
        from .collectives import lowp_comm_bytes
        total = 0.0
        for nm in self.param_names:
            shape = tuple(self._arg_shapes[nm])
            if self._lowp_on:
                total += lowp_comm_bytes(
                    shape, n, 2, keep_shard=self._zero_keeps_shard(nm))
            else:
                size = int(np.prod(shape or (1,)))
                total += 2 * (n - 1) / n * size * 4 * self.grad_accum
        return int(total)

    def _place(self, value, sharding):
        if sharding is None:
            return value
        if self.multihost:
            # each process contributes its addressable part (for a
            # replicated sharding: the full identical array)
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(value))
        return jax.device_put(value, sharding)

    def _local_rows(self, out):
        """This process's rows of a batch-sharded global output (already
        whole on single-host)."""
        if not self.multihost:
            return out
        # fast path assumes sharding along dim 0 only; an output that
        # came back sharded along a non-batch dim (e.g. tensor-parallel
        # param_specs) must be assembled globally first
        if any(any(sl != slice(None) and (sl.start, sl.stop) != (0, dim)
                   for sl, dim in zip(s.index[1:], out.shape[1:]))
               for s in out.addressable_shards):
            full = self._host_value(out)
            rows = out.shape[0] // jax.process_count()
            p = jax.process_index()
            return jnp.asarray(full[p * rows:(p + 1) * rows])
        shards = {}
        for s in out.addressable_shards:
            start = s.index[0].start or 0 if s.index else 0
            shards[start] = s.data
        parts = [shards[k] for k in sorted(shards)]
        if len(parts) == 1:
            return jnp.asarray(parts[0])
        # shards live on different local devices; assemble host-side
        # (outputs are small: batch rows x classes)
        return jnp.asarray(np.concatenate([np.asarray(p) for p in parts], 0))

    # ------------------------------------------------------------------
    def _build(self):
        prog = self.prog
        param_set = set(self.param_names)
        arg_names = prog.arg_names
        aux_names = self.aux_names
        compute_dtype = self.compute_dtype
        index_inputs = _index_inputs(prog.nodes)
        init_fn, update_fn = make_update_fn(self.optimizer, self.param_names)
        self._update_fn = update_fn

        def _forward(params, aux_vals, batch, key, is_train):
            with jax.named_scope(_CAST_SCOPE):
                # raw-uint8 input batches (NativeImageRecordIter
                # dtype="uint8"): the float cast happens HERE, on device
                # — the caller shipped quarter-size bytes over the host
                # link and the graph still sees float input
                batch = {n: (v.astype(compute_dtype or jnp.float32)
                             if v.dtype == jnp.uint8 else v)
                         for n, v in batch.items()}
                if compute_dtype is not None:
                    params = {n: (v.astype(compute_dtype)
                                  if jnp.issubdtype(v.dtype, jnp.floating)
                                  else v)
                              for n, v in params.items()}
                    batch = {n: (v.astype(compute_dtype)
                                 if jnp.issubdtype(v.dtype, jnp.floating)
                                 and n not in index_inputs else v)
                             for n, v in batch.items()}
                    aux_vals = [(v.astype(compute_dtype)
                                 if jnp.issubdtype(v.dtype, jnp.floating)
                                 else v)
                                for v in aux_vals]
            vals = [params[n] if n in param_set else batch[n]
                    for n in arg_names]
            outs, new_aux = prog._eval(vals, list(aux_vals), key, is_train)
            return outs, new_aux

        policy = remat_policy(self.remat)
        sentinel_on = self.sentinel != "off"
        scaling = self.loss_scale is not None and self._ls_applies
        dynamic_ls = self.loss_scale == "dynamic"
        growth = self.ls_growth_interval
        K = self.grad_accum
        ndata = self._data_axis_size()
        zero_on = self._zero_on
        lowp_on = self._lowp_on
        mesh = self.mesh
        has_rng = prog.has_rng

        # --- ZeRO-1 planning: per-leaf optimizer-state (and grad)
        # shardings along the mesh ``data`` axis, computed from the
        # abstract state pytree so init can place state ALREADY sharded
        # (peak HBM never holds a replicated copy) and resume can place
        # restored leaves back onto the owned shards.
        self._opt_shardings = None
        self._grad_shardings = None
        if mesh is not None and mesh.size > 1:
            from .optim import zero_state_shardings
            from .mesh import zero_spec as _zero_spec
            self._opt_shardings = zero_state_shardings(
                mesh, self.optimizer, self.param_names, self._arg_shapes,
                self.param_specs, zero=1 if zero_on else 0)
            if zero_on:
                self._grad_shardings = {
                    n: NamedSharding(mesh, _zero_spec(
                        self.param_specs.get(n, PartitionSpec()),
                        self._arg_shapes[n], ndata))
                    for n in self.param_names}

        def _micro_backward(params, aux_vals, batch, key, scale):
            """One microbatch fwd+vjp.  Returns ``(outs, new_aux tuple,
            f32 grads)`` with the loss scale still folded into the grads
            — unscaling happens once per STEP, after accumulation and
            the cross-chip reduction, so every microbatch pays only the
            seed multiply."""
            def fwd(p):
                return _forward(p, list(aux_vals), batch, key, True)

            if policy is not None:
                fwd = jax.checkpoint(fwd, policy=policy)
            (outs, new_aux), vjp = jax.vjp(fwd, params)
            # cotangent seeds in the OUTPUT dtype (bf16 under
            # compute_dtype): the whole backward chain runs
            # low-precision elementwise — the byte-diet dtype policy's
            # cotangent half; its reduction half (f32 accumulation)
            # lives in the op backward formulations (op/bytediet.py) and
            # in the f32 master-weight grad cast below.  The loss scale
            # rides the seeds: small bf16 cotangents stay out of
            # flush-to-zero.
            with jax.named_scope(_CAST_SCOPE):
                if scale is None:
                    seeds = tuple(jnp.ones(o.shape, o.dtype) for o in outs)
                else:
                    seeds = tuple(jnp.full(o.shape, scale.astype(o.dtype),
                                           o.dtype) for o in outs)
                cot = (seeds,
                       tuple(jnp.zeros(a.shape, a.dtype) for a in new_aux))
            grads = vjp(cot)[0]
            with jax.named_scope(_CAST_SCOPE):
                grads = {n: g.astype(jnp.float32) for n, g in grads.items()}
                # aux (BN moving stats) keep fp32 master copies like
                # params do
                new_aux = tuple(
                    v.astype(jnp.float32)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v
                    for v in new_aux)
            return outs, new_aux, grads

        def _accum_backward(params, aux_vals, batch, key, scale, spmd):
            """K-microbatch gradient accumulation inside ONE jitted step
            (``grad_accum``): reshape the batch to a leading microbatch
            dim and ``lax.scan`` the vjp over it, summing into an f32
            grad buffer; the optimizer update fires once per K.  On the
            lowp (shard_map) path the cross-chip reduction also fires
            once per K — the SPMD path's psum stays inside each scan
            iteration because GSPMD cannot represent an unreduced
            partial-sum carry (documented in perf.md)."""
            if K == 1:
                return _micro_backward(params, tuple(aux_vals), batch, key,
                                       scale)
            with jax.named_scope(_CAST_SCOPE):
                mb = {}
                for nm, v in batch.items():
                    m = v.shape[0] // K
                    v = v.reshape((K, m) + v.shape[1:])
                    if spmd and self._batch_shardings is not None \
                            and "data" in mesh.axis_names:
                        # keep each MICROBATCH row-sharded over the data
                        # axis (the reshape would otherwise tempt the
                        # partitioner to shard the scan dim)
                        v = jax.lax.with_sharding_constraint(
                            v, NamedSharding(mesh,
                                             PartitionSpec(None, "data")))
                    mb[nm] = v
                gsum0 = {nm: jnp.zeros(params[nm].shape, jnp.float32)
                         for nm in params}
                steps = jnp.arange(K)

            def body(carry, xs):
                aux_c, gsum = carry
                batch_i, i = xs
                with jax.named_scope(_CAST_SCOPE):
                    k = jax.random.fold_in(key, i) if has_rng else key
                outs, new_aux, g = _micro_backward(params, aux_c, batch_i,
                                                   k, scale)
                with jax.named_scope(_CAST_SCOPE):
                    gsum = jax.tree.map(jnp.add, gsum, g)
                return (new_aux, gsum), outs

            (aux_fin, gsum), outs_k = jax.lax.scan(
                body, (tuple(aux_vals), gsum0), (mb, steps))
            with jax.named_scope(_CAST_SCOPE):
                # microbatch k produced rows [k*m, (k+1)*m): flattening
                # the (K, m, ...) stack restores the original batch order
                outs = tuple(o.reshape((o.shape[0] * o.shape[1],)
                                       + o.shape[2:]) for o in outs_k)
            return outs, aux_fin, gsum

        if lowp_on:
            from .collectives import lowp_allreduce
            keep_shard = {nm: self._zero_keeps_shard(nm)
                          for nm in self.param_names}

            def _lowp_backward(params, aux_vals, batch, key, scale):
                """Reduced-precision gradient comm (``grad_dtype=bf16``):
                the backward runs shard_map'd over the data axis so the
                gradient reduction is EXPLICIT — local grads round to
                bf16 before the wire and the reduction accumulates in
                f32 (collectives.lowp_allreduce), halving cross-chip
                gradient bytes.  Per-replica semantics shift with the
                manual sharding: BN batch stats are computed per shard
                and pmean-combined (the reference's multi-device BN),
                and dropout decorrelates via a per-shard key fold."""
                def local(params, aux_vals, batch, key, *maybe_scale):
                    sc = maybe_scale[0] if maybe_scale else None
                    if has_rng:
                        with jax.named_scope(_CAST_SCOPE):
                            key2 = jax.random.fold_in(
                                key, jax.lax.axis_index("data"))
                    else:
                        key2 = key
                    outs, new_aux, g = _accum_backward(
                        params, aux_vals, batch, key2, sc, spmd=False)
                    with jax.named_scope("grad_allreduce_bf16"):
                        g = {nm: lowp_allreduce(gl, "data", ndata,
                                                jnp.bfloat16,
                                                keep_shard=keep_shard[nm])
                             for nm, gl in g.items()}
                        new_aux = tuple(
                            jax.lax.pmean(v, "data")
                            if jnp.issubdtype(v.dtype, jnp.floating) else v
                            for v in new_aux)
                    return outs, new_aux, g

                P = PartitionSpec
                gspecs = {nm: P("data") if keep_shard[nm] else P()
                          for nm in self.param_names}
                in_specs = (P(), P(), P("data"), P()) + (
                    (P(),) if scale is not None else ())
                args = (params, tuple(aux_vals), batch, key) + (
                    (scale,) if scale is not None else ())
                # check_vma can't statically see through the
                # all_to_all/all_gather pair; replication of the P()
                # outputs holds by construction (pmean'd aux, gathered
                # grads)
                return jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=(P("data"), P(), gspecs),
                                 check_vma=False)(*args)

        def _run_backward(params, aux, batch, key, scale):
            """fwd+bwd (+accumulation, +grad comm) for one step: returns
            ``(outs, new_aux tuple, f32 grads)`` with the loss scale
            divided back out and, under zero=1, grads constrained onto
            the owned shard (reduce-scatter instead of all-reduce — the
            update only ever reads the shard)."""
            aux_vals = [aux[n] for n in aux_names]
            if lowp_on:
                outs, new_aux, grads = _lowp_backward(params, aux_vals,
                                                      batch, key, scale)
            else:
                outs, new_aux, grads = _accum_backward(params, aux_vals,
                                                       batch, key, scale,
                                                       spmd=True)
            if scale is not None:
                with jax.named_scope(_CAST_SCOPE):
                    inv = 1.0 / scale
                    grads = {n: g * inv for n, g in grads.items()}
            if zero_on:
                with jax.named_scope("zero_grad_shard"):
                    grads = {n: jax.lax.with_sharding_constraint(
                        g, self._grad_shardings[n])
                        for n, g in grads.items()}
            return outs, new_aux, grads

        p_shard_all = {n: self._param_sharding(n) for n in self.param_names}

        def _apply_update(params, grads, opt_state, lr, t):
            # named scope: the breakdown tool attributes optimizer-state
            # traffic to this label instead of "(unattributed)"
            with jax.named_scope("optimizer_update"):
                new_params, new_state = update_fn(params, grads, opt_state,
                                                  lr, t)
            if zero_on:
                with jax.named_scope("zero_shard"):
                    # state stays on the owned shard; updated params
                    # all-gather back to their own (replicated or
                    # tensor-parallel) sharding for the next forward
                    new_state = {
                        n: jax.tree.map(jax.lax.with_sharding_constraint,
                                        new_state[n],
                                        self._opt_shardings[n])
                        for n in new_state}
                    new_params = {
                        n: jax.lax.with_sharding_constraint(
                            v, p_shard_all[n])
                        for n, v in new_params.items()}
            return new_params, new_state

        def step(params, aux, opt_state, batch, lr, t, key):
            outs, new_aux, grads = _run_backward(params, aux, batch, key,
                                                 None)
            new_params, new_state = _apply_update(params, grads, opt_state,
                                                  lr, t)
            with jax.named_scope(_CAST_SCOPE):
                outs = tuple(o.astype(jnp.float32) for o in outs)
            return new_params, dict(zip(aux_names, new_aux)), new_state, outs

        param_names_sorted = list(self.param_names)

        def step_sentinel(params, aux, opt_state, sent, batch, lr, t, key):
            """The sentinel build: same math as ``step`` plus a global
            grad-finiteness flag on the already-materialized f32 grads.
            Non-finite ⇒ every state leaf lax-selects its OLD value (the
            skip), the effective update counter ``sent["t"]`` holds, and
            the skip counters advance — all on device, zero host
            round-trips (the ``abort`` host check reads ``consec``
            explicitly).  Skip-equals-drop is exact for the optimizer's
            time axis; the HOST ``num_update`` (lr_scheduler ticks, the
            step RNG key) still advances on a skip — GradScaler
            semantics, see docs/how_to/resilience.md."""
            scale = sent["scale"] if scaling else None
            outs, new_aux, grads = _run_backward(params, aux, batch, key,
                                                 scale)
            with jax.named_scope("sentinel_finite"):
                finite = jnp.bool_(True)
                for n in param_names_sorted:
                    finite = jnp.logical_and(
                        finite, jnp.all(jnp.isfinite(grads[n])))
                t_eff = sent["t"] + 1
            new_params, new_state = _apply_update(params, grads, opt_state,
                                                  lr, t_eff)
            with jax.named_scope("sentinel_select"):
                keep = lambda new, old: jnp.where(finite, new, old)  # noqa: E731
                new_params = jax.tree.map(keep, new_params, params)
                new_state = jax.tree.map(keep, new_state, opt_state)
                new_aux = tuple(keep(v, aux[n])
                                for n, v in zip(aux_names, new_aux))
                # the skip counters and the loss scale's schedule
                good = jnp.where(finite, sent["good"] + 1, jnp.int32(0))
                new_scale = sent["scale"]
                if dynamic_ls:
                    grown = good >= growth
                    new_scale = jnp.where(
                        finite,
                        jnp.where(grown,
                                  jnp.minimum(new_scale * 2.0,
                                              jnp.float32(_LS_MAX)),
                                  new_scale),
                        jnp.maximum(new_scale * 0.5, jnp.float32(1.0)))
                    good = jnp.where(grown, jnp.int32(0), good)
                new_sent = {
                    "skips": sent["skips"] + jnp.where(finite, 0, 1),
                    "consec": jnp.where(finite, jnp.int32(0),
                                        sent["consec"] + 1),
                    "good": good,
                    "t": jnp.where(finite, t_eff, sent["t"]),
                    "scale": new_scale,
                }
            with jax.named_scope(_CAST_SCOPE):
                outs = tuple(o.astype(jnp.float32) for o in outs)
            return (new_params, dict(zip(aux_names, new_aux)), new_state,
                    new_sent, outs)

        def evaluate(params, aux, batch, key):
            aux_vals = [aux[n] for n in aux_names]
            outs, _ = _forward(params, aux_vals, batch, key, False)
            with jax.named_scope(_CAST_SCOPE):
                return tuple(o.astype(jnp.float32) for o in outs)

        def evaluate_train(params, aux, batch, key):
            aux_vals = [aux[n] for n in aux_names]
            outs, _ = _forward(params, aux_vals, batch, key, True)
            with jax.named_scope(_CAST_SCOPE):
                return tuple(o.astype(jnp.float32) for o in outs)

        # --- integrity fingerprint + vote, fused into the step
        # (docs/how_to/resilience.md "Silent data corruption"): every
        # `integrity_period`-th update dispatches a check-step program
        # that bitcasts the carried (params, aux, opt-state) leaves to
        # uint32 and tree-folds them into per-leaf and global checksums
        # fused with the update — one read of state bytes, no host
        # round-trip; all other steps dispatch the plain program an
        # unarmed trainer runs.  "vote" additionally shard_maps the per-leaf
        # fingerprints over the data axis: replicated state must be
        # bit-identical across replicas, so an all-gathered row per
        # replica turns a flaky chip into a countable minority (ZeRO-1
        # shards fingerprint per-shard and sit out the vote — shards
        # legitimately differ).
        integ_on = self._resolve_integrity()
        self._integ_fused = integ_on
        sentinel_or_plain = step_sentinel if sentinel_on else step
        n_sent = 1 if sentinel_on else 0
        step_check = None
        if integ_on:
            # TWO programs, not a lax.cond riding every call: the
            # check-step program fuses the fingerprint with the update,
            # and the other `period - 1` steps dispatch the SAME plain
            # program an unarmed trainer runs — the cond variant kept
            # the carry + flag as per-call args, a fixed ~0.2 ms of
            # dispatch per step that dwarfs a small model's whole step
            # (and 'off-period steps execute nothing extra' held for
            # the device, not the host).  Costs one extra compile.
            integ_update = self._make_integ_update()
            n_core = 3 + n_sent

            def step_check(*args):
                integ = args[n_core]
                batch, lr, t, key = args[n_core + 1:]
                new_integ = integ_update(args[0], args[1], args[2],
                                         integ, jnp.bool_(True), t)
                core = sentinel_or_plain(*(args[:n_core]
                                           + (batch, lr, t, key)))
                return core[:-1] + (new_integ, core[-1])

        step_fn = sentinel_or_plain
        # donate state + sentinel; in the check program NOT the integ
        # carry (its buffer is replaced by the check, but the replay
        # paths re-read the pre-step carry) — batch sits one slot later
        # there
        donate = tuple(range(3 + n_sent)) + (
            (3 + n_sent,) if self.donate_batch else ())
        donate_check = tuple(range(3 + n_sent)) + (
            (3 + n_sent + 1,) if self.donate_batch else ())

        if self.mesh is not None and self.mesh.size > 1:
            mesh = self.mesh
            if "data" in mesh.axis_names:
                self._batch_shardings = {
                    n: batch_sharding(mesh, len(self._input_shapes[n]))
                    for n in self._input_shapes}
            else:
                # model/seq-only mesh: inputs replicated, params sharded
                self._batch_shardings = {
                    n: replicated(mesh) for n in self._input_shapes}
            rep = replicated(mesh)
            p_shard = {n: self._param_sharding(n) for n in self.param_names}
            a_shard = {n: self._param_sharding(n) for n in self.aux_names}
            # opt state mirrors param sharding per leaf — except under
            # zero=1, where the explicit zero-sharded specs are enforced
            # at the boundary (in == out == owned shard: the donated
            # update stays a true in-place shard write).  The sentinel
            # state is five replicated scalars (sharding left to the
            # partitioner), donated with the rest of the carried state.
            opt_in = self._opt_shardings
            # OUTPUT shardings for the carried state are pinned to the
            # same specs as the inputs: the partitioner is otherwise
            # free to hand state back under a different layout (a
            # model-sharded classifier tempts it to co-shard BN aux or
            # conv-weight momentum, breaking the donation alias and the
            # NEXT call's in_shardings; zero's constrained-but-unpinned
            # params came back row-sharded).  in == out == planned spec
            # keeps every donated state write a true in-place update.
            # Sentinel/integrity scalars and the graph outputs stay
            # unpinned.
        # every trainer program is a CompiledProgram artifact: counted
        # traces, one lint/obs surface, and — with MXTPU_PROGRAM_CACHE
        # armed — a persisted AOT executable a restarted process loads
        # instead of recompiling (docs/how_to/compiled_programs.md)
        self._pkey = pkey = self._program_key()

        def _prog_of(name, fn, **jkw):
            return _program.CompiledProgram(
                "trainer.%s" % name, fn, key=dict(pkey, prog=name),
                jit_kwargs=jkw)

        if self.mesh is not None and self.mesh.size > 1:
            in_core = (p_shard, a_shard, opt_in) + (None,) * n_sent
            in_tail = (self._batch_shardings, None, None, None)
            out_core = (p_shard, a_shard, opt_in) + (None,) * n_sent
            self._step_fn = _prog_of(
                "step", step_fn,
                in_shardings=in_core + in_tail,
                out_shardings=out_core + (None,),
                donate_argnums=donate)
            if step_check is not None:
                self._step_check_fn = _prog_of(
                    "step_check", step_check,
                    in_shardings=in_core + (None,) + in_tail,
                    out_shardings=out_core + (None, None),
                    donate_argnums=donate_check)
            self._eval_fn = _prog_of(
                "eval", evaluate,
                in_shardings=(p_shard, a_shard, self._batch_shardings,
                              None))
            self._eval_train_fn = _prog_of(
                "eval_train", evaluate_train,
                in_shardings=(p_shard, a_shard, self._batch_shardings,
                              None))
        else:
            self._step_fn = _prog_of("step", step_fn,
                                     donate_argnums=donate)
            if step_check is not None:
                self._step_check_fn = _prog_of("step_check", step_check,
                                               donate_argnums=donate_check)
            self._eval_fn = _prog_of("eval", evaluate)
            self._eval_train_fn = _prog_of("eval_train", evaluate_train)

    # ------------------------------------------------------------------
    def _device_batch(self, batch: Dict) -> Dict:
        out = {}
        for n in self._input_shapes:
            v = batch[n]
            if self.multihost:
                v = v.asnumpy() if isinstance(v, NDArray) else np.asarray(v)
                out[n] = jax.make_array_from_process_local_data(
                    self._batch_shardings[n], v)
                continue
            if isinstance(v, NDArray):
                v = v.data
            elif isinstance(v, jax.Array):
                pass          # already on device — never bounce via host
            else:
                v = jnp.asarray(np.asarray(v))
            if self._batch_shardings is not None:
                want = self._batch_shardings[n]
                # a batch the staging pipeline already committed to the
                # right sharding (DeviceUploadIter resolves the
                # trainer's shardings per batch) passes through — no
                # second device_put dispatch per input per step
                if not (isinstance(v, jax.Array)
                        and getattr(v, "sharding", None) == want):
                    v = jax.device_put(v, want)
            out[n] = v
        return out

    def step(self, batch: Dict, lr: Optional[float] = None) -> List[NDArray]:
        """One fused train step.  Returns the graph outputs.

        The step is a ``jax.profiler.StepTraceAnnotation`` named
        ``train`` with the update's number, so that a profiler capture
        marks the step boundaries; its host time feeds
        ``train.host_ms_p50``."""
        t0 = time.perf_counter()
        with jax.profiler.StepTraceAnnotation("train",
                                              step_num=self.num_update + 1):
            outs = self._step(batch, lr)
        if self._stepped:
            self._host_ms.append((time.perf_counter() - t0) * 1e3)
        self._stepped = True
        return outs

    def _step(self, batch: Dict, lr: Optional[float]) -> List[NDArray]:
        if self.params is None:
            raise MXNetError("call bind() + init_params() first")
        self.num_update += 1
        self.optimizer.num_update = self.num_update
        if lr is None:
            if self.optimizer.lr_scheduler is not None:
                lr = self.optimizer.lr_scheduler(self.num_update)
            else:
                lr = self.optimizer.lr
        key = jax.random.fold_in(self._key, self.num_update) \
            if self.prog.has_rng else self._key
        # whole-host death (docs/how_to/multi_host.md "Elastic
        # training"): SIGKILL-faithful, before this rank's shard enters
        # the step collectives.  Elastic runs hit the same directive one
        # layer up (ElasticCoordinator.guard, before the step barrier);
        # this site covers non-elastic runs.
        if _faults.hit("host_dead", step=self.num_update,
                       rank=_process_index()):
            import os
            os._exit(137)
        corr = ("s%d" % self.num_update) if _obs.OBS else None
        with _obs.phase("train.h2d", corr=corr):
            dev_batch = self._device_batch(batch)
        # fault injection (docs/how_to/resilience.md): poison the staged
        # batch so the backward materializes non-finite grads and the
        # sentinel's skip/abort path runs for real
        if _faults.hit("nan_grad", step=self.num_update):
            dev_batch = self._poison_batch(dev_batch)
        # cache the lr device scalar: one H2D per lr *change*, not per step
        if self._lr_cache is None or self._lr_cache[0] != lr:
            self._lr_cache = (lr, jnp.float32(lr))
        # integrity check cadence (docs/how_to/resilience.md "Silent
        # data corruption"): fp/vote fingerprint inside THIS step's
        # program; audit replays the whole step from copied inputs
        check_now = self._integ is not None and \
            self.num_update % self.integrity_period == 0
        audit_now = self._integ_mode == "audit" and \
            self.num_update % self.integrity_period == 0
        t_dev = jnp.int32(max(1, self.num_update))
        if check_now and self._integ_external:
            # ZeRO-1: the standalone vote reads THIS update's incoming
            # state (same bits the fused check would have hashed) before
            # the step's all-gather can launder a divergent replica
            with _obs.phase("train.integrity", corr=corr,
                            attrs={"mode": self._integ_mode}):
                self._external_vote()
                self._integrity_after_check()
            check_now = False
        use_check = (self._integ is not None and self._integ_fused
                     and check_now)
        saved = self._audit_snapshot(dev_batch) if audit_now else None
        args = (self.params, self.aux, self.opt_state)
        if self._sent is not None:
            args += (self._sent,)
        if use_check:
            args += (self._integ,)
        args += (dev_batch, self._lr_cache[1], t_dev, key)
        with _obs.phase("train.dispatch", corr=corr):
            out = (self._step_check_fn if use_check
                   else self._step_fn)(*args)
        self.params, self.aux, self.opt_state = out[0], out[1], out[2]
        i = 3
        if self._sent is not None:
            self._sent = out[i]
            i += 1
        if use_check:
            self._integ = out[i]
            i += 1
        outs = out[i]
        if self._sent is not None and self.sentinel == "abort":
            # abort mode accepts the per-step device->host sync: the
            # point IS to stop the moment K batches in a row went bad
            consec = int(np.asarray(
                self._host_value(self._sent["consec"])))
            if consec >= self.sentinel_max_skips:
                raise MXNetError(
                    "step sentinel: %d consecutive non-finite "
                    "gradient steps (threshold %d) at update %d — "
                    "aborting (MXTPU_SENTINEL=abort)"
                    % (consec, self.sentinel_max_skips,
                       self.num_update))
        # silent-corruption injection (docs/how_to/resilience.md): flip
        # one mantissa bit of a state leaf on one replica's device copy
        # AFTER the update — a corrupt HBM write the NaN sentinel can
        # never see; the next integrity check has to notice it instead
        if _faults.active("bitflip"):
            self._apply_bitflip_faults()
        if audit_now:
            with _obs.phase("train.integrity", corr=corr,
                            attrs={"mode": "audit"}):
                self._audit_check(saved, t_dev, key)
        if check_now:
            with _obs.phase("train.integrity", corr=corr,
                            attrs={"mode": self._integ_mode}):
                self._integrity_after_check()
        return [NDArray(self._local_rows(o)) for o in outs]

    def _poison_batch(self, dev_batch: Dict) -> Dict:
        """Replace the first floating input with NaN (the ``nan_grad``
        fault): elementwise multiply keeps shape, dtype, and sharding."""
        out = dict(dev_batch)
        for n in self.data_names + self.label_names:
            v = out.get(n)
            if v is not None and jnp.issubdtype(v.dtype, jnp.floating):
                out[n] = v * jnp.asarray(float("nan"), v.dtype)
                return out
        raise MXNetError("nan_grad fault: no floating input to poison "
                         "among %s" % (list(dev_batch),))

    # ------------------------------------------------- integrity (host)
    def _named_state(self):
        from .. import integrity as _integrity
        return _integrity.named_state_leaves(self.params, self.aux,
                                             self.opt_state)

    def _run_fp(self, named):
        """Run the cached standalone fingerprint program over ``named``
        (path, leaf) pairs; returns device (gfp, per-leaf) scalars."""
        from .. import integrity as _integrity
        salts = jnp.asarray(np.array(
            [_integrity.path_salt(p) for p, _ in named], np.uint32))
        if self._fp_fn is None:
            def fp_impl(leaves, salts):
                lf = jnp.stack([_integrity.leaf_fingerprint(v)
                                for v in leaves])
                return _integrity.fold_fingerprints(lf, salts), lf
            self._fp_fn = _program.CompiledProgram(
                "trainer.fp", fp_impl,
                key=dict(self._pkey, prog="fp"))
        return self._fp_fn([v for _, v in named], salts)

    def state_fingerprint(self) -> dict:
        """Device-computed fingerprint of the carried (params, aux,
        opt-state) — the record ``CheckpointManager.save`` stamps into
        the manifest so a reloaded checkpoint can be re-hashed against
        what the DEVICE held at save time (catching post-CRC byte
        patches and corrupt host transfers alike).  One compiled
        program, cached; reads L+1 scalars."""
        from .. import integrity as _integrity
        if self.params is None:
            raise MXNetError("state_fingerprint needs bind()+init_params()")
        if self._integ_mode == "vote":
            self._save_vote_check()
        named = self._named_state()
        paths = [p for p, _ in named]
        gfp, lf = self._run_fp(named)
        lf = np.asarray(self._host_value(lf))
        return _integrity.manifest_record(
            int(np.asarray(self._host_value(gfp))),
            {p: int(v) for p, v in zip(paths, lf)},
            mode=self._integ_mode)

    def _global_fp_int(self, params, aux, opt_state) -> int:
        from .. import integrity as _integrity
        named = _integrity.named_state_leaves(params, aux, opt_state)
        gfp, _ = self._run_fp(named)
        return int(np.asarray(self._host_value(gfp)))

    def _save_vote_check(self):
        """Replica agreement on the CURRENT state before a fingerprint
        is stamped into a manifest: a corruption landing between the
        last periodic check and an epoch-end save would otherwise be
        hashed into a 'verified' checkpoint (host reads of a replicated
        array take replica 0's copy, so the saved bytes and the record
        agree with each other while the replicas do not) — and rollback
        would then restore the corruption to EVERY replica, converting
        a detectable divergence into a permanent silent one.  Runs the
        same standalone program as _external_vote (a local carry: this
        is a gate, not a periodic check — it must not touch self._integ
        or the divergence counters)."""
        from .. import integrity as _integrity
        from ..integrity import IntegrityError
        if self._vote_fn is None:
            self._vote_fn = _program.CompiledProgram(
                "trainer.vote", self._make_integ_update(),
                key=dict(self._pkey, prog="vote"))
        integ = self._vote_fn(
            self.params, self.aux, self.opt_state, self._init_integ(),
            jnp.bool_(True), jnp.int32(max(1, self.num_update)))
        if int(np.asarray(self._host_value(integ["agree"]))):
            return
        mat = np.asarray(self._host_value(integ["leaf"]))
        rep_cols = np.where(self._integ_rep_mask)[0]
        _, blamed, div_cols = _integrity.blame_minority(mat, rep_cols)
        raise IntegrityError(
            "state_fingerprint REFUSED at update %d: replicas disagree "
            "on replicated state leaf/leaves %s (blamed replica(s): %s) "
            "— stamping this state would mint a verified-but-corrupt "
            "checkpoint; the save stays CRC-only and the next integrity "
            "check rolls back past it"
            % (self.num_update,
               [self._integ_paths[c] for c in div_cols][:4], blamed))

    def _external_vote(self):
        """The ZeRO-1 vote: a standalone compiled program whose only
        consumer of the state is the manual-sharding fingerprint, so
        each device provably hashes ITS copy (see _resolve_integrity —
        the fused step's zero partitioning may rebuild a replicated
        operand from its shards and launder the divergence).  One extra
        dispatch per integrity period."""
        if self._vote_fn is None:
            self._vote_fn = _program.CompiledProgram(
                "trainer.vote", self._make_integ_update(),
                key=dict(self._pkey, prog="vote"))
        self._integ = self._vote_fn(
            self.params, self.aux, self.opt_state, self._integ,
            jnp.bool_(True), jnp.int32(max(1, self.num_update)))

    def _apply_bitflip_faults(self):
        """Consume armed ``bitflip`` directives: corrupt the matched
        state leaf on the targeted replica, on device."""
        from .. import integrity as _integrity
        ndata = max(1, self._data_axis_size())
        for rank in range(ndata):
            payload = _faults.hit_params("bitflip", step=self.num_update,
                                         rank=rank)
            if payload is None:
                continue
            pattern = str(payload.get("leaf", "*"))
            bit = int(payload.get("bit", 12))
            named = self._named_state()
            f32_paths = [p for p, v in named
                         if getattr(v, "dtype", None) == jnp.float32]
            target = _integrity.match_leaf(pattern, f32_paths)
            if target is None:
                raise MXNetError(
                    "bitflip fault: leaf glob %r matches no f32 state "
                    "leaf (have %s%s)"
                    % (pattern, f32_paths[:6],
                       "..." if len(f32_paths) > 6 else ""))
            value = dict(named)[target]
            mesh = self.mesh if self._data_axis_size() > 1 else None
            flipped = _integrity.bitflip(
                value, rank, bit=bit, mesh=mesh,
                spec=self._state_leaf_spec(target) if mesh is not None
                else None)
            self._set_state_leaf(target, flipped)
            import logging as _logging
            _logging.getLogger("mxtpu.integrity").warning(
                "bitflip fault fired: leaf %s bit %d rank %d at update "
                "%d", target, bit, rank, self.num_update)

    def _set_state_leaf(self, path: str, value) -> None:
        import jax.tree_util as jtu
        ns, _, rest = path.partition(":")
        if ns == "arg":
            self.params[rest] = value
            return
        if ns == "aux":
            self.aux[rest] = value
            return
        for name in self.opt_state:
            flat, treedef = jtu.tree_flatten(self.opt_state[name])
            with_path = jtu.tree_flatten_with_path(
                self.opt_state[name])[0]
            for i, (kp, _) in enumerate(with_path):
                if "opt:%s%s" % (name, jtu.keystr(kp)) == path:
                    flat[i] = value
                    self.opt_state[name] = jtu.tree_unflatten(treedef,
                                                              flat)
                    return
        raise MXNetError("no state leaf at %r" % (path,))

    def _audit_snapshot(self, dev_batch):
        """On-device copies of everything the step consumes — the
        ``(params, batch, rng)`` the deterministic replay re-runs from.
        Copies, not aliases: the step donates its inputs."""
        copy = jax.tree.map(jnp.copy, (
            self.params, self.aux, self.opt_state,
            self._sent if self._sent is not None else {}))
        batch = {n: jnp.copy(v) for n, v in dev_batch.items()} \
            if self.donate_batch else dev_batch
        return copy + (batch,)

    def _audit_check(self, saved, t_dev, key):
        """The single-device audit: re-execute the step from the saved
        inputs and compare output-state fingerprints.  XLA programs are
        deterministic, so ANY difference — a flaky ALU, a corrupt HBM
        write (or the injected ``bitflip``) — is a divergence."""
        from ..integrity import IntegrityError
        s_params, s_aux, s_opt, s_sent, s_batch = saved
        args = (s_params, s_aux, s_opt)
        if self._sent is not None:
            args += (s_sent,)
        args += (s_batch, self._lr_cache[1], t_dev, key)
        out = self._step_fn(*args)
        fp_live = self._global_fp_int(self.params, self.aux,
                                      self.opt_state)
        fp_replay = self._global_fp_int(out[0], out[1], out[2])
        if fp_live == fp_replay:
            return
        record = {"step": int(self.num_update), "mode": "audit",
                  "world": 1, "fps": [[fp_live], [fp_replay]],
                  "leaves": [], "blamed": None}
        self.integrity_divergences += 1
        raise IntegrityError(
            "integrity audit: update %d executed twice from identical "
            "inputs produced different state fingerprints (%08x vs "
            "replay %08x) — silent corruption during execution; roll "
            "back to the last verified checkpoint"
            % (self.num_update, fp_live, fp_replay), record)

    def _integrity_after_check(self):
        """Host half of a fp/vote check step: read the (tiny) agree
        flag; on disagreement build the divergence record, blame the
        strict minority when one exists, and raise.  On an AGREEING
        check that replays a previously recorded divergence step, close
        the loop: the replica whose recorded fingerprints match the
        honest replay is exonerated, the rest are blamed (this is how a
        1-vs-1 split — two replicas, no majority — gets attributed)."""
        from .. import integrity as _integrity
        agree = bool(int(np.asarray(
            self._host_value(self._integ["agree"]))))
        pend = self._integrity_pending
        if agree:
            if pend is not None and pend.get("mode") == "vote" \
                    and pend.get("step") == self.num_update:
                self._integrity_pending = None
                mat = np.asarray(self._host_value(self._integ["leaf"]))
                rep = np.where(self._integ_rep_mask)[0]
                fresh = [int(v) for v in mat[0][rep]]
                rows = pend.get("fps") or []
                exonerated = [
                    r for r in range(len(rows))
                    if [int(rows[r][c]) for c in rep] == fresh]
                blamed = sorted(set(range(len(rows)))
                                - set(exonerated)) if exonerated else None
                pend["blamed"] = blamed
                import logging as _logging
                log = _logging.getLogger("mxtpu.integrity")
                if blamed:
                    self.integrity_blamed.append(pend)
                    log.warning(
                        "integrity: rollback replay of update %d "
                        "matches replica(s) %s — BLAMING replica(s) %s "
                        "for the recorded divergence (leaves %s)",
                        self.num_update, exonerated, blamed,
                        pend.get("leaves"))
                    if self.on_integrity_blame is not None:
                        self.on_integrity_blame(pend)
                else:
                    log.warning(
                        "integrity: rollback replay of update %d "
                        "matches no recorded replica — blame "
                        "indeterminate (corruption predated the check "
                        "window)", self.num_update)
            return
        from ..integrity import IntegrityError
        mat = np.asarray(self._host_value(self._integ["leaf"]))
        rep_cols = np.where(self._integ_rep_mask)[0]
        _, blamed, div_cols = _integrity.blame_minority(mat, rep_cols)
        record = {"step": int(self.num_update), "mode": "vote",
                  "world": int(mat.shape[0]),
                  "fps": [[int(v) for v in row] for row in mat],
                  "leaves": [self._integ_paths[c] for c in div_cols],
                  "blamed": blamed}
        self.integrity_divergences += 1
        if blamed is not None:
            self.integrity_blamed.append(record)
            if self.on_integrity_blame is not None:
                self.on_integrity_blame(record)
            self._integrity_pending = None
        else:
            # no strict majority (e.g. 2 replicas): the rollback replay
            # of this step resolves attribution — see the agree branch
            self._integrity_pending = record
        raise IntegrityError(
            "integrity vote FAILED at update %d: replicas disagree on "
            "%d replicated state leaf/leaves %s — blamed replica(s): "
            "%s; roll back to the last verified checkpoint and re-step"
            % (self.num_update, len(div_cols), record["leaves"][:4],
               blamed if blamed is not None else
               "indeterminate (no strict majority)"), record)

    @property
    def sentinel_skips(self) -> int:
        """Total sentinel-skipped steps (device counter; reading it
        syncs, so poll it at epoch/bench granularity, not per step).
        Every read refreshes this trainer's
        ``train.trainer<N>.sentinel_skips`` registry gauge (instance
        scoped — two trainers in one process must not clobber each
        other), so an ``obs.snapshot()`` scrape sees the same number
        the fit loop last saw."""
        if self._sent is None:
            return 0
        skips = int(np.asarray(self._host_value(self._sent["skips"])))
        gauge = getattr(self, "_obs_skips_gauge", None)
        if gauge is None:
            gauge = self._obs_skips_gauge = _obs.gauge(
                "%s.sentinel_skips"
                % _obs.REGISTRY.scope("train.trainer"))
        gauge.set(skips)
        return skips

    @property
    def loss_scale_value(self) -> float:
        """Current loss scale (1.0 when scaling is off)."""
        if self._sent is None:
            return 1.0
        return float(np.asarray(self._host_value(self._sent["scale"])))

    def forward(self, batch: Dict) -> List[NDArray]:
        """Inference forward (is_train=False) as one compiled program."""
        dev_batch = self._device_batch(batch)
        outs = self._eval_fn(self.params, self.aux, dev_batch, self._key)
        return [NDArray(self._local_rows(o)) for o in outs]

    def forward_train(self, batch: Dict) -> List[NDArray]:
        """Training-mode forward WITHOUT the update — for callers that
        read outputs between forward(is_train=True) and the fused step.
        Costs one extra compiled program; the fused ``step`` is the fast
        path."""
        dev_batch = self._device_batch(batch)
        outs = self._eval_train_fn(self.params, self.aux, dev_batch,
                                   self._key)
        return [NDArray(self._local_rows(o)) for o in outs]

    def lint(self, config: Optional[Dict] = None,
             input_dtypes: Optional[Dict] = None):
        """Trace-time lint of the fused step: re-trace ``_step_fn`` to
        its pjit jaxpr and run the jaxpr-level hazard passes (f64
        widening, host callbacks, non-donated state buffers, unfused
        gather/scatter), each finding attributed to its symbol layer via
        the per-node named scopes.  Pure ``jax.make_jaxpr`` — no device
        execution.  Pass ``input_dtypes`` (name -> dtype) for int-token
        or uint8-pipeline inputs so the trace matches the real step.
        Returns an ``analysis.LintReport``."""
        from .. import analysis
        return analysis.lint_trainer(self, config=config,
                                     input_dtypes=input_dtypes)

    # ------------------------------------------------ lowered programs
    def abstract_step_args(self, input_dtypes: Optional[Dict] = None):
        """The fused step's argument pytree as ``ShapeDtypeStruct``s —
        exactly what ``_step_fn`` consumes, so ``jax.make_jaxpr`` can
        re-derive the step program without touching device state.
        Shared by the lint (``analysis.lint_trainer``) and comm
        (:meth:`comm_plan`) paths so both analyze the SAME program.
        ``input_dtypes`` overrides traced batch dtypes (name -> dtype)
        for int-token / uint8-pipeline models; unlisted inputs trace
        float32."""
        if self._step_fn is None or self.params is None:
            raise MXNetError("abstract_step_args needs a bound, "
                             "initialized Trainer (bind() + "
                             "init_params() first)")
        input_dtypes = input_dtypes or {}
        sds = lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype)  # noqa: E731
        sent = self._sent
        return (
            {n: sds(v) for n, v in self.params.items()},
            {n: sds(v) for n, v in self.aux.items()},
            jax.tree_util.tree_map(sds, self.opt_state),
        ) + ((jax.tree_util.tree_map(sds, sent),) if sent is not None
             else ()) + (
            {n: jax.ShapeDtypeStruct(
                tuple(s), np.dtype(input_dtypes.get(n, np.float32)))
             for n, s in self._input_shapes.items()},
            jnp.float32(0.01), jnp.int32(1), jax.random.key(0),
        )

    def step_jaxpr(self, input_dtypes: Optional[Dict] = None,
                   x64: bool = False):
        """The fused step traced to its ClosedJaxpr (pure
        ``jax.make_jaxpr`` — no device execution).  ``x64=True`` traces
        under ``enable_x64`` so an f64 widening APPEARS instead of
        being silently truncated (the lint path); the comm path traces
        plain, seeing the wire dtypes the program actually runs."""
        args = self.abstract_step_args(input_dtypes)
        if x64:
            with jax.enable_x64(True):
                return jax.make_jaxpr(self._step_fn)(*args)
        return jax.make_jaxpr(self._step_fn)(*args)

    def comm_plan(self, input_dtypes: Optional[Dict] = None):
        """The step's ordered comm plan: every collective the compiled
        step will issue, with axis, dtype, element count, predicted
        per-chip wire bytes, and named-scope layer provenance
        (``analysis.comm_passes.CommEntry``).

        Two sources, by construction complementary (docs/how_to/
        static_analysis.md "Communication analysis"):

        * **jaxpr-extracted** — explicit collectives in the traced
          program: the shard_map'd bf16 gradient wire
          (``lowp_allreduce``'s all_to_all / all_gather), shard_map'd
          parallelism bodies.
        * **spmd-synthesized** — on the plain SPMD path the gradient
          psum is inserted by GSPMD at compile time and never appears
          as a jaxpr equation; the trainer synthesizes those entries
          from its own sharding plan with the SAME analytic model as
          :meth:`grad_comm_bytes_per_step`, one psum per param leaf
          (x ``grad_accum`` — the SPMD psum lives inside each scan
          iteration).

        The plan total therefore agrees with
        ``grad_comm_bytes_per_step`` (``tests/test_comm_lint.py`` holds
        the two equal on every corner), and its digest
        (``analysis.plan_digest``) is the cross-rank parity token the
        elastic guard checks before the first step."""
        from ..analysis import comm_passes
        from .collectives import collective_wire_bytes
        axis_sizes = dict(self.mesh.shape) if self.mesh is not None else {}
        plan = comm_passes.extract_comm_plan(
            self.step_jaxpr(input_dtypes), axis_sizes)
        n = self._data_axis_size()
        if n > 1 and not self._lowp_on:
            # GSPMD-implied gradient reduction (no jaxpr equation to
            # extract): one data-axis psum per param leaf, fired per
            # microbatch
            for nm in self.param_names:
                size = int(np.prod(tuple(self._arg_shapes[nm]) or (1,)))
                wire = collective_wire_bytes("psum", size, 4, n)
                plan.append(comm_passes.CommEntry(
                    len(plan), "psum", "data", "float32", size,
                    wire * self.grad_accum, layer=nm, bwd=True,
                    repeat=self.grad_accum, source="spmd"))
        return plan

    def mem_timeline(self, input_dtypes: Optional[Dict] = None):
        """The fused step's predicted buffer-liveness timeline
        (``analysis.mem_passes.MemTimeline``): per-chip peak bytes
        under this trainer's sharding plan, the argmax program point,
        and the per-layer breakdown — the static capacity answer to
        "does this config fit before I run it".  Pure
        ``jax.make_jaxpr``; no device execution."""
        from ..analysis import mem_passes
        return mem_passes.trainer_timeline(self, input_dtypes)

    def predicted_peak_bytes(self,
                             input_dtypes: Optional[Dict] = None) -> int:
        """Predicted per-chip peak HBM bytes of one fused step (the
        ``mem_timeline`` peak) — what autotune's feasibility surrogate
        and the serving admission ledger consume."""
        return int(self.mem_timeline(input_dtypes).peak_bytes_per_chip)

    def get_opt_states(self) -> bytes:
        """Serialize (num_update, optimizer state pytree[, sentinel
        state]) — the fused analog of ``Updater.get_states`` (reference
        ``optimizer.py``).  The sentinel's effective update counter and
        loss scale ride along so a resumed run continues the SAME time
        axis a skip-free replay would."""
        import pickle
        state = jax.tree.map(self._host_value, self.opt_state)
        if self._sent is None:
            return pickle.dumps((self.num_update, state))
        sent = {k: np.asarray(self._host_value(v))
                for k, v in self._sent.items()}
        return pickle.dumps((self.num_update, state, sent))

    def set_opt_states(self, blob: bytes) -> None:
        import pickle
        try:
            loaded = pickle.loads(blob)
        except Exception as e:                      # noqa: BLE001
            raise MXNetError(
                "optimizer state blob is truncated or corrupt: %s"
                % (e,)) from e
        sent_host = None
        if len(loaded) == 3:
            num_update, state, sent_host = loaded
        else:                      # pre-sentinel blobs stay loadable
            num_update, state = loaded
        self.num_update = num_update
        self.optimizer.num_update = num_update
        if self.sentinel != "off":
            if sent_host is not None:
                self._sent = {k: (jnp.float32(v) if k == "scale"
                                  else jnp.int32(v))
                              for k, v in sent_host.items()}
            else:
                # blob predates the sentinel: seed the effective update
                # counter from num_update (no skips recorded)
                self._sent = self._init_sentinel(num_update)
        if self._integ_mode in ("fp", "vote"):
            # restored state invalidates the carried fingerprints; a
            # PENDING divergence record survives on the host so the
            # rollback replay can still resolve blame
            self._integ = self._init_integ()
        cur = self.opt_state

        def _restore(sharding, c, n):
            # restore onto the PLANNED sharding — the zero-sharded spec
            # under zero=1, else the param sharding (opt state mirrors
            # it per leaf).  NOT the current leaf's own sharding: that
            # can be an uncommitted single-device placement from the
            # jitted init_fn, and committing the restored copy there
            # would trip the step's device-set consistency check on a
            # mesh.  The serialized blob always holds gathered-on-host
            # GLOBAL leaves (``get_opt_states`` reads through
            # ``_host_value``), so an old replicated blob restores onto
            # a zero-sharded run — and vice versa — by construction.
            if sharding is None:
                return jnp.asarray(n)
            if self.multihost:
                # hand each device exactly its slice of the global array
                n = np.asarray(n)
                return jax.make_array_from_callback(
                    n.shape, sharding, lambda idx: n[idx])
            return jax.device_put(jnp.asarray(n), sharding)

        if self._opt_shardings is not None:
            # per-LEAF shardings (zero-sharded or param-mirrored)
            self.opt_state = {
                name: jax.tree.map(_restore, self._opt_shardings[name],
                                   cur[name], state[name])
                for name in cur}
        else:
            self.opt_state = {
                name: jax.tree.map(
                    lambda c, n, _sh=self._param_sharding(name):
                    _restore(_sh, c, n), cur[name], state[name])
                for name in cur}

    # ------------------------------------------------------------------
    def _host_value(self, v):
        """Global host copy of a (possibly multi-host) device array.
        Replicated leaves read the local replica; sharded leaves
        all-gather — a COLLECTIVE, so on multi-host every process must
        call checkpoint reads in lockstep (as ``Module.fit`` does)."""
        if not self.multihost:
            return np.asarray(v)
        if getattr(v, "is_fully_replicated", True):
            return np.asarray(v.addressable_data(0))
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(v, tiled=True))

    def get_params(self):
        if self.multihost:
            arg = {n: NDArray(jnp.asarray(self._host_value(v)))
                   for n, v in self.params.items()}
            aux = {n: NDArray(jnp.asarray(self._host_value(v)))
                   for n, v in self.aux.items()}
            return arg, aux
        arg = {n: NDArray(v) for n, v in self.params.items()}
        aux = {n: NDArray(v) for n, v in self.aux.items()}
        return arg, aux

    def set_params(self, arg_params, aux_params=None):
        def _val(v):
            # device-resident values: no host round-trip (each asnumpy
            # drains the dispatch queue), but DO copy on device — the donated step fn would otherwise
            # delete the caller's buffer after the next step
            raw = v.data if isinstance(v, NDArray) else np.asarray(v)
            return jnp.copy(jnp.asarray(raw, dtype=jnp.float32))

        for n, v in (arg_params or {}).items():
            if n in self.params:
                self.params[n] = self._place(_val(v),
                                             self._param_sharding(n))
        for n, v in (aux_params or {}).items():
            if n in self.aux:
                self.aux[n] = self._place(_val(v),
                                          self._param_sharding(n))
