"""Graph executor: Symbol -> one jitted XLA computation.

Reference: ``GraphExecutor`` (``src/executor/graph_executor.cc:372-446``)
runs a 10-stage pass pipeline then pushes one engine op per node.  Here
``bind`` builds a single pure function that walks the graph (a Python trace,
run once), jits it, and:

  * ``forward(is_train=True)`` calls ``jax.vjp`` on the jitted function —
    the forward executes as ONE compiled XLA program and the residuals are
    kept for backward (no recompute; the linearize/transpose caches make the
    per-step Python overhead bounded).
  * ``backward(out_grads)`` calls the pullback — one more compiled program.
  * the reference's mirror option (``MXNET_BACKWARD_DO_MIRROR``) is the
    ``remat_segment`` node attribute: a run of consecutive nodes that
    carry one value is evaluated as one function whose intermediates the
    backward pass computes again instead of keeping
    (``_GraphProgram._eval_segment``).
  * memory planning (``PlanMemory``), in-place detection
    (``DetectInplaceAddTo``) and op fusion (bulk segments) are all XLA's
    job; none of the reference's passes exist here because the compiler
    subsumes them.

PRNG for stochastic nodes (Dropout): a key is folded per forward call and
per node — the functional replacement of ``ResourceRequest::kRandom``.
"""
from __future__ import annotations

import collections
import itertools
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .base import MXNetError, _dtype, current_context
from .ndarray import NDArray, zeros
from .op.registry import OpContext
from .symbol import Symbol, _topo

__all__ = ["Executor", "bind", "simple_bind", "SEGMENT_ATTR"]

# The node attribute that names a recomputation segment
# (``with mx.AttrScope(remat_segment="u1"): ...``): this framework's form
# of the reference's ``force_mirroring`` attribute and
# ``MXNET_BACKWARD_DO_MIRROR`` (``src/executor/graph_executor.cc:210``).
# There the planner picks single nodes whose outputs it recomputes; here
# the Symbol's author names a whole block, whose every intermediate is
# dropped after the forward pass and computed again, once, when the
# backward pass reaches the block.
SEGMENT_ATTR = "remat_segment"
# what goes before a node's scope in the backward pass of its segment:
# AGAIN where its forward operations run a second time, BACK on their
# reverse modes, so that a device trace tells the two apart
AGAIN, BACK = "remat.", "remat_bwd."


# A run of consecutive compute nodes that carry one ``remat_segment``
# value, the entries they read from outside, the entries the rest
# reads, their auxiliary states' slots, and whether any draws from a key.
_Segment = collections.namedtuple(
    "_Segment", "name nodes reads gives aux_slots uses_rng")


def _zero_ct(value):
    """The cotangent of a value that nothing differentiable reads."""
    if jnp.issubdtype(value.dtype, jnp.inexact):
        return jnp.zeros_like(value)
    return np.zeros(np.shape(value), jax.dtypes.float0)


def _jax_device_for(ctx):
    """Map a Context onto a concrete jax device (a tpu Context degrades
    to the default backend when no TPU platform is visible)."""
    try:
        devs = jax.devices(ctx.device_type)
    except RuntimeError:
        devs = jax.devices()
    return devs[ctx.device_id % len(devs)]


class _GraphProgram:
    """The compiled form of a Symbol: pure fn + metadata."""

    def __init__(self, sym: Symbol):
        self.sym = sym
        self.nodes = _topo([e[0] for e in sym._outputs])
        self.arg_names = sym.list_arguments()
        self.aux_names = sym.list_auxiliary_states()
        self.output_entries = list(sym._outputs)
        self._arg_index = {n: i for i, n in enumerate(self.arg_names)}
        # aux slots per node
        self._aux_index = {n: i for i, n in enumerate(self.aux_names)}
        self.has_rng = any((not n.is_variable) and n.op.uses_rng
                           for n in self.nodes)
        # eval-mode forward only needs a fresh key when some op draws
        # at is_train=False (samplers); Dropout-style train-only noise
        # must not cost per-forward key derivation in inference
        self.has_eval_rng = any((not n.is_variable) and n.op.uses_rng
                                and n.op.rng_in_eval for n in self.nodes)
        self.has_host_callback = any((not n.is_variable)
                                     and n.op.host_callback
                                     for n in self.nodes)
        # target backend for platform-specialized op lowerings
        self.platform = None
        # residual/intermediate dtype policy for backward formulations
        # (op/bytediet.py); None inherits the process default
        self.dtype_policy = None
        # group2ctx placement: node name -> jax device.  The TPU analog
        # of the reference's PlaceDevice pass + _CrossDeviceCopy insertion
        # (src/executor/graph_executor.cc:241-318): inside the single
        # jitted program, a node with a placement gets its outputs pinned
        # with jax.device_put; XLA inserts the cross-device transfers.
        self.placement = {}
        self._jitted = {}
        self._plan = self._plan_segments()

    # ------------------------------------------------------------------
    def _plan_segments(self):
        """None for a Symbol with no marked node (the walk is then the
        plain one), else ``(steps, rng_index)``: the compute nodes in
        the walk's own order, every run of consecutive ones that carry
        one ``remat_segment`` value gathered into a :class:`_Segment`,
        and for every node the number the plain walk folds into its
        key."""
        compute = [n for n in self.nodes if not n.is_variable]
        if not any(n.attrs.get(SEGMENT_ATTR) for n in compute):
            return None
        rng_index, count = {}, 0
        for n in self.nodes:
            rng_index[id(n)] = count
            count += 1 if n.is_variable else n.op.n_outputs(n.params)
        readers = {}                     # entry -> the nodes that read it
        for n in compute:
            for c, i in n.inputs:
                readers.setdefault((id(c), i), set()).add(id(n))
        outputs = {(id(nd), i) for nd, i in self.output_entries}
        steps = []
        for name, run in itertools.groupby(
                compute, lambda n: n.attrs.get(SEGMENT_ATTR) or None):
            ns = list(run)
            if name is None:
                steps.extend(ns)
                continue
            for n in ns:
                if n.op.host_callback:
                    raise MXNetError(
                        "node %r (op %s) calls back into Python and cannot "
                        "be in recomputation segment %r: it would be called "
                        "twice a step" % (n.name, n.op.name, name))
            inside = {id(n) for n in ns}
            steps.append(_Segment(
                name=name, nodes=ns,
                reads=list(dict.fromkeys(
                    (id(c), i) for n in ns for c, i in n.inputs
                    if id(c) not in inside)),
                gives=[(id(n), i) for n in ns
                       for i in range(n.op.n_outputs(n.params))
                       if (id(n), i) in outputs
                       or readers.get((id(n), i), set()) - inside],
                aux_slots=[self._aux_index["%s_%s" % (n.name, a)]
                           for n in ns for a in n.aux_names()],
                uses_rng=any(n.op.uses_rng for n in ns)))
        return steps, rng_index

    # ------------------------------------------------------------------
    def _eval_node(self, n, env, aux_vals, aux_out, rng_key, is_train,
                   monitor=None, rng_index=None, scope=None):
        """Run one compute node against ``env`` (in-place)."""
        in_vals = [env[(id(c), i)] for c, i in n.inputs]
        aux_names = n.aux_names()
        aux_slots = [self._aux_index["%s_%s" % (n.name, a)]
                     for a in aux_names]
        node_aux = [aux_vals[s] for s in aux_slots]
        # the named scope stamps the symbol name into the XLA metadata
        # (op_name="jit(..)/<node>/..") of every primitive this node
        # traces, its key's derivation included — benchmark/lib/
        # tracered.py joins a traced device op back to its symbol-level
        # layer through it
        with jax.named_scope(scope or n.name):
            if aux_names:
                node_aux = [jax.lax.stop_gradient(v) for v in node_aux]
            rng = None
            if n.op.uses_rng:
                rng = jax.random.fold_in(
                    rng_key, len(env) if rng_index is None else rng_index)
            ctx = OpContext(is_train=is_train, rng=rng,
                            platform=self.platform,
                            dtype_policy=self.dtype_policy)
            outs, aux_updates = n.op.apply(n.params, ctx,
                                           *(in_vals + node_aux))
        dev = self.placement.get(n.name)
        if dev is not None:
            outs = tuple(jax.device_put(o, dev) for o in outs)
        for i, v in enumerate(outs):
            env[(id(n), i)] = v
            if monitor is not None:
                monitor("%s_%s" % (n.name, n.op.list_outputs(n.params)[i]),
                        v)
        for s, v in zip(aux_slots, aux_updates):
            aux_out[s] = v

    def _eval_segment(self, seg, env, aux_vals, aux_out, rng_key,
                      rng_index):
        """Run a segment as one function of the entries it reads from
        outside, which are all the backward pass keeps of it: what lies
        between is computed again when the cotangents of ``seg.gives``
        arrive, behind a barrier that keeps the compiler from merging
        the second computation with the first.  This is what
        ``jax.checkpoint`` does, written out node by node: a
        ``checkpoint`` body is lowered under the one name ``checkpoint``,
        before its nodes' own scopes, and a device trace could no longer
        tell a segment's nodes apart (tests/test_executor.py holds both
        forms against each other).  Here a node keeps its scope, with
        ``remat.`` before it where its forward operations run again and
        ``remat_bwd.`` on their reverse modes.  Auxiliary states and
        keys are inputs, so a BatchNorm or Dropout node inside computes
        the same statistics, the same mask and the same new state as
        outside, both times."""
        n_in, n_aux = len(seg.reads), len(seg.aux_slots)
        impl = jax.random.key_impl(rng_key) if seg.uses_rng else None

        def unpack(flat):
            key = jax.random.wrap_key_data(flat[-1], impl=impl) \
                if seg.uses_rng else None
            return (dict(zip(seg.reads, flat[:n_in])),
                    dict(zip(seg.aux_slots, flat[n_in:n_in + n_aux])), key)

        def body(*flat):
            local, aux_in, key = unpack(flat)
            new_aux = {}
            for n in seg.nodes:
                self._eval_node(n, local, aux_in, new_aux, key, True,
                                rng_index=rng_index[id(n)])
            return tuple(local[e] for e in seg.gives) \
                + tuple(new_aux[s] for s in seg.aux_slots)

        def body_bwd(flat, cts):
            local, aux_in, key = unpack(lax.optimization_barrier(flat))
            pulls = []
            for n in seg.nodes:
                ins = [(id(c), i) for c, i in n.inputs]
                outs = [(id(n), i) for i in range(n.op.n_outputs(n.params))]

                def node(*vals, n=n, ins=ins, outs=outs):
                    at = dict(zip(ins, vals))
                    self._eval_node(n, at, aux_in, {}, key, True,
                                    rng_index=rng_index[id(n)],
                                    scope=AGAIN + n.name)
                    return tuple(at[e] for e in outs)

                vals, pull = jax.vjp(node, *[local[e] for e in ins])
                local.update(zip(outs, vals))
                pulls.append((BACK + n.name, ins, outs, pull))
            ct = dict(zip(seg.gives, cts))
            for scope, ins, outs, pull in reversed(pulls):
                with jax.named_scope(scope):
                    got = pull(tuple(ct.pop(e) if e in ct
                                     else _zero_ct(local[e]) for e in outs))
                for e, g in zip(ins, got):
                    if g.dtype != jax.dtypes.float0:
                        ct[e] = ct[e] + g if e in ct else g
            return tuple(ct[e] if e in ct else _zero_ct(v)
                         for e, v in zip(seg.reads, flat)) \
                + tuple(_zero_ct(v) for v in flat[n_in:])

        run = jax.custom_vjp(body)
        run.defvjp(lambda *flat: (body(*flat), flat), body_bwd)
        flat = [env[e] for e in seg.reads] \
            + [aux_vals[s] for s in seg.aux_slots]
        if seg.uses_rng:
            flat.append(jax.random.key_data(rng_key))
        out = run(*flat)
        env.update(zip(seg.gives, out))
        for s, v in zip(seg.aux_slots, out[len(seg.gives):]):
            aux_out[s] = v

    def _eval_planned(self, arg_vals, aux_vals, rng_key):
        """The walk of a Symbol with marked nodes, in training."""
        steps, rng_index = self._plan
        env = {(id(n), 0): arg_vals[self._arg_index[n.name]]
               for n in self.nodes if n.is_variable}
        aux_out = list(aux_vals)
        for step in steps:
            if isinstance(step, _Segment):
                self._eval_segment(step, env, aux_vals, aux_out, rng_key,
                                   rng_index)
            else:
                self._eval_node(step, env, aux_vals, aux_out, rng_key,
                                True, rng_index=rng_index[id(step)])
        outputs = tuple(env[(id(nd), i)] for nd, i in self.output_entries)
        return outputs, tuple(aux_out)

    def _eval(self, arg_vals, aux_vals, rng_key, is_train, monitor=None):
        if self._plan is not None and is_train and monitor is None:
            return self._eval_planned(arg_vals, aux_vals, rng_key)
        env = {}
        aux_out = list(aux_vals)
        for n in self.nodes:
            if n.is_variable:
                env[(id(n), 0)] = arg_vals[self._arg_index[n.name]]
                continue
            self._eval_node(n, env, aux_vals, aux_out, rng_key, is_train,
                            monitor)
        outputs = tuple(env[(id(nd), i)] for nd, i in self.output_entries)
        return outputs, tuple(aux_out)

    def jitted(self, is_train):
        if is_train not in self._jitted:
            def fn(arg_vals, aux_vals, rng_key):
                return self._eval(list(arg_vals), list(aux_vals), rng_key,
                                  is_train)
            # one unified compiled-program artifact per (symbol, mode):
            # counted, lint-visible, and — eval mode, MXTPU_PROGRAM_CACHE
            # armed — persisted, so a re-bound process loads the forward
            # instead of re-tracing it.  group2ctx placements pin nodes
            # to concrete local devices, which don't belong in a
            # cross-process key: those programs stay in-memory only.
            from . import program as _program
            key = None
            if not self.placement:
                key = {"symbol": _program.symbol_digest(self.sym),
                       "train": bool(is_train),
                       "platform": self.platform,
                       "dtype_policy": self.dtype_policy}
            self._jitted[is_train] = _program.CompiledProgram(
                "executor.forward", fn, key=key)
        return self._jitted[is_train]


class Executor:
    """Bound executor (reference ``include/mxnet/executor.h:34-102``)."""

    def __init__(self, sym: Symbol, ctx, args: Dict[str, NDArray],
                 args_grad: Optional[Dict[str, NDArray]],
                 grad_req, aux_states: Dict[str, NDArray],
                 group2ctx=None):
        self._symbol = sym
        self._ctx = ctx or current_context()
        self._prog = _GraphProgram(sym)
        self.arg_dict = args
        self.grad_dict = args_grad or {}
        self.aux_dict = aux_states
        self.arg_arrays = [args[n] for n in self._prog.arg_names]
        # platform for backend-specialized lowerings: taken from where
        # the bound arrays actually live (a tpu Context is a host device
        # when the process was held to the CPU, e.g. the CPU test mesh)
        if self.arg_arrays:
            plat = next(iter(self.arg_arrays[0].data.devices())).platform
        else:
            plat = jax.default_backend()
        self._prog.platform = plat

        self.grad_arrays = [self.grad_dict.get(n) for n in self._prog.arg_names]
        self.aux_arrays = [aux_states[n] for n in self._prog.aux_names]
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self._prog.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self._prog.arg_names, grad_req))
        self.grad_req = grad_req
        self._group2ctx = group2ctx or {}
        if self._group2ctx:
            attrs = sym.attr_dict()
            for n in self._prog.nodes:
                if n.is_variable:
                    continue
                group = (getattr(n, "attrs", None) or {}).get("ctx_group") \
                    or attrs.get(n.name, {}).get("ctx_group")
                if group in self._group2ctx:
                    self._prog.placement[n.name] = \
                        _jax_device_for(self._group2ctx[group])
        self._outputs: List[NDArray] = []
        self._vjp = None
        self._monitor = None
        self._lint_report = None   # set by simple_bind's lint hook
        self._debug_ann = None     # cached analyzer annotation
        self._const_key = None      # cached rng key for rng-free programs
        self._const_key_dev = None
        self._partial = None      # partial_forward's carried env
        self._partial_done = False  # a sequence ran to completion
        self._rng_counter = 0

    @property
    def outputs(self):
        return self._outputs

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self._outputs))

    # ------------------------------------------------------------------
    def _next_key(self, is_train=True):
        from . import random as _random
        if (self._prog.has_rng and is_train) or self._prog.has_eval_rng:
            return _random.next_key()
        # the key is a dead argument this mode (rng-free program, or
        # train-only noise ops at is_train=False) — build and place it
        # ONCE (each jax.random.key / fold_in / device_put is a
        # dispatched op)
        if self._const_key is None:
            self._const_key = jax.random.key(0)
        return self._const_key

    def _await_host_callbacks(self, vals):
        """Wait for a program that calls back into Python before anything
        that consumes it is dispatched.  The callback runs NDArray ops on
        the device its program occupies, and the runtime admits a bounded
        number of computations in flight (XLA's CPU client takes a
        semaphore in ``Execute``): ones queued behind this program keep
        their slot while they wait for it, so a caller that ran far enough
        ahead leaves the callback waiting for a slot that only its own
        return can free."""
        if self._prog.has_host_callback:
            jax.block_until_ready(vals)

    def _eager_committed(self, vals):
        """Pin values for the eager per-node paths (monitor, partial
        forward).  Bound arrays can be UNCOMMITTED — allocated on the
        host while another platform is the jax default.  The jitted
        paths still execute where the arrays live, but eager ops on
        uncommitted inputs dispatch to the DEFAULT platform, silently
        changing matmul precision when that default is a TPU; committing
        the inputs keeps eager evaluation numerically identical to the
        compiled path."""
        try:
            dev = list(self.arg_arrays[0].data.devices())[0]
        except Exception:
            return list(vals)
        return [jax.device_put(v, dev) for v in vals]

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("unknown argument %s" % k)
            if isinstance(v, NDArray):
                self.arg_dict[k]._set_data(
                    v.data.astype(self.arg_dict[k].dtype))
            else:
                self.arg_dict[k]._sync_copyfrom(v)
        arg_vals = tuple(a.data for a in self.arg_arrays)
        aux_vals = tuple(a.data for a in self.aux_arrays)
        key = self._next_key(is_train)
        if arg_vals and key is self._const_key:
            # const key: placement is one-time too (see _next_key)
            try:
                dev = list(arg_vals[0].devices())[0]
                if self._const_key_dev != dev:
                    self._const_key = jax.device_put(key, dev)
                    self._const_key_dev = dev
                key = self._const_key
            except Exception:
                pass
        elif arg_vals:
            try:  # co-locate the key with this executor's device
                key = jax.device_put(key, list(arg_vals[0].devices())[0])
            except Exception:
                pass

        self._partial = None      # a full forward supersedes any
        self._partial_done = False  # in-flight or completed partial sequence
        from . import profiler as _prof
        if self._monitor is not None:
            # per-op tapped evaluation (runs the forward once eagerly to
            # feed the monitor; training then falls through to the shared
            # compiled-vjp path below, like the reference keeps backward
            # working while the monitor disables bulk exec)
            def cb(name, val):
                self._monitor(name, NDArray(val))
            outs, new_aux = self._prog._eval(
                self._eager_committed(arg_vals),
                self._eager_committed(aux_vals), key, is_train, monitor=cb)
            self._vjp = None
        if is_train:
            with _prof.record_scope("Forward", str(self._ctx)):
                fn = self._prog.jitted(True)
                (outs, new_aux), vjp = jax.vjp(
                    lambda a, x: fn(a, x, key), arg_vals, aux_vals)
            self._vjp = vjp
        elif self._monitor is None:
            with _prof.record_scope("Forward", str(self._ctx)):
                fn = self._prog.jitted(False)
                outs, new_aux = fn(arg_vals, aux_vals, key)
            self._vjp = None
        self._await_host_callbacks((outs, new_aux))
        for arr, v in zip(self.aux_arrays, new_aux):
            arr._set_data(v)
        self._outputs = [NDArray(o) for o in outs]
        return self._outputs

    def partial_forward(self, is_train=False, step=0):
        """Run exactly forward node ``step``; returns the number of steps
        left (reference ``include/mxnet/executor.h:44-51`` /
        ``GraphExecutor::PartialForward``: call with increasing ``step``
        from 0 until 0 is returned).  Eager per-node evaluation — a
        debugging surface, like monitor mode; outputs are published once
        the last node has run."""
        prog = self._prog
        compute = [n for n in prog.nodes if not n.is_variable]
        if step >= len(compute):
            # "done" is only a valid answer right after a sequence ran to
            # completion; a cold or mid-sequence out-of-range step is the
            # same ordering error as any other out-of-order call (the
            # caller would otherwise read stale/empty outputs)
            if not compute or (step > 0 and self._partial is None
                               and self._partial_done):
                return 0
            raise MXNetError(
                "partial_forward steps must be issued in order from 0 "
                "(expected step %d, got %d)"
                % (self._partial[3] if self._partial else 0, step))
        if step == 0:
            self._partial_done = False
            var_nodes = [n for n in prog.nodes if n.is_variable]
            var_vals = self._eager_committed(
                [self.arg_dict[n.name].data for n in var_nodes])
            env = {(id(n), 0): v for n, v in zip(var_nodes, var_vals)}
            self._partial = (
                env,
                self._eager_committed([a.data for a in self.aux_arrays]),
                self._next_key(is_train), 0)
        if self._partial is None or self._partial[3] != step:
            raise MXNetError(
                "partial_forward steps must be issued in order from 0 "
                "(expected step %s, got %d)"
                % (self._partial[3] if self._partial else 0, step))
        env, aux_out, key, _ = self._partial
        aux_vals = self._eager_committed([a.data for a in self.aux_arrays])
        prog._eval_node(compute[step], env, aux_vals, aux_out, key,
                        is_train, monitor=None)
        left = len(compute) - step - 1
        if left == 0:
            for arr, v in zip(self.aux_arrays, aux_out):
                arr._set_data(v)
            self._outputs = [NDArray(env[(id(nd), i)])
                             for nd, i in prog.output_entries]
            self._partial = None
            self._partial_done = True
            self._vjp = None     # outputs no longer match any pullback
        else:
            self._partial = (env, aux_out, key, step + 1)
        return left

    def backward(self, out_grads=None):
        if self._vjp is None:
            raise MXNetError("run forward(is_train=True) before backward")
        if out_grads is None:
            out_grads = []
        elif isinstance(out_grads, NDArray):
            out_grads = [out_grads]
        cotangents = []
        for i, o in enumerate(self._outputs):
            if i < len(out_grads) and out_grads[i] is not None:
                g = out_grads[i]
                cotangents.append(g.data if isinstance(g, NDArray)
                                  else jnp.asarray(g))
            else:
                cotangents.append(jnp.ones(o.shape, o.dtype))
        aux_cot = tuple(jnp.zeros(a.shape, a.dtype) for a in self.aux_arrays)
        from . import profiler as _prof
        with _prof.record_scope("Backward", str(self._ctx)):
            arg_grads, _aux_grads = self._vjp((tuple(cotangents), aux_cot))
        self._await_host_callbacks(arg_grads)
        for name, arr, g in zip(self._prog.arg_names, self.grad_arrays,
                                arg_grads):
            req = self.grad_req.get(name, "null")
            if arr is None or req == "null":
                continue
            if req == "add":
                arr._set_data(arr.data + g.astype(arr.dtype))
            else:
                arr._set_data(g.astype(arr.dtype))
        return [NDArray(g) for g in arg_grads]

    # ------------------------------------------------------------------
    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes (jit recompiles per shape — the
        TPU analog of the reference's shared-memory rebind)."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for name, shape in zip(self._prog.arg_names, arg_shapes):
            old = self.arg_dict[name]
            if tuple(old.shape) == tuple(shape):
                new_args[name] = old
            else:
                new_args[name] = zeros(shape, self._ctx, old.dtype)
        new_aux = {}
        for name, shape in zip(self._prog.aux_names, aux_shapes):
            old = self.aux_dict[name]
            new_aux[name] = old if tuple(old.shape) == tuple(shape) \
                else zeros(shape, self._ctx, old.dtype)
        grads = None
        if self.grad_dict:
            grads = {n: zeros(new_args[n].shape, self._ctx, new_args[n].dtype)
                     for n in self.grad_dict}
        return Executor(self._symbol, self._ctx, new_args, grads,
                        self.grad_req, new_aux, self._group2ctx)

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        def _assign(dst, src):
            val = src.data.astype(dst.dtype)
            try:  # keep the executor's device placement
                dev = list(dst.data.devices())[0]
                val = jax.device_put(val, dev)
            except Exception:
                pass
            dst._set_data(val)

        for name, arr in arg_params.items():
            if name in self.arg_dict:
                _assign(self.arg_dict[name], arr)
            elif not allow_extra_params:
                raise MXNetError("unknown argument %s" % name)
        if aux_params:
            for name, arr in aux_params.items():
                if name in self.aux_dict:
                    _assign(self.aux_dict[name], arr)
                elif not allow_extra_params:
                    raise MXNetError("unknown aux state %s" % name)

    def install_monitor(self, callback):
        """Per-op output tap (reference ``graph_executor.cc:757-778``;
        disables whole-graph fusion exactly like the reference disables
        bulk exec)."""
        self._monitor = callback

    def _annotation(self):
        """The analyzer's annotated graph (per-node inferred
        shape/dtype) for this executor's bound shapes — computed lazily,
        shared between ``debug_str`` and lint provenance so the two
        always agree."""
        if self._debug_ann is not None:
            return self._debug_ann or None   # False = sticky failure
        rep = self._lint_report
        if rep is not None and rep.annotation is not None:
            self._debug_ann = rep.annotation
            return self._debug_ann
        try:
            from . import analysis
            view = analysis.GraphView.from_symbol(self._symbol)
            ann, _ = analysis.annotate(
                view,
                shapes={n: tuple(a.shape) for n, a in self.arg_dict.items()},
                dtypes={n: a.dtype for n, a in self.arg_dict.items()})
            self._debug_ann = ann
        except Exception:  # noqa: BLE001 — debug output must never raise
            self._debug_ann = False   # don't re-walk the graph per call
            return None
        return self._debug_ann

    def debug_str(self):
        lines = ["Symbol outputs: %s" % ", ".join(self._symbol.list_outputs())]
        ann = self._annotation()

        def _sd(idx, n_out=1):
            if ann is None:
                return ""
            outs = []
            for i in range(n_out):
                s = ann.shape.get((idx, i))
                t = ann.dtype.get((idx, i))
                outs.append("%s %s" % (t if t is not None else "?",
                                       s if s is not None else "?"))
            return ", out=[%s]" % "; ".join(outs)

        # GraphView.from_symbol enumerates the same _topo order as
        # self._prog.nodes, so positional index IS the annotation key
        for i, n in enumerate(self._prog.nodes):
            if n.is_variable:
                lines.append("Variable:%s%s" % (n.name, _sd(i)))
            else:
                where = self._prog.placement.get(n.name)
                lines.append("Op:%s, Name=%s%s%s" % (
                    n.op.name, n.name, _sd(i, n.num_outputs()),
                    ", Device=%s" % where if where is not None else ""))
        if self._lint_report is not None and self._lint_report.findings:
            lines.append("Graph lint findings:")
            for f in self._lint_report.findings:
                lines.append("  " + f.format())
        return "\n".join(lines)


# ----------------------------------------------------------------------
def bind(sym, ctx, args, args_grad=None, grad_req="write", aux_states=None,
         group2ctx=None, shared_exec=None):
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    args = _to_dict(args, arg_names, "args")
    if args_grad is not None:
        args_grad = _to_dict(args_grad, arg_names, "args_grad", allow_partial=True)
    aux_states = _to_dict(aux_states or [], aux_names, "aux_states",
                          allow_missing=(len(aux_names) == 0))
    if len(aux_names) and not aux_states:
        raise MXNetError("aux_states required for %s" % aux_names)
    return Executor(sym, ctx, args, args_grad, grad_req, aux_states,
                    group2ctx)


def simple_bind(sym, ctx=None, grad_req="write", type_dict=None,
                group2ctx=None, shared_exec=None, _graph_lint=True,
                **kwargs):
    ctx = ctx or current_context()
    type_dict = type_dict or {}
    arg_names = sym.list_arguments()
    aux_names = sym.list_auxiliary_states()
    # lint first: the analyzer's annotation walk IS a full shape+dtype
    # inference, so when it resolves cleanly the bind reuses it and
    # pays ONE inference walk total (lint included) instead of the
    # separate infer_shape + infer_type passes
    report = _lint_at_bind(sym, kwargs, type_dict) if _graph_lint else None
    shapes_types = report and _shapes_from_annotation(
        report, arg_names, aux_names)
    if shapes_types is not None:
        arg_shapes, arg_types, aux_shapes, aux_types = shapes_types
    else:
        # canonical inference path: raises the canonical MXNetErrors
        # for unresolvable/conflicting graphs (also the lint-off path)
        arg_shapes, _, aux_shapes = sym.infer_shape(**kwargs)
        if arg_shapes is None:
            raise MXNetError("cannot infer shapes from %s" % kwargs)
        arg_types, _, aux_types = sym.infer_type(**type_dict)
    args = {n: zeros(s, ctx, t or np.float32)
            for n, s, t in zip(arg_names, arg_shapes, arg_types)}
    if isinstance(grad_req, dict):
        reqs = grad_req
    elif isinstance(grad_req, (list, tuple)):
        reqs = dict(zip(arg_names, grad_req))
    else:
        reqs = {n: grad_req for n in arg_names}
    args_grad = {n: zeros(s, ctx, t or np.float32)
                 for n, s, t in zip(arg_names, arg_shapes, arg_types)
                 if reqs.get(n, "null") != "null"}
    aux_states = {n: zeros(s, ctx, t or np.float32)
                  for n, s, t in zip(aux_names, aux_shapes, aux_types)}
    exe = Executor(sym, ctx, args, args_grad, grad_req, aux_states, group2ctx)
    if report is not None:
        exe._lint_report = report
    return exe


def _shapes_from_annotation(report, arg_names, aux_names):
    """Arg/aux shapes+dtypes out of a clean lint annotation; None when
    any entry is unresolved (or the lint found errors) — the caller
    then re-runs canonical inference for its canonical exceptions."""
    ann = report.annotation
    if ann is None or report.errors():
        return None
    if any(ann.var_shape.get(n) is None for n in arg_names) \
            or any(ann.aux_shape.get(n) is None for n in aux_names):
        return None
    return ([ann.var_shape[n] for n in arg_names],
            [ann.var_dtype.get(n) for n in arg_names],
            [ann.aux_shape[n] for n in aux_names],
            [ann.aux_dtype.get(n) for n in aux_names])


def _lint_at_bind(sym, shapes, dtypes):
    """Symbol-level lint at ``simple_bind`` time: surfaces findings as
    a GraphLintWarning and returns the report (whose annotation the
    bind reuses for allocation).  ``MXTPU_GRAPH_LINT=0`` disables."""
    import os
    if os.environ.get("MXTPU_GRAPH_LINT", "1") == "0":
        return None
    try:
        from . import analysis
        report = analysis.lint_symbol(sym, shapes=shapes, dtypes=dtypes,
                                      trace=False)
    except Exception:  # noqa: BLE001 — lint must never break binding
        return None
    c = report.counts()
    if c["error"] or c["warn"]:
        import warnings
        worst = (report.errors() or report.warnings())[0]
        warnings.warn(
            "graph lint: %d error / %d warn finding(s), e.g. %s  "
            "(Executor.debug_str() lists all; MXTPU_GRAPH_LINT=0 "
            "disables)" % (c["error"], c["warn"], worst.format()),
            # _lint_at_bind -> executor.simple_bind -> Symbol.simple_bind
            # -> the USER's bind call, which the warning should name
            analysis.GraphLintWarning, stacklevel=4)
    return report


def _to_dict(arrays, names, what, allow_partial=False, allow_missing=False):
    if isinstance(arrays, dict):
        missing = [n for n in names if n not in arrays]
        if missing and not (allow_partial or allow_missing):
            raise MXNetError("%s missing entries for %s" % (what, missing))
        return {n: arrays[n] for n in names if n in arrays}
    arrays = list(arrays)
    if len(arrays) != len(names) and not allow_missing:
        raise MXNetError("%s length %d != expected %d (%s)"
                         % (what, len(arrays), len(names), names))
    return dict(zip(names, arrays))
