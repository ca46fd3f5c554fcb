"""Unified operator registry.

The reference has *two* op registration paths — legacy stateful
``OperatorProperty`` layers (``include/mxnet/operator.h:77-155``) and NNVM
stateless ``FCompute`` ops (``include/mxnet/op_attr_types.h:33-63``).  On TPU
both collapse into one concept: **an op is a pure JAX function** plus
metadata.  Shape/type inference is derived with ``jax.eval_shape`` (replacing
FInferShape/FInferType), gradients come from JAX autodiff (replacing
FGradient), and "stateful" layers (BatchNorm's moving stats) are modeled as
explicit auxiliary inputs/outputs — the same notion as the reference's
``ListAuxiliaryStates`` (``operator.h:137``).

Every registered op automatically gets:
  * an imperative front-end  ``mx.nd.<name>(...)``   (eager, autograd-traced)
  * a symbolic front-end     ``mx.sym.<Name>(...)``  (graph node)
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError

_REGISTRY: Dict[str, "Op"] = {}
_ALIASES: Dict[str, str] = {}


class OpContext:
    """Runtime context threaded into every op body.

    ``is_train`` is a *static* (trace-time) flag — mode-dependent ops
    (Dropout, BatchNorm) branch on it in Python, producing separate XLA
    programs per mode, which is the jit-friendly analog of the reference's
    ``OpContext.is_train`` (``include/mxnet/operator.h:48``).
    ``rng`` is a JAX PRNG key for ops that declared ``uses_rng`` — the
    functional replacement of ``ResourceRequest::kRandom``
    (``include/mxnet/resource.h:18-36``).
    ``platform`` is the target backend of the executor/trainer that is
    tracing this op ("tpu"/"cpu"/...; None = process default) — ops with
    backend-specialized kernels (Pallas flash attention) select their
    lowering with it.
    ``dtype_policy`` selects the residual/intermediate dtype policy for
    backward formulations ("bytediet"/"legacy"; None = the process
    default, see ``op/bytediet.py``) — another static trace-time flag,
    threaded from ``Trainer``/``Executor``.
    """

    __slots__ = ("is_train", "rng", "platform", "dtype_policy")

    def __init__(self, is_train=False, rng=None, platform=None,
                 dtype_policy=None):
        self.is_train = is_train
        self.rng = rng
        self.platform = platform
        self.dtype_policy = dtype_policy


def _parse_bool(v):
    if isinstance(v, str):
        return v.lower() in ("true", "1", "yes")
    return bool(v)


def _parse_shape(v):
    if v is None:
        return None
    if isinstance(v, str):
        v = ast.literal_eval(v)
    if isinstance(v, (int, np.integer)):
        return (int(v),)
    return tuple(int(x) for x in v)


def _parse_dtype(v):
    from ..base import _dtype
    return _dtype(v)


def _parse_floats(v):
    """Tuple-of-floats params ('(0.1, 0.2)' strings, scalars, sequences)."""
    if v is None:
        return None
    if isinstance(v, str):
        v = ast.literal_eval(v)
    if isinstance(v, (int, float, np.floating, np.integer)):
        return (float(v),)
    return tuple(float(x) for x in v)


_COERCE = {
    int: lambda v: int(float(v)) if isinstance(v, str) else int(v),
    float: float,
    bool: _parse_bool,
    str: str,
    "shape": _parse_shape,
    "dtype": _parse_dtype,
    "floats": _parse_floats,
}


@dataclass
class Param:
    """Typed op parameter — the dmlc::Parameter equivalent.

    Reference per-op kwargs come through string-parsed dmlc Parameter structs
    (e.g. ``src/operator/optimizer_op.cc:12-28``); here the same coercion
    (string -> typed value) happens at call time so symbols serialized to
    JSON (all-string attrs) round-trip.
    """

    name: str
    type: Any = float
    default: Any = None
    required: bool = False
    enum: Optional[Sequence[str]] = None

    def coerce(self, v):
        if v is None:
            return None
        v = _COERCE.get(self.type, self.type)(v)
        if self.enum is not None and v not in self.enum:
            raise MXNetError(
                "param %s expects one of %s, got %r" % (self.name, self.enum, v))
        return v


@dataclass
class Op:
    """A registered operator."""

    name: str
    fn: Callable  # fn(params: dict, ctx: OpContext, *arrays) -> array | tuple
    params_spec: Tuple[Param, ...] = ()
    # input names; a callable receives parsed params (e.g. FC drops 'bias'
    # when no_bias=True — reference fully_connected-inl.h ListArguments)
    input_names: Any = ("data",)
    aux_names: Any = ()
    num_outputs: Any = 1  # int or callable(params) -> int
    output_names: Any = None  # callable(params) -> names; default ["output"]
    infer_shape: Optional[Callable] = None  # (params, in_shapes) -> (in,out,aux)
    infer_dtype: Optional[Callable] = None
    uses_rng: bool = False
    # rng consumed even at is_train=False.  Defaults to uses_rng so an
    # unclassified rng op (e.g. a third-party sampler registered via
    # extension-ops) stays correct — fresh keys every forward.  The
    # audited train-only noise ops (Dropout, rrelu, RNN dropout)
    # explicitly opt OUT so an inference executor never pays per-forward
    # key derivation — each eager key op is a dispatch of its own.
    # ``None`` means "inherit uses_rng".
    rng_in_eval: Optional[bool] = None
    mode_dependent: bool = False  # retrace per is_train value
    # fn calls back into Python on the host while the program runs
    # (operator.py's CustomOp bridge); the executor awaits such programs
    host_callback: bool = False
    hint: str = ""  # auto-naming hint, defaults to lowercased name
    # gauges an op's auxiliary state stands for: (params, {aux name:
    # host array}) -> {gauge name: number}.  Whoever owns the state
    # (the fused trainer) publishes them on ``obs.snapshot()``, without
    # knowing the op by name; nodes that name one gauge are averaged
    gauges: Optional[Callable] = None
    # ops whose outputs must not be differentiated through label-style inputs
    # handle that themselves via jax.custom_vjp / stop_gradient in `fn`.

    def __post_init__(self):
        if self.rng_in_eval is None:
            self.rng_in_eval = self.uses_rng

    def list_inputs(self, params) -> List[str]:
        names = self.input_names(params) if callable(self.input_names) else self.input_names
        return list(names)

    def list_aux(self, params) -> List[str]:
        names = self.aux_names(params) if callable(self.aux_names) else self.aux_names
        return list(names)

    def n_outputs(self, params) -> int:
        return self.num_outputs(params) if callable(self.num_outputs) else self.num_outputs

    def list_outputs(self, params) -> List[str]:
        if self.output_names is not None:
            return list(self.output_names(params))
        n = self.n_outputs(params)
        return ["output"] if n == 1 else ["output%d" % i for i in range(n)]

    # ------------------------------------------------------------------
    def parse_params(self, kwargs: Dict[str, Any]) -> Dict[str, Any]:
        params = {}
        spec = {p.name: p for p in self.params_spec}
        for k, v in kwargs.items():
            if k in spec:
                params[k] = spec[k].coerce(v)
            elif k.startswith("__") and k.endswith("__"):
                # escape hatch: dunder group attrs (__lr_mult__ and kin)
                # ride through untouched — op bodies never read them,
                # but serialization keeps them with the node
                params[k] = v
            else:
                # typo'd kwargs silently dropping is the classic MXNet
                # footgun (reference dmlc::Parameter ignores unknown
                # keys); reject with a did-you-mean
                import difflib
                close = difflib.get_close_matches(k, spec, n=1)
                hint = "; did you mean %r?" % close[0] if close else ""
                raise MXNetError(
                    "%s got unknown parameter %r%s (known parameters: %s)"
                    % (self.name, k, hint, sorted(spec) or "none"))
        for p in self.params_spec:
            if p.name not in params:
                if p.required:
                    raise MXNetError(
                        "%s missing required parameter %r" % (self.name, p.name))
                params[p.name] = p.default
        return params

    # ------------------------------------------------------------------
    def apply(self, params, ctx: OpContext, *arrays):
        """Run the op body; returns (outputs_tuple, aux_updates_tuple)."""
        out = self.fn(params, ctx, *arrays)
        if not isinstance(out, tuple):
            out = (out,)
        n_out = self.n_outputs(params)
        n_aux = len(self.list_aux(params))
        if len(out) != n_out + n_aux:
            raise MXNetError(
                "%s returned %d arrays, expected %d outputs + %d aux" %
                (self.name, len(out), n_out, n_aux))
        return out[:n_out], out[n_out:]

    # ------------------------------------------------------------------
    def infer_shape_generic(self, params, in_shapes, aux_shapes=None):
        """Shape inference.

        Unlike the reference's hand-written per-op InferShape, the default
        path abstractly evaluates the op body (``jax.eval_shape``) — the op
        *is* its own shape function.  Ops with learnable parameters whose
        shapes must be inferred *backwards* from the data (FullyConnected
        infers ``weight=(num_hidden, in_dim)``) provide ``infer_shape``.
        """
        in_shapes = list(in_shapes)
        n_aux = len(self.list_aux(params))
        if self.infer_shape is not None:
            ret = self.infer_shape(params, in_shapes)
            if ret is not None:
                in_s, out_s, aux_s = ret
                return list(in_s), list(out_s), list(aux_s)
        if any(s is None or any(d == 0 for d in s) for s in in_shapes):
            # try same-shape propagation for unknown inputs
            known = [s for s in in_shapes if s is not None and all(d != 0 for d in s)]
            if known and all(s is None or s == known[0] for s in in_shapes):
                in_shapes = [known[0]] * len(in_shapes)
            else:
                raise MXNetError(
                    "cannot infer shapes for %s from %s" % (self.name, in_shapes))
        dtypes = self._default_dtypes(params, len(in_shapes) + n_aux)
        structs = [jax.ShapeDtypeStruct(tuple(s), dt)
                   for s, dt in zip(in_shapes, dtypes)]
        aux_structs = [jax.ShapeDtypeStruct((1,), np.float32)] * n_aux
        if aux_shapes and all(a is not None for a in aux_shapes):
            aux_structs = [jax.ShapeDtypeStruct(tuple(s), np.float32)
                           for s in aux_shapes]
        ctx = OpContext(is_train=False, rng=jax.random.key(0) if self.uses_rng else None)
        out = jax.eval_shape(lambda *xs: self.fn(params, ctx, *xs),
                             *(structs + aux_structs))
        if not isinstance(out, tuple):
            out = (out,)
        n_out = self.n_outputs(params)
        out_shapes = [tuple(o.shape) for o in out[:n_out]]
        aux_out = [tuple(o.shape) for o in out[n_out:]]
        if not aux_out:
            aux_out = [tuple(a.shape) for a in aux_structs][:n_aux]
        return in_shapes, out_shapes, aux_out

    def _default_dtypes(self, params, n):
        dt = params.get("dtype", None) if params else None
        return [np.dtype(dt) if dt is not None else np.float32] * n

    def infer_dtype_generic(self, params, in_dtypes):
        if self.infer_dtype is not None:
            return self.infer_dtype(params, in_dtypes)
        known = [d for d in in_dtypes if d is not None]
        dt = known[0] if known else np.dtype(np.float32)
        in_dtypes = [d if d is not None else dt for d in in_dtypes]
        # an explicit ``dtype`` param (Cast, creation ops, samplers)
        # DEFINES the output dtype; propagating the input dtype instead
        # hid every Cast from type inference (and from the f64 lint)
        out_dt = params.get("dtype") if params else None
        out_dt = np.dtype(out_dt) if out_dt is not None else dt
        n_out = self.n_outputs(params)
        n_aux = len(self.list_aux(params))
        return in_dtypes, [out_dt] * n_out, [out_dt] * n_aux


def register(name, fn=None, **kwargs) -> Callable:
    """Register an op.  Usable as decorator or direct call."""

    def _do(f):
        op = Op(name=name, fn=f, hint=kwargs.pop("hint", name.lstrip("_").lower()),
                **kwargs)
        _REGISTRY[name] = op
        return f

    if fn is not None:
        return _do(fn)
    return _do


def alias(alias_name, target):
    _ALIASES[alias_name] = target


def get(name) -> Op:
    if name in _ALIASES:
        name = _ALIASES[name]
    if name not in _REGISTRY:
        raise MXNetError("operator %r is not registered" % name)
    return _REGISTRY[name]


def exists(name) -> bool:
    return name in _REGISTRY or name in _ALIASES


def list_ops() -> List[str]:
    return sorted(_REGISTRY) + sorted(_ALIASES)
