"""Custom operators written in Python.

Reference: ``python/mxnet/operator.py:52-187`` + the C callback plumbing in
``src/operator/custom/custom-inl.h:35-196``.  The reference runs CustomOp
callbacks on a dedicated thread against NDArrays; here the callback is
spliced into the XLA program with ``jax.pure_callback`` (a host round-trip
— the same performance cliff the reference documents for custom ops), and
the backward pass is wired through ``jax.custom_vjp`` so custom ops are
autograd-transparent in both the imperative and compiled paths.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import NDArray
from .op import registry as _reg
from .op.registry import Op, Param

_CUSTOM_PROPS: Dict[str, type] = {}


class CustomOp(object):
    """Base class for custom operators (reference ``operator.py:408``)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError()

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError()

    def assign(self, dst, req, src):
        """Write ``src`` into ``dst`` honoring the OpReqType
        (reference semantics of ``kWriteTo``/``kAddTo``/``kNullOp``)."""
        if req == "null":
            return
        if req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] += src


class CustomOpProp(object):
    """Operator-property for custom ops (reference ``operator.py:500``)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return (in_type, [in_type[0]] * len(self.list_outputs()),
                [in_type[0]] * len(self.list_auxiliary_states()))

    def list_outputs(self):
        return ["output"]

    def list_arguments(self):
        return ["data"]

    def list_auxiliary_states(self):
        return []

    def need_top_grad(self):
        return self.need_top_grad_

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        deps = []
        if self.need_top_grad_:
            deps.extend(out_grad)
        deps.extend(in_data)
        deps.extend(out_data)
        return deps

    def create_operator(self, ctx, in_shapes, in_dtypes):
        raise NotImplementedError()


def register(reg_name):
    """Register a CustomOpProp class under ``op_type=reg_name``
    (reference ``operator.py:611``)."""

    def do_register(prop_cls):
        _CUSTOM_PROPS[reg_name] = prop_cls
        return prop_cls

    return do_register


def get_prop_cls(op_type):
    if op_type not in _CUSTOM_PROPS:
        raise MXNetError("custom op type %s is not registered" % op_type)
    return _CUSTOM_PROPS[op_type]


def _make_custom_fn(op_type, prop_kwargs):
    """Build the pure-JAX body for a Custom node: pure_callback forward +
    custom_vjp backward calling the user's python CustomOp."""
    prop = get_prop_cls(op_type)(**prop_kwargs)
    return _make_custom_fn_from_prop(prop, "Custom[%s]" % op_type)


def _make_custom_fn_from_prop(prop, op_name):
    arg_names = prop.list_arguments()
    out_names = prop.list_outputs()
    n_in, n_out = len(arg_names), len(out_names)
    op_holder = {}

    def _get_op(in_shapes, in_dtypes):
        key = tuple(in_shapes)
        if key not in op_holder:
            from .base import current_context
            op_holder[key] = prop.create_operator(current_context(),
                                                  list(in_shapes),
                                                  list(in_dtypes))
        return op_holder[key]

    def _host_forward(is_train, *arrays):
        in_nd = [NDArray(jnp.asarray(a)) for a in arrays]
        in_shapes = [a.shape for a in arrays]
        _, out_shapes, _ = prop.infer_shape(in_shapes)
        out_nd = [NDArray(jnp.zeros(s, arrays[0].dtype)) for s in out_shapes]
        op = _get_op(in_shapes, [a.dtype for a in arrays])
        op.forward(is_train=is_train, req=["write"] * n_out,
                   in_data=in_nd, out_data=out_nd, aux=[])
        return tuple(np.asarray(o.asnumpy(), dtype=np.asarray(arrays[0]).dtype)
                     for o in out_nd)

    def _host_backward(*arrays):
        outs_grad = [jnp.asarray(a) for a in arrays[:n_out]]
        ins = [jnp.asarray(a) for a in arrays[n_out:n_out + n_in]]
        outs = [jnp.asarray(a) for a in arrays[n_out + n_in:]]
        in_nd = [NDArray(a) for a in ins]
        out_nd = [NDArray(a) for a in outs]
        og_nd = [NDArray(a) for a in outs_grad]
        ig_nd = [NDArray(jnp.zeros(a.shape, a.dtype)) for a in ins]
        op = _get_op([a.shape for a in ins], [a.dtype for a in ins])
        op.backward(req=["write"] * n_in, out_grad=og_nd, in_data=in_nd,
                    out_data=out_nd, in_grad=ig_nd, aux=[])
        return tuple(np.asarray(g.asnumpy(), dtype=np.asarray(ins[0]).dtype)
                     for g in ig_nd)

    def fn(params, ctx, *arrays):
        is_train = ctx.is_train

        @jax.custom_vjp
        def custom(*ins):
            in_shapes = [tuple(a.shape) for a in ins]
            _, out_shapes, _ = prop.infer_shape(in_shapes)
            result_shape = tuple(
                jax.ShapeDtypeStruct(tuple(s), ins[0].dtype)
                for s in out_shapes)
            return jax.pure_callback(
                lambda *a: _host_forward(is_train, *a), result_shape, *ins)

        def custom_fwd(*ins):
            outs = custom(*ins)
            return outs, (ins, outs)

        def custom_bwd(res, gs):
            ins, outs = res
            in_shapes = [jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)
                         for a in ins]
            grads = jax.pure_callback(_host_backward, tuple(in_shapes),
                                      *(tuple(gs) + tuple(ins) + tuple(outs)))
            return tuple(grads)

        custom.defvjp(custom_fwd, custom_bwd)
        out = custom(*arrays)
        return out if len(out) > 1 else out[0]

    custom_op = Op(
        name=op_name, fn=fn,
        params_spec=(), input_names=tuple(arg_names),
        aux_names=tuple(prop.list_auxiliary_states()),
        num_outputs=n_out, hint="custom",
        infer_shape=lambda p, in_shapes: prop.infer_shape(in_shapes),
        mode_dependent=True, host_callback=True)
    return custom_op


def _register_and_create(op, args, kwargs):
    """Register a freshly-built custom Op (JSON round-trip needs the
    registry row) and create its symbol node from Symbol inputs."""
    from .symbol import Symbol, _create
    bad = [a for a in args if not isinstance(a, Symbol)]
    if bad:
        raise MXNetError(
            "custom op inputs must be Symbols, got %s"
            % [type(a).__name__ for a in bad])
    _reg._REGISTRY[op.name] = op
    return _create(op.name, list(args), dict(kwargs))


# ----------------------------------------------------------------------
# Legacy foreign-function op classes (reference ``operator.py:19-257``:
# PythonOp -> NumpyOp / NDArrayOp, the pre-CustomOp API behind the
# ``_Native`` / ``_NDArray`` callback operators,
# ``src/operator/custom/native_op-inl.h`` / ``ndarray_op-inl.h``).
# Same subclassing surface; the substrate is the modern Custom machinery
# (pure_callback + custom_vjp) instead of C function-pointer structs.
class PythonOp(object):
    """Base: subclass, override ``forward``/``backward``/``infer_shape``/
    ``list_arguments``/``list_outputs``; calling the instance on input
    symbols yields the graph node (reference ``operator.py:19-118``)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def __call__(self, *args, **kwargs):
        return self.get_symbol(*args, **kwargs)

    # default behaviors: identity forward, all-ones backward, shape
    # passthrough, one data input -> one output
    def forward(self, in_data, out_data):
        out_data[0][:] = in_data[0]

    def backward(self, out_grad, in_data, out_data, in_grad):
        in_grad[0][:] = 1.0

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]]

    def need_top_grad(self):
        return self.need_top_grad_

    # NumpyOp presents numpy copies (flushed back after the call);
    # NDArrayOp presents the NDArrays themselves
    _use_numpy = False
    _node_kind = "_Python"
    _instances = 0

    def get_symbol(self, *args, **kwargs):
        legacy = self
        use_numpy = self._use_numpy

        def _views(nd_list):
            # writable copies: asnumpy() views of jax buffers are
            # read-only, and legacy ops mutate in place
            return [np.array(a.asnumpy()) for a in nd_list] if use_numpy \
                else list(nd_list)

        def _flush(nd_list, views):
            if use_numpy:
                for dst, v in zip(nd_list, views):
                    dst[:] = v

        class _Adapter(CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                outs = _views(out_data)
                legacy.forward(in_data=_views(in_data), out_data=outs)
                _flush(out_data, outs)

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):
                grads = _views(in_grad)
                legacy.backward(out_grad=_views(out_grad),
                                in_data=_views(in_data),
                                out_data=_views(out_data),
                                in_grad=grads)
                _flush(in_grad, grads)

        class _Prop(CustomOpProp):
            def __init__(self):
                super().__init__(need_top_grad=legacy.need_top_grad())

            def list_arguments(self):
                return legacy.list_arguments()

            def list_outputs(self):
                return legacy.list_outputs()

            def infer_shape(self, in_shape):
                shapes = legacy.infer_shape(in_shape)
                # legacy returns (in, out); modern adds aux
                return (shapes if len(shapes) == 3
                        else (shapes[0], shapes[1], []))

            def create_operator(self, ctx, in_shapes, in_dtypes):
                return _Adapter()

        # build + register once per INSTANCE (unique suffix: two
        # differently-configured instances of the same subclass must not
        # overwrite each other's row; re-calls on one instance reuse it)
        if getattr(self, "_op", None) is None:
            PythonOp._instances += 1
            self._op = _make_custom_fn_from_prop(
                _Prop(), "%s[%s:%d]" % (self._node_kind,
                                        type(self).__name__,
                                        PythonOp._instances))
        return _register_and_create(self._op, args, kwargs)


class NumpyOp(PythonOp):
    """Forward/backward see numpy arrays; mutate ``out_data[i][:]``
    in place (reference ``operator.py:120-225`` — the ``_Native`` op)."""

    _node_kind = "_Native"
    _use_numpy = True


class NDArrayOp(PythonOp):
    """Forward/backward see NDArrays directly (reference
    ``operator.py:226-257`` — the ``_NDArray`` op)."""

    _node_kind = "_NDArray"


# alias kept for scripts that imported the C-callback flavor by name
NativeOp = NumpyOp


def _custom_entry(namespace):
    """Front-end ``Custom(..., op_type=...)`` for nd/sym namespaces."""

    def Custom(*args, **kwargs):
        op_type = kwargs.pop("op_type", None)
        if op_type is None:
            raise MXNetError("Custom requires op_type=")
        name = kwargs.pop("name", None)
        known = {"need_top_grad"}
        prop_kwargs = {}
        passthrough = {}
        prop_cls = get_prop_cls(op_type)
        import inspect
        sig = set(inspect.signature(prop_cls.__init__).parameters) - {"self"}
        for k in list(kwargs):
            if k in sig or k in known:
                prop_kwargs[k] = kwargs.pop(k)
        op = _make_custom_fn(op_type, prop_kwargs)
        if namespace == "sym":
            if name is not None:
                kwargs["name"] = name
            return _register_and_create(op, args, kwargs)
        from .op.invoke import invoke
        arrays = [a for a in args if isinstance(a, NDArray)]
        res = invoke(op, arrays, kwargs)
        return res[0] if len(res) == 1 else res

    return Custom
