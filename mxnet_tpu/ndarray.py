"""Imperative NDArray over ``jax.Array``.

Re-design of the reference NDArray (``include/mxnet/ndarray.h:58-445``).  The
reference pairs every array with an engine variable and schedules each
mutation through the threaded dependency engine; on TPU, JAX's async
dispatch already provides the same RAW/WAR/WAW ordering per buffer, so an
NDArray is simply a *mutable cell holding an immutable jax.Array*:

  * mutation  (``+=``, ``__setitem__``, optimizer updates) swaps the cell's
    value — under jit XLA turns the functional update into true in-place
    buffer reuse (donation), which is the TPU analog of ``kWriteInplace``.
  * views (``Slice/At/Reshape``, ``ndarray.h:284-310``) hold a reference to
    their base cell and re-derive on read / write through on assignment,
    matching the reference's write-through slice semantics.
  * ``WaitToRead/WaitToWrite`` -> ``block_until_ready``; ``waitall`` ->
    sync on all live arrays.

Save/Load use the reference's exact binary format
(``src/ndarray/ndarray.cc:623-706``: magic 0x112, dmlc vectors, per-array
TShape + Context + type_flag + raw bytes) so ``.params`` checkpoints are
interchangeable with the reference.
"""
from __future__ import annotations

import struct
from numbers import Number

import numpy as np

import jax
import jax.numpy as jnp

from .base import (Context, MXNetError, _DTYPE_MX_TO_NP, _DTYPE_NP_TO_MX,
                   _dtype, current_context, mx_real_t)
from .op import registry as _reg

_py_slice = slice  # generated op `nd.slice` shadows the builtin in this module

__all__ = ["NDArray", "empty", "zeros", "ones", "full", "array", "arange",
           "concatenate", "save", "load", "waitall", "onehot_encode", "moveaxis"]


def waitall():
    """Block until all async computation finishes (ref ``ndarray.py:95``)."""
    jax.effects_barrier()
    (jnp.zeros(()) + 0).block_until_ready()


class NDArray:
    """N-dimensional array on a device (CPU or TPU HBM)."""

    __slots__ = ("_data", "_base", "_view", "_writable", "grad", "_fresh_grad",
                 "__weakref__")
    # make numpy defer binary ops to us (a.k.a. mx.nd wins in np_arr * nd_arr)
    __array_priority__ = 1000.0

    def __init__(self, data, base=None, view=None, writable=True):
        self._data = data  # jax.Array (None for views)
        self._base = base  # parent NDArray for views
        self._view = view  # ("slice", start, stop) | ("at", i) | ("reshape", shape)
        self._writable = writable
        self.grad = None  # attached by autograd.mark_variables
        self._fresh_grad = False

    # ------------------------------------------------------------------
    # raw value plumbing
    @property
    def data(self):
        """Current jax.Array value (derived through the view chain)."""
        if self._base is None:
            return self._data
        base = self._base.data
        kind = self._view[0]
        if kind == "slice":
            return base[self._view[1]:self._view[2]]
        if kind == "at":
            return base[self._view[1]]
        if kind == "reshape":
            return base.reshape(self._view[1])
        raise MXNetError("unknown view kind %s" % kind)

    def _set_data(self, value):
        if not self._writable:
            raise MXNetError("trying to write to a read-only NDArray")
        if self._base is None:
            # Placement is sticky under mutation: a cpu-context array must
            # not drift to the default platform just because a freshly
            # computed (uncommitted) value replaces its contents.  An
            # explicitly committed value — device_put by the caller, or a
            # sharded mesh output — wins and re-homes the array.
            old = self._data
            if (old is not None and getattr(old, "committed", False)
                    and not getattr(value, "committed", True)):
                try:
                    devs = old.devices()
                    if len(devs) == 1 and devs != value.devices():
                        value = jax.device_put(value, list(devs)[0])
                except Exception:
                    pass
            self._data = value
            return
        base_val = self._base.data
        kind = self._view[0]
        if kind == "slice":
            new = base_val.at[self._view[1]:self._view[2]].set(value)
        elif kind == "at":
            new = base_val.at[self._view[1]].set(value)
        elif kind == "reshape":
            new = value.reshape(base_val.shape)
        else:
            raise MXNetError("unknown view kind %s" % kind)
        self._base._set_data(new)

    # ------------------------------------------------------------------
    # properties
    @property
    def shape(self):
        if self._base is not None:
            # derive without materializing
            bshape = self._base.shape
            kind = self._view[0]
            if kind == "slice":
                return (self._view[2] - self._view[1],) + tuple(bshape[1:])
            if kind == "at":
                return tuple(bshape[1:])
            if kind == "reshape":
                return tuple(self._view[1])
        return tuple(self._data.shape)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def dtype(self):
        if self._base is not None:
            return self._base.dtype
        return np.dtype(self._data.dtype)

    @property
    def context(self):
        d = self.data
        dev = list(d.devices())[0] if hasattr(d, "devices") else None
        if dev is None:
            return current_context()
        return Context.from_jax_device(dev)

    ctx = context

    @property
    def T(self):
        return transpose(self)

    @property
    def handle(self):
        return self  # FFI-compat shim: the NDArray is its own handle

    # ------------------------------------------------------------------
    # conversion
    def asnumpy(self):
        return np.asarray(self.data)

    def asscalar(self):
        if self.shape != (1,) and self.shape != ():
            raise MXNetError("the current array is not a scalar")
        return self.asnumpy().reshape(())[()]

    def astype(self, dtype):
        return NDArray(self.data.astype(_dtype(dtype)))

    def copy(self):
        return NDArray(self.data + 0 if np.issubdtype(self.dtype, np.number)
                       else jnp.array(self.data))

    def copyto(self, other):
        """Copy into another NDArray or to a Context (ref ``ndarray.py:780``)."""
        if isinstance(other, NDArray):
            if other is self:
                return other
            other._set_data(_to_device(self.data, other.context).astype(other.dtype))
            return other
        if isinstance(other, Context):
            return NDArray(_to_device(self.data, other))
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, context):
        if self.context == context:
            return self
        return self.copyto(context)

    def reshape(self, shape):
        if isinstance(shape, int):
            shape = (shape,)
        shape = _fill_reshape(self.shape, tuple(shape))
        return NDArray(None, base=self, view=("reshape", shape))

    def broadcast_to(self, shape):
        return NDArray(jnp.broadcast_to(self.data, tuple(shape)))

    # ------------------------------------------------------------------
    # sync
    def wait_to_read(self):
        self.data.block_until_ready()

    def wait_to_write(self):
        self.data.block_until_ready()

    # ------------------------------------------------------------------
    # indexing
    def __getitem__(self, key):
        if isinstance(key, (int, np.integer)):
            return NDArray(None, base=self, view=("at", int(key)))
        if isinstance(key, _py_slice):
            if key.step is not None and key.step != 1:
                raise MXNetError("slice step is not supported")
            start, stop, _ = key.indices(self.shape[0])
            return NDArray(None, base=self, view=("slice", start, stop))
        raise MXNetError("NDArray only supports int and slice indexing")

    def __setitem__(self, key, value):
        if isinstance(value, NDArray):
            value = value.data
        elif isinstance(value, Number):
            pass
        else:
            value = jnp.asarray(np.asarray(value), dtype=self.dtype)
        if isinstance(key, _py_slice) and key.start is None and key.stop is None \
                and key.step in (None, 1):
            if isinstance(value, Number):
                self._set_data(jnp.full(self.shape, value, dtype=self.dtype))
            else:
                self._set_data(jnp.broadcast_to(jnp.asarray(value, dtype=self.dtype),
                                                self.shape))
            return
        view = self[key] if isinstance(key, (int, np.integer, _py_slice)) else None
        if view is None:
            raise MXNetError("unsupported key type for __setitem__")
        if isinstance(value, Number):
            view._set_data(jnp.full(view.shape, value, dtype=self.dtype))
        else:
            view._set_data(jnp.asarray(value, dtype=self.dtype))

    def _sync_copyfrom(self, source_array):
        src = np.asarray(source_array, dtype=self.dtype)
        if src.shape != self.shape:
            raise MXNetError("array shape do not match the shape of NDArray")
        self._set_data(_place(jnp.asarray(src), self.context))

    # ------------------------------------------------------------------
    # arithmetic — routed through the op registry so autograd sees them
    def __add__(self, other):
        return _ufunc(self, other, "_plus", "_plus_scalar")

    __radd__ = __add__

    def __iadd__(self, other):
        res = _ufunc(self, other, "_plus", "_plus_scalar")
        self._set_data(res.data)
        return self

    def __sub__(self, other):
        return _ufunc(self, other, "_minus", "_minus_scalar")

    def __rsub__(self, other):
        return _ufunc(self, other, None, "_rminus_scalar")

    def __isub__(self, other):
        res = _ufunc(self, other, "_minus", "_minus_scalar")
        self._set_data(res.data)
        return self

    def __mul__(self, other):
        return _ufunc(self, other, "_mul", "_mul_scalar")

    __rmul__ = __mul__

    def __imul__(self, other):
        res = _ufunc(self, other, "_mul", "_mul_scalar")
        self._set_data(res.data)
        return self

    def __neg__(self):
        return _ufunc(self, -1.0, "_mul", "_mul_scalar")

    def __div__(self, other):
        return _ufunc(self, other, "_div", "_div_scalar")

    __truediv__ = __div__

    def __rdiv__(self, other):
        return _ufunc(self, other, None, "_rdiv_scalar")

    __rtruediv__ = __rdiv__

    def __itruediv__(self, other):
        res = _ufunc(self, other, "_div", "_div_scalar")
        self._set_data(res.data)
        return self

    def __mod__(self, other):
        return _ufunc(self, other, "_mod", "_mod_scalar")

    def __pow__(self, other):
        return _ufunc(self, other, "_power", "_power_scalar")

    def __eq__(self, other):
        return _ufunc(self, other, "_equal", "_equal_scalar")

    def __ne__(self, other):
        return _ufunc(self, other, "_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return _ufunc(self, other, "_greater", "_greater_scalar")

    def __ge__(self, other):
        return _ufunc(self, other, "_greater_equal", "_greater_equal_scalar")

    def __lt__(self, other):
        return _ufunc(self, other, "_lesser", "_lesser_scalar")

    def __le__(self, other):
        return _ufunc(self, other, "_lesser_equal", "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __bool__(self):
        raise MXNetError(
            "The truth value of an NDArray is ambiguous; use asscalar()")

    def __len__(self):
        return self.shape[0]

    def __repr__(self):
        return "<NDArray %s @%s>" % ("x".join(map(str, self.shape)), self.context)

    # pickling / attach_grad -------------------------------------------
    def __getstate__(self):
        return {"data": self.asnumpy(), "writable": self._writable}

    def __setstate__(self, state):
        self._data = jnp.asarray(state["data"])
        self._base = None
        self._view = None
        self._writable = state["writable"]
        self.grad = None
        self._fresh_grad = False

    def attach_grad(self, grad_req="write"):
        from . import autograd
        autograd.mark_variables([self], [zeros(self.shape, self.context, self.dtype)],
                                [grad_req])

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from . import autograd
        autograd.backward([self], [out_grad] if out_grad is not None else None)


def _to_device(value, ctx: Context):
    return jax.device_put(value, ctx.jax_device())


def _place(value, ctx: Context):
    return jax.device_put(value, ctx.jax_device())


def _fill_reshape(old_shape, new_shape):
    if any(d == -1 for d in new_shape):
        known = int(np.prod([d for d in new_shape if d != -1])) or 1
        total = int(np.prod(old_shape)) if old_shape else 1
        new_shape = tuple(total // known if d == -1 else d for d in new_shape)
    return new_shape


def _ufunc(lhs, rhs, array_op, scalar_op):
    """Binary op dispatch: NDArray/NDArray vs NDArray/scalar
    (reference ``ndarray.py:1151`` _ufunc_helper)."""
    from .op.invoke import invoke
    if isinstance(rhs, NDArray):
        if array_op is None:
            raise MXNetError("operation not supported between two NDArrays")
        return invoke(_reg.get(array_op), [lhs, rhs], {})[0]
    if isinstance(rhs, Number):
        return invoke(_reg.get(scalar_op), [lhs], {"scalar": float(rhs)})[0]
    raise TypeError("type %s not supported" % str(type(rhs)))


# ----------------------------------------------------------------------
# creation functions (reference ndarray.py:888-1151)
def empty(shape, ctx=None, dtype=mx_real_t):
    return zeros(shape, ctx, dtype)


def zeros(shape, ctx=None, dtype=mx_real_t):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.zeros(shape, dtype=_dtype(dtype)), ctx))


def ones(shape, ctx=None, dtype=mx_real_t):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.ones(shape, dtype=_dtype(dtype)), ctx))


def full(shape, val, ctx=None, dtype=mx_real_t):
    ctx = ctx or current_context()
    if isinstance(shape, int):
        shape = (shape,)
    return NDArray(_place(jnp.full(shape, val, dtype=_dtype(dtype)), ctx))


def array(source_array, ctx=None, dtype=None):
    ctx = ctx or current_context()
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
    else:
        src = np.asarray(source_array)
    if dtype is None:
        dtype = src.dtype if src.dtype != np.float64 else mx_real_t
    src = np.asarray(src, dtype=_dtype(dtype))
    if src.ndim == 0:
        src = src.reshape((1,))
    return NDArray(_place(jnp.asarray(src), ctx))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=mx_real_t):
    ctx = ctx or current_context()
    vals = np.arange(start, stop, step, dtype=_dtype(dtype))
    if repeat != 1:
        vals = np.repeat(vals, repeat)
    return NDArray(_place(jnp.asarray(vals), ctx))


def concatenate(arrays, axis=0, always_copy=True):
    if len(arrays) == 1 and not always_copy:
        return arrays[0]
    return NDArray(jnp.concatenate([a.data for a in arrays], axis=axis))


def moveaxis(tensor, source, destination):
    return NDArray(jnp.moveaxis(tensor.data, source, destination))


def onehot_encode(indices, out):
    """One-hot encode into ``out`` (reference ``ndarray.py:877``)."""
    depth = out.shape[1]
    out._set_data(jax.nn.one_hot(indices.data.astype(jnp.int32), depth,
                                 dtype=out.dtype))
    return out


# ----------------------------------------------------------------------
# binary serialization — reference-compatible on-disk format
_MAGIC = 0x112


def _save_one(f, arr: NDArray):
    a = arr.asnumpy()
    shape = arr.shape
    f.write(struct.pack("<I", len(shape)))
    if len(shape) == 0:
        # ndim==0 is the reference's "none" array: shape only, no payload
        # (src/ndarray/ndarray.cc:626 "if (is_none()) return")
        return
    f.write(struct.pack("<%dI" % len(shape), *shape))
    ctx = arr.context
    # persist accelerator arrays with the reference's gpu devtype id (2) so
    # files round-trip; loads always land on the current default device.
    devtype = ctx.device_typeid if ctx.device_typeid <= 2 else 2
    f.write(struct.pack("<ii", devtype, ctx.device_id))
    npdt = np.dtype(a.dtype)
    if npdt not in _DTYPE_NP_TO_MX:
        a = a.astype(np.float32)
        npdt = np.dtype(np.float32)
    f.write(struct.pack("<i", _DTYPE_NP_TO_MX[npdt]))
    f.write(np.ascontiguousarray(a).tobytes())


def _load_one(f) -> NDArray:
    ndim, = struct.unpack("<I", f.read(4))
    shape = struct.unpack("<%dI" % ndim, f.read(4 * ndim)) if ndim else ()
    if ndim == 0:
        return NDArray(jnp.zeros(()))
    _devtype, _devid = struct.unpack("<ii", f.read(8))
    type_flag, = struct.unpack("<i", f.read(4))
    dt = _DTYPE_MX_TO_NP[type_flag]
    count = int(np.prod(shape))
    buf = f.read(count * dt.itemsize)
    a = np.frombuffer(buf, dtype=dt).reshape(shape)
    return array(a, dtype=dt)


def save(fname, data):
    """Save NDArrays in the reference binary format
    (``src/ndarray/ndarray.cc:680-691``)."""
    if isinstance(data, NDArray):
        data, names = [data], []
    elif isinstance(data, dict):
        names = list(data.keys())
        data = list(data.values())
    elif isinstance(data, (list, tuple)):
        names = []
    else:
        raise TypeError("save expects dict/list/NDArray")
    with open(fname, "wb") as f:
        f.write(struct.pack("<QQ", _MAGIC, 0))
        f.write(struct.pack("<Q", len(data)))
        for arr in data:
            _save_one(f, arr)
        f.write(struct.pack("<Q", len(names)))
        for n in names:
            b = n.encode("utf-8")
            f.write(struct.pack("<Q", len(b)))
            f.write(b)


def load(fname):
    """Load NDArrays saved by :func:`save` (or by the reference)."""
    with open(fname, "rb") as f:
        return _load_fileobj(f)


def load_buffer(blob):
    """Load NDArrays from an in-memory params blob (the C predict API's
    load-from-bytes path, reference ``c_predict_api.cc:87-117``)."""
    import io as _pyio
    return _load_fileobj(_pyio.BytesIO(blob))


def _load_fileobj(f):
    magic, _ = struct.unpack("<QQ", f.read(16))
    if magic != _MAGIC:
        raise MXNetError("Invalid NDArray file format")
    n, = struct.unpack("<Q", f.read(8))
    data = [_load_one(f) for _ in range(n)]
    k, = struct.unpack("<Q", f.read(8))
    names = []
    for _ in range(k):
        ln, = struct.unpack("<Q", f.read(8))
        names.append(f.read(ln).decode("utf-8"))
    if names:
        return dict(zip(names, data))
    return data


def transpose(arr, axes=None):
    return NDArray(jnp.transpose(arr.data, axes))


def __getattr__(name):
    """Ops registered AFTER import — out-of-tree op packages
    (examples/extension-ops), CustomOp materialization — resolve lazily
    from the registry (PEP 562), so late registration gets the same
    ``mx.nd.<op>`` surface as in-tree ops."""
    from .op import registry as _late_reg
    try:
        op = _late_reg.get(name)
    except Exception:
        raise AttributeError("module %r has no attribute %r"
                             % (__name__, name))
    from .op.invoke import make_ndarray_function
    fn = make_ndarray_function(op)
    globals()[name] = fn
    return fn
