"""Core types: Context (device model), dtype flags, errors.

TPU-native re-design of the reference's ``include/mxnet/base.h:116-292``
(Context) and mshadow's dtype flags.  Instead of mapping device ids to CUDA
streams, a Context resolves to a concrete ``jax.Device``; ``tpu`` is a
first-class device type.  All compute is dispatched through XLA, so there is
no stream/engine machinery here — ``RunContext.stream`` has no analog.
"""
from __future__ import annotations

import threading

import numpy as np

import jax

__all__ = [
    "MXNetError", "Context", "cpu", "gpu", "tpu", "current_context",
    "mx_real_t", "_DTYPE_NP_TO_MX", "_DTYPE_MX_TO_NP", "string_types",
]

string_types = (str,)


class MXNetError(RuntimeError):
    """Framework error type (reference: dmlc error -> MXGetLastError)."""


# dtype <-> integer flag mapping, mirrors mshadow's type flags
# (reference usage: include/mxnet/tensor_blob.h type_flag_).  bfloat16 is a
# TPU-native extension flag.
_DTYPE_NP_TO_MX = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.float16): 2,
    np.dtype(np.uint8): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int8): 5,
    np.dtype(np.int64): 6,
    np.dtype(jax.numpy.bfloat16): 7,
    np.dtype(bool): 8,
}
_DTYPE_MX_TO_NP = {v: k for k, v in _DTYPE_NP_TO_MX.items()}

mx_real_t = np.float32


def _dtype(dtype):
    """Normalize a user dtype (np dtype / str / mx flag) to np.dtype."""
    if dtype is None:
        return np.dtype(mx_real_t)
    if isinstance(dtype, int) and not isinstance(dtype, bool):
        return _DTYPE_MX_TO_NP[dtype]
    if dtype == "bfloat16":
        return np.dtype(jax.numpy.bfloat16)
    return np.dtype(dtype)


class Context:
    """Device context: ``cpu(0)``, ``tpu(3)``...

    Mirrors the reference Context (``include/mxnet/base.h:116-207``): a
    (device type, device id) pair with string form ``"tpu(0)"``.  ``gpu`` is
    accepted as an alias for ``tpu`` so reference training scripts that pass
    ``--gpus 0`` run unmodified on TPU chips.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # --- jax resolution -------------------------------------------------
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device.

        ``tpu``/``gpu`` contexts resolve to the accelerator backend.  They
        resolve to a host CPU device only when the process was held to
        the CPU outright (``JAX_PLATFORMS=cpu`` / ``jax_platforms``, as
        the test suite and every CI stage set); otherwise a missing
        accelerator raises, so a ``tpu`` context never quietly computes
        on the host.
        """
        kind = self.device_type
        if kind in ("tpu", "gpu"):
            devs = _accelerator_devices()
            if devs:
                # multi-process runs name devices by global id, which
                # wraps onto this host's; in one process an id past the
                # last chip would quietly mean chip 0
                if self.device_id >= len(devs) and jax.process_count() == 1:
                    raise MXNetError(
                        "%s: this process has %d accelerator device(s)"
                        % (self, len(devs)))
                return devs[self.device_id % len(devs)]
            if not _cpu_chosen():
                raise MXNetError(
                    "%s: JAX found no accelerator (default backend %r). "
                    "Set JAX_PLATFORMS=cpu to run this context on the "
                    "host CPU on purpose." % (self, jax.default_backend()))
            kind = "cpu"
        if kind in ("cpu", "cpu_pinned"):
            # local_devices: in a multi-process run only this host's
            # devices are addressable (placement on a peer's is an error)
            devs = jax.local_devices(backend="cpu")
            return devs[self.device_id % len(devs)]
        raise MXNetError("unknown device type %s" % kind)

    @classmethod
    def from_jax_device(cls, dev) -> "Context":
        if dev.platform == "tpu":
            return Context("tpu", dev.id)
        if dev.platform == "gpu":
            return Context("gpu", dev.id)
        return Context("cpu", dev.id)


def _cpu_chosen() -> bool:
    """True when the process was held to the CPU outright."""
    return (jax.config.jax_platforms or "") == "cpu"


def _accelerator_devices():
    """This process's accelerator devices; [] on a CPU-only backend.  A
    backend that fails to start raises: it is not a CPU."""
    if jax.default_backend() == "cpu":
        return []
    return jax.local_devices()


def cpu(device_id=0):
    """Return a CPU context (reference ``base.h:240``)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Alias of :func:`tpu` — accelerator context (reference ``base.h:252``)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the device type this framework is built for."""
    return Context("tpu", device_id)


def default_context() -> Context:
    """Framework default: the accelerator if present, else CPU."""
    override = getattr(Context._default_ctx, "value", None)
    if override is not None:
        return override
    if _accelerator_devices():
        return Context("tpu", 0)
    return Context("cpu", 0)


def set_default_context(ctx: Context):
    """Set the process default context (reference
    ``test_utils.py:34`` set_default_context)."""
    Context._default_ctx.value = ctx


def current_context() -> Context:
    """The context from the innermost ``with mx.Context(...)`` scope."""
    ctx = getattr(Context._default_ctx, "value", None)
    return ctx if ctx is not None else default_context()


Context.default_ctx = property(lambda self: current_context())
