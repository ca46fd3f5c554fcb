"""Deploy-only predictor — the analog of the reference's predict-only C API
(``include/mxnet/c_predict_api.h``, ``src/c_api/c_predict_api.cc``): load a
saved symbol + params, feed inputs, fetch outputs.  No optimizer, no
autograd.

The forward itself lives in the process-wide keyed compiled-forward cache
(``serving/compiled.py``): the compiled program takes the weights as
ARGUMENTS, so every Predictor (and every serving bucket — see
``serving/server.py``) over the same (symbol, input shapes, dtypes)
shares one compilation.  ``from_checkpoint`` of an already-loaded model
costs a params parse and nothing else.

Dtypes are honored end to end: ``set_input`` casts to the dtype type
inference derives from the loaded params (bf16 weights ⇒ bf16 input
staging), and ``get_output`` returns the program's own output dtype —
the bf16/int8 tiers INFER_BENCH reports no longer round-trip through
f32 host copies.  The native C ABI (``native/mxtpu_c_api.cc``,
MXPredSetInput/GetOutput) remains an ``mx_float`` surface like the
reference's — serve non-f32 models through the Python/serving path.

The same object backs the native C ABI in ``native/mxtpu_c_api.cc``
(MXPredCreate/SetInput/Forward/GetOutput), so C/C++ deployments link one
shared library exactly like the reference's amalgamated predict build.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .base import MXNetError
from . import ndarray as nd
from . import symbol as sym

__all__ = ["Predictor"]


def _load_params_bytes(blob: bytes):
    """Parse a ``prefix-NNNN.params`` blob (NDArray.Save format,
    reference ``c_predict_api.cc:87-117``)."""
    save_dict = nd.load_buffer(blob)
    arg_params, aux_params = {}, {}
    for k, v in save_dict.items():
        if k.startswith("arg:"):
            arg_params[k[4:]] = v
        elif k.startswith("aux:"):
            aux_params[k[4:]] = v
        else:                       # unprefixed = arg (reference behavior)
            arg_params[k] = v
    return arg_params, aux_params


class Predictor(object):
    """Forward-only inference over a saved model.

    Parameters
    ----------
    symbol_json : str
        the ``*-symbol.json`` content.
    param_bytes : bytes
        the ``*.params`` file content.
    input_shapes : dict name -> shape
        every data input's shape (batch included).
    dev_type/dev_id : str/int
        kept for C-API signature parity; TPU placement is automatic.
    """

    def __init__(self, symbol_json: str, param_bytes: bytes,
                 input_shapes: Dict[str, Sequence[int]],
                 dev_type: str = "tpu", dev_id: int = 0):
        from .serving.compiled import compiled_forward

        self.symbol = sym.load_json(symbol_json)
        arg_params, aux_params = _load_params_bytes(param_bytes)
        self.input_shapes = {k: tuple(v) for k, v in input_shapes.items()}

        arg_names = self.symbol.list_arguments()
        aux_names = self.symbol.list_auxiliary_states()
        arg_shapes, out_shapes, aux_shapes = \
            self.symbol.infer_shape(**self.input_shapes)
        self._out_shapes = [tuple(s) for s in out_shapes]
        shape_of = dict(zip(arg_names, arg_shapes))

        self._params = {}
        label_names = []
        for name, shape in zip(arg_names, arg_shapes):
            if name in self.input_shapes:
                continue
            if name in arg_params:
                if tuple(arg_params[name].shape) != tuple(shape):
                    raise MXNetError(
                        "param %s shape %s != expected %s"
                        % (name, arg_params[name].shape, tuple(shape)))
                self._params[name] = jnp.asarray(arg_params[name].data)
            elif name.endswith("label"):
                # unused loss-layer label input: zero-filled per forward
                label_names.append(name)
            else:
                raise MXNetError(
                    "parameter %s missing from the params blob" % name)
        self._aux = {}
        for name, shape in zip(aux_names, aux_shapes):
            if name not in aux_params:
                self._aux[name] = jnp.zeros(shape, jnp.float32)
            else:
                self._aux[name] = jnp.asarray(aux_params[name].data)

        # bound dtypes: what type inference derives from the LOADED
        # params (a bf16 checkpoint binds bf16 inputs), f32 fallback —
        # set_input stages in this dtype, no silent f32 round-trip
        from .serving.compiled import infer_input_dtypes
        self._input_dtypes = infer_input_dtypes(
            self.symbol, self._params,
            list(self.input_shapes) + label_names)
        self._label_shapes = {n: tuple(shape_of[n]) for n in label_names}

        self._cf = compiled_forward(
            self.symbol, list(self.input_shapes) + label_names,
            platform=jax.default_backend())
        # warm the declared signature now: a second Predictor over the
        # same model (or a serving bucket at this batch) compiles nothing
        feed_shapes = dict(self.input_shapes, **self._label_shapes)
        self._cf.aot_compile(self._params, self._aux, feed_shapes,
                             self._input_dtypes)
        self._inputs: Dict[str, np.ndarray] = {}
        self._outputs: Optional[List] = None

    @classmethod
    def from_checkpoint(cls, prefix: str, epoch: int,
                        input_shapes: Dict[str, Sequence[int]]):
        with open("%s-symbol.json" % prefix) as f:
            symbol_json = f.read()
        with open("%s-%04d.params" % (prefix, epoch), "rb") as f:
            param_bytes = f.read()
        return cls(symbol_json, param_bytes, input_shapes)

    # -- c_predict_api-shaped surface ---------------------------------
    def input_dtype(self, name: str) -> np.dtype:
        """The dtype ``set_input`` stages ``name`` in (derived from the
        loaded param dtypes by type inference)."""
        if name not in self.input_shapes:
            raise MXNetError("%s is not a declared input" % name)
        return self._input_dtypes[name]

    def set_input(self, name: str, value) -> None:
        if name not in self.input_shapes:
            raise MXNetError("%s is not a declared input" % name)
        arr = np.asarray(value)
        if tuple(arr.shape) != self.input_shapes[name]:
            raise MXNetError("input %s shape %s != declared %s"
                             % (name, arr.shape, self.input_shapes[name]))
        self._inputs[name] = np.ascontiguousarray(
            arr, dtype=self._input_dtypes[name])

    def forward(self) -> None:
        missing = [n for n in self.input_shapes if n not in self._inputs]
        if missing:
            raise MXNetError("set_input(%s) before forward()" % missing)
        feed = dict(self._inputs)
        for n, s in self._label_shapes.items():
            feed[n] = np.zeros(s, self._input_dtypes[n])
        self._outputs = list(self._cf.run(self._params, self._aux, feed))

    def get_output_shape(self, index: int):
        return self._out_shapes[index]

    @property
    def num_outputs(self) -> int:
        return len(self._out_shapes)

    def get_output(self, index: int) -> np.ndarray:
        """Host copy of output ``index`` in the program's OWN output
        dtype (bf16 programs return bf16 — cast at the call site if a
        f32 view is wanted; the C ABI's f32 contract is unchanged for
        the f32 models it serves)."""
        if self._outputs is None:
            raise MXNetError("call forward() first")
        return np.asarray(self._outputs[index])

    def predict(self, **inputs) -> List[np.ndarray]:
        """Convenience: set every input, forward, return all outputs."""
        for name, value in inputs.items():
            self.set_input(name, value)
        self.forward()
        return [self.get_output(i) for i in range(self.num_outputs)]
