"""Registry-wide operator sweep.

The reference's single most important test asset is its systematic
gradient checking of the op library (``tests/python/unittest/
test_operator.py`` + ``python/mxnet/test_utils.py:300-601`` — SURVEY §4).
This module replicates that coverage mechanically: every op in the
unified registry (``mxnet_tpu/op/registry.py``) must appear in the case
table below; differentiable ops get a finite-difference gradient check
against the symbolic backward, everything else gets a forward contract
check.  ``test_registry_fully_covered`` fails when a newly registered op
has no case, and ``test_sweep_report`` prints the counted coverage.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.op import registry as _registry
from mxnet_tpu.test_utils import (check_numeric_gradient,
                                  check_symbolic_forward)

R = np.random.RandomState(7)


def randn(*s):
    return R.randn(*s).astype("f")


def pos(*s):
    return (np.abs(R.randn(*s)) + 0.5).astype("f")


def unit(*s):
    return R.uniform(-0.9, 0.9, s).astype("f")


def nz(*s):
    """Values bounded away from 0 (kinks of abs/relu/sign)."""
    x = R.randn(*s).astype("f")
    return np.sign(x) * (np.abs(x) + 0.4)


def distinct(*s):
    """Unique, well-separated values (max/min/pool tie-breaking)."""
    n = int(np.prod(s))
    v = (np.arange(n) * 0.37 + 0.1).astype("f")
    R.shuffle(v)
    return v.reshape(s)


def ints(hi, *s):
    return R.randint(0, hi, s).astype("f")


CASES = []
_SEEN = set()


def G(op, loc, params=None, *, out=None, grad_nodes=None, aux=None,
      rtol=5e-2, atol=5e-3, eps=1e-3, id_suffix=""):
    """A finite-difference gradient-check case."""
    CASES.append(dict(kind="grad", op=op, loc=loc, params=params or {},
                      out=out, grad_nodes=grad_nodes, aux=aux, rtol=rtol,
                      atol=atol, eps=eps,
                      id=op + (("::" + id_suffix) if id_suffix else "")))
    _SEEN.add(op)


def I(op, runner):
    """An imperative-only case (ops that cannot run under tracing,
    e.g. host-side image decode)."""
    CASES.append(dict(kind="imp", op=op, run=runner, id=op))
    _SEEN.add(op)


def F(op, loc, params=None, *, fwd=None, aux=None, out=None, check=None,
      id_suffix=""):
    """A forward-contract case: ``fwd(loc arrays) -> expected`` or a
    free-form ``check(outputs, loc arrays)`` property."""
    CASES.append(dict(kind="fwd", op=op, loc=loc, params=params or {},
                      fwd=fwd, aux=aux, out=out, check=check,
                      id=op + (("::" + id_suffix) if id_suffix else "")))
    _SEEN.add(op)


# ======================================================================
# unary math — smooth everywhere
for name in ["identity", "negative", "sigmoid", "tanh", "softrelu", "erf",
             "sin", "cos", "sinh", "cosh", "arctan", "arcsinh", "degrees",
             "radians", "exp", "expm1", "square", "softmax", "log_softmax",
             "make_loss_internal", "_CrossDeviceCopy"]:
    G(name, {"data": randn(2, 3)})
G("tan", {"data": unit(2, 3)})
# positive domain
for name in ["sqrt", "rsqrt", "cbrt", "rcbrt", "log", "log10", "log2",
             "log1p", "reciprocal", "gamma", "gammaln"]:
    G(name, {"data": pos(2, 3)})
# restricted domains
G("arcsin", {"data": unit(2, 3)})
G("arccos", {"data": unit(2, 3)})
G("arctanh", {"data": unit(2, 3)})
G("arccosh", {"data": pos(2, 3) + 1.0})
# kinked at 0 — keep inputs away
G("abs", {"data": nz(2, 3)})
G("relu", {"data": nz(2, 3)})
G("smooth_l1", {"data": nz(2, 3) * 3}, {"scalar": 1.0})
G("clip", {"data": randn(2, 3) * 2}, {"a_min": -0.45, "a_max": 0.45})

# shape/layout ops
G("Flatten", {"data": randn(2, 3, 2)})
G("Reshape", {"data": randn(2, 3)}, {"shape": (3, 2)})
G("expand_dims", {"data": randn(2, 3)}, {"axis": 1})
G("transpose", {"data": randn(2, 3)})
G("SwapAxis", {"data": randn(2, 3, 2)}, {"dim1": 0, "dim2": 2})
G("tile", {"data": randn(2, 3)}, {"reps": (2, 1)})
G("repeat", {"data": randn(2, 3)}, {"repeats": 2})
G("reverse", {"data": randn(2, 3)}, {"axis": 0})
G("slice", {"data": randn(3, 4)}, {"begin": (0, 1), "end": (2, 3)})
G("slice_axis", {"data": randn(3, 4)}, {"axis": 1, "begin": 0, "end": 2})
G("Pad", {"data": randn(1, 2, 3, 3)},
  {"pad_width": (0, 0, 0, 0, 1, 1, 1, 1), "mode": "constant"})
G("broadcast_axis", {"data": randn(1, 3)}, {"axis": 0, "size": 2})
G("broadcast_to", {"data": randn(1, 3)}, {"shape": (2, 3)})
G("Cast", {"data": randn(2, 3)}, {"dtype": "float32"})
G("Concat", {"a": randn(2, 2), "b": randn(2, 3)},
  {"num_args": 2, "dim": 1})
G("add_n", {"a": randn(2, 3), "b": randn(2, 3)}, {"num_args": 2})
G("SliceChannel", {"data": randn(2, 4)}, {"num_outputs": 2}, out=0)
G("Crop", {"data": randn(1, 2, 4, 4)},
  {"num_args": 1, "h_w": (2, 2), "center_crop": True})

# reductions
for name in ["sum", "mean", "nansum"]:
    G(name, {"data": randn(2, 3)})
for name in ["prod", "nanprod"]:
    G(name, {"data": pos(2, 3)})
G("max", {"data": distinct(2, 3)})
G("min", {"data": distinct(2, 3)})
G("norm", {"data": pos(2, 3)})

# binary elemwise
for name in ["_plus", "_minus", "_mul", "_hypot"]:
    G(name, {"lhs": nz(2, 3), "rhs": nz(2, 3)})
G("_div", {"lhs": randn(2, 3), "rhs": pos(2, 3)})
G("_power", {"lhs": pos(2, 3), "rhs": randn(2, 3)})
G("_maximum", {"lhs": distinct(2, 3), "rhs": distinct(2, 3)})
G("_minimum", {"lhs": distinct(2, 3), "rhs": distinct(2, 3)})
F("_mod", {"lhs": pos(2, 3) * 5, "rhs": pos(2, 3)},
  fwd=lambda lhs, rhs: np.mod(lhs, rhs))
G("dot", {"lhs": randn(2, 3), "rhs": randn(3, 2)})
G("batch_dot", {"lhs": randn(2, 2, 3), "rhs": randn(2, 3, 2)})

# scalar variants
for name in ["_plus_scalar", "_minus_scalar", "_rminus_scalar",
             "_mul_scalar", "_div_scalar", "_hypot_scalar",
             "_rpower_scalar"]:
    G(name, {"data": nz(2, 3)}, {"scalar": 2.0})
G("_rdiv_scalar", {"data": pos(2, 3)}, {"scalar": 2.0})
G("_power_scalar", {"data": pos(2, 3)}, {"scalar": 2.0})
G("_maximum_scalar", {"data": distinct(2, 3)}, {"scalar": 1.05})
G("_minimum_scalar", {"data": distinct(2, 3)}, {"scalar": 1.05})
F("_mod_scalar", {"data": pos(2, 3) * 5}, {"scalar": 2.0},
  fwd=lambda data: np.mod(data, 2.0))
F("_rmod_scalar", {"data": pos(2, 3) + 1}, {"scalar": 5.0},
  fwd=lambda data: np.mod(5.0, data))

# broadcast binary
for name in ["broadcast_add", "broadcast_sub", "broadcast_mul",
             "broadcast_hypot"]:
    G(name, {"lhs": nz(2, 3), "rhs": nz(1, 3)})
G("broadcast_div", {"lhs": randn(2, 3), "rhs": pos(1, 3)})
G("broadcast_power", {"lhs": pos(2, 3), "rhs": randn(1, 3)})
G("broadcast_maximum", {"lhs": distinct(2, 3), "rhs": distinct(1, 3)})
G("broadcast_minimum", {"lhs": distinct(2, 3), "rhs": distinct(1, 3)})
F("broadcast_mod", {"lhs": pos(2, 3) * 5, "rhs": pos(1, 3)},
  fwd=lambda lhs, rhs: np.mod(lhs, rhs))

# comparisons (forward contracts)
_CMP = {"equal": np.equal, "not_equal": np.not_equal,
        "greater": np.greater, "greater_equal": np.greater_equal,
        "lesser": np.less, "lesser_equal": np.less_equal}
for stem, np_fn in _CMP.items():
    a, b = ints(3, 2, 3), ints(3, 2, 3)
    F("_" + stem, {"lhs": a, "rhs": b},
      fwd=lambda lhs, rhs, f=np_fn: f(lhs, rhs).astype("f"))
    F("_%s_scalar" % stem, {"data": a}, {"scalar": 1.0},
      fwd=lambda data, f=np_fn: f(data, 1.0).astype("f"))
    F("broadcast_" + stem, {"lhs": a, "rhs": b[:1]},
      fwd=lambda lhs, rhs, f=np_fn: f(lhs, rhs).astype("f"))

# rounding/sign family (zero gradient by definition)
for name, np_fn in [("ceil", np.ceil), ("floor", np.floor),
                    ("round", np.round), ("rint", np.rint),
                    ("trunc", np.trunc), ("fix", np.fix),
                    ("sign", np.sign)]:
    F(name, {"data": randn(2, 3) * 3}, fwd=np_fn)

# indexing / selection
G("where", {"condition": ints(2, 2, 3), "x": randn(2, 3), "y": randn(2, 3)},
  grad_nodes=["x", "y"])
G("take", {"a": randn(5, 3), "indices": ints(5, 4)}, grad_nodes=["a"])
G("pick", {"data": randn(3, 4), "index": ints(4, 3)}, grad_nodes=["data"])
G("Embedding", {"data": ints(5, 2, 3), "weight": randn(5, 4)},
  {"input_dim": 5, "output_dim": 4}, grad_nodes=["weight"])
F("batch_take", {"a": randn(3, 4), "indices": ints(4, 3)},
  fwd=lambda a, indices: a[np.arange(3), indices.astype(int)])
F("one_hot", {"indices": ints(4, 5)}, {"depth": 4},
  fwd=lambda indices: np.eye(4, dtype="f")[indices.astype(int)])
F("argmax", {"data": distinct(3, 4)}, {"axis": 1},
  fwd=lambda data: np.argmax(data, 1).astype("f"))
F("argmin", {"data": distinct(3, 4)}, {"axis": 1},
  fwd=lambda data: np.argmin(data, 1).astype("f"))
F("argmax_channel", {"data": distinct(3, 4)},
  fwd=lambda data: np.argmax(data, 1).astype("f"))
F("sort", {"data": distinct(3, 4)}, fwd=lambda data: np.sort(data, -1))
F("argsort", {"data": distinct(3, 4)},
  fwd=lambda data: np.argsort(data, -1).astype("f"))
F("topk", {"data": distinct(3, 4)}, {"k": 2},
  fwd=lambda data: np.argsort(data, -1)[:, ::-1][:, :2].astype("f"))

# imperative-only: host-side image decode (reference image_io.cc)
def _imdecode_case():
    import io as _io
    import mxnet_tpu as _mx
    try:
        from PIL import Image
    except ImportError:
        pytest.skip("no PIL")
    img = (np.arange(4 * 6 * 3) % 255).astype("uint8").reshape(4, 6, 3)
    buf = _io.BytesIO()
    Image.fromarray(img).save(buf, format="PNG")
    raw = np.frombuffer(buf.getvalue(), dtype=np.uint8)
    out = _mx.nd._imdecode(_mx.nd.array(raw.astype("f")))
    np.testing.assert_array_equal(out.asnumpy().astype("uint8"), img)


I("_imdecode", _imdecode_case)

# identity-ish plumbing ops
F("BlockGrad", {"data": randn(2, 3)}, fwd=lambda data: data)
F("_identity_with_attr_like_rhs", {"lhs": randn(2, 3), "rhs": randn(2, 3)},
  fwd=lambda lhs, rhs: lhs)

# init ops
F("_zeros", {}, {"shape": (2, 3)}, fwd=lambda: np.zeros((2, 3), "f"))
F("_ones", {}, {"shape": (2, 3)}, fwd=lambda: np.ones((2, 3), "f"))
F("_full", {}, {"shape": (2, 3), "value": 2.5},
  fwd=lambda: np.full((2, 3), 2.5, "f"))
F("_arange", {}, {"start": 1, "stop": 7, "step": 2},
  fwd=lambda: np.arange(1, 7, 2).astype("f"))
F("zeros_like", {"data": randn(2, 3)}, fwd=np.zeros_like)
F("ones_like", {"data": randn(2, 3)}, fwd=np.ones_like)

# samplers: shape + domain/moment sanity on a large draw
def _sampler(name, params, check):
    F(name, {}, dict(params, shape=(4000,)), check=check)


_sampler("_sample_uniform", {"low": 0.0, "high": 2.0},
         lambda o: (o >= 0).all() and (o < 2).all() and
         abs(o.mean() - 1.0) < 0.1)
_sampler("_sample_normal", {"loc": 0.0, "scale": 1.0},
         lambda o: abs(o.mean()) < 0.1 and abs(o.std() - 1) < 0.1)
_sampler("_sample_gamma", {"alpha": 2.0, "beta": 1.0},
         lambda o: (o > 0).all() and abs(o.mean() - 2.0) < 0.25)
_sampler("_sample_exponential", {"lam": 2.0},
         lambda o: (o >= 0).all() and abs(o.mean() - 0.5) < 0.1)
_sampler("_sample_poisson", {"lam": 3.0},
         lambda o: (o >= 0).all() and abs(o.mean() - 3.0) < 0.3)
_sampler("_sample_negbinomial", {"k": 3, "p": 0.5},
         lambda o: (o >= 0).all())
_sampler("_sample_gennegbinomial", {"mu": 2.0, "alpha": 0.5},
         lambda o: (o >= 0).all())

# optimizer update ops (forward contracts vs the straightforward math)
F("sgd_update", {"weight": randn(2, 3), "grad": randn(2, 3)},
  {"lr": 0.1},
  fwd=lambda weight, grad: weight - 0.1 * grad)
F("sgd_mom_update",
  {"weight": randn(2, 3), "grad": randn(2, 3), "mom": randn(2, 3)},
  {"lr": 0.1, "momentum": 0.9}, out=0,
  fwd=lambda weight, grad, mom: weight + (0.9 * mom - 0.1 * grad))
F("adam_update",
  {"weight": randn(2, 3), "grad": randn(2, 3), "mean": randn(2, 3),
   "var": pos(2, 3)},
  {"lr": 0.1, "t": 1}, out=0,
  fwd=lambda weight, grad, mean, var:
  weight - 0.1 * np.sqrt(1 - 0.999) / (1 - 0.9) *
  (0.9 * mean + 0.1 * grad) /
  (np.sqrt(0.999 * var + 0.001 * grad * grad) + 1e-8))
F("rmsprop_update",
  {"weight": randn(2, 3), "grad": randn(2, 3), "n": pos(2, 3)},
  {"lr": 0.1, "gamma1": 0.9}, out=0,
  fwd=lambda weight, grad, n: weight - 0.1 * grad /
  np.sqrt(0.9 * n + 0.1 * grad * grad + 1e-8))
F("rmspropalex_update",
  {"weight": randn(2, 3), "grad": randn(2, 3), "n": pos(2, 3),
   "g": randn(2, 3), "delta": randn(2, 3)},
  {"lr": 0.1}, out=0, check=lambda o: np.isfinite(o).all())

# NN layers
G("FullyConnected",
  {"data": randn(2, 3), "weight": randn(4, 3), "bias": randn(4)},
  {"num_hidden": 4})
G("Convolution",
  {"data": randn(1, 2, 4, 4), "weight": randn(2, 2, 2, 2),
   "bias": randn(2)}, {"kernel": (2, 2), "num_filter": 2})
G("Deconvolution",
  {"data": randn(1, 2, 3, 3), "weight": randn(2, 2, 2, 2),
   "bias": randn(2)}, {"kernel": (2, 2), "num_filter": 2})
G("Pooling", {"data": distinct(1, 2, 4, 4)},
  {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"},
  id_suffix="max")
# OVERLAPPING windows (kernel > stride — the ResNet stem geometry):
# one input position feeds several windows, whose cotangents
# select_and_scatter_add must sum there.  eps=1e-2: pooling
# is piecewise linear (distinct() separates values by 0.37, no argmax
# flip) and a 1e-3 central difference of the ~1e2-magnitude f32 loss
# is quantization-limited (ULP ~1.5e-5 vs a ~3e-4 numerator).
# R-state save/restore: keep the shared stream unchanged for every
# later case (their data — and borderline lowp tolerances — must not
# depend on cases inserted above them)
_R_STATE = R.get_state()
G("Pooling", {"data": distinct(1, 2, 5, 5)},
  {"kernel": (3, 3), "stride": (2, 2), "pad": (1, 1),
   "pool_type": "max"}, id_suffix="max-overlap", eps=1e-2)
G("Pooling", {"data": distinct(1, 5, 5, 2)},
  {"kernel": (3, 3), "stride": (2, 2), "pool_type": "max",
   "layout": "NHWC"}, id_suffix="max-nhwc", eps=1e-2)
R.set_state(_R_STATE)
G("Pooling", {"data": randn(1, 2, 4, 4)},
  {"kernel": (2, 2), "stride": (2, 2), "pool_type": "avg"},
  id_suffix="avg")
for act in ["relu", "sigmoid", "tanh", "softrelu", "gelu", "silu"]:
    G("Activation", {"data": nz(2, 3)}, {"act_type": act}, id_suffix=act)
G("LeakyReLU", {"data": nz(2, 3)}, {"act_type": "leaky", "slope": 0.1})
G("Dropout", {"data": randn(2, 3)}, {"p": 0.0})
F("Dropout", {"data": pos(5, 5)}, {"p": 0.5}, id_suffix="eval-identity",
  fwd=lambda data: data)
G("BatchNorm",
  {"data": randn(2, 3, 2, 2), "gamma": pos(3), "beta": randn(3)},
  aux={"moving_mean": np.zeros(3, "f"), "moving_var": np.ones(3, "f")},
  rtol=8e-2, atol=2e-2)
# channels-last (the fused ResNet path's axis=3): exercises the
# byte-diet fused BN backward over NHWC reduce axes (R-state
# save/restore as above: later cases keep their original data)
_R_STATE = R.get_state()
G("BatchNorm",
  {"data": randn(2, 2, 2, 3), "gamma": pos(3), "beta": randn(3)},
  {"axis": 3},
  aux={"moving_mean": np.zeros(3, "f"), "moving_var": np.ones(3, "f")},
  rtol=8e-2, atol=2e-2, id_suffix="nhwc")
R.set_state(_R_STATE)
G("InstanceNorm",
  {"data": randn(2, 3, 4, 4), "gamma": pos(3), "beta": randn(3)},
  rtol=8e-2, atol=2e-2)
G("LayerNorm",
  {"data": randn(2, 6), "gamma": pos(6), "beta": randn(6)},
  rtol=8e-2, atol=2e-2)
G("RMSNorm", {"data": randn(2, 6), "gamma": pos(6)}, rtol=8e-2, atol=2e-2)
G("RotaryEmbedding", {"data": randn(1, 3, 2, 8)},
  {"base": 100.0, "offset": 4, "dim": 4})
# expert layer: the router's choice is a step function of its inputs, so
# its contract is checked forward; the experts' part is smooth in the
# data, the router's weights and the three leaves
F("MoERouter", {"data": distinct(3, 4) - 1.0, "weight": distinct(6, 4) - 1.0},
  {"num_experts": 6, "top_k": 2, "scale": 1.8},
  aux={"bias": np.zeros(6, "f")}, out=1,
  fwd=lambda data, weight: (lambda s: 1.8 * np.sort(s, 1)[:, :-3:-1]
                            / np.sort(s, 1)[:, -2:].sum(1, keepdims=True))(
      1 / (1 + np.exp(-data @ weight.T))))
G("MoEExperts",
  {"data": randn(5, 4), "expert": np.array(
      [[0, 3], [1, 2], [5, 1], [2, 4], [1, 0]], "f"),
   "weight": pos(5, 2), "gate_weight": randn(2, 3, 4) * 0.5,
   "up_weight": randn(2, 3, 4) * 0.5, "down_weight": randn(2, 4, 3) * 0.5},
  {"num_experts": 6, "experts_held": 2, "first_expert": 1, "num_hidden": 3},
  aux={"count": np.zeros(6, "f")},
  grad_nodes=["data", "weight", "gate_weight", "up_weight", "down_weight"],
  rtol=8e-2, atol=2e-2)
G("L2Normalization", {"data": nz(2, 6)})
G("LRN", {"data": pos(1, 3, 3, 3)}, {"nsize": 3}, rtol=8e-2, atol=2e-2)
G("SoftmaxActivation", {"data": randn(2, 4)})
G("UpSampling", {"data": randn(1, 2, 3, 3)},
  {"scale": 2, "sample_type": "nearest", "num_args": 1})
G("RNN",
  {"data": randn(2, 2, 3), "parameters": randn(24) * 0.3,
   "state": randn(1, 2, 3)},
  {"state_size": 3, "num_layers": 1, "mode": "rnn_tanh"},
  out=0, rtol=8e-2, atol=2e-2)

# sequence ops (T, N, C)
G("SequenceLast", {"data": randn(3, 2, 4)})
G("SequenceReverse", {"data": randn(3, 2, 4)})
G("SequenceMask", {"data": randn(3, 2, 4)})

# losses: custom backward semantics — forward contracts here (their
# backward rules are asserted in test_operator.py)
_sm = lambda z: np.exp(z - z.max(1, keepdims=True)) / \
    np.exp(z - z.max(1, keepdims=True)).sum(1, keepdims=True)
F("SoftmaxOutput", {"data": randn(3, 4), "label": ints(4, 3)},
  fwd=lambda data, label: _sm(data))
F("LinearRegressionOutput", {"data": randn(3, 2), "label": randn(3, 2)},
  fwd=lambda data, label: data)
F("LogisticRegressionOutput", {"data": randn(3, 2), "label": randn(3, 2)},
  fwd=lambda data, label: 1 / (1 + np.exp(-data)))
F("MAERegressionOutput", {"data": randn(3, 2), "label": randn(3, 2)},
  fwd=lambda data, label: data)
F("SVMOutput", {"data": randn(3, 4), "label": ints(4, 3)},
  fwd=lambda data, label: data)
F("MakeLoss", {"data": pos(3, 2)}, fwd=lambda data: data)
F("WarpCTC", {"data": randn(8, 5), "label": ints(4, 2, 3)},
  {"label_length": 3, "input_length": 4},
  fwd=lambda data, label: _sm(data))   # fwd = softmax; CTC grad is
                                       # enumeration-checked in test_ctc.py
F("softmax_cross_entropy", {"data": randn(3, 4), "label": ints(4, 3)},
  fwd=lambda data, label:
  np.array([-np.log(_sm(data))[np.arange(3), label.astype(int)].sum()],
           dtype="f"))
F("IdentityAttachKLSparseReg", {"data": unit(3, 4) * 0.4 + 0.5},
  aux={"moving_avg": np.full(1, 0.5, "f")}, fwd=lambda data: data)

# vision / contrib
G("GridGenerator", {"data": randn(2, 6) * 0.1},
  {"transform_type": "affine", "target_shape": (3, 3)})
G("SpatialTransformer",
  {"data": randn(1, 2, 4, 4), "loc": randn(1, 6) * 0.05},
  {"target_shape": (4, 4), "transform_type": "affine",
   "sampler_type": "bilinear"}, rtol=8e-2, atol=2e-2)
G("BilinearSampler",
  {"data": randn(1, 2, 4, 4),
   "grid": unit(1, 2, 3, 3) * 0.73},
  rtol=8e-2, atol=2e-2)
G("ROIPooling",
  {"data": distinct(1, 2, 4, 4),
   "rois": np.array([[0, 0, 0, 3, 3]], "f")},
  {"pooled_size": (2, 2), "spatial_scale": 1.0},
  grad_nodes=["data"], rtol=8e-2, atol=2e-2)
G("Correlation",
  {"data1": randn(1, 2, 4, 4), "data2": randn(1, 2, 4, 4)},
  {"kernel_size": 1, "max_displacement": 1, "stride1": 1, "stride2": 1},
  rtol=8e-2, atol=2e-2)
F("count_sketch",
  {"data": randn(2, 4), "h": ints(2, 4), "s": np.sign(randn(4))},
  {"out_dim": 2}, check=lambda o: o.shape == (2, 2))
F("fft", {"data": randn(2, 4)}, check=lambda o: o.shape == (2, 8))
F("ifft", {"data": randn(2, 8)}, check=lambda o: o.shape == (2, 4))
F("MultiBoxPrior", {"data": randn(1, 2, 4, 4)},
  {"sizes": "(0.5,)", "ratios": "(1.0,)"},
  check=lambda o: np.isfinite(o).all())
F("MultiBoxTarget",
  {"anchor": np.array([[[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]], "f"),
   "label": np.array([[[0, 0.1, 0.1, 0.4, 0.4]]], "f"),
   "cls_pred": pos(1, 2, 2)},
  out=0, check=lambda o: np.isfinite(o).all())
F("MultiBoxDetection",
  {"cls_prob": pos(1, 2, 2), "loc_pred": randn(1, 8),
   "anchor": np.array([[[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]], "f")},
  check=lambda o: np.isfinite(o).all())
F("Proposal",
  {"cls_prob": pos(1, 2, 4, 4), "bbox_pred": randn(1, 4, 4, 4) * 0.1,
   "im_info": np.array([[32, 32, 1.0]], "f")},
  {"feature_stride": 8, "scales": "(8,)", "ratios": "(1.0,)",
   "rpn_pre_nms_top_n": 6, "rpn_post_nms_top_n": 4},
  check=lambda o: np.isfinite(o).all())
F("_contrib_DotProductAttention",
  {"query": randn(2, 3, 2, 4), "key": randn(2, 3, 2, 4),
   "value": randn(2, 3, 2, 4)},
  check=lambda o: o.shape == (2, 3, 2, 4))
# a state along the sequence: two chunks of 16 positions; the gate is a
# log decay and stays below 0
G("_contrib_GatedDeltaRule",
  {"query": unit(1, 32, 2, 4), "key": unit(1, 32, 2, 4),
   "value": randn(1, 32, 2, 3), "gate": -pos(1, 32, 2, 4) * 0.3,
   "beta": R.uniform(0.2, 0.8, (1, 32, 2)).astype("f")},
  {"chunk": 16}, rtol=8e-2, atol=2e-2)
# and with a gate bound declared and held: the diagonal blocks as the
# product, two chunks of two blocks
G("_contrib_GatedDeltaRule",
  {"query": unit(1, 64, 2, 4), "key": unit(1, 64, 2, 4),
   "value": randn(1, 64, 2, 3),
   "gate": -R.uniform(0.0, 3.0, (1, 64, 2, 4)).astype("f"),
   "beta": R.uniform(0.2, 0.8, (1, 64, 2)).astype("f")},
  {"chunk": 32, "lower_bound": -3.0}, rtol=8e-2, atol=2e-2,
  id_suffix="bounded")
G("_contrib_ShortConv", {"data": randn(2, 6, 3), "weight": randn(3, 4)})
# a loss a row, its reverse mode its own; and the exit distribution of
# three gates, whose rows sum to 1
G("_contrib_RowCrossEntropy", {"data": randn(3, 5), "label": ints(5, 3)},
  grad_nodes=["data"])
G("_contrib_ExitDistribution",
  {"data": R.uniform(0.2, 0.8, (3, 3)).astype("f")},
  aux={"pass_share": np.zeros(4, "f")})

# differentiable aliases exercise the alias path end-to-end
_ALIAS_GRADS = {
    "elemwise_add": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "elemwise_sub": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "elemwise_mul": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "_add": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "_sub": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "_Plus": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "_Minus": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "_Mul": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "_grad_add": {"lhs": randn(2, 3), "rhs": randn(2, 3)},
    "_copy": {"data": randn(2, 3)},
    "flatten": {"data": randn(2, 3, 2)},
    "sum_axis": {"data": randn(2, 3)},
    "max_axis": {"data": distinct(2, 3)},
    "min_axis": {"data": distinct(2, 3)},
}
for name, loc in _ALIAS_GRADS.items():
    G(name, dict(loc))
G("elemwise_div", {"lhs": randn(2, 3), "rhs": pos(2, 3)})
G("_Div", {"lhs": randn(2, 3), "rhs": pos(2, 3)})
G("reshape", {"data": randn(2, 3)}, {"shape": (3, 2)})
G("swapaxes", {"data": randn(2, 3, 2)}, {"dim1": 0, "dim2": 2})
G("flip", {"data": randn(2, 3)}, {"axis": 0})
G("cast", {"data": randn(2, 3)}, {"dtype": "float32"})
G("concat", {"a": randn(2, 2), "b": randn(2, 3)}, {"num_args": 2, "dim": 1})
G("ElementWiseSum", {"a": randn(2, 3), "b": randn(2, 3)}, {"num_args": 2})
G("_sum_n", {"a": randn(2, 3), "b": randn(2, 3)}, {"num_args": 2})
G("split", {"data": randn(2, 4)}, {"num_outputs": 2}, out=0)
G("pad", {"data": randn(1, 2, 3, 3)},
  {"pad_width": (0, 0, 0, 0, 1, 1, 1, 1), "mode": "constant"})
G("broadcast_axes", {"data": randn(1, 3)}, {"axis": 0, "size": 2})
G("Convolution_v1",
  {"data": randn(1, 2, 4, 4), "weight": randn(2, 2, 2, 2),
   "bias": randn(2)}, {"kernel": (2, 2), "num_filter": 2})
G("Pooling_v1", {"data": randn(1, 2, 4, 4)},
  {"kernel": (2, 2), "stride": (2, 2), "pool_type": "avg"})
F("stop_gradient", {"data": randn(2, 3)}, fwd=lambda data: data)
F("zeros", {}, {"shape": (2, 3)}, fwd=lambda: np.zeros((2, 3), "f"))
F("ones", {}, {"shape": (2, 3)}, fwd=lambda: np.ones((2, 3), "f"))
F("full", {}, {"shape": (2, 3), "value": 1.5},
  fwd=lambda: np.full((2, 3), 1.5, "f"))
F("Softmax", {"data": randn(3, 4), "label": ints(4, 3)},
  fwd=lambda data, label: _sm(data))
for alias in ["uniform", "random_uniform", "_random_uniform"]:
    _sampler(alias, {"low": 0.0, "high": 1.0},
             lambda o: (o >= 0).all() and (o < 1).all())
for alias in ["normal", "random_normal", "_random_normal"]:
    _sampler(alias, {"loc": 0.0, "scale": 1.0},
             lambda o: abs(o.mean()) < 0.1)
_sampler("exponential", {"lam": 1.0}, lambda o: (o >= 0).all())
_sampler("random_exponential", {"lam": 1.0}, lambda o: (o >= 0).all())
_sampler("random_gamma", {"alpha": 2.0, "beta": 1.0},
         lambda o: (o > 0).all())
_sampler("poisson", {"lam": 2.0}, lambda o: (o >= 0).all())
_sampler("random_poisson", {"lam": 2.0}, lambda o: (o >= 0).all())
_sampler("negative_binomial", {"k": 3, "p": 0.5}, lambda o: (o >= 0).all())
_sampler("random_negative_binomial", {"k": 3, "p": 0.5},
         lambda o: (o >= 0).all())
_sampler("generalized_negative_binomial", {"mu": 2.0, "alpha": 0.5},
         lambda o: (o >= 0).all())
_sampler("random_generalized_negative_binomial", {"mu": 2.0, "alpha": 0.5},
         lambda o: (o >= 0).all())
# contrib aliases
F("_contrib_fft", {"data": randn(2, 4)}, check=lambda o: o.shape == (2, 8))
F("_contrib_ifft", {"data": randn(2, 8)},
  check=lambda o: o.shape == (2, 4))
F("_contrib_count_sketch",
  {"data": randn(2, 4), "h": ints(2, 4), "s": np.sign(randn(4))},
  {"out_dim": 2}, check=lambda o: o.shape == (2, 2))
F("_contrib_MultiBoxPrior", {"data": randn(1, 2, 4, 4)},
  {"sizes": "(0.5,)", "ratios": "(1.0,)"},
  check=lambda o: np.isfinite(o).all())
F("_contrib_MultiBoxTarget",
  {"anchor": np.array([[[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]], "f"),
   "label": np.array([[[0, 0.1, 0.1, 0.4, 0.4]]], "f"),
   "cls_pred": pos(1, 2, 2)},
  out=0, check=lambda o: np.isfinite(o).all())
F("_contrib_MultiBoxDetection",
  {"cls_prob": pos(1, 2, 2), "loc_pred": randn(1, 8),
   "anchor": np.array([[[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]], "f")},
  check=lambda o: np.isfinite(o).all())
F("_contrib_Proposal",
  {"cls_prob": pos(1, 2, 4, 4), "bbox_pred": randn(1, 4, 4, 4) * 0.1,
   "im_info": np.array([[32, 32, 1.0]], "f")},
  {"feature_stride": 8, "scales": "(8,)", "ratios": "(1.0,)",
   "rpn_pre_nms_top_n": 6, "rpn_post_nms_top_n": 4},
  check=lambda o: np.isfinite(o).all())


# ======================================================================
def _build_symbol(case):
    fn = getattr(mx.symbol, case["op"])
    variables = [mx.sym.Variable(n) for n in case["loc"]]
    kwargs = dict(case["params"])
    aux = case.get("aux")
    if aux:
        # pin the node name so auxiliary state names are deterministic
        kwargs["name"] = "opx"
        aux = {"opx_" + k: v for k, v in aux.items()}
    sym = fn(*variables, **kwargs)
    if case.get("out") is not None:
        sym = sym[case["out"]]
    return sym, aux


@pytest.mark.parametrize("case", CASES, ids=[c["id"] for c in CASES])
def test_op_case(case):
    if case["kind"] == "imp":
        case["run"]()
        return
    sym, aux = _build_symbol(case)
    if case["kind"] == "grad":
        check_numeric_gradient(
            sym, dict(case["loc"]), aux_states=aux,
            numeric_eps=case["eps"], rtol=case["rtol"], atol=case["atol"],
            grad_nodes=case["grad_nodes"])
        return
    # forward contract
    args = [case["loc"][k] for k in case["loc"]]
    if case.get("fwd") is not None:
        expected = case["fwd"](*args)
        if not isinstance(expected, (list, tuple)):
            expected = [expected]
        check_symbolic_forward(sym, dict(case["loc"]), expected,
                               rtol=1e-3, atol=1e-4, aux_states=aux)
    else:
        exe = sym.bind(mx.current_context(),
                       args={k: mx.nd.array(v)
                             for k, v in case["loc"].items()},
                       aux_states={k: mx.nd.array(v)
                                   for k, v in (aux or {}).items()} or None)
        exe.forward(is_train=False)
        out = exe.outputs[0].asnumpy()
        assert case["check"](out), "%s forward contract failed" % case["id"]


def test_registry_fully_covered():
    """Every registered op (and alias) must appear in the sweep.
    Dynamically materialized custom entries — sym.Custom's Custom[...]
    and the legacy PythonOp families _Native[...]/_NDArray[...]/
    _Python[...] — are the one exclusion: they exist only after user
    code registers them (other tests may have done so in-process)."""
    dynamic = ("Custom[", "_Native[", "_NDArray[", "_Python[")
    everything = {n for n in set(_registry._REGISTRY) |
                  set(_registry._ALIASES)
                  if not n.startswith(dynamic)}
    missing = everything - _SEEN
    assert not missing, "ops with no sweep case: %s" % sorted(missing)


def test_sweep_report(capsys):
    grads = {c["op"] for c in CASES if c["kind"] == "grad"}
    fwds = {c["op"] for c in CASES if c["kind"] == "fwd"} - grads
    n_reg = len(set(_registry._REGISTRY))
    with capsys.disabled():
        print("\nOP SWEEP: %d registered ops + %d aliases; "
              "%d names gradient-checked, %d forward-checked" %
              (n_reg, len(_registry._ALIASES), len(grads), len(fwds)))
    assert len(grads) >= 150, "gradient-checked op names below target"


# ======================================================================
# Reduced-precision tier (the reference crossed dtypes with
# check_consistency's fp16-vs-fp32 executor pairs, test_utils.py:676).
# Every gradient-checked op runs a bf16 forward-consistency check
# against its own f32 forward; the flagship-model core additionally
# runs f16.  Integral-valued inputs (indices, labels, masks) stay f32 —
# bf16 would corrupt ids above 256 and the contract under test is the
# op's float arithmetic, not its index plumbing.

# ops whose grad-case CONTRACT cannot run reduced (reason required):
LOWP_SKIP = {
    # output is integer-exact positions; bf16 quantizes the .5-spaced
    # input grid used by the case into ties
    "argmax_channel": "tie-breaking contract needs exact input grid",
}

# flagship core (ResNet/transformer hot path): must hold in f16 too
F16_CORE = {
    "Convolution", "Deconvolution", "FullyConnected", "BatchNorm",
    "Activation", "Pooling", "SoftmaxOutput", "softmax", "relu",
    "sigmoid", "tanh", "exp", "log", "sqrt", "square", "dot",
    "batch_dot", "broadcast_add", "broadcast_mul", "broadcast_sub",
    "broadcast_div", "elemwise_add", "_plus", "_minus", "_mul", "_div",
    "sum", "mean", "max", "min", "transpose", "Reshape", "Flatten",
    "Concat", "slice", "SliceChannel", "Embedding", "LayerNorm",
    "Dropout", "LeakyReLU", "clip", "abs", "negative",
}

def _lowp_eligible(c):
    """grad cases + deterministic fwd cases (samplers re-key between
    the two executors, so rng ops can't be consistency-compared)."""
    if c["kind"] == "imp" or c["op"] in LOWP_SKIP:
        return False
    if c["kind"] == "fwd":
        try:
            if _registry.get(c["op"]).uses_rng:
                return False
        except Exception:
            return False
    return True


_GRAD_OPS_SEEN = set()
_LOWP_CASES = []
for _c in sorted(CASES, key=lambda c: c["kind"] != "grad"):
    if not _lowp_eligible(_c):
        continue
    if _c["op"] in _GRAD_OPS_SEEN:
        continue                      # one dtype crossing per op name
    _GRAD_OPS_SEEN.add(_c["op"])
    _LOWP_CASES.append((_c, "bfloat16"))
    if _c["op"] in F16_CORE:
        _LOWP_CASES.append((_c, "float16"))


def _forward_in_dtype(case, dtype):
    sym, aux = _build_symbol(case)

    def cast(v):
        v = np.asarray(v, "f")
        arr = mx.nd.array(v)
        if dtype != "float32" and v.dtype.kind == "f" \
                and not np.all(v == np.round(v)):
            return arr.astype(dtype)
        return arr
    args = {k: cast(v) for k, v in case["loc"].items()}
    auxs = {k: cast(v) for k, v in (aux or {}).items()} or None
    exe = sym.bind(mx.current_context(), args=args, aux_states=auxs)
    exe.forward(is_train=False)
    return [o.asnumpy().astype(np.float32) for o in exe.outputs]


@pytest.mark.parametrize(
    "case,dtype", _LOWP_CASES,
    ids=["%s::%s" % (c["id"], "half" if d == "float16" else "bf16")
         for c, d in _LOWP_CASES])
def test_op_lowp_forward(case, dtype):
    """Reduced-precision forward tracks the op's own f32 forward within
    representation tolerance (~2^-8 for bf16, ~2^-10 for f16, headroom
    for accumulation)."""
    ref = _forward_in_dtype(case, "float32")
    low = _forward_in_dtype(case, dtype)
    rtol = 0.06 if dtype == "bfloat16" else 0.02
    for a, b in zip(ref, low):
        scale = max(float(np.abs(a).max()), 1e-2)
        np.testing.assert_allclose(
            b, a, rtol=rtol, atol=rtol * scale,
            err_msg="%s diverges in %s" % (case["id"], dtype))


def test_lowp_report(capsys):
    bf16 = {c["op"] for c, d in _LOWP_CASES if d == "bfloat16"}
    f16 = {c["op"] for c, d in _LOWP_CASES if d == "float16"}
    with capsys.disabled():
        print("\nLOW-PRECISION SWEEP: %d ops bf16 forward-checked, "
              "%d flagship-core ops also f16; %d skipped (%s)" %
              (len(bf16), len(f16), len(LOWP_SKIP),
               ", ".join(sorted(LOWP_SKIP))))
    assert len(bf16) >= 140
    missing_core = {n for n in F16_CORE
                    if n in {c["op"] for c in CASES}} - f16
    assert not missing_core, missing_core


# ----------------------------------------------------------------------
# Reduced-precision BACKWARD tier: the fused trainer computes gradients
# in bf16 (Trainer compute_dtype), so the flagship-core ops' bf16
# backward must track their own f32 backward within representation
# tolerance — the gradient half of the reference's check_consistency
# dtype crossing (test_utils.py:676-760), which this sweep previously
# exercised forward-only.

def _bwd_eligible(c):
    if c["kind"] != "grad" or c["op"] not in F16_CORE:
        return False
    if c["op"] in LOWP_SKIP:
        return False
    try:
        if _registry.get(c["op"]).uses_rng:
            return False      # the two executors would draw new keys
    except Exception:
        return False
    return True


_BWD_OPS_SEEN = set()
_BWD_CASES = []
for _c in CASES:
    if _bwd_eligible(_c) and _c["op"] not in _BWD_OPS_SEEN:
        _BWD_OPS_SEEN.add(_c["op"])
        _BWD_CASES.append(_c)


def _grads_in_dtype(case, dtype):
    """Bind in ``dtype``, run fwd(train)+bwd with all-ones head
    gradients, return the f32 view of every requested input grad."""
    sym, aux = _build_symbol(case)

    def cast(v):
        v = np.asarray(v, "f")
        arr = mx.nd.array(v)
        if dtype != "float32" and not np.all(v == np.round(v)):
            return arr.astype(dtype)
        return arr

    args = {k: cast(v) for k, v in case["loc"].items()}
    targets = list(case["grad_nodes"] or case["loc"])
    grads = {k: mx.nd.zeros(np.asarray(case["loc"][k]).shape,
                            dtype=args[k].dtype) for k in targets}
    auxs = {k: cast(v) for k, v in (aux or {}).items()} or None
    exe = sym.bind(mx.current_context(), args=args, args_grad=grads,
                   aux_states=auxs)
    exe.forward(is_train=True)
    # deterministic NON-uniform head gradients: a constant cotangent is
    # degenerate for normalizing ops (softmax/BN jacobians annihilate
    # it, leaving only rounding noise to compare)
    hg = np.random.RandomState(11)
    exe.backward([mx.nd.array(
        hg.normal(0, 1, o.shape).astype("f")).astype(o.dtype)
        for o in exe.outputs])
    return {k: grads[k].asnumpy().astype(np.float32) for k in targets}


@pytest.mark.parametrize("case", _BWD_CASES,
                         ids=[c["id"] + "::bf16bwd" for c in _BWD_CASES])
def test_op_lowp_backward(case):
    """bf16 input gradients track the op's own f32 gradients within
    bf16 representation tolerance (~2^-8, headroom for accumulation)."""
    ref = _grads_in_dtype(case, "float32")
    low = _grads_in_dtype(case, "bfloat16")
    for k in ref:
        scale = max(float(np.abs(ref[k]).max()), 1e-2)
        np.testing.assert_allclose(
            low[k], ref[k], rtol=0.08, atol=0.08 * scale,
            err_msg="%s: bf16 backward diverges for input %r"
                    % (case["id"], k))


def test_lowp_backward_report(capsys):
    ops = {c["op"] for c in _BWD_CASES}
    with capsys.disabled():
        print("\nLOW-PRECISION BACKWARD SWEEP: %d flagship-core ops "
              "bf16-gradient-checked against f32" % len(ops))
    core_with_grad_cases = {c["op"] for c in CASES
                            if c["kind"] == "grad"} & F16_CORE
    missing = {o for o in core_with_grad_cases
               if o not in ops and o not in LOWP_SKIP
               and not _registry.get(o).uses_rng}
    assert not missing, "core ops missing bf16 bwd coverage: %s" % missing
    assert len(ops) >= 25, len(ops)
