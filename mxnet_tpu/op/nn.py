"""Neural-network layer ops.

Reference: the per-op triplets under ``src/operator/`` (SURVEY §2.2) —
FullyConnected (``fully_connected-inl.h:47-121``), Convolution, Pooling,
BatchNorm, Dropout, Activation, the loss/output ops
(``softmax_output-inl.h``, ``regression_output-inl.h``), sequence ops, etc.
TPU-first choices:
  * convs/matmuls go through ``lax.conv_general_dilated`` / ``lax.dot`` so
    XLA tiles them onto the MXU; bf16 inputs accumulate in f32.
  * mode-dependent layers (BatchNorm/Dropout) branch on the *static*
    ``ctx.is_train`` flag — two compiled programs, no runtime flag tensor.
  * output ops (SoftmaxOutput & friends) use ``jax.custom_vjp`` to reproduce
    the reference's "loss layers inject their own gradient" contract.
  * BatchNorm's moving stats are explicit aux inputs/outputs (functional
    equivalent of ``ListAuxiliaryStates``).
"""
from __future__ import annotations

from functools import partial
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from . import bytediet as _bd
from .registry import Param, register, alias


def _acc(dt):
    # bf16 matmuls/convs accumulate in f32 on the MXU natively; asking for
    # preferred_element_type=f32 breaks lax's conv transpose rule under
    # vjp (f32 cotangent vs bf16 operand), so never request promotion.
    return None


# ----------------------------------------------------------------------
# FullyConnected
@register("FullyConnected",
          params_spec=(Param("num_hidden", int, required=True),
                       Param("no_bias", bool, False),
                       Param("flatten", bool, True)),
          input_names=lambda p: ["data", "weight"] + ([] if p.get("no_bias") else ["bias"]),
          hint="fullyconnected")
def _fully_connected(p, c, data, weight, bias=None):
    if data.ndim > 2:
        data = data.reshape((data.shape[0], -1))
    out = lax.dot(data, weight.T, preferred_element_type=_acc(data.dtype))
    if out.dtype != data.dtype:
        out = out.astype(data.dtype)
    if bias is not None:
        out = out + bias
    return out


def _fc_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    in_dim = int(np.prod(dshape[1:]))
    shapes = [tuple(dshape), (p["num_hidden"], in_dim)]
    if not p["no_bias"]:
        shapes.append((p["num_hidden"],))
    return shapes, [(dshape[0], p["num_hidden"])], []


# ----------------------------------------------------------------------
# Convolution / Deconvolution
def _conv_spec():
    return (Param("kernel", "shape", required=True),
            Param("stride", "shape", None),
            Param("dilate", "shape", None),
            Param("pad", "shape", None),
            Param("num_filter", int, required=True),
            Param("num_group", int, 1),
            Param("workspace", int, 1024),
            Param("no_bias", bool, False),
            Param("cudnn_tune", str, None),
            Param("cudnn_off", bool, False),
            Param("layout", str, None))


def _conv_tuple(v, nd, default=1):
    if v is None:
        return (default,) * nd
    return tuple(v)


@register("Convolution", params_spec=_conv_spec(),
          input_names=lambda p: ["data", "weight"] + ([] if p.get("no_bias") else ["bias"]),
          hint="convolution")
def _convolution(p, c, data, weight, bias=None):
    nd = len(p["kernel"])
    stride = _conv_tuple(p["stride"], nd)
    dilate = _conv_tuple(p["dilate"], nd)
    pad = _conv_tuple(p["pad"], nd, 0)
    channels_last = _channels_last(p.get("layout"), nd)
    dn = lax.conv_dimension_numbers(
        data.shape, weight.shape,
        _conv_dimnums(nd, channels_last))
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(q, q) for q in pad], rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=p["num_group"],
        preferred_element_type=_acc(data.dtype))
    if out.dtype != data.dtype:
        out = out.astype(data.dtype)
    if bias is not None:
        bshape = ((1,) * (nd + 1) + (-1,)) if channels_last \
            else ((1, -1) + (1,) * nd)
        out = out + bias.reshape(bshape)
    return out


def _channels_last(layout, nd):
    """The reference's ``layout`` param ("NCHW"/"NHWC"/"NCW"/"NWC"/
    "NCDHW"/"NDHWC").  Channels-last is the TPU-preferred layout: lanes
    map to channels, so XLA tiles the conv onto the MXU without the
    internal relayout-transposes NCHW needs."""
    if layout is None:
        return False
    layout = layout.upper()
    if layout in ("NCW", "NCHW", "NCDHW"):
        return False
    if layout in ("NWC", "NHWC", "NDHWC"):
        return True
    raise MXNetError("unsupported convolution layout %s" % layout)


def _conv_dimnums(nd, channels_last=False):
    spatial = "DHW"[-nd:] if nd <= 3 else None
    if spatial is None:
        raise MXNetError("Convolution supports 1-3 spatial dims")
    if channels_last:
        # data N..C, weight ..IO (HWIO): the native TPU convolution layout
        return ("N" + spatial + "C", spatial + "IO", "N" + spatial + "C")
    # NCHW/OIHW layout family (the reference's only CPU layout)
    return ("NC" + spatial, "OI" + spatial, "NC" + spatial)


def _conv_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    nd = len(p["kernel"])
    channels_last = _channels_last(p.get("layout"), nd)
    cin = dshape[-1] if channels_last else dshape[1]
    if channels_last:
        wshape = tuple(p["kernel"]) + (cin // p["num_group"],
                                       p["num_filter"])
        in_sp = dshape[1:-1]
    else:
        wshape = (p["num_filter"], cin // p["num_group"]) + tuple(p["kernel"])
        in_sp = dshape[2:]
    stride = _conv_tuple(p["stride"], nd)
    dilate = _conv_tuple(p["dilate"], nd)
    pad = _conv_tuple(p["pad"], nd, 0)
    out_sp = tuple(
        (in_sp[i] + 2 * pad[i] - (dilate[i] * (p["kernel"][i] - 1) + 1))
        // stride[i] + 1 for i in range(nd))
    shapes = [tuple(dshape), wshape]
    if not p["no_bias"]:
        shapes.append((p["num_filter"],))
    out = (dshape[0],) + out_sp + (p["num_filter"],) if channels_last \
        else (dshape[0], p["num_filter"]) + out_sp
    return shapes, [out], []


@register("Deconvolution",
          params_spec=_conv_spec() + (Param("adj", "shape", None),
                                      Param("target_shape", "shape", None)),
          input_names=lambda p: ["data", "weight"] + ([] if p.get("no_bias") else ["bias"]),
          hint="deconvolution")
def _deconvolution(p, c, data, weight, bias=None):
    # transposed conv as lhs-dilated conv (supports groups + kernel dilation,
    # which lax.conv_transpose does not).  weight layout (Cin, Cout/g, *k)
    # mirrors the reference (deconv reuses Convolution's weight transposed).
    nd = len(p["kernel"])
    channels_last = _channels_last(p.get("layout"), nd)
    if channels_last:
        # keep the reference (Cin, Cout/g, *k) weight; relayout the data
        # around the NCHW kernel path (XLA folds the moveaxes into its
        # layout assignment)
        data = jnp.moveaxis(data, -1, 1)
    g = p["num_group"]
    stride = _conv_tuple(p["stride"], nd)
    dilate = _conv_tuple(p["dilate"], nd)
    pad = _conv_tuple(p["pad"], nd, 0)
    adj = _conv_tuple(p["adj"], nd, 0)
    kernel = tuple(p["kernel"])
    cin = weight.shape[0]
    cout_per_g = weight.shape[1]
    # (Cin, Cout/g, *k) -> (g, Cin/g, Cout/g, *k) -> (Cout, Cin/g, *k), flipped
    w = weight.reshape((g, cin // g, cout_per_g) + kernel)
    w = jnp.swapaxes(w, 1, 2).reshape((g * cout_per_g, cin // g) + kernel)
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    eff_k = tuple(dilate[i] * (kernel[i] - 1) + 1 for i in range(nd))
    padding = [(eff_k[i] - 1 - pad[i], eff_k[i] - 1 - pad[i] + adj[i])
               for i in range(nd)]
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _conv_dimnums(nd))
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=g, preferred_element_type=_acc(data.dtype))
    if out.dtype != data.dtype:
        out = out.astype(data.dtype)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    if channels_last:
        out = jnp.moveaxis(out, 1, -1)
    return out


def _deconv_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    nd = len(p["kernel"])
    channels_last = _channels_last(p.get("layout"), nd)
    stride = _conv_tuple(p["stride"], nd)
    pad = _conv_tuple(p["pad"], nd, 0)
    adj = _conv_tuple(p["adj"], nd, 0)
    cin = dshape[-1] if channels_last else dshape[1]
    in_sp = dshape[1:-1] if channels_last else dshape[2:]
    wshape = (cin, p["num_filter"] // p["num_group"]) + tuple(p["kernel"])
    out_sp = tuple(stride[i] * (in_sp[i] - 1) + p["kernel"][i]
                   - 2 * pad[i] + adj[i] for i in range(nd))
    shapes = [tuple(dshape), wshape]
    if not p["no_bias"]:
        shapes.append((p["num_filter"],))
    out = (dshape[0],) + out_sp + (p["num_filter"],) if channels_last \
        else (dshape[0], p["num_filter"]) + out_sp
    return shapes, [out], []


# ----------------------------------------------------------------------
# Pooling
@register("Pooling",
          params_spec=(Param("kernel", "shape", required=True),
                       Param("pool_type", str, "max",
                             enum=("max", "avg", "sum")),
                       Param("global_pool", bool, False),
                       Param("pooling_convention", str, "valid",
                             enum=("valid", "full")),
                       Param("stride", "shape", None),
                       Param("pad", "shape", None),
                       Param("layout", str, None),
                       Param("cudnn_off", bool, False)),
          hint="pooling")
def _pooling(p, c, data):
    nd = data.ndim - 2
    channels_last = _channels_last(p.get("layout"), nd)
    sp0 = 1 if channels_last else 2           # first spatial dim index
    spatial = data.shape[sp0:sp0 + nd]
    if p["global_pool"]:
        kernel = spatial
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = tuple(p["kernel"])
        stride = _conv_tuple(p["stride"], nd)
        pad = _conv_tuple(p["pad"], nd, 0)
    lo_hi = []
    for i in range(nd):
        lo = pad[i]
        hi = pad[i]
        if p["pooling_convention"] == "full" and not p["global_pool"]:
            size = spatial[i] + 2 * pad[i] - kernel[i]
            rem = size % stride[i]
            if rem != 0:
                hi += stride[i] - rem  # ceil instead of floor
        lo_hi.append((lo, hi))
    if channels_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        padding = ((0, 0),) + tuple(lo_hi) + ((0, 0),)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        padding = ((0, 0), (0, 0)) + tuple(lo_hi)
    if p["pool_type"] == "max":
        # one formulation for training and evaluation under either
        # dtype policy: autodiff's backward of this reduce_window is
        # XLA's dense select-and-scatter window op (first maximum wins
        # a tie).  An index-map scatter-add in its place cost the v5e a
        # 51M-element sort and a serial scatter (PERF.md, PR 28).
        if jnp.issubdtype(data.dtype, jnp.floating):
            init = np.array(-np.inf, data.dtype)
        else:
            init = np.array(np.iinfo(np.dtype(data.dtype)).min, data.dtype)
        return lax.reduce_window(data, init, lax.max,
                                 window, strides, padding)
    summed = lax.reduce_window(data, np.array(0, data.dtype), lax.add,
                               window, strides, padding)
    if p["pool_type"] == "sum":
        return summed
    # avg: reference divides by full kernel size (count_include_pad style)
    return summed / float(np.prod(kernel))


alias("Pooling_v1", "Pooling")


def _pool_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    nd = len(dshape) - 2
    channels_last = _channels_last(p.get("layout"), nd)

    def assemble(sp):
        if channels_last:
            return (dshape[0],) + tuple(sp) + (dshape[-1],)
        return tuple(dshape[:2]) + tuple(sp)

    spatial = dshape[1:-1] if channels_last else dshape[2:]
    if p["global_pool"]:
        return [tuple(dshape)], [assemble((1,) * nd)], []
    kernel = tuple(p["kernel"])
    stride = _conv_tuple(p["stride"], nd)
    pad = _conv_tuple(p["pad"], nd, 0)
    out_sp = []
    for i in range(nd):
        size = spatial[i] + 2 * pad[i] - kernel[i]
        if p["pooling_convention"] == "full":
            out_sp.append(int(np.ceil(size / stride[i])) + 1)
        else:
            out_sp.append(size // stride[i] + 1)
    return [tuple(dshape)], [assemble(out_sp)], []


# ----------------------------------------------------------------------
# Activations
@register("Activation",
          params_spec=(Param("act_type", str, required=True,
                             enum=("relu", "sigmoid", "tanh", "softrelu",
                                   "gelu", "silu")),),
          hint="activation")
def _activation(p, c, a):
    if p["act_type"] == "relu" and _bd.enabled(c):
        # backward mask from the output (already resident — the next
        # layer's residual) instead of a saved input: op/bytediet.py
        return _bd.relu_save_output(a)
    return {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid,
            "tanh": jnp.tanh, "softrelu": jax.nn.softplus,
            "gelu": jax.nn.gelu, "silu": jax.nn.silu}[p["act_type"]](a)


@register("LayerNorm",
          params_spec=(Param("axis", int, -1),
                       Param("eps", float, 1e-5)),
          input_names=("data", "gamma", "beta"),
          hint="layernorm")
def _layer_norm(p, c, data, gamma, beta):
    """Layer normalization over one axis with learned scale/shift.
    (Transformer-era addition; the reference's nearest op is
    ``InstanceNorm``, ``src/operator/instance_norm-inl.h``.)"""
    ax = p["axis"]
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    normed = (data - mean) * jax.lax.rsqrt(var + p["eps"])
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    return normed * gamma.reshape(shape) + beta.reshape(shape)


@register("RMSNorm",
          params_spec=(Param("axis", int, -1),
                       Param("eps", float, 1e-5)),
          input_names=("data", "gamma"),
          hint="rmsnorm")
def _rms_norm(p, c, data, gamma):
    """x / sqrt(mean(x^2) + eps) * gamma over one axis; the mean of
    squares in float32 whatever the input's type."""
    ax = p["axis"]
    x = data.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=ax, keepdims=True)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    out = x * jax.lax.rsqrt(ms + p["eps"]) \
        * gamma.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


def _yarn_inv_freq(base, n, factor, original, beta_fast, beta_slow):
    """YaRN's inverse frequencies over ``n`` rotated dims (arXiv:2309.00071
    sec. 3.2, as transformers' ``_compute_yarn_parameters`` computes
    them): the dims that turn fewer than ``beta_slow`` times over the
    ``original`` context are interpolated (divided by ``factor``), those
    that turn more than ``beta_fast`` times keep their frequency, and a
    linear ramp blends the dims between, its ends floored and ceiled."""
    def dim_of(turns):
        return n * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), n - 1)
    if low == high:
        high += 0.001
    extrapolated = 1.0 / base ** (jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ramp = jnp.clip((jnp.arange(n // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


@register("RotaryEmbedding",
          params_spec=(Param("base", float, 10000.0),
                       Param("offset", int, 0),
                       Param("dim", int, 0),
                       Param("interleaved", bool, False),
                       Param("rope_type", str, "default",
                             enum=("default", "yarn")),
                       Param("factor", float, 1.0),
                       Param("original_max_position", int, 0),
                       Param("beta_fast", float, 32.0),
                       Param("beta_slow", float, 1.0),
                       # yarn's; 0: 0.1 ln(factor) + 1
                       Param("attention_factor", float, 0.0)),
          hint="rotaryembedding")
def _rotary_embedding(p, c, data):
    """Rotary position embedding on a slice of the head dimension; with
    ``rope_type`` ``yarn`` YaRN's frequencies (arXiv:2309.00071), cos
    and sin times ``attention_factor``.

    ``data`` (batch, time, heads, head_dim); position t is the index
    along axis 1.  Dims ``offset .. offset + dim`` of the last axis
    (``dim`` 0: to the end) are rotated by the angle t * base**(-2i/dim)
    in pairs: dim i with dim i + dim/2 (rotate-half), or with
    ``interleaved`` dim 2i with dim 2i + 1; the other dims pass through.
    ``rope_type`` ``yarn`` takes YaRN's frequencies over the rotated dims
    (``factor``, ``original_max_position``, ``beta_fast``,
    ``beta_slow``: :func:`_yarn_inv_freq`) and scales cos and sin by
    ``attention_factor``; the dims passed through stay as they are.
    Angles and the rotation in float32."""
    lo = p["offset"]
    n = p["dim"] or data.shape[-1] - lo
    half = n // 2
    if p["rope_type"] == "yarn":
        if p["original_max_position"] <= 0 or p["factor"] <= 0:
            raise MXNetError("RotaryEmbedding: yarn needs a positive factor "
                             "and original_max_position")
        inv_freq = _yarn_inv_freq(p["base"], n, p["factor"],
                                  p["original_max_position"],
                                  p["beta_fast"], p["beta_slow"])
        mscale = p["attention_factor"] or 0.1 * math.log(p["factor"]) + 1.0
    else:
        inv_freq = p["base"] ** (-jnp.arange(half, dtype=jnp.float32)
                                 * 2.0 / n)
        mscale = 1.0
    ang = jnp.arange(data.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x = data[..., lo:lo + n].astype(jnp.float32)
    if p["interleaved"]:
        # a pair's partner by two rolls and a select, which keep the
        # lanes where they are (a reshape to (.., dim/2, 2) would not)
        cos, sin = jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)
        even = jnp.arange(n) % 2 == 0
        partner = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                            jnp.roll(x, 1, axis=-1))
        rot = (x * cos + partner * sin).astype(data.dtype)
    else:
        x1, x2 = x[..., :half], x[..., half:]
        rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1).astype(data.dtype)
    return jnp.concatenate([data[..., :lo], rot, data[..., lo + n:]],
                           axis=-1)


@register("LeakyReLU",
          params_spec=(Param("act_type", str, "leaky",
                             enum=("rrelu", "leaky", "prelu", "elu")),
                       Param("slope", float, 0.25),
                       Param("lower_bound", float, 0.125),
                       Param("upper_bound", float, 0.334)),
          input_names=lambda p: ["data", "gamma"] if p.get("act_type") == "prelu" else ["data"],
          uses_rng=True, rng_in_eval=False, hint="leakyrelu")
def _leaky_relu(p, c, data, gamma=None):
    t = p["act_type"]
    if t == "leaky":
        return jnp.where(data > 0, data, p["slope"] * data)
    if t == "elu":
        return jnp.where(data > 0, data, p["slope"] * jnp.expm1(data))
    if t == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data > 0, data, g * data)
    # rrelu: random slope in train, mean slope in test
    if c.is_train:
        slope = jax.random.uniform(c.rng, data.shape, data.dtype,
                                   p["lower_bound"], p["upper_bound"])
    else:
        slope = (p["lower_bound"] + p["upper_bound"]) / 2.0
    return jnp.where(data > 0, data, slope * data)


def _prelu_infer_shape(p, in_shapes):
    if p["act_type"] != "prelu":
        return None
    dshape = in_shapes[0]
    if dshape is None:
        return None
    return [tuple(dshape), (dshape[1],)], [tuple(dshape)], []


@register("SoftmaxActivation",
          params_spec=(Param("mode", str, "instance", enum=("instance", "channel")),),
          hint="softmaxactivation")
def _softmax_activation(p, c, a):
    if p["mode"] == "channel":
        return jax.nn.softmax(a, axis=1)
    return jax.nn.softmax(a.reshape((a.shape[0], -1)), axis=-1).reshape(a.shape)


@register("softmax", params_spec=(Param("axis", int, -1),
                                  Param("temperature", float, None)))
def _softmax(p, c, a):
    t = p["temperature"]
    return jax.nn.softmax(a / t if t else a, axis=p["axis"])


@register("log_softmax", params_spec=(Param("axis", int, -1),
                                      Param("temperature", float, None)))
def _log_softmax(p, c, a):
    t = p["temperature"]
    return jax.nn.log_softmax(a / t if t else a, axis=p["axis"])


# ----------------------------------------------------------------------
# Dropout
@register("Dropout", params_spec=(Param("p", float, 0.5),),
          uses_rng=True, rng_in_eval=False, hint="dropout")
def _dropout(p, c, a):
    if not c.is_train or p["p"] <= 0.0:
        return a
    keep = 1.0 - p["p"]
    mask = jax.random.bernoulli(c.rng, keep, a.shape)
    return jnp.where(mask, a / keep, jnp.zeros((), a.dtype))


# ----------------------------------------------------------------------
# Normalization layers
@register("BatchNorm",
          params_spec=(Param("eps", float, 1e-3),
                       Param("momentum", float, 0.9),
                       Param("fix_gamma", bool, True),
                       Param("use_global_stats", bool, False),
                       Param("output_mean_var", bool, False),
                       Param("axis", int, 1),
                       Param("cudnn_off", bool, False)),
          input_names=("data", "gamma", "beta"),
          aux_names=("moving_mean", "moving_var"),
          num_outputs=lambda p: 3 if p.get("output_mean_var") else 1,
          output_names=lambda p: (["output", "mean", "var"]
                                  if p.get("output_mean_var") else ["output"]),
          hint="batchnorm")
def _batch_norm(p, c, data, gamma, beta, moving_mean, moving_var):
    ax = p["axis"]
    reduce_axes = tuple(i for i in range(data.ndim) if i != ax)
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    if p["fix_gamma"]:
        gamma = lax.stop_gradient(jnp.ones_like(gamma))
    use_batch_stats = c.is_train and not p["use_global_stats"]
    if use_batch_stats:
        # SINGLE-PASS statistics with f32 accumulation: sum(x-c) and
        # sum((x-c)^2) reduce together over ONE read of the activation
        # in its own dtype, widened inside the reduction (jnp.var's
        # (x-mean)^2 formulation needs a second full pass — on a
        # byte-bound step the extra read is the cost).  No widened copy
        # of the activation is written: the fallback below takes it as
        # it is and widens it inside its own branch.
        # Centering on the RUNNING mean c (an aux input — free) keeps
        # the E[.]-mean^2 subtraction benign at steady state, and
        # bytediet.bn_batch_stats guards the catastrophic regime (batch
        # mean far from c: first steps after init, distribution shift)
        # with a scalar |d1|-vs-sqrt(d2) check that falls back to exact
        # two-pass statistics.  (LayerNorm and InstanceNorm keep exact
        # two-pass jnp.var: their reductions stay within one
        # VMEM-resident row, where the second pass costs no HBM
        # traffic.)
        center32 = lax.stop_gradient(moving_mean.astype(jnp.float32))
        mean32, var32 = _bd.bn_batch_stats(data, center32, reduce_axes)
        mean = mean32.astype(data.dtype)
        var = var32.astype(data.dtype)
        m = p["momentum"]
        new_mean = moving_mean * m + lax.stop_gradient(mean) * (1 - m)
        new_var = moving_var * m + lax.stop_gradient(var) * (1 - m)
        if _bd.enabled(c) and not p["output_mean_var"]:
            # byte-diet backward: closed-form BN gradient as one fused
            # elementwise pass (dx = x·A + dy·S + B, per-channel f32
            # A/S/B) instead of autodiff's activation-sized stat-
            # broadcast temporaries; the duplicate statistics here and
            # inside the custom vjp CSE into one pass (op/bytediet.py).
            cfg = (tuple(int(i) for i in reduce_axes), int(ax),
                   float(p["eps"]))
            out = _bd.bn_train_normalize(cfg, data, gamma, beta, center32)
            return out, new_mean, new_var
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    inv = lax.rsqrt(var + p["eps"])
    out = (data - mean.reshape(bshape)) * inv.reshape(bshape) \
        * gamma.reshape(bshape) + beta.reshape(bshape)
    if p["output_mean_var"]:
        return out, mean, var, new_mean, new_var
    return out, new_mean, new_var


def _bn_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None:
        return None
    ch = (dshape[p["axis"]],)
    return [tuple(dshape), ch, ch], \
        ([tuple(dshape), ch, ch] if p["output_mean_var"] else [tuple(dshape)]), \
        [ch, ch]


@register("InstanceNorm", params_spec=(Param("eps", float, 1e-3),),
          input_names=("data", "gamma", "beta"), hint="instancenorm")
def _instance_norm(p, c, data, gamma, beta):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return ((data - mean) * lax.rsqrt(var + p["eps"])
            * gamma.reshape(bshape) + beta.reshape(bshape))


def _in_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None:
        return None
    ch = (dshape[1],)
    return [tuple(dshape), ch, ch], [tuple(dshape)], []


@register("L2Normalization",
          params_spec=(Param("eps", float, 1e-10),
                       Param("mode", str, "instance",
                             enum=("instance", "channel", "spatial"))),
          hint="l2normalization")
def _l2_normalization(p, c, a):
    if p["mode"] == "instance":
        axes = tuple(range(1, a.ndim))
    elif p["mode"] == "channel":
        axes = (1,)
    else:
        axes = tuple(range(2, a.ndim))
    # the sum of squares in float32 whatever the input's type
    x = a.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axes, keepdims=True) + p["eps"])
    return (x / norm).astype(a.dtype)


@register("LRN", params_spec=(Param("alpha", float, 1e-4),
                              Param("beta", float, 0.75),
                              Param("knorm", float, 2.0),
                              Param("nsize", int, required=True)),
          hint="lrn")
def _lrn(p, c, a):
    nsize = p["nsize"]
    half = nsize // 2
    sq = a * a
    # sliding window sum over the channel axis, unrolled into nsize
    # shifted adds (nsize is tiny; avoids a reduce_window the TPU
    # backend mis-lowers when padding a non-spatial dim)
    C = a.shape[1]
    pad = [(0, 0)] * a.ndim
    pad[1] = (half, half)
    sq_pad = jnp.pad(sq, pad)
    window_sum = sq_pad[:, 0:C]
    for i in range(1, nsize):
        window_sum = window_sum + lax.slice_in_dim(sq_pad, i, i + C, axis=1)
    scale = p["knorm"] + (p["alpha"] / p["nsize"]) * window_sum
    return a / jnp.power(scale, p["beta"])


# ----------------------------------------------------------------------
# Output/loss ops — custom VJPs reproduce the reference's injected grads
def _hashable(p):
    return tuple(sorted((k, v if not isinstance(v, (list, tuple)) else tuple(v))
                        for k, v in p.items() if v is not None))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _softmax_output_p(pspec, data, label):
    return _softmax_output_fwd_only(dict(pspec), data)


def _softmax_output_fwd_only(p, data):
    if p.get("multi_output"):
        return jax.nn.softmax(data, axis=1)
    if p.get("preserve_shape"):
        return jax.nn.softmax(data, axis=-1)
    return jax.nn.softmax(data.reshape((data.shape[0], -1)), axis=-1) \
        .reshape(data.shape)


def _softmax_output_fwd(pspec, data, label):
    out = _softmax_output_p(pspec, data, label)
    return out, (out, label)


def _softmax_output_bwd(pspec, res, g):
    p = dict(pspec)
    out, label = res
    grad_scale = p.get("grad_scale", 1.0)
    if p.get("multi_output"):
        # data (n, c, ...), label (n, ...): one-hot over axis 1
        oh = jax.nn.one_hot(label.astype(jnp.int32), out.shape[1], axis=1,
                            dtype=out.dtype)
        grad = out - oh
        valid = jnp.ones(label.shape, out.dtype)
        if p.get("use_ignore"):
            valid = (label != p.get("ignore_label", -1.0)).astype(out.dtype)
            grad = grad * jnp.expand_dims(valid, 1)
    elif label.ndim == out.ndim:
        grad = out - label.astype(out.dtype)  # dense label
        valid = jnp.ones(label.shape[:1], out.dtype)
    else:
        oh = jax.nn.one_hot(label.astype(jnp.int32), out.shape[-1],
                            dtype=out.dtype)
        grad = out - oh.reshape(out.shape)
        valid = jnp.ones(label.shape, out.dtype)
        if p.get("use_ignore"):
            valid = (label != p.get("ignore_label", -1.0)).astype(out.dtype)
            grad = grad * valid.reshape(label.shape + (1,) * (out.ndim - label.ndim))
    norm = p.get("normalization", "null")
    if norm == "batch":
        grad = grad / out.shape[0]
    elif norm == "valid":
        grad = grad / jnp.maximum(jnp.sum(valid), 1.0)
    if p.get("out_grad"):
        grad = grad * g
    return grad * grad_scale, jnp.zeros_like(label)


_softmax_output_p.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput",
          params_spec=(Param("grad_scale", float, 1.0),
                       Param("ignore_label", float, -1.0),
                       Param("multi_output", bool, False),
                       Param("use_ignore", bool, False),
                       Param("preserve_shape", bool, False),
                       Param("normalization", str, "null",
                             enum=("null", "batch", "valid")),
                       Param("out_grad", bool, False)),
          input_names=("data", "label"), hint="softmaxoutput")
def _softmax_output(p, c, data, label):
    return _softmax_output_p(_hashable(p), data, label)


alias("Softmax", "SoftmaxOutput")


def _softmax_out_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None:
        return None
    if p.get("multi_output"):
        lshape = (dshape[0],) + tuple(dshape[2:])
    else:
        lshape = (dshape[0],)
    if in_shapes[1] is not None and tuple(in_shapes[1]) != lshape \
            and 0 not in in_shapes[1]:
        lshape = tuple(in_shapes[1])  # dense labels allowed
    return [tuple(dshape), lshape], [tuple(dshape)], []


def _make_regression(name, fwd, bwd_fn):
    @partial(jax.custom_vjp, nondiff_argnums=(0,))
    def op(grad_scale, data, label):
        return fwd(data)

    def op_fwd(grad_scale, data, label):
        out = op(grad_scale, data, label)
        return out, (out, label)

    def op_bwd(grad_scale, res, g):
        out, label = res
        num_output = int(np.prod(label.shape[1:])) if label.ndim > 1 else 1
        grad = bwd_fn(out, label.reshape(out.shape)) * (grad_scale / num_output)
        return grad, jnp.zeros_like(label)

    op.defvjp(op_fwd, op_bwd)

    @register(name, params_spec=(Param("grad_scale", float, 1.0),),
              input_names=("data", "label"), hint=name.lower())
    def _regression(p, c, data, label, _op=op):
        return _op(p["grad_scale"], data, label)

    def _infer(p, in_shapes):
        dshape = in_shapes[0]
        if dshape is None:
            return None
        lshape = in_shapes[1]
        if lshape is None or 0 in lshape:
            if len(dshape) == 2 and dshape[1] == 1:
                lshape = (dshape[0],)
            else:
                lshape = tuple(dshape)
        return [tuple(dshape), tuple(lshape)], [tuple(dshape)], []

    from . import registry as _r
    _r.get(name).infer_shape = _infer


_make_regression("LinearRegressionOutput", lambda d: d, lambda o, l: o - l)
_make_regression("LogisticRegressionOutput", jax.nn.sigmoid, lambda o, l: o - l)
_make_regression("MAERegressionOutput", lambda d: d, lambda o, l: jnp.sign(o - l))


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _svm_output_p(pspec, data, label):
    return data


def _svm_fwd(pspec, data, label):
    return data, (data, label)


def _svm_bwd(pspec, res, g):
    p = dict(pspec)
    data, label = res
    margin = p.get("margin", 1.0)
    coef = p.get("regularization_coefficient", 1.0)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[1], dtype=data.dtype)
    if p.get("use_linear"):
        # L1-SVM: grad is -+1 where margin violated
        viol = (margin - (2 * oh - 1) * data) > 0
        grad = jnp.where(viol, -(2 * oh - 1), 0.0) * coef
    else:
        # L2-SVM
        dist = margin - (2 * oh - 1) * data
        grad = jnp.where(dist > 0, -2 * (2 * oh - 1) * dist, 0.0) * coef
    return grad.astype(data.dtype), jnp.zeros_like(label)


_svm_output_p.defvjp(_svm_fwd, _svm_bwd)


@register("SVMOutput",
          params_spec=(Param("margin", float, 1.0),
                       Param("regularization_coefficient", float, 1.0),
                       Param("use_linear", bool, False)),
          input_names=("data", "label"), hint="svmoutput")
def _svm_output(p, c, data, label):
    return _svm_output_p(_hashable(p), data, label)


from . import registry as _reg_mod
_reg_mod.get("SVMOutput").infer_shape = _softmax_out_infer_shape


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _make_loss_p(grad_scale, normalization, data):
    return data


def _make_loss_fwd(grad_scale, normalization, data):
    return data, data.shape


def _make_loss_bwd(grad_scale, normalization, shape, g):
    grad = jnp.full(shape, grad_scale)
    if normalization == "batch":
        grad = grad / shape[0]
    return (grad,)


_make_loss_p.defvjp(_make_loss_fwd, _make_loss_bwd)


@register("MakeLoss",
          params_spec=(Param("grad_scale", float, 1.0),
                       Param("valid_thresh", float, 0.0),
                       Param("normalization", str, "null",
                             enum=("null", "batch", "valid"))),
          hint="makeloss")
def _make_loss(p, c, data):
    return _make_loss_p(p["grad_scale"], p["normalization"], data)


@register("softmax_cross_entropy", input_names=("data", "label"))
def _softmax_cross_entropy(p, c, data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(
        logp, label.astype(jnp.int32).reshape((-1, 1)), axis=-1)
    return -jnp.sum(picked).reshape((1,))


@jax.custom_vjp
def _row_cross_entropy(data, label):
    return _row_cross_entropy_fwd(data, label)[0]


def _row_cross_entropy_fwd(data, label):
    idx = label.astype(jnp.int32).reshape((-1, 1))
    x = data.astype(jnp.float32)
    top = lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
    lse = top[:, 0] + jnp.log(jnp.sum(jnp.exp(x - top), axis=-1))
    picked = jnp.take_along_axis(data, idx, axis=-1)[:, 0]
    # the logits as they came and one float32 a row: no float32 copy of
    # the logits and no softmax lives from here to the backward pass
    return lse - picked.astype(jnp.float32), (data, label, lse)


def _row_cross_entropy_bwd(res, g):
    data, label, lse = res
    idx = label.astype(jnp.int32).reshape((-1, 1))
    prob = jnp.exp(data.astype(jnp.float32) - lse[:, None])
    hit = idx == lax.broadcasted_iota(jnp.int32, data.shape, 1)
    grad = (prob - hit.astype(jnp.float32)) * g.astype(jnp.float32)[:, None]
    return grad.astype(data.dtype), jnp.zeros_like(label)


_row_cross_entropy.defvjp(_row_cross_entropy_fwd, _row_cross_entropy_bwd)


def _row_xent_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    return [tuple(dshape), (dshape[0],)], [(dshape[0],)], []


def _row_xent_infer_dtype(p, in_dtypes):
    dt = in_dtypes[0] if in_dtypes[0] is not None \
        else jnp.dtype(jnp.float32)
    label = in_dtypes[1] if in_dtypes[1] is not None else dt
    return [dt, label], [jnp.dtype(jnp.float32)], []


@register("_contrib_RowCrossEntropy", input_names=("data", "label"),
          hint="rowcrossentropy", infer_shape=_row_xent_infer_shape,
          infer_dtype=_row_xent_infer_dtype)
def _row_cross_entropy_op(p, c, data, label):
    """data (rows, classes) logits, label (rows,) class ids -> (rows,)
    float32: -log softmax(data)[label] a row, a differentiable value
    (``SoftmaxOutput`` injects a gradient at a constant scale and
    ``softmax_cross_entropy`` sums over the rows; a loss that weighs
    its rows by something learned needs the rows).  The log-sum-exp is
    taken in float32 whatever the logits' type.  The reverse mode is
    its own, in ``op/bytediet.py``'s manner: it keeps the logits as
    given and the log-sum-exp, and gives back (softmax - onehot) x the
    row's cotangent in the logits' type, the one-hot as a comparison
    and not a scatter."""
    return _row_cross_entropy(data, label)


def _exit_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    steps = dshape[1] + 1
    return [tuple(dshape)], [(dshape[0], steps)], [(steps,)]


def _exit_infer_dtype(p, in_dtypes):
    f32 = jnp.dtype(jnp.float32)
    return [in_dtypes[0] if in_dtypes[0] is not None else f32], [f32], [f32]


def _exit_gauges(p, aux):
    """``loop.expected_steps``: the last step's mean over positions of
    the pass a position exits after, sum of t x p_t from t = 1: 1 or
    the number of passes says a gate is stuck."""
    mean = np.asarray(aux["pass_share"], np.float64)
    return {"loop.expected_steps":
            float(np.sum(mean * np.arange(1, mean.size + 1)))}


@register("_contrib_ExitDistribution", aux_names=("pass_share",),
          hint="exitdistribution", infer_shape=_exit_infer_shape,
          infer_dtype=_exit_infer_dtype, gauges=_exit_gauges)
def _exit_distribution(p, c, data, pass_share):
    """data (rows, U - 1): the probability lambda_t of stopping after
    pass t, given that pass t was reached, for every pass but the last
    -> (rows, U) float32, the distribution over the pass a row exits
    after: p_t = lambda_t prod_{j<t} (1 - lambda_j), and what is left,
    prod_{j<U} (1 - lambda_j), for the last pass (arXiv:2510.25741
    sec. 3).  Every row sums to 1.  The auxiliary state is the step's
    mean distribution over the rows, which ``obs.snapshot()`` turns into
    the gauge ``loop.expected_steps``."""
    lam = data.astype(jnp.float32)
    stay = jnp.cumprod(1.0 - lam, axis=1)
    before = jnp.concatenate([jnp.ones_like(lam[:, :1]), stay[:, :-1]], 1)
    dist = jnp.concatenate([lam * before, stay[:, -1:]], axis=1)
    return dist, lax.stop_gradient(jnp.mean(dist, axis=0))


@register("IdentityAttachKLSparseReg",
          params_spec=(Param("sparseness_target", float, 0.1),
                       Param("penalty", float, 0.001),
                       Param("momentum", float, 0.9)),
          aux_names=("moving_avg",), hint="identityattachklsparsereg")
def _identity_kl_sparse(p, c, data, moving_avg):
    # forward = identity; KL sparsity penalty enters through the custom grad
    # of the running mean activation (reference: identity_attach_KL_sparse_reg)
    rho_hat = jnp.mean(jax.nn.sigmoid(data))
    new_avg = moving_avg * p["momentum"] + rho_hat * (1 - p["momentum"])
    rho = p["sparseness_target"]
    penalty = p["penalty"] * (-rho / (rho_hat + 1e-8) + (1 - rho) / (1 - rho_hat + 1e-8))
    out = data + lax.stop_gradient(jnp.zeros_like(data)) \
        + (penalty - lax.stop_gradient(penalty)) * jnp.ones_like(data)
    return out, lax.stop_gradient(new_avg)


# ----------------------------------------------------------------------
# Sequence ops (variable-length batches; reference sequence_*-inl.h)
def _seq_spec():
    return (Param("use_sequence_length", bool, False),
            Param("axis", int, 0))


@register("SequenceLast", params_spec=_seq_spec(),
          input_names=lambda p: ["data"] + (["sequence_length"]
                                            if p.get("use_sequence_length") else []),
          hint="sequencelast")
def _sequence_last(p, c, data, sequence_length=None):
    if sequence_length is None:
        return data[-1]
    idx = (sequence_length.astype(jnp.int32) - 1)
    return jax.vmap(lambda t, i: t[i], in_axes=(1, 0))(data, idx)


@register("SequenceMask", params_spec=_seq_spec() + (Param("value", float, 0.0),),
          input_names=lambda p: ["data"] + (["sequence_length"]
                                            if p.get("use_sequence_length") else []),
          hint="sequencemask")
def _sequence_mask(p, c, data, sequence_length=None):
    if sequence_length is None:
        return data
    T = data.shape[0]
    steps = jnp.arange(T).reshape((T, 1) + (1,) * (data.ndim - 2))
    lens = sequence_length.reshape((1, -1) + (1,) * (data.ndim - 2))
    return jnp.where(steps < lens, data, jnp.asarray(p["value"], data.dtype))


@register("SequenceReverse", params_spec=_seq_spec(),
          input_names=lambda p: ["data"] + (["sequence_length"]
                                            if p.get("use_sequence_length") else []),
          hint="sequencereverse")
def _sequence_reverse(p, c, data, sequence_length=None):
    if sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]

    def rev(col, ln):
        idx = jnp.where(jnp.arange(T) < ln, ln - 1 - jnp.arange(T),
                        jnp.arange(T))
        return col[idx]

    return jax.vmap(rev, in_axes=(1, 0), out_axes=1)(
        data, sequence_length.astype(jnp.int32))


# ----------------------------------------------------------------------
# UpSampling
@register("UpSampling",
          params_spec=(Param("scale", int, required=True),
                       Param("num_filter", int, 0),
                       Param("sample_type", str, "nearest",
                             enum=("nearest", "bilinear")),
                       Param("multi_input_mode", str, "concat",
                             enum=("concat", "sum")),
                       Param("num_args", int, 1),
                       Param("workspace", int, 512)),
          input_names=lambda p: ["arg%d" % i for i in range(p["num_args"])],
          hint="upsampling")
def _upsampling(p, c, *xs):
    s = p["scale"]
    outs = []
    target = None
    for x in xs:
        up = jnp.repeat(jnp.repeat(x, s, axis=2), s, axis=3) \
            if p["sample_type"] == "nearest" else _bilinear_resize(x, s)
        if target is None:
            target = up.shape[2:]
        elif up.shape[2:] != target:
            up = up[:, :, :target[0], :target[1]]
        outs.append(up)
    if len(outs) == 1:
        return outs[0]
    if p["multi_input_mode"] == "sum":
        out = outs[0]
        for o in outs[1:]:
            out = out + o
        return out
    return jnp.concatenate(outs, axis=1)


def _bilinear_resize(x, s):
    n, ch, h, w = x.shape
    return jax.image.resize(x, (n, ch, h * s, w * s), method="bilinear")


def _ln_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    n = dshape[p["axis"]]
    return [tuple(dshape), (n,), (n,)], [tuple(dshape)], []


def _rms_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    return [tuple(dshape), (dshape[p["axis"]],)], [tuple(dshape)], []


# registry fixups: attach custom bidirectional shape inference
_reg_mod.get("LayerNorm").infer_shape = _ln_infer_shape
_reg_mod.get("RMSNorm").infer_shape = _rms_infer_shape
_reg_mod.get("FullyConnected").infer_shape = _fc_infer_shape
_reg_mod.get("Convolution").infer_shape = _conv_infer_shape
alias("Convolution_v1", "Convolution")
_reg_mod.get("Deconvolution").infer_shape = _deconv_infer_shape
_reg_mod.get("Pooling").infer_shape = _pool_infer_shape
_reg_mod.get("BatchNorm").infer_shape = _bn_infer_shape
_reg_mod.get("InstanceNorm").infer_shape = _in_infer_shape
_reg_mod.get("LeakyReLU").infer_shape = _prelu_infer_shape
_reg_mod.get("SoftmaxOutput").infer_shape = _softmax_out_infer_shape
_reg_mod.get("BatchNorm").mode_dependent = True
_reg_mod.get("Dropout").mode_dependent = True
