"""Plain reference for the ``resnet50`` configuration.

ResNet-50 in the pre-activation form (He et al. 2016, arXiv:1603.05027;
the layer widths of He et al. 2015, arXiv:1512.03385, table 1, 50-layer
column), channels last, as the upstream
``example/image-classification/symbols/resnet.py`` builds it: 7x7/2
stem, BN, ReLU, 3x3/2 max pool, [3, 4, 6, 3] bottleneck units of
BN-ReLU-conv, a final BN-ReLU, global average pool, one dense layer,
softmax cross-entropy.

Straightforward ``jax.numpy`` in float32.  It imports nothing of the
program under test: parameter names are the upstream symbol's public
names, which is how the harness hands one set of seeded weights to both
sides.  ``jax.checkpoint`` around each unit only bounds the memory of
the backward pass (float32 at batch 256 does not fit otherwise); it
changes no value.

``cast`` is the hook of the lower-precision control: it is applied to
both operands of every convolution and matrix product, as float8
training computes its products (what ``cast`` does to the cotangents
that come back through it is its own affair); accumulation, the
products' results, normalization, the residual sums and the loss stay
in float32.  The reference itself passes ``None``.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 2e-5
BN_MOMENTUM = 0.9


def _units(cfg):
    return list(zip(cfg["units"], cfg["filters"]))


def _convs(cfg):
    """(name, kernel, stride, cin, cout, input_hw) of every convolution,
    in forward order.  ``input_hw`` is the side of the input map."""
    hw = cfg["image_size"]
    out = [("conv0", 7, 2, 3, cfg["stem_filters"], hw)]
    hw //= 4                                    # stem stride, max pool
    cin = cfg["stem_filters"]
    for i, (n, cout) in enumerate(_units(cfg)):
        for j in range(n):
            name = "stage%d_unit%d" % (i + 1, j + 1)
            stride = 2 if (j == 0 and i > 0) else 1
            mid = cout // 4
            out.append((name + "_conv1", 1, 1, cin, mid, hw))
            out.append((name + "_conv2", 3, stride, mid, mid, hw))
            out.append((name + "_conv3", 1, 1, mid, cout, hw // stride))
            if j == 0:
                out.append((name + "_sc", 1, stride, cin, cout, hw))
            hw //= stride
            cin = cout
    return out


def _bns(cfg):
    out = [("bn0", cfg["stem_filters"])]
    cin = cfg["stem_filters"]
    for i, (n, cout) in enumerate(_units(cfg)):
        for j in range(n):
            name = "stage%d_unit%d" % (i + 1, j + 1)
            out += [(name + "_bn1", cin), (name + "_bn2", cout // 4),
                    (name + "_bn3", cout // 4)]
            cin = cout
    return out + [("bn_final", cin)]


def param_shapes(cfg):
    """({parameter: shape}, {auxiliary state: shape})."""
    params, aux = {}, {}
    for name, k, _, cin, cout, _ in _convs(cfg):
        params[name + "_weight"] = (k, k, cin, cout)          # HWIO
    for name, c in _bns(cfg):
        params[name + "_gamma"] = (c,)
        params[name + "_beta"] = (c,)
        aux[name + "_moving_mean"] = (c,)
        aux[name + "_moving_var"] = (c,)
    params["fc1_weight"] = (cfg["num_classes"], cfg["filters"][-1])
    params["fc1_bias"] = (cfg["num_classes"],)
    return params, aux


def init(cfg, key):
    """Seeded float32 weights: He-normal on fan-in for convolutions and
    the dense layer, gamma 1, beta and bias 0, moving mean 0, var 1."""
    pshapes, ashapes = param_shapes(cfg)
    params, aux = {}, {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        if name.endswith("_weight"):
            fan_in = shape[0] * shape[1] * shape[2] if len(shape) == 4 \
                else shape[1]
            params[name] = jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32) \
                * jnp.sqrt(2.0 / fan_in).astype(jnp.float32)
        elif name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            params[name] = jnp.zeros(shape, jnp.float32)
    for name, shape in ashapes.items():
        aux[name] = (jnp.ones if name.endswith("_var") else jnp.zeros)(
            shape, jnp.float32)
    return params, aux


def _conv(x, w, stride, pad, cast):
    if cast is not None:
        x, w = cast(x), cast(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _bn(x, params, aux, new_aux, name):
    """Training-mode batch normalization: batch statistics (biased
    variance) normalize; the moving statistics take a momentum step."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    new_aux[name + "_moving_mean"] = BN_MOMENTUM * aux[
        name + "_moving_mean"] + (1 - BN_MOMENTUM) * lax.stop_gradient(mean)
    new_aux[name + "_moving_var"] = BN_MOMENTUM * aux[
        name + "_moving_var"] + (1 - BN_MOMENTUM) * lax.stop_gradient(var)
    return (x - mean) * lax.rsqrt(var + BN_EPS) * params[name + "_gamma"] \
        + params[name + "_beta"]


def _unit(x, params, aux, name, stride, first, cast):
    new_aux = {}
    a1 = jax.nn.relu(_bn(x, params, aux, new_aux, name + "_bn1"))
    c1 = _conv(a1, params[name + "_conv1_weight"], 1, 0, cast)
    a2 = jax.nn.relu(_bn(c1, params, aux, new_aux, name + "_bn2"))
    c2 = _conv(a2, params[name + "_conv2_weight"], stride, 1, cast)
    a3 = jax.nn.relu(_bn(c2, params, aux, new_aux, name + "_bn3"))
    c3 = _conv(a3, params[name + "_conv3_weight"], 1, 0, cast)
    sc = _conv(a1, params[name + "_sc_weight"], stride, 0, cast) \
        if first else x
    return c3 + sc, new_aux


def loss(cfg, params, aux, data, label, cast=None):
    """Mean softmax cross-entropy of one batch, and the auxiliary state
    after it.  ``data`` (N, H, W, 3) float32, ``label`` (N,) int32."""
    new_aux = {}
    x = _conv(data, params["conv0_weight"], 2, 3, cast)
    x = jax.nn.relu(_bn(x, params, aux, new_aux, "bn0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for i, (n, _) in enumerate(_units(cfg)):
        for j in range(n):
            name = "stage%d_unit%d" % (i + 1, j + 1)
            unit = jax.checkpoint(functools.partial(
                _unit, name=name, stride=2 if (j == 0 and i > 0) else 1,
                first=j == 0, cast=cast))
            x, unit_aux = unit(x, params, aux)
            new_aux.update(unit_aux)
    x = jax.nn.relu(_bn(x, params, aux, new_aux, "bn_final"))
    x = jnp.mean(x, axis=(1, 2))
    w = params["fc1_weight"]
    if cast is not None:
        x, w = cast(x), cast(w)
    logits = jnp.dot(x, w.T, precision=lax.Precision.HIGHEST) \
        + params["fc1_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, label[:, None], axis=1)
    return jnp.mean(nll), new_aux


# ----------------------------------------------------------------------
# operations and bytes, from shapes
def costs(cfg, batch):
    """What the algorithm needs for one step of ``batch`` samples.

    ``model_flops``: forward and backward multiply-adds of every
    convolution and the dense layer, two operations each, nothing
    recomputed (backward is twice the forward: one product for the
    input's gradient, one for the weight's; the stem has no input
    gradient to compute but is counted alike, as is usual).
    ``matmul``: the same operations, and the bytes those products must
    move at ``act_bytes`` an element: every operand read and every
    result written once, forward and backward.
    """
    act = cfg.get("act_bytes", 2)
    flops = 0
    nbytes = 0
    by_layer = {}
    for name, k, stride, cin, cout, hw in _convs(cfg):
        ho = hw // stride
        macs = batch * ho * ho * k * k * cin * cout
        flops += 3 * 2 * macs
        by_layer[name] = 3 * 2 * macs
        x, y, w = batch * hw * hw * cin, batch * ho * ho * cout, \
            k * k * cin * cout
        # fwd: read x, w, write y; dgrad: read dy, w, write dx;
        # wgrad: read x, dy, write dw
        nbytes += act * ((x + w + y) + (y + w + x) + (x + y + w))
    c, f = cfg["num_classes"], cfg["filters"][-1]
    by_layer["fc1"] = 3 * 2 * batch * f * c
    flops += by_layer["fc1"]
    nbytes += act * 3 * (batch * f + f * c + batch * c)
    return {"model_flops": flops, "by_layer": by_layer,
            "matmul": {"flops": flops, "bytes": nbytes}}
