"""Operator tests (reference ``tests/python/unittest/test_operator.py``):
golden values vs numpy + finite-difference gradient checks."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  check_symbolic_forward,
                                  check_symbolic_backward)


def test_elemwise_binary_ops():
    a = np.random.randn(3, 4).astype("f")
    b = np.random.randn(3, 4).astype("f")
    x = mx.sym.Variable("x")
    y = mx.sym.Variable("y")
    for sym_op, np_fn in [
            (mx.symbol.elemwise_add(x, y), lambda: a + b),
            (mx.symbol.elemwise_sub(x, y), lambda: a - b),
            (mx.symbol.elemwise_mul(x, y), lambda: a * b),
            (mx.symbol.elemwise_div(x, y), lambda: a / b)]:
        check_symbolic_forward(sym_op, {"x": a, "y": b}, [np_fn()],
                               rtol=1e-4, atol=1e-5)


def test_unary_math_ops():
    a = np.abs(np.random.randn(3, 4).astype("f")) + 0.5
    x = mx.sym.Variable("x")
    cases = [
        (mx.symbol.sqrt(x), np.sqrt(a)),
        (mx.symbol.exp(x), np.exp(a)),
        (mx.symbol.log(x), np.log(a)),
        (mx.symbol.square(x), a * a),
        (mx.symbol.abs(x), np.abs(a)),
        (mx.symbol.sigmoid(x), 1 / (1 + np.exp(-a))),
        (mx.symbol.tanh(x), np.tanh(a)),
        (mx.symbol.relu(x), np.maximum(a, 0)),
        (mx.symbol.rsqrt(x), 1.0 / np.sqrt(a)),
        (mx.symbol.reciprocal(x), 1.0 / a),
    ]
    for sym_op, expected in cases:
        check_symbolic_forward(sym_op, {"x": a}, [expected], rtol=1e-4,
                               atol=1e-5)


def test_scalar_ops():
    a = np.random.randn(3, 4).astype("f")
    x = mx.sym.Variable("x")
    check_symbolic_forward(x + 2.0, {"x": a}, [a + 2])
    check_symbolic_forward(x - 2.0, {"x": a}, [a - 2])
    check_symbolic_forward(2.0 - x, {"x": a}, [2 - a], rtol=1e-4, atol=1e-5)
    check_symbolic_forward(x * 3.0, {"x": a}, [a * 3], rtol=1e-4, atol=1e-5)
    check_symbolic_forward(x / 2.0, {"x": a}, [a / 2], rtol=1e-4, atol=1e-5)


def test_broadcast_ops():
    a = np.random.randn(3, 1).astype("f")
    b = np.random.randn(1, 4).astype("f")
    x = mx.sym.Variable("x")
    y = mx.sym.Variable("y")
    check_symbolic_forward(mx.symbol.broadcast_add(x, y),
                           {"x": a, "y": b}, [a + b])
    check_symbolic_forward(mx.symbol.broadcast_mul(x, y),
                           {"x": a, "y": b}, [a * b])
    check_symbolic_forward(mx.symbol.broadcast_maximum(x, y),
                           {"x": a, "y": b}, [np.maximum(a, b)])


def test_reduce_ops():
    a = np.random.randn(2, 3, 4).astype("f")
    x = mx.sym.Variable("x")
    check_symbolic_forward(mx.symbol.sum(x, axis=1), {"x": a},
                           [a.sum(axis=1)], rtol=1e-4, atol=1e-5)
    check_symbolic_forward(mx.symbol.mean(x, axis=(0, 2)), {"x": a},
                           [a.mean(axis=(0, 2))], rtol=1e-4, atol=1e-5)
    check_symbolic_forward(mx.symbol.max(x, axis=2, keepdims=True), {"x": a},
                           [a.max(axis=2, keepdims=True)])
    check_symbolic_forward(mx.symbol.prod(x, axis=0), {"x": a},
                           [a.prod(axis=0)], rtol=1e-4, atol=1e-5)


def test_argmax_argsort_topk():
    a = np.random.randn(3, 5).astype("f")
    x = mx.sym.Variable("x")
    check_symbolic_forward(mx.symbol.argmax(x, axis=1), {"x": a},
                           [a.argmax(axis=1).astype("f")])
    check_symbolic_forward(mx.symbol.argmin(x, axis=1), {"x": a},
                           [a.argmin(axis=1).astype("f")])
    check_symbolic_forward(mx.symbol.sort(x, axis=1), {"x": a},
                           [np.sort(a, axis=1)])


def test_matrix_ops():
    a = np.random.randn(2, 3).astype("f")
    b = np.random.randn(3, 4).astype("f")
    x = mx.sym.Variable("x")
    y = mx.sym.Variable("y")
    check_symbolic_forward(mx.symbol.dot(x, y), {"x": a, "y": b}, [a @ b],
                           rtol=1e-4, atol=1e-5)
    check_numeric_gradient(mx.symbol.dot(x, y), {"x": a, "y": b},
                           numeric_eps=1e-2, rtol=2e-2, atol=1e-2)
    c = np.random.randn(4, 2, 3).astype("f")
    d = np.random.randn(4, 3, 5).astype("f")
    check_symbolic_forward(mx.symbol.batch_dot(x, y), {"x": c, "y": d},
                           [np.einsum("bij,bjk->bik", c, d)], rtol=1e-4,
                           atol=1e-5)


def test_shape_ops():
    a = np.random.randn(2, 3, 4).astype("f")
    x = mx.sym.Variable("x")
    check_symbolic_forward(mx.symbol.Reshape(x, shape=(2, 12)), {"x": a},
                           [a.reshape(2, 12)])
    check_symbolic_forward(mx.symbol.Flatten(x), {"x": a},
                           [a.reshape(2, 12)])
    check_symbolic_forward(mx.symbol.transpose(x, axes=(2, 0, 1)), {"x": a},
                           [a.transpose(2, 0, 1)])
    check_symbolic_forward(mx.symbol.expand_dims(x, axis=1), {"x": a},
                           [a[:, None]])
    check_symbolic_forward(mx.symbol.slice_axis(x, axis=2, begin=1, end=3),
                           {"x": a}, [a[:, :, 1:3]])
    check_symbolic_forward(mx.symbol.SwapAxis(x, dim1=0, dim2=2), {"x": a},
                           [a.swapaxes(0, 2)])
    check_symbolic_forward(mx.symbol.tile(x, reps=(1, 2, 1)), {"x": a},
                           [np.tile(a, (1, 2, 1))])
    check_symbolic_forward(mx.symbol.reverse(x, axis=1), {"x": a},
                           [a[:, ::-1]])


def test_concat_split():
    a = np.random.randn(2, 3).astype("f")
    b = np.random.randn(2, 5).astype("f")
    x = mx.sym.Variable("x")
    y = mx.sym.Variable("y")
    out = mx.symbol.Concat(x, y, dim=1)
    check_symbolic_forward(out, {"x": a, "y": b},
                           [np.concatenate([a, b], axis=1)])
    c = np.random.randn(4, 6).astype("f")
    s = mx.symbol.SliceChannel(mx.sym.Variable("x"), num_outputs=3, axis=1)
    check_symbolic_forward(s, {"x": c}, list(np.split(c, 3, axis=1)))


def test_fully_connected():
    x = mx.sym.Variable("x")
    w = mx.sym.Variable("w")
    b = mx.sym.Variable("b")
    fc = mx.symbol.FullyConnected(data=x, weight=w, bias=b, num_hidden=4)
    a = np.random.randn(5, 3).astype("f")
    wv = np.random.randn(4, 3).astype("f")
    bv = np.random.randn(4).astype("f")
    check_symbolic_forward(fc, {"x": a, "w": wv, "b": bv},
                           [a @ wv.T + bv], rtol=1e-4, atol=1e-5)
    check_numeric_gradient(fc, {"x": a, "w": wv, "b": bv},
                           numeric_eps=1e-2, rtol=2e-2, atol=2e-2)


def test_activation_grads():
    a = np.random.randn(3, 4).astype("f")
    a += np.sign(a) * 0.1  # keep away from the relu kink for FD checking
    for act in ["relu", "sigmoid", "tanh", "softrelu"]:
        x = mx.sym.Variable("x")
        sym = mx.symbol.Activation(x, act_type=act)
        check_numeric_gradient(sym, {"x": a}, numeric_eps=1e-2, rtol=2e-2,
                               atol=2e-2)


def test_leaky_relu():
    a = np.random.randn(3, 4).astype("f")
    x = mx.sym.Variable("x")
    sym = mx.symbol.LeakyReLU(x, act_type="leaky", slope=0.1)
    check_symbolic_forward(sym, {"x": a}, [np.where(a > 0, a, 0.1 * a)],
                           rtol=1e-4, atol=1e-5)


def test_convolution():
    x = mx.sym.Variable("x")
    w = mx.sym.Variable("w")
    b = mx.sym.Variable("b")
    conv = mx.symbol.Convolution(data=x, weight=w, bias=b, num_filter=2,
                                 kernel=(3, 3), stride=(1, 1), pad=(1, 1))
    a = np.random.randn(1, 3, 5, 5).astype("f")
    arg_shapes, out_shapes, _ = conv.infer_shape(x=(1, 3, 5, 5))
    assert out_shapes[0] == (1, 2, 5, 5)
    wv = np.random.randn(*dict(zip(conv.list_arguments(), arg_shapes))["w"]).astype("f")
    bv = np.zeros(2, dtype="f")
    # verify against scipy-style direct convolution (cross-correlation)
    exe = conv.bind(mx.cpu(), {"x": mx.nd.array(a), "w": mx.nd.array(wv),
                               "b": mx.nd.array(bv)})
    out = exe.forward()[0].asnumpy()
    pad = np.pad(a, ((0, 0), (0, 0), (1, 1), (1, 1)))
    expect = np.zeros((1, 2, 5, 5), dtype="f")
    for f in range(2):
        for i in range(5):
            for j in range(5):
                expect[0, f, i, j] = np.sum(
                    pad[0, :, i:i + 3, j:j + 3] * wv[f])
    assert_almost_equal(expect, out, rtol=1e-3, atol=1e-3)


def test_pooling():
    x = mx.sym.Variable("x")
    a = np.random.randn(1, 1, 4, 4).astype("f")
    pool = mx.symbol.Pooling(x, kernel=(2, 2), stride=(2, 2),
                             pool_type="max")
    expect = a.reshape(1, 1, 2, 2, 2, 2).max(axis=(3, 5))
    check_symbolic_forward(pool, {"x": a}, [expect])
    avg = mx.symbol.Pooling(x, kernel=(2, 2), stride=(2, 2),
                            pool_type="avg")
    expect = a.reshape(1, 1, 2, 2, 2, 2).mean(axis=(3, 5))
    check_symbolic_forward(avg, {"x": a}, [expect], rtol=1e-4, atol=1e-5)


def _first_max_pool_grad(x, g, kernel, stride, pad, full):
    """Max pooling's input gradient over NHWC ``x`` written out: every
    window hands its cotangent to its first maximum in row-major order;
    a window that holds nothing but padding hands it to nobody."""
    n, h, w, c = x.shape

    def starts(size):
        span = size + 2 * pad - kernel
        count = (-(-span // stride) if full else span // stride) + 1
        return [i * stride - pad for i in range(count)]

    dx = np.zeros(x.shape, np.float64)
    tied = []
    for oi, i0 in enumerate(starts(h)):
        for oj, j0 in enumerate(starts(w)):
            cells = [(i, j) for i in range(max(i0, 0), min(i0 + kernel, h))
                     for j in range(max(j0, 0), min(j0 + kernel, w))]
            if not cells:
                continue
            vals = np.stack([x[:, i, j, :] for i, j in cells])
            first = np.argmax(vals, axis=0)        # first of equal maxima
            tied.append((vals == vals.max(axis=0)).sum(axis=0) > 1)
            for k, (i, j) in enumerate(cells):
                dx[:, i, j, :] += np.where(first == k, g[:, oi, oj, :], 0)
    return dx, np.mean(tied)


@pytest.mark.parametrize("policy", ["bytediet", "legacy"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("convention", ["valid", "full"])
@pytest.mark.parametrize("layout", ["NHWC", "NCHW"])
@pytest.mark.parametrize("kernel,stride,pad", [(3, 2, 1), (3, 1, 1),
                                               (2, 2, 1)])
def test_max_pool_backward_ties(kernel, stride, pad, layout, convention,
                                dtype, policy):
    """Training-mode max ``Pooling`` on a post-ReLU input rounded until
    most windows tie: the input gradient is, exactly, that of the plain
    ``reduce_window`` and the first maximum's.  2x2/2 pad 1 over 3x3
    under ``full`` ends on a window of padding alone."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mxnet_tpu.op.registry import OpContext, get

    rng = np.random.RandomState(kernel * 10 + stride)
    size = (3, 3) if kernel == 2 else (7, 10)
    x = np.maximum(np.round(rng.randn(2, *size, 3) * 0.5), 0)
    # numpy sees NHWC; the op sees its layout
    to_op, to_nhwc = ((0, 1, 2, 3),) * 2 if layout == "NHWC" \
        else ((0, 3, 1, 2), (0, 2, 3, 1))
    op = get("Pooling")
    params = op.parse_params(dict(
        kernel=(kernel, kernel), stride=(stride, stride), pad=(pad, pad),
        pool_type="max", pooling_convention=convention, layout=layout))
    ctx = OpContext(is_train=True, dtype_policy=policy)
    data = jnp.asarray(x.transpose(to_op), dtype)
    out, vjp = jax.vjp(lambda d: op.apply(params, ctx, d)[0][0], data)
    out_size = np.transpose(out, to_nhwc).shape[1:3]
    # small whole numbers: sums of up to nine are exact in bfloat16 too
    g = rng.randint(1, 5, size=(2,) + out_size + (3,)).astype("f")
    cot = jnp.asarray(g.transpose(to_op), dtype)
    dx, = vjp(cot)
    assert dx.dtype == data.dtype

    window, strides, padding = [1] * 4, [1] * 4, [(0, 0)] * 4
    for ax, n_in, n_out in zip((1, 2) if layout == "NHWC" else (2, 3),
                               size, out_size):
        hi = (n_out - 1) * stride + kernel - pad - n_in
        window[ax], strides[ax], padding[ax] = kernel, stride, (pad, hi)
    plain_out, plain_vjp = jax.vjp(
        lambda d: lax.reduce_window(d, np.array(-np.inf, d.dtype), lax.max,
                                    window, strides, padding), data)
    np.testing.assert_array_equal(np.asarray(out, "f"),
                                  np.asarray(plain_out, "f"))
    np.testing.assert_array_equal(np.asarray(dx, "f"),
                                  np.asarray(plain_vjp(cot)[0], "f"))

    want, tied = _first_max_pool_grad(x, g, kernel, stride, pad,
                                      convention == "full")
    assert tied > 0.5
    got = np.asarray(dx, "f").transpose(to_nhwc)
    np.testing.assert_array_equal(got, want)
    empty = ~np.isfinite(np.asarray(out, "f").transpose(to_nhwc))
    assert empty.any() == (kernel == 2 and convention == "full")
    assert got.sum() == g[~empty].sum()


def test_softmax_output():
    x = mx.sym.Variable("x")
    l = mx.sym.Variable("l")
    sym = mx.symbol.SoftmaxOutput(data=x, label=l, name="softmax")
    a = np.random.randn(4, 5).astype("f")
    lab = np.array([1, 0, 3, 2], dtype="f")
    e = np.exp(a - a.max(axis=1, keepdims=True))
    p = e / e.sum(axis=1, keepdims=True)
    check_symbolic_forward(sym, {"x": a, "l": lab}, [p], rtol=1e-4, atol=1e-5)
    # gradient = (p - onehot)/batch... reference uses p - onehot
    exe = sym.bind(mx.cpu(), {"x": mx.nd.array(a), "l": mx.nd.array(lab)},
                   args_grad={"x": mx.nd.zeros((4, 5))})
    exe.forward(is_train=True)
    exe.backward()
    onehot = np.eye(5)[lab.astype(int)]
    assert_almost_equal(exe.grad_dict["x"].asnumpy(), (p - onehot),
                        rtol=1e-4, atol=1e-5)


def test_batchnorm_train_and_moments():
    x = mx.sym.Variable("x")
    bn = mx.symbol.BatchNorm(x, eps=1e-5, momentum=0.9, name="bn")
    a = np.random.randn(8, 3, 2, 2).astype("f") * 2 + 1
    exe = bn.simple_bind(ctx=mx.cpu(), x=a.shape)
    exe.arg_dict["x"][:] = a
    exe.arg_dict["bn_gamma"][:] = 1
    exe.arg_dict["bn_beta"][:] = 0
    out = exe.forward(is_train=True)[0].asnumpy()
    mean = a.mean(axis=(0, 2, 3))
    var = a.var(axis=(0, 2, 3))
    expect = (a - mean[None, :, None, None]) / np.sqrt(
        var[None, :, None, None] + 1e-5)
    assert_almost_equal(expect, out, rtol=1e-3, atol=1e-3)
    # moving stats updated
    mm = exe.aux_dict["bn_moving_mean"].asnumpy()
    assert_almost_equal(mm, 0.1 * mean, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("policy", ["bytediet", "legacy"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("regime", ["fallback", "steady"])
def test_batchnorm_statistics_against_float64(regime, dtype, policy):
    """Train-mode ``BatchNorm`` on channels whose means are about 10^3
    times their deviation.  With ``moving_mean`` 0 the single-pass
    moments cancel (d1² > 63/64·d2 on every channel) and the op takes
    its two-pass fallback; with ``moving_mean`` at the batch mean they
    do not.  Either way mean, variance, the updated aux and the output
    match float64 two-pass statistics of the data as the op sees it.
    The variance's tolerance (1e-5 in float32, 1e-2 in bfloat16, whose
    outputs round to 2^-9) is one that the single-pass formula alone
    misses in the fallback regime: on this data it is off by 56% in
    float32 and 43% in bfloat16 on its worst channel, 56,000 and 43
    times the tolerances.  The output is held to four of the dtype's
    epsilons at the channel's magnitude over its deviation: 5e-4 of a
    deviation in float32, where the single-pass variance puts it off by
    up to a fifth of its value; in bfloat16 that is tens of deviations,
    and the statistics decide."""
    import jax.numpy as jnp
    from mxnet_tpu.op.registry import OpContext, get

    rng = np.random.RandomState(0)
    mean = np.array([1000.0, -2000.0, 3000.0, 500.0])
    x = rng.randn(16, 4, 8, 8) * (np.abs(mean) / 1000)[:, None, None] \
        + mean[:, None, None]
    data = jnp.asarray(x, dtype)
    x64 = np.asarray(data.astype(jnp.float32), np.float64)
    axes = (0, 2, 3)
    m64 = x64.mean(axis=axes)
    v64 = ((x64 - m64[:, None, None]) ** 2).mean(axis=axes)
    center = np.zeros(4) if regime == "fallback" else m64
    moving_mean = jnp.asarray(center, dtype)
    c = np.asarray(moving_mean.astype(jnp.float32), np.float64)
    d1 = (x64 - c[:, None, None]).mean(axis=axes)
    d2 = ((x64 - c[:, None, None]) ** 2).mean(axis=axes)
    assert ((d1 * d1 > 63 / 64 * d2) == (regime == "fallback")).all()

    tol = 1e-5 if dtype == "float32" else 1e-2

    def got(a):
        return np.asarray(jnp.asarray(a).astype(jnp.float32), np.float64)

    if regime == "fallback":
        xc = data.astype(jnp.float32)
        fast = jnp.sum(xc * xc, axis=axes) / 1024 \
            - (jnp.sum(xc, axis=axes) / 1024) ** 2
        assert np.max(np.abs(got(fast) - v64) / v64) > 10 * tol

    op = get("BatchNorm")
    ctx = OpContext(is_train=True, dtype_policy=policy)
    ones, zeros = jnp.ones(4, dtype), jnp.zeros(4, dtype)
    want = (x64 - m64[:, None, None]) / np.sqrt(v64[:, None, None] + 1e-5)
    out_tol = 4 * float(jnp.finfo(dtype).eps) \
        * np.abs(x64).max(axis=axes) / np.sqrt(v64)
    for output_mean_var in (True, False):
        params = op.parse_params(dict(eps=1e-5, momentum=0.9,
                                      fix_gamma=True,
                                      output_mean_var=output_mean_var))
        outs, (new_mean, new_var) = op.apply(params, ctx, data, ones, zeros,
                                             moving_mean, zeros)
        if output_mean_var:
            np.testing.assert_allclose(got(outs[1]), m64, rtol=tol)
            np.testing.assert_allclose(got(outs[2]), v64, rtol=tol)
        np.testing.assert_allclose(got(new_mean), 0.9 * c + 0.1 * m64,
                                   rtol=tol)
        np.testing.assert_allclose(got(new_var), 0.1 * v64, rtol=tol)
        err = np.abs(got(outs[0]) - want).max(axis=axes)
        assert (err <= out_tol).all(), (err, out_tol)


def test_dropout_modes():
    x = mx.sym.Variable("x")
    sym = mx.symbol.Dropout(x, p=0.5)
    a = np.ones((100, 100), dtype="f")
    exe = sym.simple_bind(ctx=mx.cpu(), x=a.shape)
    exe.arg_dict["x"][:] = a
    # eval mode: identity
    out = exe.forward(is_train=False)[0].asnumpy()
    assert np.allclose(out, a)
    # train mode: ~half dropped, scaled by 1/(1-p)
    out = exe.forward(is_train=True)[0].asnumpy()
    frac = (out == 0).mean()
    assert 0.4 < frac < 0.6
    assert np.allclose(out[out != 0], 2.0)


def test_embedding_take():
    w = np.random.randn(10, 4).astype("f")
    idx = np.array([1, 3, 5], dtype="f")
    d = mx.sym.Variable("d")
    wt = mx.sym.Variable("w")
    emb = mx.symbol.Embedding(data=d, weight=wt, input_dim=10, output_dim=4)
    check_symbolic_forward(emb, {"d": idx, "w": w}, [w[[1, 3, 5]]])


def test_where_clip():
    cond = np.array([[1, 0], [0, 1]], dtype="f")
    a = np.random.randn(2, 2).astype("f")
    b = np.random.randn(2, 2).astype("f")
    c = mx.sym.Variable("c")
    x = mx.sym.Variable("x")
    y = mx.sym.Variable("y")
    check_symbolic_forward(mx.symbol.where(c, x, y),
                           {"c": cond, "x": a, "y": b},
                           [np.where(cond > 0, a, b)])
    check_symbolic_forward(mx.symbol.clip(x, a_min=-0.5, a_max=0.5),
                           {"x": a}, [np.clip(a, -0.5, 0.5)])


def test_loss_ops_gradient_semantics():
    """Regression-output losses bake their gradient via custom VJP."""
    x = mx.sym.Variable("x")
    l = mx.sym.Variable("l")
    a = np.random.randn(4, 3).astype("f")
    lab = np.random.randn(4, 3).astype("f")
    lin = mx.symbol.LinearRegressionOutput(data=x, label=l)
    exe = lin.bind(mx.cpu(), {"x": mx.nd.array(a), "l": mx.nd.array(lab)},
                   args_grad={"x": mx.nd.zeros(a.shape)})
    exe.forward(is_train=True)
    assert_almost_equal(exe.outputs[0].asnumpy(), a)
    exe.backward()
    # reference regression_output-inl.h:76: grad = grad_scale/num_output
    # * (out - label), num_output = outputs per sample
    assert_almost_equal(exe.grad_dict["x"].asnumpy(), (a - lab) / 3,
                        rtol=1e-4, atol=1e-5)


def test_block_grad():
    x = mx.sym.Variable("x")
    sym = mx.symbol.BlockGrad(mx.symbol.tanh(x)) + x
    a = np.random.randn(3, 3).astype("f")
    exe = sym.bind(mx.cpu(), {"x": mx.nd.array(a)},
                   args_grad={"x": mx.nd.zeros(a.shape)})
    exe.forward(is_train=True)
    exe.backward()
    # gradient flows only through the identity branch
    assert_almost_equal(exe.grad_dict["x"].asnumpy(), np.ones((3, 3)))


def test_numeric_gradient_mlp():
    """End-to-end gradient check through a small MLP."""
    x = mx.sym.Variable("x")
    fc1 = mx.symbol.FullyConnected(x, num_hidden=6, name="fc1")
    act = mx.symbol.tanh(fc1)
    fc2 = mx.symbol.FullyConnected(act, num_hidden=3, name="fc2")
    shapes = dict(x=(4, 5))
    arg_shapes, _, _ = fc2.infer_shape(**shapes)
    loc = {n: np.random.randn(*s).astype("f") * 0.5
           for n, s in zip(fc2.list_arguments(), arg_shapes)}
    check_numeric_gradient(fc2, loc, numeric_eps=1e-2, rtol=5e-2, atol=2e-2)


def test_sequence_ops():
    a = np.random.randn(5, 3, 4).astype("f")  # (T, N, C)
    length = np.array([2, 5, 3], dtype="f")
    x = mx.sym.Variable("x")
    sl = mx.sym.Variable("sl")
    last = mx.symbol.SequenceLast(data=x, sequence_length=sl,
                                  use_sequence_length=True)
    expect = np.stack([a[1, 0], a[4, 1], a[2, 2]])
    check_symbolic_forward(last, {"x": a, "sl": length}, [expect])
    mask = mx.symbol.SequenceMask(data=x, sequence_length=sl,
                                  use_sequence_length=True, value=0.0)
    expect = a.copy()
    expect[2:, 0] = 0
    expect[3:, 2] = 0
    check_symbolic_forward(mask, {"x": a, "sl": length}, [expect])


def test_one_hot_pick():
    idx = np.array([0, 2, 1], dtype="f")
    x = mx.sym.Variable("x")
    check_symbolic_forward(mx.symbol.one_hot(x, depth=4), {"x": idx},
                           [np.eye(4, dtype="f")[[0, 2, 1]]])
    a = np.random.randn(3, 4).astype("f")
    d = mx.sym.Variable("d")
    i = mx.sym.Variable("i")
    check_symbolic_forward(mx.symbol.pick(d, i, axis=1),
                           {"d": a, "i": idx},
                           [a[np.arange(3), idx.astype(int)]])


def test_lrn():
    # golden NumPy sliding-window model of src/operator/lrn-inl.h
    rng = np.random.RandomState(3)
    a = rng.rand(2, 7, 3, 3).astype("f") + 0.5
    nsize, alpha, beta, knorm = 3, 1e-2, 0.75, 2.0
    sq = a * a
    pad = np.pad(sq, ((0, 0), (nsize // 2, nsize // 2), (0, 0), (0, 0)))
    win = sum(pad[:, i:i + 7] for i in range(nsize))
    expect = a / (knorm + alpha / nsize * win) ** beta
    x = mx.sym.Variable("x")
    sym = mx.sym.LRN(x, nsize=nsize, alpha=alpha, beta=beta, knorm=knorm)
    check_symbolic_forward(sym, {"x": a}, [expect])
    check_numeric_gradient(sym, {"x": a}, numeric_eps=1e-2,
                           rtol=0.05, atol=1e-3)


def test_layer_norm():
    rng = np.random.RandomState(5)
    a = rng.rand(4, 6).astype("f") * 3 + 1
    g = rng.rand(6).astype("f")
    b = rng.rand(6).astype("f")
    mean = a.mean(-1, keepdims=True)
    var = a.var(-1, keepdims=True)
    expect = (a - mean) / np.sqrt(var + 1e-5) * g + b
    x, ga, be = (mx.sym.Variable(n) for n in ("x", "g", "b"))
    sym = mx.sym.LayerNorm(x, ga, be)
    check_symbolic_forward(sym, {"x": a, "g": g, "b": b}, [expect],
                           rtol=1e-4, atol=1e-5)
    check_numeric_gradient(sym, {"x": a, "g": g, "b": b},
                           numeric_eps=1e-2, rtol=0.06, atol=1e-2)
