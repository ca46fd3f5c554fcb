"""Custom python operators + the _imdecode operator (reference
``src/operator/custom/custom-inl.h`` / ``python/mxnet/operator.py`` and
``src/io/image_io.cc``)."""
import numpy as np
import pytest

import mxnet_tpu as mx


@mx.operator.register("scaled_sigmoid")
class ScaledSigmoidProp(mx.operator.CustomOpProp):
    def __init__(self, scale="1.0"):
        super().__init__(need_top_grad=True)
        self.scale = float(scale)

    def create_operator(self, ctx, in_shapes, in_dtypes):
        scale = self.scale

        class ScaledSigmoid(mx.operator.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                x = in_data[0].asnumpy()
                self.assign(out_data[0], req[0],
                            mx.nd.array(scale / (1 + np.exp(-x))))

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):
                y = out_data[0].asnumpy() / scale
                g = out_grad[0].asnumpy()
                self.assign(in_grad[0], req[0],
                            mx.nd.array(g * scale * y * (1 - y)))

        return ScaledSigmoid()


def test_custom_op_imperative():
    x = np.random.RandomState(0).randn(3, 4).astype("f")
    out = mx.nd.Custom(mx.nd.array(x), op_type="scaled_sigmoid",
                       scale="2.0")
    np.testing.assert_allclose(out.asnumpy(), 2 / (1 + np.exp(-x)),
                               rtol=1e-5)


def test_custom_op_symbolic_forward_backward():
    rng = np.random.RandomState(1)
    x = rng.randn(2, 3).astype("f")
    data = mx.sym.Variable("data")
    net = mx.sym.Custom(data, op_type="scaled_sigmoid", scale="1.0",
                        name="cs")
    args = {"data": mx.nd.array(x)}
    grads = {"data": mx.nd.zeros(x.shape)}
    ex = net.bind(mx.cpu(), args=args, args_grad=grads)
    ex.forward(is_train=True)
    y = 1 / (1 + np.exp(-x))
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), y, rtol=1e-5)
    ex.backward([mx.nd.ones(x.shape)])
    np.testing.assert_allclose(grads["data"].asnumpy(), y * (1 - y),
                               rtol=1e-4, atol=1e-5)


def test_custom_op_in_module_training():
    """sym.Custom participates in a fit() loop end-to-end."""
    rng = np.random.RandomState(0)
    x = rng.randn(64, 6).astype("f")
    w = rng.randn(6, 2).astype("f")
    y = np.argmax(x @ w, 1).astype("f")
    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    h = mx.sym.Custom(h, op_type="scaled_sigmoid", scale="1.0")
    h = mx.sym.FullyConnected(h, num_hidden=2, name="fc2")
    net = mx.sym.SoftmaxOutput(h, name="softmax")
    it = mx.io.NDArrayIter(x, y, batch_size=16)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=10, optimizer_params={"learning_rate": 0.5},
            initializer=mx.init.Xavier())
    it.reset()
    assert mod.score(it, "acc")[0][1] > 0.9


def test_imdecode_operator():
    pil = pytest.importorskip("PIL.Image")
    import io as _io
    rng = np.random.RandomState(3)
    img = rng.randint(0, 255, (5, 7, 3)).astype("uint8")
    buf = _io.BytesIO()
    pil.fromarray(img).save(buf, format="PNG")
    raw = np.frombuffer(buf.getvalue(), dtype=np.uint8)

    out = mx.nd._imdecode(mx.nd.array(raw.astype("f")))
    np.testing.assert_array_equal(out.asnumpy().astype("uint8"), img)

    # crop window + channel clamp params
    out2 = mx.nd._imdecode(mx.nd.array(raw.astype("f")),
                           x0=1, y0=1, x1=4, y1=3, c=2)
    np.testing.assert_array_equal(out2.asnumpy().astype("uint8"),
                                  img[1:3, 1:4, :2])


def test_legacy_numpy_op():
    """The pre-CustomOp foreign-function API (reference
    ``operator.py:19-225`` NumpyOp -> the ``_Native`` callback op)."""

    class NumpySoftmax(mx.operator.NumpyOp):
        def __init__(self):
            super().__init__(need_top_grad=False)

        def list_arguments(self):
            return ["data", "label"]

        def infer_shape(self, in_shape):
            return [in_shape[0], (in_shape[0][0],)], [in_shape[0]]

        def forward(self, in_data, out_data):
            x, y = in_data[0], out_data[0]
            y[:] = np.exp(x - x.max(axis=1, keepdims=True))
            y /= y.sum(axis=1, keepdims=True)

        def backward(self, out_grad, in_data, out_data, in_grad):
            lab, y, dx = in_data[1], out_data[0], in_grad[0]
            dx[:] = y.copy()
            dx[np.arange(lab.shape[0]), lab.astype(np.int32)] -= 1.0

    net = NumpySoftmax()(mx.sym.Variable("data"), name="softmax")
    rng = np.random.RandomState(0)
    x = rng.randn(6, 4).astype("f")
    lab = rng.randint(0, 4, (6,)).astype("f")
    label_name = [n for n in net.list_arguments() if n != "data"][0]
    args = {"data": mx.nd.array(x), label_name: mx.nd.array(lab)}
    grads = {k: mx.nd.zeros(v.shape) for k, v in args.items()}
    ex = net.bind(mx.cpu(), args=args, args_grad=grads)
    ex.forward(is_train=True)
    ref = np.exp(x - x.max(1, keepdims=True))
    ref /= ref.sum(1, keepdims=True)
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), ref, rtol=1e-5)
    ex.backward([mx.nd.ones(x.shape)])
    want = ref.copy()
    want[np.arange(6), lab.astype(int)] -= 1
    np.testing.assert_allclose(ex.grad_dict["data"].asnumpy(), want,
                               rtol=1e-4, atol=1e-5)


def test_legacy_ndarray_op():
    """NDArrayOp flavor (reference ``operator.py:226-257`` — the
    ``_NDArray`` callback op): forward/backward see NDArrays."""

    class ScaleOp(mx.operator.NDArrayOp):
        def forward(self, in_data, out_data):
            out_data[0][:] = in_data[0] * 3.0

        def backward(self, out_grad, in_data, out_data, in_grad):
            in_grad[0][:] = out_grad[0] * 3.0

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]]

    x = np.random.RandomState(0).randn(3, 4).astype("f")
    net = ScaleOp()(mx.sym.Variable("data"))
    ex = net.bind(mx.cpu(), args={"data": mx.nd.array(x)},
                  args_grad={"data": mx.nd.zeros(x.shape)})
    ex.forward(is_train=True)
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), 3 * x, rtol=1e-6)
    ex.backward([mx.nd.ones(x.shape)])
    np.testing.assert_allclose(ex.grad_dict["data"].asnumpy(),
                               np.full_like(x, 3.0), rtol=1e-6)


_CALLBACK_BEHIND_A_FULL_QUEUE = """
import functools
import threading
import jax
import jax.numpy as jnp
import mxnet_tpu as mx

queued = threading.Event()


class Double(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        self.assign(out_data[0], req[0], in_data[0] * 2)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        queued.wait(2.0)    # a slow callback: the caller gets ahead of it
        self.assign(in_grad[0], req[0], out_grad[0] * 2)


@mx.operator.register("double")
class DoubleProp(mx.operator.CustomOpProp):
    def create_operator(self, ctx, in_shapes, in_dtypes):
        return Double()


@functools.partial(jax.jit, static_argnums=1)
def work(g, n=3):       # large enough to be dispatched asynchronously
    for _ in range(n):
        g = jnp.tanh(g @ g)
    return g


x = jnp.full((512, 512), 1e-3, "float32")
work(x).block_until_ready()
work(x, 60).block_until_ready()
net = mx.sym.Custom(mx.sym.Variable("data"), op_type="double")
args = {"data": mx.nd.NDArray(x)}
grads = {"data": mx.nd.zeros((512, 512))}
ex = net.bind(mx.cpu(), args=args, args_grad=grads)
ex.forward(is_train=True)
# a cotangent that is still being computed: the runtime runs a program
# with a callback on the caller's own thread when its inputs are ready
ex.backward([mx.nd.NDArray(work(x, 60))])
outs = [work(grads["data"].data) for _ in range(40)]
queued.set()
jax.block_until_ready(outs)
print("backward and its consumers returned")
"""


def test_custom_op_callback_behind_a_full_queue():
    """The runtime admits a bounded number of computations in flight, and
    one that waits for its input keeps its slot.  A caller that queues
    that many consumers behind a backward whose callback has not
    returned leaves the callback's own NDArray ops waiting for a slot
    for ever (the torch-interop example, on a loaded machine), unless
    the executor awaits a program with a host callback.  In a
    subprocess: no signal reaches a main thread that waits inside the
    runtime."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=root + os.pathsep + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, "-c", _CALLBACK_BEHIND_A_FULL_QUEUE],
        capture_output=True, text=True, timeout=120, env=env)
    assert res.returncode == 0 and "consumers returned" in res.stdout, \
        res.stdout[-1000:] + res.stderr[-2000:]
