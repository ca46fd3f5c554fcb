"""NDArray tests (reference ``tests/python/unittest/test_ndarray.py``)."""
import os
import tempfile

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ndarray import NDArray


def test_creation():
    a = mx.nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert np.all(a.asnumpy() == 0)
    b = mx.nd.ones((2,), dtype="int32")
    assert b.dtype == np.int32
    c = mx.nd.full((2, 2), 7.5)
    assert np.all(c.asnumpy() == 7.5)
    d = mx.nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2)
    e = mx.nd.arange(0, 10, 2)
    assert list(e.asnumpy()) == [0, 2, 4, 6, 8]


def test_elementwise():
    a = mx.nd.array([1.0, 2.0, 3.0])
    b = mx.nd.array([4.0, 5.0, 6.0])
    assert np.allclose((a + b).asnumpy(), [5, 7, 9])
    assert np.allclose((a - b).asnumpy(), [-3, -3, -3])
    assert np.allclose((a * b).asnumpy(), [4, 10, 18])
    assert np.allclose((b / a).asnumpy(), [4, 2.5, 2])
    assert np.allclose((a + 1).asnumpy(), [2, 3, 4])
    assert np.allclose((1 + a).asnumpy(), [2, 3, 4])
    assert np.allclose((2 - a).asnumpy(), [1, 0, -1])
    assert np.allclose((6 / b).asnumpy(), [1.5, 1.2, 1.0])
    assert np.allclose((a ** 2).asnumpy(), [1, 4, 9])
    assert np.allclose((-a).asnumpy(), [-1, -2, -3])


def test_inplace():
    a = mx.nd.ones((2, 2))
    a += 1
    assert np.all(a.asnumpy() == 2)
    a *= 3
    assert np.all(a.asnumpy() == 6)
    a -= 2
    assert np.all(a.asnumpy() == 4)
    a /= 4
    assert np.all(a.asnumpy() == 1)


def test_comparison():
    a = mx.nd.array([1.0, 2.0, 3.0])
    b = mx.nd.array([3.0, 2.0, 1.0])
    assert list((a == b).asnumpy()) == [0, 1, 0]
    assert list((a != b).asnumpy()) == [1, 0, 1]
    assert list((a > b).asnumpy()) == [0, 0, 1]
    assert list((a >= b).asnumpy()) == [0, 1, 1]
    assert list((a < b).asnumpy()) == [1, 0, 0]


def test_slice_view_writethrough():
    """Views write through to their base (reference ndarray.h:284-310)."""
    a = mx.nd.zeros((4, 3))
    s = a[1:3]
    assert s.shape == (2, 3)
    s[:] = 5
    assert np.all(a.asnumpy()[1:3] == 5)
    assert np.all(a.asnumpy()[0] == 0)
    row = a[0]
    row[:] = 7
    assert np.all(a.asnumpy()[0] == 7)


def test_reshape_view():
    a = mx.nd.arange(0, 6)
    r = a.reshape((2, 3))
    assert r.shape == (2, 3)
    r2 = a.reshape((3, -1))
    assert r2.shape == (3, 2)


def test_setitem():
    a = mx.nd.zeros((3, 3))
    a[:] = 1
    assert np.all(a.asnumpy() == 1)
    a[1] = 2
    assert np.all(a.asnumpy()[1] == 2)
    a[0:2] = np.arange(6).reshape(2, 3)
    assert np.allclose(a.asnumpy()[0:2], np.arange(6).reshape(2, 3))


def test_copyto_astype():
    a = mx.nd.array([1.5, 2.5])
    b = mx.nd.zeros((2,))
    a.copyto(b)
    assert np.allclose(b.asnumpy(), [1.5, 2.5])
    c = a.astype("int32")
    assert c.dtype == np.int32


def test_save_load_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "x.params")
        arrs = {"w": mx.nd.array(np.random.randn(3, 4).astype("f")),
                "b": mx.nd.array(np.random.randn(4).astype("f"))}
        mx.nd.save(fname, arrs)
        loaded = mx.nd.load(fname)
        assert set(loaded) == {"w", "b"}
        for k in arrs:
            assert np.allclose(loaded[k].asnumpy(), arrs[k].asnumpy())
        # list form
        mx.nd.save(fname, [arrs["w"]])
        loaded = mx.nd.load(fname)
        assert isinstance(loaded, list)
        assert np.allclose(loaded[0].asnumpy(), arrs["w"].asnumpy())


def test_binary_format_layout():
    """The on-disk header matches the reference format magic."""
    import struct
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "x.params")
        mx.nd.save(fname, {"a": mx.nd.ones((2,))})
        with open(fname, "rb") as f:
            magic, _ = struct.unpack("<QQ", f.read(16))
        assert magic == 0x112


def test_generated_ops():
    a = mx.nd.array(np.abs(np.random.randn(3, 4)).astype("f") + 0.5)
    assert np.allclose(mx.nd.sqrt(a).asnumpy(), np.sqrt(a.asnumpy()),
                       atol=1e-6)
    assert np.allclose(mx.nd.log(a).asnumpy(), np.log(a.asnumpy()), atol=1e-6)
    assert np.allclose(mx.nd.sum(a).asnumpy(), a.asnumpy().sum(), atol=1e-5)
    assert np.allclose(mx.nd.dot(a, mx.nd.transpose(a)).asnumpy(),
                       a.asnumpy() @ a.asnumpy().T, atol=1e-5)


def test_wait_and_context():
    a = mx.nd.ones((2, 2))
    a.wait_to_read()
    mx.nd.waitall()
    assert a.context.device_type in ("cpu", "tpu", "gpu")


@pytest.mark.parametrize("cpu_chosen", [True, False])
def test_tpu_context_never_hides_the_cpu(monkeypatch, cpu_chosen):
    """A ``tpu`` context is a host device only where the CPU was chosen
    outright (as conftest does); otherwise no accelerator is an error,
    not a quiet CPU."""
    from mxnet_tpu import base
    monkeypatch.setattr(base, "_cpu_chosen", lambda: cpu_chosen)
    if cpu_chosen:
        assert mx.tpu().jax_device().platform == "cpu"
        assert mx.gpu(1).jax_device().platform == "cpu"
    else:
        with pytest.raises(mx.MXNetError, match="no accelerator"):
            mx.tpu().jax_device()
        assert base.default_context() == mx.cpu()
    assert mx.cpu().jax_device().platform == "cpu"


def test_truthiness_raises():
    a = mx.nd.ones((2,))
    with pytest.raises(mx.MXNetError):
        bool(a)


def test_sampling():
    mx.random.seed(42)
    u = mx.nd.uniform(low=0, high=1, shape=(1000,))
    vals = u.asnumpy()
    assert vals.min() >= 0 and vals.max() <= 1
    assert 0.4 < vals.mean() < 0.6
    n = mx.nd.normal(loc=5, scale=0.1, shape=(1000,))
    assert 4.9 < n.asnumpy().mean() < 5.1
    # determinism with same seed
    mx.random.seed(7)
    a = mx.nd.uniform(shape=(5,)).asnumpy()
    mx.random.seed(7)
    b = mx.nd.uniform(shape=(5,)).asnumpy()
    assert np.allclose(a, b)


def test_onehot_encode():
    idx = mx.nd.array([0, 2, 1])
    out = mx.nd.zeros((3, 3))
    mx.nd.onehot_encode(idx, out)
    assert np.allclose(out.asnumpy(), np.eye(3)[[0, 2, 1]])
