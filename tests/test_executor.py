"""Executor tests (reference ``tests/python/unittest/test_executor.py``)."""
import numpy as np
import pytest

import mxnet_tpu as mx


def test_bind_forward_backward():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = a * b + a
    av = np.random.randn(3, 4).astype("f")
    bv = np.random.randn(3, 4).astype("f")
    exe = c.bind(mx.cpu(), {"a": mx.nd.array(av), "b": mx.nd.array(bv)},
                 args_grad={"a": mx.nd.zeros((3, 4)),
                            "b": mx.nd.zeros((3, 4))})
    out = exe.forward(is_train=True)[0].asnumpy()
    assert np.allclose(out, av * bv + av, atol=1e-6)
    og = np.random.randn(3, 4).astype("f")
    exe.backward(mx.nd.array(og))
    assert np.allclose(exe.grad_dict["a"].asnumpy(), og * (bv + 1), atol=1e-5)
    assert np.allclose(exe.grad_dict["b"].asnumpy(), og * av, atol=1e-5)


def test_grad_req_add():
    a = mx.sym.Variable("a")
    out = mx.symbol.square(a)
    av = np.random.randn(2, 2).astype("f")
    ga = mx.nd.ones((2, 2))
    exe = out.bind(mx.cpu(), {"a": mx.nd.array(av)}, args_grad={"a": ga},
                   grad_req="add")
    exe.forward(is_train=True)
    exe.backward(mx.nd.ones((2, 2)))
    assert np.allclose(ga.asnumpy(), 1 + 2 * av, atol=1e-5)


def test_grad_req_null():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    out = a * b
    exe = out.bind(mx.cpu(), {"a": mx.nd.ones((2,)), "b": mx.nd.ones((2,))},
                   args_grad={"a": mx.nd.zeros((2,))},
                   grad_req={"a": "write", "b": "null"})
    exe.forward(is_train=True)
    exe.backward(mx.nd.ones((2,)))
    assert np.allclose(exe.grad_dict["a"].asnumpy(), [1, 1])


def test_simple_bind():
    x = mx.sym.Variable("x")
    fc = mx.symbol.FullyConnected(x, num_hidden=4, name="fc")
    exe = fc.simple_bind(ctx=mx.cpu(), x=(2, 3))
    assert exe.arg_dict["fc_weight"].shape == (4, 3)
    exe.forward()
    assert exe.outputs[0].shape == (2, 4)


def test_forward_kwargs_update():
    x = mx.sym.Variable("x")
    out = mx.symbol.square(x)
    exe = out.simple_bind(ctx=mx.cpu(), x=(2, 2))
    o1 = exe.forward(x=np.full((2, 2), 2.0, dtype="f"))[0].asnumpy()
    assert np.allclose(o1, 4)
    o2 = exe.forward(x=np.full((2, 2), 3.0, dtype="f"))[0].asnumpy()
    assert np.allclose(o2, 9)


def test_reshape():
    x = mx.sym.Variable("x")
    fc = mx.symbol.FullyConnected(x, num_hidden=4, name="fc")
    exe = fc.simple_bind(ctx=mx.cpu(), x=(2, 3))
    exe.arg_dict["fc_weight"][:] = 1.0
    new_exe = exe.reshape(x=(5, 3))
    assert new_exe.arg_dict["x"].shape == (5, 3)
    # weights carried over
    assert np.allclose(new_exe.arg_dict["fc_weight"].asnumpy(), 1.0)
    new_exe.forward()
    assert new_exe.outputs[0].shape == (5, 4)


def test_output_dict():
    x = mx.sym.Variable("x")
    out = mx.symbol.tanh(x, name="t")
    exe = out.simple_bind(ctx=mx.cpu(), x=(2, 2))
    exe.forward()
    assert "t_output" in exe.output_dict


def test_monitor_callback():
    x = mx.sym.Variable("x")
    h = mx.symbol.tanh(x, name="t")
    out = mx.symbol.square(h, name="s")
    exe = out.simple_bind(ctx=mx.cpu(), x=(2, 2))
    seen = []
    exe.install_monitor(lambda name, arr: seen.append(name))
    exe.forward()
    assert "t_output" in seen and "s_output" in seen


def test_partial_forward():
    """PartialForward contract (reference ``executor.h:44-51``): issue
    one forward node per call with increasing step until 0 left; final
    outputs match a whole forward()."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    rng = np.random.RandomState(0)
    args = {"data": mx.nd.array(rng.randn(3, 5).astype("f")),
            "fc_weight": mx.nd.array(rng.randn(4, 5).astype("f")),
            "fc_bias": mx.nd.zeros((4,)),
            "fc2_weight": mx.nd.array(rng.randn(2, 4).astype("f")),
            "fc2_bias": mx.nd.zeros((2,))}
    ex = net.bind(mx.cpu(), args=args)
    want = ex.forward(is_train=False)[0].asnumpy()

    step = 0
    left = ex.partial_forward(is_train=False, step=step)
    steps = 1
    while left:
        step += 1
        left = ex.partial_forward(is_train=False, step=step)
        steps += 1
    assert steps == 3            # fc, tanh, fc2
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), want, rtol=1e-6)


def test_partial_forward_ordering_and_invalidation():
    """Out-of-order steps raise; a full forward() supersedes an
    in-flight partial sequence (no stale mixed-state outputs)."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                name="fc")
    net = mx.sym.Activation(net, act_type="relu")
    rng = np.random.RandomState(1)
    args = {"data": mx.nd.array(rng.randn(2, 4).astype("f")),
            "fc_weight": mx.nd.array(rng.randn(3, 4).astype("f")),
            "fc_bias": mx.nd.zeros((3,))}
    ex = net.bind(mx.cpu(), args=args)

    # steps must be issued in order from 0
    with pytest.raises(Exception):
        ex.partial_forward(is_train=False, step=1)

    # start a partial run, then interrupt it with a full forward on new
    # data; the old sequence must not resume silently
    ex.partial_forward(is_train=False, step=0)
    args["data"][:] = rng.randn(2, 4).astype("f")
    want = ex.forward(is_train=False)[0].asnumpy()
    with pytest.raises(Exception):
        ex.partial_forward(is_train=False, step=1)   # stale sequence gone
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), want, rtol=1e-6)


def test_partial_forward_cold_out_of_range_raises():
    """A too-large step with no active sequence is an ordering error,
    not 'done' — returning 0 would let the caller read stale outputs."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                name="fc")
    rng = np.random.RandomState(2)
    args = {"data": mx.nd.array(rng.randn(2, 4).astype("f")),
            "fc_weight": mx.nd.array(rng.randn(3, 4).astype("f")),
            "fc_bias": mx.nd.zeros((3,))}
    ex = net.bind(mx.cpu(), args=args)
    with pytest.raises(Exception):
        ex.partial_forward(is_train=False, step=99)
    # after a completed sequence, an off-the-end step still reads as done
    left, step = 1, 0
    left = ex.partial_forward(is_train=False, step=0)
    while left:
        step += 1
        left = ex.partial_forward(is_train=False, step=step)
    assert ex.partial_forward(is_train=False, step=step + 1) == 0
    # ...but a full forward invalidates that too
    ex.forward(is_train=False)
    with pytest.raises(Exception):
        ex.partial_forward(is_train=False, step=99)


def test_eval_forward_skips_key_derivation(monkeypatch):
    """Train-only noise ops (Dropout) must not cost per-forward PRNG
    derivation at is_train=False — every eager key op is a dispatch
    of its own (the round-4 inference fix).  Samplers
    (rng_in_eval) must still draw fresh keys every forward."""
    from mxnet_tpu import random as mxrandom

    calls = {"n": 0}
    real = mxrandom.next_key

    def counting_next_key():
        calls["n"] += 1
        return real()
    monkeypatch.setattr(mxrandom, "next_key", counting_next_key)

    net = mx.sym.Dropout(mx.sym.Variable("data"), p=0.5)
    ex = net.simple_bind(mx.cpu(), grad_req="null", data=(2, 8))
    ex.arg_dict["data"][:] = np.ones((2, 8), "f")
    for _ in range(3):
        ex.forward(is_train=False)
    assert calls["n"] == 0, "eval forward of a train-only-noise " \
        "program must reuse the cached const key"
    ex.forward(is_train=True)
    assert calls["n"] == 1, "train forward must derive a fresh key"

    calls["n"] = 0
    samp = mx.sym.Group([mx.sym.uniform(shape=(2, 2))])
    sex = samp.simple_bind(mx.cpu(), grad_req="null")
    a = sex.forward(is_train=False)[0].asnumpy().copy()
    b = sex.forward(is_train=False)[0].asnumpy().copy()
    assert calls["n"] == 2, "sampler eval forwards must draw fresh keys"
    assert not np.allclose(a, b), "sampler eval draws must differ"


def test_load_general_adopts_whole_batch_buffer():
    """Whole-batch same-dtype same-device loads must adopt the source
    buffer (zero dispatched ops) instead of slicing — the other half of
    the round-4 dispatch fix (executor_manager._load_general)."""
    from mxnet_tpu import io
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))], for_training=False)
    mod.init_params(mx.init.Uniform(0.1))
    src = mx.nd.array(np.random.RandomState(0).rand(4, 8).astype("f"))
    mod.forward(io.DataBatch(data=[src],
                             label=[mx.nd.zeros((4,))]), is_train=False)
    bound = mod._exec_group.execs[0].arg_dict["data"]
    assert bound.data is src.data, \
        "fast path must alias the caller's buffer, not copy"
    # mismatched dtype still goes through the casting copy path
    src16 = src.astype("float16")
    mod.forward(io.DataBatch(data=[src16],
                             label=[mx.nd.zeros((4,))]), is_train=False)
    bound = mod._exec_group.execs[0].arg_dict["data"]
    assert bound.data is not src16.data
    assert str(bound.dtype) == "float32"
