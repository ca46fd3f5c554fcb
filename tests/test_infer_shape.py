"""Shape inference tests (reference
``tests/python/unittest/test_infer_shape.py``)."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models


def test_mlp_infer():
    data = mx.sym.Variable("data")
    fc1 = mx.symbol.FullyConnected(data, name="fc1", num_hidden=30)
    act = mx.symbol.Activation(fc1, act_type="relu")
    fc2 = mx.symbol.FullyConnected(act, name="fc2", num_hidden=10)
    out = mx.symbol.SoftmaxOutput(fc2, name="sm")
    arg_shapes, out_shapes, _ = out.infer_shape(data=(100, 50))
    shapes = dict(zip(out.list_arguments(), arg_shapes))
    assert shapes["fc1_weight"] == (30, 50)
    assert shapes["fc1_bias"] == (30,)
    assert shapes["fc2_weight"] == (10, 30)
    assert shapes["sm_label"] == (100,)
    assert out_shapes == [(100, 10)]


def test_conv_infer():
    data = mx.sym.Variable("data")
    conv = mx.symbol.Convolution(data, num_filter=16, kernel=(3, 3),
                                 stride=(2, 2), pad=(1, 1), name="conv")
    arg_shapes, out_shapes, _ = conv.infer_shape(data=(4, 3, 32, 32))
    shapes = dict(zip(conv.list_arguments(), arg_shapes))
    assert shapes["conv_weight"] == (16, 3, 3, 3)
    assert out_shapes == [(4, 16, 16, 16)]


def test_backward_infer_from_weight():
    """Weight shape given, data dim inferred (reference
    test_infer_shape.py backward inference)."""
    data = mx.sym.Variable("data")
    fc1 = mx.symbol.FullyConnected(data, name="fc1", num_hidden=30)
    arg_shapes, out_shapes, _ = fc1.infer_shape(data=(10, 50))
    assert out_shapes[0] == (10, 30)


def test_incomplete_infer_partial():
    data = mx.sym.Variable("data")
    fc1 = mx.symbol.FullyConnected(data, name="fc1", num_hidden=30)
    arg_shapes, out_shapes, _ = fc1.infer_shape_partial()
    # with no shapes known, args stay None rather than raising
    assert out_shapes[0] is None or out_shapes[0] == ()


def test_mismatch_raises():
    a = mx.sym.Variable("a")
    b = mx.symbol.elemwise_add(a, a)
    with pytest.raises(mx.MXNetError):
        # inconsistent: elemwise over mismatched shapes
        c = mx.symbol.elemwise_add(mx.sym.Variable("x"), mx.sym.Variable("y"))
        c.infer_shape(x=(2, 3), y=(3, 2))


def test_batchnorm_aux_shapes():
    data = mx.sym.Variable("data")
    bn = mx.symbol.BatchNorm(data, name="bn")
    arg_shapes, out_shapes, aux_shapes = bn.infer_shape(data=(4, 8, 5, 5))
    assert aux_shapes == [(8,), (8,)]
    assert out_shapes[0] == (4, 8, 5, 5)


def test_reshape_infer():
    data = mx.sym.Variable("data")
    r = mx.symbol.Reshape(data, shape=(-1, 6))
    _, out_shapes, _ = r.infer_shape(data=(4, 3, 2))
    assert out_shapes == [(4, 6)]


def test_variable_shape_attr_used():
    v = mx.sym.Variable("v", shape=(5, 5))
    out = mx.symbol.tanh(v)
    _, out_shapes, _ = out.infer_shape()
    assert out_shapes == [(5, 5)]


# ----------------------------------------------------------------------
# the attention op's shape rule: no walk of a Symbol's shapes traces a
# Pallas kernel (set-up walks a Symbol three times, and a trace of the
# flash kernels a node on every walk was most of those walks' time)
_BENCH_CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs")


@pytest.fixture
def no_kernel(monkeypatch):
    """``pl.pallas_call`` counts its calls and refuses each."""
    from jax.experimental import pallas as pl
    calls = []

    def refuse(*args, **kwargs):
        calls.append(kwargs.get("name"))
        raise AssertionError("a walk of shapes traced a Pallas kernel")

    monkeypatch.setattr(pl, "pallas_call", refuse)
    return calls


@pytest.mark.parametrize("config,batch,outputs", [
    pytest.param("gpt2-medium", (8, 1024), [(8192, 50257)],
                 id="gpt2m_train"),
    pytest.param("glm-4.7-flash", (1, 4096), [(4096, 19360), (4096, 19360)],
                 id="glm47flash_train"),
])
def test_walk_of_a_cells_symbol_traces_no_kernel(no_kernel, config, batch,
                                                 outputs):
    """The two token cells' Symbols at their published sizes, 24 and 6
    attention nodes: ``infer_shape`` succeeds with the kernel refused."""
    with open(os.path.join(_BENCH_CONFIGS, config + ".json")) as f:
        symbol = json.load(f)["symbol"]
    sym = models.get_symbol(symbol["network"], **symbol["kwargs"])
    nodes = json.loads(sym.tojson())["nodes"]
    assert sum(n["op"] == "_contrib_DotProductAttention" for n in nodes) \
        == {"gpt2-medium": 24, "glm-4.7-flash": 6}[config]
    arg_shapes, out_shapes, _ = sym.infer_shape(data=batch,
                                                softmax_label=batch)
    assert no_kernel == []
    assert [tuple(s) for s in out_shapes] == outputs
    assert all(s is not None and 0 not in s for s in arg_shapes)


@pytest.mark.parametrize("q,k,v,flash", [
    pytest.param((8, 1024, 16, 64), (8, 1024, 16, 64), (8, 1024, 16, 64),
                 True, id="gpt2m_train"),
    pytest.param((1, 4096, 20, 256), (1, 4096, 20, 256), (1, 4096, 20, 256),
                 True, id="glm47flash_train"),
    pytest.param((2, 128, 4, 64), (2, 384, 4, 64), (2, 384, 4, 64),
                 True, id="t_q-not-t_kv"),
    # the kernels take one width for q, k and v; the plain path does not
    pytest.param((2, 128, 4, 192), (2, 256, 4, 192), (2, 256, 4, 128),
                 False, id="d_v-not-d_qk"),
])
def test_attention_shape_rule_is_what_the_op_body_returns(q, k, v, flash):
    import jax
    from mxnet_tpu.op import registry
    from mxnet_tpu.op.attention import _attention_infer_shape
    op = registry.get("_contrib_DotProductAttention")
    params = op.parse_params({"causal": True, "flash": flash})
    body = jax.eval_shape(
        lambda *xs: op.fn(params, registry.OpContext(), *xs),
        *(jax.ShapeDtypeStruct(s, np.float32) for s in (q, k, v)))
    in_s, out_s, aux_s = op.infer_shape_generic(params, [q, k, v])
    assert out_s == [tuple(body.shape)] == [q[:3] + v[3:]]
    assert in_s == [q, k, v] and aux_s == []
    # an unknown input is the generic path's to refuse
    assert _attention_infer_shape(params, [q, None, v]) is None
    assert _attention_infer_shape(params, [q, k, (0,) * 4]) is None
