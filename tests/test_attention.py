"""Flash attention (Pallas kernel, interpret mode on the CPU test mesh)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.op.pallas import flash_attention, flash_attention_reference


def _qkv(rng, b, tq, tkv, h, d):
    q = jnp.asarray(rng.normal(0, 1, (b, tq, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, tkv, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, tkv, h, d)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("tq,tkv,causal", [
    (64, 64, False), (64, 64, True),
    (37, 53, False),          # ragged (padding path)
    (100, 100, True),         # ragged + causal
    (32, 128, True),          # cross-attention shapes
])
def test_flash_forward_matches_reference(tq, tkv, causal):
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng, 2, tq, tkv, 3, 16)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    ref = flash_attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# (t_q, t_kv, causal, heads, head, block_q, block_k, dtype): the
# forward test's shapes, the two 48 x 48 cases this test began with, blocks
# that differ, a head of 128, bfloat16 inputs, lengths where the causal
# mask or the padding leaves whole key columns (32 x 128) or key blocks
# (96 x 40: t_q > t_kv) without a single live score, and head counts and
# widths at which the backward takes two, four or one head a grid step
_GRAD_CASES = [
    pytest.param(48, 48, False, 2, 8, 16, 16, jnp.float32,
                 id="48x48"),
    pytest.param(48, 48, True, 2, 8, 16, 16, jnp.float32,
                 id="48x48-causal"),
    pytest.param(37, 53, False, 2, 16, 32, 32, jnp.float32,
                 id="ragged-37x53"),
    pytest.param(100, 100, True, 2, 16, 32, 32, jnp.float32,
                 id="ragged-causal-100x100"),
    pytest.param(32, 128, True, 2, 16, 32, 32, jnp.float32,
                 id="cross-32x128-causal"),
    pytest.param(96, 40, True, 2, 16, 32, 32, jnp.float32,
                 id="masked-96x40-causal"),
    pytest.param(100, 100, True, 2, 16, 32, 16, jnp.float32,
                 id="blocks-32x16-causal"),
    pytest.param(70, 90, False, 2, 16, 16, 64, jnp.float32,
                 id="blocks-16x64"),
    pytest.param(64, 64, True, 2, 128, 32, 32, jnp.float32,
                 id="head-128"),
    pytest.param(64, 64, True, 4, 32, 32, 32, jnp.float32,
                 id="four-heads-of-32-a-step"),
    pytest.param(40, 72, False, 3, 48, 32, 32, jnp.float32,
                 id="three-heads-of-48-one-a-step"),
    pytest.param(64, 64, True, 2, 16, 32, 32, jnp.bfloat16,
                 id="bf16"),
    pytest.param(100, 100, True, 2, 64, 32, 32, jnp.bfloat16,
                 id="bf16-ragged-causal-head-64"),
]


@pytest.mark.parametrize("tq,tkv,causal,h,d,block_q,block_k,dtype",
                         _GRAD_CASES)
def test_flash_gradients_match_reference(tq, tkv, causal, h, d, block_q,
                                         block_k, dtype):
    """dq, dk and dv of the Pallas backward (interpreted here) against
    ``jax.grad`` of the O(T^2) oracle in float32."""
    rng = np.random.RandomState(1)
    q, k, v = _qkv(rng, 2, tq, tkv, h, d)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, block_q=block_q,
            block_k=block_k).astype(jnp.float32)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_reference(
            q, k, v, causal=causal)))

    low = (x.astype(dtype) for x in (q, k, v))
    g = jax.grad(loss_flash, argnums=(0, 1, 2))(*low)
    # the oracle sees what the kernel saw: the inputs as rounded to dtype
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        *(x.astype(dtype).astype(jnp.float32) for x in (q, k, v)))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    for name, a, b in zip(("dq", "dk", "dv"), g, gr):
        assert a.dtype == dtype and a.shape == b.shape, name
        a = np.asarray(a, np.float32)
        b = np.asarray(b)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol, err_msg=name)
        # a key no query may see (past t_q under the causal mask) gets no
        # gradient at all, not a small one
        if name != "dq":
            dead = np.abs(b).max(axis=(0, 2, 3)) == 0
            assert (a[:, dead] == 0).all(), name


def test_flash_bf16_io():
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng, 1, 64, 64, 2, 16)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = flash_attention_reference(q.astype(jnp.float32),
                                    k.astype(jnp.float32),
                                    v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


def test_dot_product_attention_op_nd_and_sym():
    rng = np.random.RandomState(3)
    qn, kn, vn = (rng.normal(0, 1, (2, 40, 2, 8)).astype(np.float32)
                  for _ in range(3))
    # imperative
    out = mx.nd._contrib_DotProductAttention(
        mx.nd.array(qn), mx.nd.array(kn), mx.nd.array(vn),
        causal=True, block_q=16, block_k=16)
    ref = flash_attention_reference(jnp.asarray(qn), jnp.asarray(kn),
                                    jnp.asarray(vn), causal=True)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # symbolic
    q = mx.sym.Variable("q")
    k = mx.sym.Variable("k")
    v = mx.sym.Variable("v")
    sym = mx.sym._contrib_DotProductAttention(q, k, v, causal=True,
                                              block_q=16, block_k=16)
    ex = sym.bind(mx.tpu(), {"q": mx.nd.array(qn), "k": mx.nd.array(kn),
                             "v": mx.nd.array(vn)})
    (o,) = ex.forward()
    np.testing.assert_allclose(o.asnumpy(), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_matches_ring_attention():
    """Single-device flash and multi-device ring agree on the same input."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.ring_attention import ring_attention_sharded
    rng = np.random.RandomState(4)
    q, k, v = _qkv(rng, 2, 64, 64, 2, 8)
    mesh = make_mesh({"seq": 4})
    ring = ring_attention_sharded(q, k, v, mesh, axis="seq", causal=True)
    flash = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(flash),
                               rtol=1e-5, atol=1e-5)
