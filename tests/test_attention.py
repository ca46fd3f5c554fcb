"""Flash attention (Pallas kernel, interpret mode on the CPU test mesh)."""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.op.pallas import flash_attention, flash_attention_reference

# the module: the package's attribute of this name is the function
_fa = importlib.import_module("mxnet_tpu.op.pallas.flash_attention")


def _qkv(rng, b, tq, tkv, h, d):
    q = jnp.asarray(rng.normal(0, 1, (b, tq, h, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, tkv, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, tkv, h, d)).astype(np.float32))
    return q, k, v


def _traced_by_path():
    c = mx.obs.snapshot()["counters"]
    return {path: c.get("attention.flash." + path, 0)
            for path in ("in_place", "folded")}


def _assert_path(before, path):
    """The counters say which way the node just traced was laid out."""
    after = _traced_by_path()
    other = "folded" if path == "in_place" else "in_place"
    assert after[path] > before[path], (path, before, after)
    assert after[other] == before[other], (path, before, after)


# (t_q, t_kv, causal, heads, head, block_q, block_k, dtype, path): the
# five shapes this test began with (three heads of 16: one a step, a
# fold), then what the kernels read in place (a group of heads whole
# lane tiles wide, the sequences whole blocks): two heads of 64 a step,
# four of 32, one of 128, one of 256, t_q != t_kv, blocks that differ;
# and what they cannot: an odd head count at 64 wide, a group of two
# 48-wide heads (96 lanes), a sequence that needs padding
_FWD_CASES = [
    pytest.param(64, 64, False, 3, 16, 32, 32, jnp.float32, "folded",
                 id="64-64-False"),
    pytest.param(64, 64, True, 3, 16, 32, 32, jnp.float32, "folded",
                 id="64-64-True"),
    pytest.param(37, 53, False, 3, 16, 32, 32, jnp.float32, "folded",
                 id="37-53-False"),          # ragged (padding path)
    pytest.param(100, 100, True, 3, 16, 32, 32, jnp.float32, "folded",
                 id="100-100-True"),         # ragged + causal
    pytest.param(32, 128, True, 3, 16, 32, 32, jnp.float32, "folded",
                 id="32-128-True"),          # cross-attention shapes
    pytest.param(64, 64, True, 4, 64, 32, 32, jnp.float32, "in_place",
                 id="in-place-two-heads-of-64-causal"),
    pytest.param(64, 64, False, 4, 64, 32, 32, jnp.bfloat16, "in_place",
                 id="in-place-two-heads-of-64-bf16"),
    pytest.param(64, 64, True, 8, 32, 32, 32, jnp.float32, "in_place",
                 id="in-place-four-heads-of-32-causal"),
    pytest.param(64, 64, True, 2, 128, 32, 32, jnp.bfloat16, "in_place",
                 id="in-place-head-128-causal-bf16"),
    pytest.param(64, 64, False, 2, 128, 32, 32, jnp.float32, "in_place",
                 id="in-place-head-128"),
    pytest.param(64, 64, True, 2, 256, 32, 32, jnp.bfloat16, "in_place",
                 id="in-place-head-256-causal-bf16"),
    pytest.param(32, 96, False, 2, 256, 32, 32, jnp.float32, "in_place",
                 id="in-place-head-256-cross-32x96"),
    pytest.param(32, 96, True, 2, 64, 32, 32, jnp.float32, "in_place",
                 id="in-place-cross-32x96-causal"),
    pytest.param(96, 32, True, 2, 64, 32, 32, jnp.float32, "in_place",
                 id="in-place-cross-96x32-causal"),
    pytest.param(64, 128, False, 2, 64, 32, 64, jnp.float32, "in_place",
                 id="in-place-blocks-32x64"),
    pytest.param(64, 64, True, 3, 64, 32, 32, jnp.float32, "folded",
                 id="folded-odd-head-count-at-64"),
    pytest.param(64, 64, True, 2, 48, 32, 32, jnp.float32, "folded",
                 id="folded-group-of-96-lanes"),
    pytest.param(100, 100, True, 2, 64, 32, 32, jnp.float32, "folded",
                 id="folded-ragged-causal-heads-of-64"),
    pytest.param(70, 64, False, 2, 128, 32, 32, jnp.bfloat16, "folded",
                 id="folded-ragged-queries-head-128-bf16"),
]


@pytest.mark.parametrize("tq,tkv,causal,h,d,block_q,block_k,dtype,path",
                         _FWD_CASES)
def test_flash_forward_matches_reference(tq, tkv, causal, h, d, block_q,
                                         block_k, dtype, path):
    """The forward kernel's two outputs: O against the O(T^2) oracle,
    and ``lse``, in the ``[b*h/g, g, t]`` rows the backward reads,
    against the log of the plain softmax's denominator."""
    rng = np.random.RandomState(0)
    q, k, v = (x.astype(dtype) for x in _qkv(rng, 2, tq, tkv, h, d))
    before = _traced_by_path()
    out = flash_attention(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k)
    _assert_path(before, path)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    ref = flash_attention_reference(qf, kf, vf, causal=causal)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)

    bq, bk, g, in_place = _fa._plan(tq, tkv, h, d, block_q, block_k)
    assert in_place == (path == "in_place")
    _, lse = _fa._fwd_call(q, k, v, causal, d ** -0.5, bq, bk, g, in_place,
                           True)
    assert lse.shape[:2] == (2 * h // g, g) and lse.dtype == jnp.float32
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * d ** -0.5
    if causal:
        s = jnp.where(jnp.arange(tq)[:, None] >= jnp.arange(tkv)[None, :],
                      s, -jnp.inf)
    lse_ref = jax.scipy.special.logsumexp(s, axis=-1)        # [b, h, t_q]
    got = np.asarray(lse).reshape(2, h, -1)[:, :, :tq]
    np.testing.assert_allclose(got, np.asarray(lse_ref),
                               rtol=1e-4, atol=1e-4)


# (t_q, t_kv, causal, heads, head, block_q, block_k, dtype, path): the
# forward test's first shapes, the two 48 x 48 cases this test began with,
# blocks that differ, a head of 128, bfloat16 inputs, lengths where the
# causal mask or the padding leaves whole key columns (32 x 128) or key
# blocks (96 x 40: t_q > t_kv) without a single live score, head counts
# and widths at which the kernels take two, four or one head a grid
# step; then the forward test's in-place and folded layouts
_GRAD_CASES = [
    pytest.param(48, 48, False, 2, 8, 16, 16, jnp.float32, "folded",
                 id="48x48"),
    pytest.param(48, 48, True, 2, 8, 16, 16, jnp.float32, "folded",
                 id="48x48-causal"),
    pytest.param(37, 53, False, 2, 16, 32, 32, jnp.float32, "folded",
                 id="ragged-37x53"),
    pytest.param(100, 100, True, 2, 16, 32, 32, jnp.float32, "folded",
                 id="ragged-causal-100x100"),
    pytest.param(32, 128, True, 2, 16, 32, 32, jnp.float32, "folded",
                 id="cross-32x128-causal"),
    pytest.param(96, 40, True, 2, 16, 32, 32, jnp.float32, "folded",
                 id="masked-96x40-causal"),
    pytest.param(100, 100, True, 2, 16, 32, 16, jnp.float32, "folded",
                 id="blocks-32x16-causal"),
    pytest.param(70, 90, False, 2, 16, 16, 64, jnp.float32, "folded",
                 id="blocks-16x64"),
    pytest.param(64, 64, True, 2, 128, 32, 32, jnp.float32, "in_place",
                 id="head-128"),
    pytest.param(64, 64, True, 4, 32, 32, 32, jnp.float32, "in_place",
                 id="four-heads-of-32-a-step"),
    pytest.param(40, 72, False, 3, 48, 32, 32, jnp.float32, "folded",
                 id="three-heads-of-48-one-a-step"),
    pytest.param(64, 64, True, 2, 16, 32, 32, jnp.bfloat16, "folded",
                 id="bf16"),
    pytest.param(100, 100, True, 2, 64, 32, 32, jnp.bfloat16, "folded",
                 id="bf16-ragged-causal-head-64"),
] + [case for case in _FWD_CASES if case.id.startswith(("in-", "folded-"))]


@pytest.mark.parametrize("tq,tkv,causal,h,d,block_q,block_k,dtype,path",
                         _GRAD_CASES)
def test_flash_gradients_match_reference(tq, tkv, causal, h, d, block_q,
                                         block_k, dtype, path):
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
    before = _traced_by_path()
    g = jax.grad(loss_flash, argnums=(0, 1, 2))(*low)
    _assert_path(before, path)
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


def test_layout_counters_count_what_was_traced():
    """``attention.flash.in_place`` / ``.folded`` rise once for each
    node traced, by the layout its shapes gave it, and a compiled
    program run again traces, and counts, nothing."""
    def node(shape, t_kv=None):
        q = jnp.zeros(shape, jnp.bfloat16)
        k = jnp.zeros((shape[0], t_kv or shape[1]) + shape[2:], jnp.bfloat16)
        return flash_attention(q, k, k, causal=True)

    start = _traced_by_path()
    # GPT-2 medium's and GLM-4.7-Flash's nodes, and a wide single head
    for shape in ((8, 1024, 16, 64), (1, 4096, 20, 256), (1, 512, 1, 128)):
        jax.eval_shape(lambda: node(shape))
    # 1,000 positions need padding; 3 heads of 64 and 2 of 48 are groups
    # of 64 and 96 lanes; 1,280 keys are not whole blocks of 512
    for shape, t_kv in (((4, 1000, 8, 64), None), ((1, 512, 3, 64), None),
                        ((1, 512, 2, 48), None), ((1, 1024, 2, 64), 1280)):
        jax.eval_shape(lambda: node(shape, t_kv))
    got = _traced_by_path()
    assert got["in_place"] - start["in_place"] == 3
    assert got["folded"] - start["folded"] == 4

    step = jax.jit(lambda q: flash_attention(q, q, q, block_q=32,
                                             block_k=32))
    q = jnp.ones((1, 64, 2, 64), jnp.float32)
    step(q), step(q)
    assert _traced_by_path()["in_place"] - got["in_place"] == 1


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
