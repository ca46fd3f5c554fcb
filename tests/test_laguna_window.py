"""The flash kernels under a causal window (``window`` > 0: query t sees
the keys t - window < s <= t), in interpret mode on the CPU against the
float32 oracle that masks the same pairs: forward and gradients, windows
of 1, 100, 128 and 512 positions and one at or past the sequence, blocks
aligned with the window and not, times that fill whole blocks and that
do not, float32 and bfloat16, and 48 and 64 query heads over 8 key/value
heads through the attention op.  With no window the kernels trace as
before; at or past the sequence a window changes no bit; under one the
grids step over the blocks a window reaches, not the sequence."""
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import obs
from mxnet_tpu.op import registry
from mxnet_tpu.parallel.ring_attention import attention_reference

# the module (the package exports its function under the same name)
fa = importlib.import_module("mxnet_tpu.op.pallas.flash_attention")


def rnd(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape,
                             jnp.float32).astype(dtype)


def value_and_grads(fn, q, k, v, do):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(do)


def assert_near(got, want, tol):
    for g, w in zip(got, want):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * max(np.abs(w).max(), 1.0))


# (t, window, block_q, block_k, dtype)
CASES = [
    pytest.param(256, 1, 128, 128, jnp.float32, id="w1"),
    pytest.param(256, 100, 128, 128, jnp.float32, id="w100-aligned"),
    pytest.param(300, 100, 128, 128, jnp.float32, id="w100-unaligned"),
    pytest.param(384, 128, 128, 128, jnp.bfloat16, id="w128-bf16"),
    pytest.param(512, 128, 128, 256, jnp.float32, id="w128-bk256"),
    pytest.param(512, 200, 256, 128, jnp.bfloat16, id="w200-bq256-bf16"),
    pytest.param(640, 512, 128, 128, jnp.float32, id="w512"),
    pytest.param(700, 512, 256, 256, jnp.bfloat16, id="w512-unaligned-bf16"),
    pytest.param(300, 512, 128, 128, jnp.float32, id="w-past-t"),
]


@pytest.mark.parametrize("t,window,bq,bk,dtype", CASES)
def test_windowed_kernels_are_the_oracle(t, window, bq, bk, dtype):
    shape = (1, t, 2, 64)
    q, k, v, do = (rnd(i, shape, dtype) for i in range(4))

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=bq, block_k=bk, interpret=True)

    def oracle(q, k, v):
        # the oracle in float32 on the same (rounded) operands
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        return attention_reference(*f32, causal=True, window=window)

    got = value_and_grads(kernels, q, k, v, do)
    want = value_and_grads(oracle, q, k, v, do.astype(jnp.float32))
    assert_near(got, want, 2e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("heads,dtype", [(48, jnp.float32),
                                         (64, jnp.bfloat16)])
def test_grouped_heads_in_a_window_through_the_op(heads, dtype):
    """The attention op at Laguna's grouping, 48 or 64 query heads of 128
    over 8 key/value heads, in a window of 100 over 256 positions: the
    kernels against the oracle on the repeated heads; dk and dv come
    back at 8 heads."""
    op = registry.get("_contrib_DotProductAttention")
    params = op.parse_params({"causal": True, "window": 100,
                              "block_q": 128, "block_k": 128})
    ctx = registry.OpContext(is_train=True, platform="cpu")
    q = rnd(0, (1, 256, heads, 128), dtype)
    k, v = (rnd(i, (1, 256, 8, 128), dtype) for i in (1, 2))
    do = rnd(3, (1, 256, heads, 128), dtype)

    def oracle(q, k, v):
        g = heads // 8
        f32 = [x.astype(jnp.float32) for x in (q, jnp.repeat(k, g, 2),
                                                 jnp.repeat(v, g, 2))]
        return attention_reference(*f32, causal=True, window=100)

    got = value_and_grads(lambda *a: op.fn(params, ctx, *a), q, k, v, do)
    want = value_and_grads(oracle, q, k, v, do.astype(jnp.float32))
    assert [g.shape for g in got[2:]] == [(1, 256, 8, 128)] * 2
    assert_near(got, want, 2e-5 if dtype == jnp.float32 else 2e-2)


@pytest.mark.parametrize("shape,dtype", [((1, 300, 2, 64), jnp.float32),
                                         ((1, 512, 4, 64), jnp.bfloat16)])
def test_a_window_at_or_past_the_sequence_changes_no_bit(shape, dtype):
    """Every key is in such a window, so the window's grids step over
    what the causal kernels' do, from the same first block: output and
    gradients are the causal kernels' (``window`` 0) bit for bit."""
    q, k, v, do = (rnd(i, shape, dtype) for i in range(4))

    def run(window):
        return value_and_grads(
            lambda q, k, v: fa.flash_attention(
                q, k, v, causal=True, window=window, block_q=128,
                block_k=128, interpret=True), q, k, v, do)
    causal = run(0)
    for window in (shape[1], 2 * shape[1]):
        for a, b in zip(run(window), causal):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def grids(t, h, window, block, causal=True):
    """The two kernels' grids for the forward and backward of a node."""
    x = jax.ShapeDtypeStruct((1, t, h, 128), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=causal, window=window, block_q=block,
            block_k=block, interpret=False).astype(jnp.float32))
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(x, x, x))
    return [tuple(int(n) for n in g.split(", "))
            for g in re.findall(r"grid=\(([\d, ]+)\)", text)]


def test_the_grids_span_the_window_not_the_sequence():
    """Laguna's window layer, 64 heads of 128 over 8,192 positions in a
    window of 512: at 512 x 512 blocks each q-block visits 2 k-blocks
    and each k-block 2 q-blocks, at 128 x 128 five, where the causal
    kernels step over all 16 or 64 (and, with no window, still do)."""
    assert grids(8192, 64, 512, 512) == [(1, 64, 16, 2), (1, 64, 16, 2)]
    assert grids(8192, 64, 512, 128) == [(1, 64, 64, 5), (1, 64, 64, 5)]
    assert grids(8192, 64, 0, 512) == [(1, 64, 16, 16), (1, 64, 16, 16)]
    assert grids(8192, 48, 0, 128) == [(1, 48, 64, 64), (1, 48, 64, 64)]
    # blocks of two sizes: the steps are counted over the blocks, within
    # ceil((window + block - 1) / other block) + 1 (6 and 4 here)
    assert fa._window_steps(8192, 8192, 256, 128, 512) == (6, 3)


def test_live_share_of_the_visited_tiles():
    """Of the tiles the forward visits, the share of pairs a window of
    512 over 8,192 positions keeps: a half at 512 x 512 blocks, four
    fifths at 128 x 128; a node traced under a window leaves it in the
    gauge, and is counted."""
    live = 8192 * 512 - 512 * 511 // 2
    assert fa.window_live_share(8192, 512, 512, 512) \
        == pytest.approx(live / (31 * 512 * 512))
    assert fa.window_live_share(8192, 512, 128, 128) \
        == pytest.approx(live / (310 * 128 * 128))
    assert round(fa.window_live_share(8192, 512, 512, 512), 3) == 0.5
    assert round(fa.window_live_share(8192, 512, 128, 128), 2) == 0.8
    before = obs.snapshot()["counters"].get("attention.window.nodes", 0)
    op = registry.get("_contrib_DotProductAttention")
    x = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.float32)
    jax.eval_shape(lambda q, k, v: op.fn(
        op.parse_params({"causal": True, "window": 512, "block_q": 256,
                         "block_k": 256}),
        registry.OpContext(is_train=True, platform="cpu"), q, k, v), x, x, x)
    snap = obs.snapshot()
    assert snap["counters"]["attention.window.nodes"] == before + 1
    assert snap["gauges"]["attention.window.live_share"] \
        == pytest.approx(fa.window_live_share(1024, 512, 256, 256))


def test_shape_inference_leaves_the_live_share():
    """A program loaded from the program cache is never traced, so the
    shapes of a windowed node, inferred at bind, leave the live share of
    its tiles too: at the node's own blocks, clamped to the sequence as
    the kernels clamp them, and at the kernels' blocks where it asks
    for none."""
    import mxnet_tpu as mx
    x = mx.sym.Variable("x")

    def infer(t, **kw):
        obs.gauge("attention.window.live_share").set(-1.0)
        node = mx.sym._contrib_DotProductAttention(
            x, x, x, causal=True, window=512, **kw)
        node.infer_shape(x=(1, t, 2, 128))
        return obs.snapshot()["gauges"]["attention.window.live_share"]
    assert infer(2048, block_q=256, block_k=256) \
        == pytest.approx(fa.window_live_share(2048, 512, 256, 256))
    assert infer(8192) == pytest.approx(fa.window_live_share(8192, 512,
                                                             512, 512))
    assert infer(300) == pytest.approx(fa.window_live_share(300, 512,
                                                            384, 384))
    # the oracle visits no tiles: it leaves the gauge alone
    assert infer(2048, flash=False) == -1.0


def test_a_window_is_causal_and_positive():
    q = jnp.zeros((1, 128, 2, 64))
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, q, q, causal=False, window=16)
    op = registry.get("_contrib_DotProductAttention")
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="window"):
        op.fn(op.parse_params({"causal": False, "window": 16}),
              registry.OpContext(is_train=True, platform="cpu"), q, q, q)
