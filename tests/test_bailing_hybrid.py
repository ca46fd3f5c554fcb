"""Ling-3.0-flash through the Symbol path: the chunked gated delta rule
against the token-by-token recurrence, the short convolution, the
group-limited router, attention at unequal head widths, a chip's share
of heads and experts against the whole layer, and the tiny model through
``Module``'s fused step against the benchmark's plain reference
(``benchmark/reference/ling-3.0-flash.py``, loaded by path)."""
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, obs
from mxnet_tpu import name as mxname
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import bailing_hybrid
from mxnet_tpu.op import registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(BENCH, "reference", "ling-3.0-flash.py"),
                 "ling_reference")


@pytest.fixture(scope="module")
def refsteps():
    return _load(os.path.join(BENCH, "lib", "refsteps.py"), "ling_refsteps")


def published():
    with open(os.path.join(BENCH, "configs", "ling-3.0-flash.json")) as f:
        return json.load(f)


B, T, LR = 2, 128, 0.02
# the builder's defaults: three blocks (a dense and an expert one mixing
# by the delta rule, an expert one by latent attention at 24 / 16 wide
# heads), d 64, 2 heads of 16, 32 experts in 4 groups
TINY = dict(hidden_size=64, num_attention_heads=2, head_dim=16,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=160, moe_intermediate_size=48,
            num_experts=4, num_experts_per_tok=4, n_group=4, topk_group=2,
            num_hidden_layers=3, vocab_size=512, layer_group_size=3)


def tiny_cfg(**over):
    """The published file cut to the builder's defaults: published
    layers 0 (dense), 3 and 5 (the last of a period of 3: MLA) kept, 4
    of 32 experts and 2 heads held, 128 positions (two chunks)."""
    cfg = published()
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], num_experts=32)
    cfg["deployment"] = dict(cfg["deployment"], layers_kept=[0, 3, 5])
    cfg["input"] = {"kind": "tokens", "seq_len": T, "vocab": 512}
    cfg.update(over)
    return cfg


def op_fn(name, **kwargs):
    """The registered op's body as a function of arrays."""
    op = registry.get(name)
    params = op.parse_params(kwargs)
    ctx = registry.OpContext(is_train=True, platform="cpu")

    def fn(*arrays):
        outs, aux = op.apply(params, ctx, *arrays)
        return outs[0] if len(outs) == 1 and not aux else (outs, aux)
    return fn


def rnd(seed, *shape, scale=1.0, dtype=jnp.float32):
    x = scale * jax.random.normal(jax.random.key(seed), shape, jnp.float32)
    return x.astype(dtype)


def close(got, want, tol=2e-5):
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(np.abs(want).max(), 1e-6))


# ----------------------------------------------------------------------
# the gated delta rule
def rule_operands(seed, b, t, h=2, dk=16, dv=12, decay="slow"):
    """Unit q and k, a log decay between -5 and 0 (``fast``: mostly near
    -5, 320 over a chunk, where e^-G leaves float32; ``at-bound``: -5 on
    every step and channel, the worst a bound of -5 allows, where the
    product form's factors reach e^+-40), beta in (0, 1)."""
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    g = -5 * jax.nn.sigmoid(3 * rnd(seed + 3, b, t, h, dk)
                            + (2.0 if decay == "fast" else -3.0))
    if decay == "at-bound":
        g = jnp.full_like(g, -5.0)
    return (unit(rnd(seed, b, t, h, dk)), unit(rnd(seed + 1, b, t, h, dk)),
            rnd(seed + 2, b, t, h, dv), g,
            jax.nn.sigmoid(rnd(seed + 4, b, t, h)))


@pytest.mark.parametrize("decay", ["slow", "fast", "at-bound"],
                         ids=["slow", "fast-decay", "at-bound"])
@pytest.mark.parametrize("chunks", [2, 3])
@pytest.mark.parametrize("bound", [None, -5.0], ids=["none", "bound-5"])
def test_chunked_rule_is_the_token_by_token_recurrence(bound, chunks, decay,
                                                       ref):
    """Value and the gradients of all five operands against the
    reference's scan over positions, at two and three chunks of 64, a
    batch of two rows; with no gate bound declared (the elementwise
    diagonal blocks) and with -5 (the product form)."""
    t = 64 * chunks
    args = rule_operands(10 * chunks, 2, t, decay=decay)
    rule = op_fn("_contrib_GatedDeltaRule", scale=0.25, lower_bound=bound)
    want_fn = lambda q, *rest: ref.delta_rule(q * 0.25, *rest)  # noqa: E731
    close(rule(*args), want_fn(*args))
    seed = rnd(5, 2, t, 2, 12)
    got = jax.grad(lambda *a: jnp.sum(rule(*a) * seed),
                   argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(want_fn(*a) * seed),
                    argnums=range(5))(*args)
    # at -5 on every step the decay's gradient is small beside the terms
    # it sums, and its error is the chunked form's with either diagonal:
    # against a float64 recurrence 3.5e-5 and 4.1e-5 of its norm with no
    # bound (PR 37's code), 7.7e-5 and 1.0e-4 with one (the token-by-token
    # float32 reference: 1e-7)
    tol = {"g": 2e-4} if decay == "at-bound" else {}
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        assert np.linalg.norm(a - b) \
            <= tol.get(name, 2e-5) * np.linalg.norm(b), name


def _parents_decayed_products(rows, k, G):
    """``op/delta_rule.py: _decayed_products`` as it stood before the
    gate bound (PR 37's tree), line for line."""
    from mxnet_tpu.op.delta_rule import _BLOCK, _mm
    lead, (C, K) = G.shape[:-2], G.shape[-2:]
    nb = C // _BLOCK
    blocks = lambda x: x.reshape(lead + (nb, _BLOCK, K))    # noqa: E731
    Gb, rb, kb = blocks(G), blocks(rows), blocks(k)
    # a block on the diagonal, from the differences themselves
    low = jnp.tril(jnp.ones((_BLOCK, _BLOCK), bool))[:, :, None]
    diff = jnp.where(low, Gb[..., :, None, :] - Gb[..., None, :, :],
                     -jnp.inf)
    diag = jnp.sum(rb[..., :, None, :] * kb[..., None, :, :]
                   * jnp.exp(diff), axis=-1)              # [.., nb, B, B]
    out = (diag[..., :, :, None, :]
           * jnp.eye(nb, dtype=G.dtype)[:, None, :, None]
           ).reshape(lead + (C, C))
    if nb == 1:
        return out
    # the blocks below: both factors against the running sum at the
    # rows' block start, which lies between the two positions
    start = jnp.concatenate([jnp.zeros_like(Gb[..., :1, 0, :]),
                             Gb[..., :-1, -1, :]], axis=-2)   # [.., nb, K]
    rfac = rb * jnp.exp(Gb - start[..., :, None, :])
    before = (jnp.arange(C)[None, :]
              < _BLOCK * jnp.arange(nb)[:, None])[:, :, None]  # [nb, C, 1]
    cfac = k[..., None, :, :] * jnp.exp(jnp.where(
        before, start[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    below = _mm("...isc,...irc->...isr", rfac, cfac)          # [.., nb, B, C]
    return out + below.reshape(lead + (C, C))


def _rule_jaxpr(**params):
    """The jaxpr of the op's value and the gradients of its five
    operands, 2 x 128 positions (chunks of 64: 4 blocks) of 2 heads of
    24 channels, as text."""
    args = rule_operands(70, 2, 128, dk=24)
    rule = op_fn("_contrib_GatedDeltaRule", **params)
    fn = jax.value_and_grad(lambda *a: jnp.sum(rule(*a)), argnums=range(5))
    return str(jax.make_jaxpr(fn)(*args))


def test_a_bound_takes_the_16_x_16_x_d_tiles_out_of_value_and_gradient(
        monkeypatch):
    """With a gate bound, no [.., 4 blocks, 16, 16, 24] array is left in
    the jaxpr of the value or of the gradients; with none the tiles are
    there and the jaxpr is the parent's, equation for equation; a bound
    under -9 is no bound."""
    from mxnet_tpu.op import delta_rule
    tile = re.compile(r"\[[\d,]*4,16,16,24\]")
    bounded = _rule_jaxpr(lower_bound=-5.0)
    assert not tile.search(bounded) and "dot_general" in bounded
    assert tile.search(_rule_jaxpr(lower_bound=-9.0)) is None
    plain = _rule_jaxpr()
    assert tile.search(plain)
    assert _rule_jaxpr(lower_bound=-12.0) == plain
    monkeypatch.setattr(
        delta_rule, "_decayed_products",
        lambda rows, k, G, lower_bound=None:
        _parents_decayed_products(rows, k, G))
    assert _rule_jaxpr() == plain and len(plain) > 1000


def test_no_state_crosses_the_rows_of_a_batch(ref):
    """A row's result is what the row gives alone, and the state that
    the first chunk leaves reaches the second."""
    args = rule_operands(30, 2, 128)
    rule = op_fn("_contrib_GatedDeltaRule")
    both = rule(*args)
    for row in (0, 1):
        alone = rule(*(a[row:row + 1] for a in args))
        np.testing.assert_array_equal(np.asarray(both[row]),
                                      np.asarray(alone[0]))
    late = rule(*(a[:, 64:] for a in args))       # the state lost
    assert np.abs(np.asarray(both[:, 64:] - late)).max() > 1e-3


def test_rule_in_bfloat16_keeps_its_type_and_float32_arithmetic(ref):
    args = rule_operands(40, 1, 128)
    low = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    out = op_fn("_contrib_GatedDeltaRule")(*low)
    assert out.dtype == jnp.bfloat16
    want = ref.delta_rule(low[0].astype(jnp.float32) * 0.25,
                          *(a.astype(jnp.float32) for a in low[1:]))
    close(out, want, tol=1e-2)


def test_rule_refuses_a_length_that_is_not_whole_chunks_by_name():
    args = rule_operands(50, 1, 96)
    with pytest.raises(MXNetError, match="_contrib_GatedDeltaRule.*96"):
        op_fn("_contrib_GatedDeltaRule")(*args)
    q = mx.sym.Variable("q")
    node = mx.sym._contrib_GatedDeltaRule(
        q, mx.sym.Variable("k"), mx.sym.Variable("v"), mx.sym.Variable("g"),
        mx.sym.Variable("b"), name="core")
    with pytest.raises(MXNetError, match="multiple of the chunk"):
        node.infer_shape(q=(1, 96, 2, 16), k=(1, 96, 2, 16),
                         v=(1, 96, 2, 16))
    # the shape rule: the gate's and beta's shapes from q, k and v, the
    # value's width out, no trace of the body
    before = obs.snapshot()["counters"].get("attention.kda.nodes", 0)
    arg_s, out_s, _ = node.infer_shape(q=(3, 128, 2, 16), k=(3, 128, 2, 16),
                                       v=(3, 128, 2, 12))
    assert dict(zip(node.list_arguments(), arg_s))["g"] == (3, 128, 2, 16)
    assert dict(zip(node.list_arguments(), arg_s))["b"] == (3, 128, 2)
    assert out_s == [(3, 128, 2, 12)]
    assert obs.snapshot()["counters"].get("attention.kda.nodes", 0) == before


# ----------------------------------------------------------------------
# the short convolution, the rotary pairing, the head's L2 norm
def test_short_conv_is_causal_and_the_four_tap_sum():
    x, w = rnd(60, 2, 10, 6), rnd(61, 6, 4)
    conv = op_fn("_contrib_ShortConv", kernel=4)
    y = np.asarray(conv(x, w))
    xn, wn = np.asarray(x), np.asarray(w)
    want = np.zeros_like(y)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += wn[:, j] * xn[:, t - 3 + j]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    # an input after t never moves output t, nor does another row
    moved = np.asarray(conv(x.at[0, 6:].add(1.0), w))
    np.testing.assert_array_equal(moved[0, :6], y[0, :6])
    np.testing.assert_array_equal(moved[1], y[1])
    assert np.abs(moved[0, 6:] - y[0, 6:]).max() > 0.1
    # the reverse mode against the loop's, and the inferred leaf
    seed = rnd(62, 2, 10, 6)
    gx, gw = jax.grad(lambda x, w: jnp.sum(conv(x, w) * seed), (0, 1))(x, w)
    want_gw = np.zeros((6, 4), np.float32)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want_gw[:, j] += (np.asarray(seed)[:, t]
                                  * xn[:, t - 3 + j]).sum(0)
    np.testing.assert_allclose(np.asarray(gw), want_gw, rtol=1e-4, atol=1e-5)
    assert gx.shape == x.shape
    node = mx.sym._contrib_ShortConv(mx.sym.Variable("x"), name="c")
    assert node.infer_shape(x=(2, 10, 6))[0] == [(2, 10, 6), (6, 4)]


def test_rotary_interleaved_pairs_neighbouring_dims(ref):
    x = rnd(63, 2, 5, 3, 16)
    rot = op_fn("RotaryEmbedding", base=100.0, offset=8, dim=8,
                interleaved=True)
    close(rot(x)[..., 8:], ref._rotary(x[..., 8:], 100.0))
    np.testing.assert_array_equal(np.asarray(rot(x)[..., :8]),
                                  np.asarray(x[..., :8]))
    g = jax.grad(lambda x: jnp.sum(rot(x) ** 2) / 2)(x)
    close(g, x)
    half = op_fn("RotaryEmbedding", base=100.0, offset=8, dim=8)(x)
    assert np.abs(np.asarray(half - rot(x))).max() > 0.1


def test_l2_normalization_of_a_head_in_float32_whatever_the_type():
    x = rnd(64, 6, 16, scale=30.0)
    norm = op_fn("L2Normalization", eps=1e-6)
    want = x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    close(norm(x), want)
    low = norm(x.astype(jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    close(low, norm(x.astype(jnp.bfloat16).astype(jnp.float32)), tol=8e-3)


# ----------------------------------------------------------------------
# the router within groups
def router(x, w, b, k=4, scale=2.5, **kw):
    (idx, wt, score), (bias,) = op_fn(
        "MoERouter", num_experts=w.shape[0], top_k=k, scale=scale, **kw)(
            x, w, b)
    return idx, wt, score, bias


def by_hand(score, bias, k, scale, n_group, topk_group):
    """A loop a token: groups by the sum of their two best, then the k
    best of the kept groups' experts, the lower index among equals."""
    score, pick = np.asarray(score, np.float64), \
        np.asarray(score, np.float64) + np.asarray(bias, np.float64)
    per = pick.shape[1] // n_group
    idx = np.zeros((len(pick), k), np.int64)
    for t, row in enumerate(pick):
        groups = [np.sort(row[g * per:(g + 1) * per])[-2:].sum()
                  for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-groups[g], g))
        kept = set(kept[:topk_group])
        order = sorted((e for e in range(len(row)) if e // per in kept),
                       key=lambda e: (-row[e], e))
        idx[t] = order[:k]
    w = np.take_along_axis(score, idx, 1)
    return idx, scale * w / w.sum(1, keepdims=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_router_chooses_within_the_best_groups(dtype, ref):
    x, w = rnd(70, 24, 32, dtype=dtype), rnd(71, 32, 32, scale=0.4,
                                             dtype=dtype)
    b = rnd(72, 32, scale=0.05)
    idx, wt, score, _ = router(x, w, b, n_group=4, topk_group=2)
    want_idx, want_w = by_hand(score, b, 4, 2.5, 4, 2)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(wt), want_w, rtol=1e-5)
    ref_idx, ref_w = ref.route(score, b, 4, 2.5, 4, 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    close(wt, ref_w)
    # every chosen expert lies in one of two groups, and the plain top 4
    # would have chosen otherwise for some token
    assert (np.asarray([len(set(r // 8)) for r in np.asarray(idx)])
            <= 2).all()
    plain = np.asarray(router(x, w, b)[0])
    assert (np.sort(plain, 1) != np.sort(np.asarray(idx), 1)).any()
    # the gradient reaches x and the router's matrix through the weights
    seed = rnd(73, 24, 4)
    f32 = lambda a: a.astype(jnp.float32)                   # noqa: E731
    got = jax.grad(lambda x, w: jnp.sum(router(
        x, w, b, n_group=4, topk_group=2)[1] * seed), (0, 1))(x, w)
    want = jax.grad(lambda x, w: jnp.sum(ref.route(
        jax.nn.sigmoid(x @ w.T), b, 4, 2.5, 4, 2)[1] * seed), (0, 1))(
            f32(x), f32(w))
    for a, g in zip(got, want):
        close(a, g, tol=2e-5 if dtype == jnp.float32 else 3e-2)


def test_group_ties_go_to_the_lower_index_and_the_bias_only_chooses(ref):
    x = jnp.ones((3, 8))
    w = jnp.zeros((8, 8))                  # every score 0.5: all tied
    idx, wt, score, _ = router(x, w, jnp.zeros(8), k=2, scale=1.0,
                               n_group=4, topk_group=2)
    np.testing.assert_array_equal(np.asarray(idx), [[0, 1]] * 3)
    np.testing.assert_allclose(np.asarray(wt), 0.5)
    # a bias that brings group 3 in (7 and 6 by their bias) and leaves
    # the weights to the scores; expert 0 scores highest and its group
    # stays, but both chosen come from the biased group
    w = w.at[0].set(0.05)
    b = jnp.array([0.0, 0, 0, 0, 0, 0, 0.2, 0.3])
    idx, wt, score, _ = router(x, w, b, k=2, scale=1.0, n_group=4,
                               topk_group=2)
    np.testing.assert_array_equal(np.asarray(idx), [[7, 6]] * 3)
    np.testing.assert_allclose(np.asarray(wt), 0.5, rtol=1e-6)
    want_idx, _ = ref.route(score, b, 2, 1.0, 4, 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    # one group kept: the best expert outside it cannot be chosen
    b = jnp.array([0.0, 0, 0.1, 0.1, 0, 0, 0, 0])
    idx, _, _, _ = router(x, w, b, k=2, scale=1.0, n_group=4, topk_group=1)
    np.testing.assert_array_equal(np.asarray(idx), [[2, 3]] * 3)


def test_one_group_is_todays_router_bit_for_bit():
    """``n_group`` 1 is chosen in Python: the traced computation is the
    one without the parameter, equation for equation, and the groups'
    reshape is in the trace only where there are groups."""
    from mxnet_tpu.parallel.moe import sigmoid_topk_route
    logits, b = rnd(74, 16, 32), rnd(75, 32, scale=0.05)

    def plain(logits, bias):
        # the router as it stood before the groups came
        score = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, expert = jax.lax.top_k(score + bias.astype(jnp.float32), 4)
        chosen = expert[:, :, None] == jnp.arange(score.shape[1])
        weight = jnp.sum(jnp.where(chosen, score[:, None, :], 0.0), axis=-1)
        weight = 1.8 * weight / jnp.sum(weight, axis=-1, keepdims=True)
        return expert.astype(jnp.int32), weight, score

    now = jax.make_jaxpr(lambda l, b: sigmoid_topk_route(l, b, 4, 1.8))(
        logits, b)
    assert str(now) == str(jax.make_jaxpr(plain)(logits, b))
    grouped = jax.make_jaxpr(lambda l, b: sigmoid_topk_route(
        l, b, 4, 1.8, 4, 2))(logits, b)
    assert len(grouped.eqns) > len(now.eqns)
    for got, want in zip(sigmoid_topk_route(logits, b, 4, 1.8),
                         plain(logits, b)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(MXNetError, match="MoERouter"):
        router(rnd(76, 4, 8), rnd(77, 6, 8), jnp.zeros(6), k=2, n_group=4)


# ----------------------------------------------------------------------
# attention at unequal head widths
@pytest.mark.parametrize("dims", [(24, 16), (16, 24)], ids=["24/16", "16/24"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_attention_with_value_heads_of_their_own_width(dims, dtype):
    """The flash path pads to one width and cuts the result; against
    the plain softmax, values and gradients."""
    dqk, dv = dims
    q, k = rnd(80, 2, 128, 2, dqk, dtype=dtype), \
        rnd(81, 2, 128, 2, dqk, dtype=dtype)
    v = rnd(82, 2, 128, 2, dv, dtype=dtype)
    attn = op_fn("_contrib_DotProductAttention", causal=True)
    scaled = op_fn("_contrib_DotProductAttention", causal=True,
                   scale=float(dqk) ** -0.5)

    def plain(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dqk ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((128, 128), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    out = attn(q, k, v)
    assert out.shape == (2, 128, 2, dv) and out.dtype == dtype
    close(out, plain(*f32), tol)
    close(scaled(q, k, v), plain(*f32), tol)
    seed = rnd(83, 2, 128, 2, dv)
    got = jax.grad(lambda *a: jnp.sum(attn(*a).astype(jnp.float32) * seed),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * seed), (0, 1, 2))(*f32)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        close(a, b, tol)
    node = mx.sym._contrib_DotProductAttention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"))
    assert node.infer_shape(q=q.shape, k=k.shape, v=v.shape)[1] \
        == [(2, 128, 2, dv)]


# ----------------------------------------------------------------------
# the shares add up
def mixer_out(builder, cfg, arrays, x):
    """One of the builder's mixers bound on ``arrays`` (the leaves
    without their prefix), its output for the rows ``x``."""
    with mxname.Prefix("m_"):
        net = builder(mx.sym.Variable("x"), cfg)
    args = {"x": x}
    args.update({"m_" + n: v for n, v in arrays.items()})
    assert set(net.list_arguments()) == set(args)
    ex = net.bind(mx.cpu(), {n: mx.nd.NDArray(v) for n, v in args.items()})
    return ex.forward(is_train=False)[0].data


def heads_of(leaf, name, first, held, per):
    """Heads ``first .. first + held`` of a full leaf: its rows, or for
    an output projection its columns, ``per`` a head."""
    lo, hi = first * per, (first + held) * per
    return leaf[:, lo:hi] if name.endswith("_o_weight") else leaf[lo:hi]


def builder_cfg(heads):
    return dict(seq_len=T, hidden=64, heads_held=heads, head_dim=16,
                conv_kernel=4, kda_lower_bound=-5.0, chunk=64,
                kv_lora_rank=16, qk_nope=16, qk_rope=8, v_head=16,
                rope_theta=6e6, eps=1e-6)


def test_the_head_shares_add_up_to_the_uncut_delta_rule_layer(ref):
    """Four heads in shares of two: the two shares' partial sums (the
    program's mixer on each share's rows of every per-head leaf, the
    norm's gamma whole on both) are the reference's uncut mixer."""
    cfg = tiny_cfg(num_attention_heads=4)
    z = ref._sizes(cfg)
    params, _ = ref.init(cfg, jax.random.key(90))
    full = {n[3:]: v for n, v in params.items() if n.startswith("l0_kda_")}
    x = rnd(91, 2, T, 64)
    whole = ref._kda(x, lambda n: full[n], z, cfg, None)
    per_head = {"kda_A_log": 1, "kda_beta_weight": 1}
    total = 0
    for first in (0, 2):
        share = {n: (v if n == "kda_o_norm_gamma" else
                     heads_of(v, n, first, 2, per_head.get(n, 16)))
                 for n, v in full.items()}
        total = total + mixer_out(bailing_hybrid._kda, builder_cfg(2),
                                  share, x.reshape(-1, 64))
    close(total, whole.reshape(-1, 64), tol=5e-5)
    # and it is no accident of equal shares: one share alone is not it
    assert np.abs(np.asarray(total - mixer_out(
        bailing_hybrid._kda, builder_cfg(2),
        {n: (v if n == "kda_o_norm_gamma" else
             heads_of(v, n, 0, 2, per_head.get(n, 16)))
         for n, v in full.items()}, x.reshape(-1, 64)))).max() > 1e-3


def test_the_head_shares_add_up_to_the_uncut_latent_attention_layer(ref):
    """The same for latent attention at 24 / 16 wide heads: the latent
    projection and its norm are whole on every chip and counted once,
    the query, the expansion, the gate and the output projection are
    split by heads."""
    cfg = tiny_cfg(num_attention_heads=4)
    z = ref._sizes(cfg)
    params, _ = ref.init(cfg, jax.random.key(92))
    full = {n[3:]: v for n, v in params.items() if n.startswith("l2_attn_")}
    x = rnd(93, 2, T, 64)
    whole = ref._mla(x, lambda n: full[n], z, cfg, None)
    per = {"attn_q_weight": 24, "attn_kvb_weight": 32, "attn_gate_weight": 1,
           "attn_o_weight": 16}
    total = 0
    for first in (0, 2):
        share = {n: (heads_of(v, n, first, 2, per[n]) if n in per else v)
                 for n, v in full.items()}
        total = total + mixer_out(bailing_hybrid._mla, builder_cfg(2),
                                  share, x.reshape(-1, 64))
    close(total, whole.reshape(-1, 64), tol=5e-5)


def test_the_expert_shares_add_up_to_the_uncut_expert_layer(ref):
    """32 experts in shares of 4: the eight shares' routed parts (the
    program's ops, the router choosing within 2 of 4 groups) plus the
    shared expert counted once are the reference's uncut layer."""
    cfg = tiny_cfg(num_experts=32)               # the reference holds all
    z = ref._sizes(cfg)
    assert z["held"] == z["experts"] == 32
    params, aux = ref.init(cfg, jax.random.key(94))
    p = lambda n: params["l1_" + n]                           # noqa: E731
    x = rnd(95, 1, 40, 64)
    bias = aux["l1_moe_router_bias"]
    whole, count = ref._expert_layer(x, p, bias, z, cfg, None)
    rows = x[0]
    idx, wt, _, _ = router(rows, p("moe_router_weight"), bias, n_group=4,
                           topk_group=2)
    total = ref._ffn(rows, p("moe_shared_gate_weight"),
                     p("moe_shared_up_weight"), p("moe_shared_down_weight"),
                     None)
    for first in range(0, 32, 4):
        (part,), (c,) = op_fn(
            "MoEExperts", num_experts=32, experts_held=4,
            first_expert=first, num_hidden=48)(
                rows, idx, wt, *(p("moe_experts_%s_weight" % n)
                                 [first:first + 4]
                                 for n in ("gate", "up", "down")),
                jnp.zeros(32))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(count))
        total = total + part
    close(total, whole[0])
    assert float(count.sum()) == 40 * 4


# ----------------------------------------------------------------------
# the builder
def test_published_configuration_by_shapes_alone(ref):
    """At the published widths nothing is allocated: the Symbol's
    arguments and auxiliary states are the reference's ``param_shapes``,
    578M parameters; no width is among the keys cut."""
    cfg = published()
    sym = models.get_symbol(cfg["symbol"]["network"],
                            **cfg["symbol"]["kwargs"])
    arg_s, out_s, aux_s = sym.infer_shape(data=(1, 4096),
                                          softmax_label=(1, 4096))
    have = {n: tuple(s) for n, s in zip(sym.list_arguments(), arg_s)
            if n not in ("data", "softmax_label")}
    want_p, want_a = ref.param_shapes(cfg)
    assert have == {n: tuple(s) for n, s in want_p.items()}
    assert dict(zip(sym.list_auxiliary_states(), map(tuple, aux_s))) \
        == {n: tuple(s) for n, s in want_a.items()}
    assert have["l0_kda_q_weight"] == (1024, 2560)
    assert have["l0_kda_q_conv_weight"] == (1024, 4)
    assert have["l0_kda_A_log"] == (8,) and have["l0_kda_dt_bias"] == (1024,)
    assert have["l4_attn_q_weight"] == (8 * 192, 2560)
    assert have["l4_attn_kvb_weight"] == (8 * 256, 512)
    assert have["l3_moe_experts_gate_weight"] == (8, 768, 2560)
    assert have["l2_moe_router_weight"] == (512, 2560)
    assert have["l0_mlp_gate_weight"] == (6144, 2560)
    total = sum(int(np.prod(s)) for s in have.values())
    assert round(total / 1e6) == 578

    def layer(i, part):
        return sum(int(np.prod(s)) for n, s in have.items()
                   if n.startswith("l%d_%s" % (i, part))) / 1e6
    assert round(layer(0, "kda"), 2) == 15.76
    assert round(layer(4, "attn"), 2) == 9.10
    assert round(layer(1, "moe"), 1) == 54.4
    assert out_s == [(4096, 19648)]
    z = ref._sizes(cfg)
    assert z["kinds"] == cfg["symbol"]["kwargs"]["layer_types"].split(",") \
        == ["kda"] * 4 + ["mla"] + ["kda"] * 2
    assert z["is_dense"] == [True] + [False] * 6
    for key in ("hidden_size", "head_dim", "intermediate_size",
                "moe_intermediate_size", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                "n_group", "topk_group", "short_conv_kernel_size"):
        assert key not in cfg["reduced"]
    assert sorted(cfg["reduced"]) == sorted(cfg["published"]) == [
        "num_attention_heads", "num_experts", "num_hidden_layers",
        "num_nextn_predict_layers", "vocab_size"]
    with pytest.raises(ValueError, match="layer_types"):
        models.get_symbol("bailing-hybrid", layer_types="kda,gqa")


def test_costs_of_the_rule_by_hand(ref):
    """A KDA layer at the cell's 1 x 4,096 tokens: 64 chunks of 8 heads,
    each 64^2 (3 x 128 + 2 x 128) + 6 x 64 x 128 x 128 operations
    forward and twice that back; q, k, v, g, o (128 wide) and beta once
    a pass at 2 bytes, the 128 x 128 float32 state once a chunk."""
    c = ref.costs(published(), 1)
    by = c["by_layer"]
    assert ref.kda_rule_flops(64, 128, 128) == 2_621_440 + 6_291_456
    assert by["l0_kda_core"] == 3 * 512 * 8_912_896
    assert c["kda"]["flops"] == 6 * by["l0_kda_core"]
    assert c["kda"]["bytes"] == 6 * 3 * (2 * 4096 * 8 * (5 * 128 + 1)
                                         + 4 * 512 * 128 * 128)
    assert by["l1_moe_experts"] == 6 * 512 * 3 * 2560 * 768
    assert by["l4_attn"] == 6 * 8 * (4096 * 4096 // 2) * (192 + 128)
    assert c["attention"] == {"flops": by["l4_attn"],
                              "bytes": 2 * 2 * 4096 * 8 * 2 * (192 + 128)}
    assert sum(by["l0_kda_" + n] for n in "qkvfg") \
        == 5 * 6 * 4096 * 2560 * 1024
    assert c["model_flops"] == sum(c[k]["flops"] for k in
                                   ("matmul", "experts", "attention", "kda"))


# ----------------------------------------------------------------------
# the tiny model through Module's fused step against the reference
def tiny_module(params, aux, compute_dtype):
    sym = models.get_symbol("bailing-hybrid", vocab_size=512, seq_len=T)
    mod = mx.mod.Module(context=mx.tpu(), symbol=sym,
                        compute_dtype=compute_dtype)
    mod.bind(data_shapes=[("data", (B, T))],
             label_shapes=[("softmax_label", (B, T))])
    nd = mx.nd.NDArray
    mod.init_params(initializer=None, force_init=True,
                    arg_params={n: nd(v) for n, v in params.items()},
                    aux_params={n: nd(v) for n, v in aux.items()})
    mod.init_optimizer(
        kvstore=mx.kvstore.create("dist_sync_tpu"), optimizer="sgd",
        optimizer_params={"learning_rate": LR, "momentum": 0.9, "wd": 0.0,
                          "rescale_grad": 1.0 / (B * T)})
    assert mod._trainer is not None, "Module did not take the fused path"
    return mod


def batches(seed, steps=3):
    ids = jax.random.randint(jax.random.key(seed), (steps, B, T + 1), 0,
                             512, jnp.int32)
    return [(ids[i, :, :-1], ids[i, :, 1:]) for i in range(steps)]


def program_steps(mod, feed):
    """Three steps through forward / update / update_metric; the losses,
    the first gradient (momentum after one step over minus the rate) and
    the parameters after the three."""
    metric = mx.metric.create("acc")
    losses, grad = [], None
    for i, (data, label) in enumerate(feed):
        batch = mx.io.DataBatch(data=[mx.nd.NDArray(data)],
                                label=[mx.nd.NDArray(label)], pad=0)
        mod.forward(batch, is_train=True)
        mod.update()
        mod.update_metric(metric, batch.label)
        p = jnp.take_along_axis(
            mod.get_outputs()[0].data.astype(jnp.float32),
            label.reshape(-1, 1), axis=1)
        losses.append(float(-jnp.mean(jnp.log(p))))
        if i == 0:
            grad = {n: np.asarray(v) / -LR
                    for n, v in mod._trainer.opt_state.items()}
    assert metric.num_inst == 3 * B * T
    return losses, grad, {n: np.asarray(v)
                          for n, v in mod._trainer.params.items()}


def reference_steps(ref, refsteps, cfg, params, aux, feed, cast=None):
    opt = {"learning_rate": LR, "momentum": 0.9}
    step = refsteps.make_step(ref, cfg, opt, refsteps.CASTS[cast])
    p, a = jax.tree.map(jnp.copy, (params, aux))
    mom = jax.tree.map(jnp.zeros_like, p)
    losses, grad = [], None
    for i, (data, label) in enumerate(feed):
        if i == 0:
            g = jax.grad(lambda q: ref.loss(cfg, q, a, data, label,
                                            refsteps.CASTS[cast])[0])(p)
            grad = {n: np.asarray(v) for n, v in g.items()}
        p, a, mom, loss, _ = step(p, a, mom, data, label)
        losses.append(float(loss))
    return losses, grad, {n: np.asarray(v) for n, v in p.items()}


@pytest.fixture(scope="module")
def tiny(ref, refsteps):
    cfg = tiny_cfg()
    params, aux = ref.init(cfg, jax.random.key(60))
    feed = batches(61)
    return cfg, params, aux, feed, reference_steps(ref, refsteps, cfg,
                                                   params, aux, feed)


def test_tiny_model_float32_matches_the_reference_leaf_by_leaf(tiny):
    """Three losses, the first gradient and the three-step change, every
    leaf: the norm of the difference within 1e-4 of the leaf's norm."""
    cfg, params, aux, feed, (want_l, want_g, want_p) = tiny
    mod = tiny_module(params, aux, None)
    losses, grad, after = program_steps(mod, feed)
    np.testing.assert_allclose(losses, want_l, rtol=1e-4)
    assert set(grad) == set(want_g) == set(params)
    assert sum(n.endswith(("kda_A_log", "kda_dt_bias", "conv_weight"))
               for n in params) == 2 * 5
    for n in sorted(params):
        start = np.asarray(params[n])
        for got, want in ((grad[n], want_g[n]),
                          (after[n] - start, want_p[n] - start)):
            assert np.linalg.norm(want) > 0, n
            assert np.linalg.norm(got - want) \
                <= 1e-4 * np.linalg.norm(want), n
    got_aux = {n: np.asarray(v) for n, v in mod._trainer.aux.items()}
    for n, v in aux.items():
        if n.endswith("_bias"):
            np.testing.assert_array_equal(got_aux[n], np.asarray(v))
        else:
            assert got_aux[n].sum() == B * T * 4


def gaps(refsteps, got, want, start):
    """``refsteps.compare``'s numbers from (losses, gradient, params)."""
    def norms(tree):
        return {n: float(np.linalg.norm(v)) for n, v in tree.items()}

    def pack(run):
        losses, grad, after = run
        return {"loss": losses, "grad": norms(grad),
                "change": norms({n: after[n] - start[n] for n in after}),
                "size": {n: int(v.size) for n, v in after.items()}}
    return refsteps.compare(pack(got), pack(want))


def test_tiny_model_bfloat16_stays_inside_the_float8_controls_gap(
        tiny, ref, refsteps):
    """bfloat16 compute with float32 masters: the gaps to the reference
    that the benchmark compares, against the same gaps of the float8
    control, which is the nearest precision below and reads larger."""
    cfg, params, aux, feed, want = tiny
    start = {n: np.asarray(v) for n, v in params.items()}
    mod = tiny_module(params, aux, "bfloat16")
    got = gaps(refsteps, program_steps(mod, feed), want, start)
    control = gaps(refsteps, reference_steps(ref, refsteps, cfg, params, aux,
                                             feed, cast="fp8"), want, start)
    for name in ("grad_norm_gap_median", "change_norm_gap_median",
                 "grad_norm_gap_big_median", "change_norm_gap_big_median"):
        assert got[name] < control[name], (name, got[name], control[name])
    for i in (1, 2, 3):
        assert got["loss_gap_step%d" % i] < 2e-3


def test_obs_counters_of_the_rule_after_two_steps(tiny):
    """``attention.kda.nodes`` rises by one for each rule node traced,
    ``attention.kda.bounded_nodes`` with it (the model's gate is bounded
    and says so) and ``attention.kda.chunks`` by that node's chunk
    steps, batch x t / 64; steps of a compiled program trace, and count,
    nothing."""
    cfg, params, aux, feed, _ = tiny

    def read():
        c = obs.snapshot()["counters"]
        return (c.get("attention.kda.nodes", 0),
                c.get("attention.kda.bounded_nodes", 0),
                c.get("attention.kda.chunks", 0))

    start = read()
    mod = tiny_module(params, aux, None)

    def step(data, label):
        mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(data)],
                                    label=[mx.nd.NDArray(label)], pad=0),
                    is_train=True)
        mod.update()

    step(*feed[0])
    nodes, bounded, chunks = (a - b for a, b in zip(read(), start))
    assert nodes >= 2 and nodes % 2 == 0       # two rule nodes a trace
    assert bounded == nodes
    assert chunks == nodes * B * T // 64
    step(*feed[1])
    assert read() == tuple(a + b for a, b in zip(start,
                                                 (nodes, bounded, chunks)))


def test_every_rule_node_declares_the_configurations_gate_bound():
    """The configuration's gate is the safe one, bounded below by
    ``kda_lower_bound`` by construction; every ``kda_core`` node of the
    builder says so, at the bound it is given, and a rule node with no
    bound counts as a node and not as a bounded one."""
    pub = published()
    assert pub["kda_safe_gate"] is True and pub["kda_lower_bound"] == -5
    for bound in (-5.0, -2.5):
        attrs = models.get_symbol("bailing-hybrid", vocab_size=512,
                                  seq_len=T, kda_lower_bound=bound,
                                  layer_types="kda,mla,kda").attr_dict()
        cores = {n: a for n, a in attrs.items() if n.endswith("kda_core")}
        assert sorted(cores) == ["l0_kda_core", "l2_kda_core"]
        assert {float(a["lower_bound"]) for a in cores.values()} == {bound}

    def read():
        c = obs.snapshot()["counters"]
        return (c.get("attention.kda.nodes", 0),
                c.get("attention.kda.bounded_nodes", 0))

    before = read()
    op_fn("_contrib_GatedDeltaRule")(*rule_operands(80, 1, 64))
    assert read() == (before[0] + 1, before[1])
