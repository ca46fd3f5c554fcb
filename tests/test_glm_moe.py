"""GLM-4.7-Flash through the Symbol path: the new ops against their
formulas, the expert layer that drops nothing, a chip's share against
the whole layer, and the tiny model through ``Module``'s fused step
against the benchmark's plain reference
(``benchmark/reference/glm-4.7-flash.py``, loaded by path)."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, obs
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
    return _load(os.path.join(BENCH, "reference", "glm-4.7-flash.py"),
                 "glm_reference")


@pytest.fixture(scope="module")
def refsteps():
    return _load(os.path.join(BENCH, "lib", "refsteps.py"), "glm_refsteps")


def published():
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        return json.load(f)


def tiny_cfg(**over):
    """The published file cut to 2 + 1 layers, d 64, 16 experts 4 held,
    vocabulary 512, 32 positions: the builder's defaults."""
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=2, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
               v_head_dim=32, intermediate_size=160,
               moe_intermediate_size=48, n_routed_experts=4,
               num_hidden_layers=2, vocab_size=512)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["input"] = {"kind": "tokens", "seq_len": 32, "vocab": 512}
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


def close(got, want, dtype):
    """Values agree: float32 to rounding, bfloat16 to its 8 bits, against
    the formula computed in float32 on the same (rounded) inputs."""
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(np.abs(want).max(), 1e-6))


def f32(*arrays):
    return [a.astype(jnp.float32) for a in arrays]


DTYPES = [jnp.float32, jnp.bfloat16]


# ----------------------------------------------------------------------
# the small ops against their formulas
@pytest.mark.parametrize("dtype", DTYPES)
def test_rmsnorm_is_x_over_root_mean_square_times_gamma(dtype):
    x, g = rnd(0, 6, 40, dtype=dtype), 1 + rnd(1, 40, scale=0.1, dtype=dtype)
    norm = op_fn("RMSNorm", eps=1e-5)

    def formula(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * g

    close(norm(x, g), formula(*f32(x, g)), dtype)
    w = rnd(2, 6, 40)
    got = jax.grad(lambda x, g: jnp.sum(norm(x, g).astype(jnp.float32) * w),
                   argnums=(0, 1))(x, g)
    want = jax.grad(lambda x, g: jnp.sum(formula(x, g) * w),
                    argnums=(0, 1))(*f32(x, g))
    for a, b in zip(got, want):
        close(a, b, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
def test_rotary_rotates_a_slice_of_the_head_dimension(dtype):
    x = rnd(3, 2, 5, 3, 16, dtype=dtype)          # (B, T, H, D)
    rot = op_fn("RotaryEmbedding", base=100.0, offset=8, dim=8)

    def formula(x):
        out = np.array(x, np.float64)
        for t in range(x.shape[1]):
            for i in range(4):                     # dim 8+i pairs 8+i+4
                ang = t * 100.0 ** (-2.0 * i / 8)
                a, b = x[:, t, :, 8 + i], x[:, t, :, 12 + i]
                out[:, t, :, 8 + i] = a * np.cos(ang) - b * np.sin(ang)
                out[:, t, :, 12 + i] = b * np.cos(ang) + a * np.sin(ang)
        return out

    want = formula(np.asarray(x.astype(jnp.float32), np.float64))
    close(rot(x), want, dtype)
    np.testing.assert_array_equal(np.asarray(rot(x)[..., :8], np.float32),
                                  np.asarray(x[..., :8], np.float32))
    # a rotation keeps the norm, and its reverse mode is the rotation back
    g = jax.grad(lambda x: jnp.sum(rot(x).astype(jnp.float32) ** 2) / 2)(x)
    close(g, x, dtype)
    # position 0 is not rotated; all of the last axis by default
    whole = op_fn("RotaryEmbedding", base=1e6)(x)
    close(whole[:, 0], x[:, 0], dtype)
    assert not np.allclose(np.asarray(whole[:, 1:, :, :8], np.float32),
                           np.asarray(x[:, 1:, :, :8], np.float32))


@pytest.mark.parametrize("dtype", DTYPES)
def test_gated_feed_forward_of_the_builder(dtype, ref):
    """``glm_moe._gated_ffn`` (three FullyConnected, silu, a product)
    against (silu(x W_gate) * x W_up) W_down, values and gradients."""
    from mxnet_tpu.models import glm_moe
    net = glm_moe._gated_ffn(mx.sym.Variable("x"), 24, 16, "f_")
    arrays = {"x": rnd(4, 10, 16, dtype=dtype),
              "f_gate_weight": rnd(5, 24, 16, scale=0.3, dtype=dtype),
              "f_up_weight": rnd(6, 24, 16, scale=0.3, dtype=dtype),
              "f_down_weight": rnd(7, 16, 24, scale=0.3, dtype=dtype)}
    names = net.list_arguments()
    ex = net.bind(mx.cpu(), {n: mx.nd.NDArray(arrays[n]) for n in names},
                  args_grad={n: mx.nd.NDArray(jnp.zeros_like(arrays[n]))
                             for n in names})
    out = ex.forward(is_train=True)[0].data
    seed = rnd(8, 10, 16, dtype=dtype)
    ex.backward([mx.nd.NDArray(seed)])

    def formula(x, wg, wu, wd):
        return (jax.nn.silu(x @ wg.T) * (x @ wu.T)) @ wd.T

    args = f32(*[arrays[n] for n in ("x", "f_gate_weight", "f_up_weight",
                                     "f_down_weight")])
    close(out, formula(*args), dtype)
    close(out, ref._ffn(*args, None), dtype)
    want = jax.grad(lambda *a: jnp.sum(formula(*a) * seed.astype(
        jnp.float32)), argnums=(0, 1, 2, 3))(*args)
    for n, w in zip(("x", "f_gate_weight", "f_up_weight", "f_down_weight"),
                    want):
        close(ex.grad_dict[n].data, w, dtype)


def test_silu_is_in_activations_enum():
    x = rnd(9, 7)
    close(op_fn("Activation", act_type="silu")(x), x / (1 + jnp.exp(-x)),
          jnp.float32)


# ----------------------------------------------------------------------
# the router
def router(x, w, b, k=4, scale=1.8):
    (idx, wt, score), (bias,) = op_fn(
        "MoERouter", num_experts=w.shape[0], top_k=k, scale=scale)(x, w, b)
    return idx, wt, score, bias


@pytest.mark.parametrize("dtype", DTYPES)
def test_router_scores_choice_and_weights(dtype, ref):
    x, w = rnd(10, 12, 32, dtype=dtype), rnd(11, 16, 32, scale=0.4,
                                             dtype=dtype)
    b = rnd(12, 16, scale=0.01)
    idx, wt, score, bias = router(x, w, b)
    assert idx.dtype == jnp.int32 and wt.dtype == jnp.float32 \
        and score.dtype == jnp.float32
    s = jax.nn.sigmoid(x.astype(jnp.float32) @ w.astype(jnp.float32).T)
    close(score, s, dtype)
    want_idx, want_w = ref.route(score, b, 4, 1.8)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
    close(wt, want_w, jnp.float32)
    np.testing.assert_allclose(np.asarray(wt.sum(1)), 1.8, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(bias), np.asarray(b))
    # the gradient reaches x and the router's matrix through the weights
    seed = rnd(13, 12, 4)
    got = jax.grad(lambda x, w: jnp.sum(router(x, w, b)[1] * seed),
                   argnums=(0, 1))(x, w)

    def formula(x, w):
        sc = jax.nn.sigmoid(x @ w.T)
        i, wts = ref.route(sc, b, 4, 1.8)
        return jnp.sum(wts * seed)

    want = jax.grad(formula, argnums=(0, 1))(*f32(x, w))
    for a, g in zip(got, want):
        close(a, g, dtype)


def test_router_ties_go_to_the_lower_index_and_the_bias_only_chooses(ref):
    x = jnp.ones((3, 8))
    w = jnp.zeros((6, 8))                 # every score 0.5: all tied
    idx, wt, score, _ = router(x, w, jnp.zeros(6), k=2, scale=1.0)
    np.testing.assert_array_equal(np.asarray(idx), [[0, 1]] * 3)
    np.testing.assert_allclose(np.asarray(wt), 0.5)
    # a bias that changes the chosen set, and leaves the weights to the
    # scores: expert 5 and 3 are chosen for their bias, weighted by s
    w = w.at[0].set(0.05)                 # expert 0 scores highest
    b = jnp.array([0.0, 0, 0, 0.2, 0, 0.3])
    idx, wt, score, _ = router(x, w, b, k=2, scale=1.0)
    np.testing.assert_array_equal(np.asarray(idx), [[5, 3]] * 3)
    plain, _ = ref.route(score, jnp.zeros(6), 2, 1.0)
    assert set(np.asarray(plain[0])) == {0, 1}
    np.testing.assert_allclose(np.asarray(wt), 0.5, rtol=1e-6)
    want_idx, want_w = ref.route(score, b, 2, 1.0)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))


# ----------------------------------------------------------------------
# the experts op
def experts_op(x, idx, wt, wg, wu, wd, first=0, num_experts=16):
    (y,), (count,) = op_fn(
        "MoEExperts", num_experts=num_experts, experts_held=wg.shape[0],
        first_expert=first, num_hidden=wg.shape[1])(
            x, idx, wt, wg, wu, wd, jnp.zeros(num_experts))
    return y, count


def expert_weights(dtype, g=4, h=24, d=32, seed=20):
    return (rnd(seed, g, h, d, scale=0.3, dtype=dtype),
            rnd(seed + 1, g, h, d, scale=0.3, dtype=dtype),
            rnd(seed + 2, g, d, h, scale=0.3, dtype=dtype))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("first", [0, 4, 12])
def test_experts_op_is_the_masked_dense_loop(dtype, first, ref):
    """Values and gradients (data, the router's weights, the three
    leaves) against the reference's loop over the held experts."""
    x = rnd(23, 40, 32, dtype=dtype)
    wg, wu, wd = expert_weights(dtype)
    idx, wt, _, _ = router(x, rnd(24, 16, 32, scale=0.4, dtype=dtype),
                           rnd(25, 16, scale=0.01))
    y, count = experts_op(x, idx, wt, wg, wu, wd, first)
    want = ref.routed_part(*f32(x), idx, wt, *f32(wg, wu, wd), first)
    close(y, want, dtype)
    np.testing.assert_array_equal(
        np.asarray(count), np.bincount(np.asarray(idx).ravel(),
                                       minlength=16))
    seed = rnd(26, 40, 32)
    got = jax.grad(
        lambda x, wt, wg, wu, wd: jnp.sum(experts_op(
            x, idx, wt, wg, wu, wd, first)[0].astype(jnp.float32) * seed),
        argnums=(0, 1, 2, 3, 4))(x, wt, wg, wu, wd)
    want = jax.grad(
        lambda x, wt, wg, wu, wd: jnp.sum(ref.routed_part(
            x, idx, wt, wg, wu, wd, first) * seed),
        argnums=(0, 1, 2, 3, 4))(*f32(x), wt, *f32(wg, wu, wd))
    for a, b in zip(got, want):
        close(a, b, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", ["balanced", "one_held", "all_absent"])
def test_no_entry_is_dropped_whatever_the_imbalance(dtype, case, ref):
    """Every entry to one held expert; every entry to absent experts
    (the routed part is exactly 0); an ordinary spread: each equals the
    masked dense loop, which has no capacity to run out of."""
    t, k = 48, 4
    x = rnd(30, t, 32, dtype=dtype)
    wg, wu, wd = expert_weights(dtype)
    wt = 0.1 + jax.random.uniform(jax.random.key(31), (t, k))
    if case == "balanced":
        idx = jax.random.randint(jax.random.key(32), (t, k), 0, 16)
    elif case == "one_held":
        idx = jnp.full((t, k), 6, jnp.int32)      # held: experts 4..7
    else:
        idx = jnp.tile(jnp.array([0, 1, 9, 15], jnp.int32), (t, 1))
    y, count = experts_op(x, idx.astype(jnp.int32), wt, wg, wu, wd, first=4)
    want = ref.routed_part(*f32(x), idx, wt, *f32(wg, wu, wd), 4)
    close(y, want, dtype)
    assert float(count.sum()) == t * k
    if case == "one_held":
        assert float(count[6]) == t * k
        # all four of a token's entries count: 4 x its weight x F_6(x)
        one = ref._ffn(*f32(x, wg[2], wu[2], wd[2]), None)
        close(y, wt.sum(1, keepdims=True) * one, dtype)
    if case == "all_absent":
        assert not np.asarray(y, np.float32).any()
        g = jax.grad(lambda x: jnp.sum(experts_op(
            x, idx, wt, wg, wu, wd, first=4)[0].astype(jnp.float32)))(x)
        assert not np.asarray(g, np.float32).any()


def test_rows_the_grouped_kernels_leave_unwritten_never_reach_a_product(
        monkeypatch, ref):
    """The TPU's ragged-dot kernels skip the row tiles past the groups
    and leave them unwritten, forward and in reverse mode (the v5e read
    NaN gradients through 0 x NaN).  With a ``ragged_dot`` that writes
    NaN there, as stale memory may hold, values and every gradient stay
    finite and equal the masked dense loop."""
    from mxnet_tpu.parallel import moe
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def stale(lhs, rhs, sizes):
        past = jnp.arange(lhs.shape[0])[:, None] >= jnp.sum(sizes)
        return jnp.where(past, jnp.nan, real(lhs, rhs, sizes))

    def fwd(lhs, rhs, sizes):
        return stale(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        # the transposes skip what lies past the groups, never read it
        past = jnp.arange(lhs.shape[0])[:, None] >= jnp.sum(sizes)
        da, db = vjp(jnp.where(past, 0.0, g))
        return jnp.where(past, jnp.nan, da), db, None

    stale.defvjp(fwd, bwd)
    monkeypatch.setattr(moe.jax.lax, "ragged_dot", stale)
    x = rnd(33, 40, 32)
    wg, wu, wd = expert_weights(jnp.float32)
    idx, wt, _, _ = router(x, rnd(34, 16, 32, scale=0.4),
                           rnd(35, 16, scale=0.01))
    seed = rnd(36, 40, 32)

    def loss(x, wt, wg, wu, wd):
        return jnp.sum(experts_op(x, idx, wt, wg, wu, wd, 4)[0] * seed)

    got = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, wt, wg, wu, wd)
    want = jax.grad(lambda *a: jnp.sum(ref.routed_part(
        a[0], idx, *a[1:], 4) * seed), argnums=(0, 1, 2, 3, 4))(
            x, wt, wg, wu, wd)
    close(experts_op(x, idx, wt, wg, wu, wd, 4)[0],
          ref.routed_part(x, idx, wt, wg, wu, wd, 4), jnp.float32)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        close(a, b, jnp.float32)


ABSENT = [0, 1, 9, 15]
# 64 tokens x 4 entries = 256 sorted rows, experts 4..7 of 16 held:
# (the experts of every token | "random", of how many tokens, chunk rows,
# trips)
WALKS = {
    "run_cut_by_an_edge": ("random", 64, 24, None),
    "all_on_one_held": ([6, 6, 6, 6], 64, 32, 8),
    "rows_do_not_divide_entries": ([5, 5, 5, 5], 64, 24, 11),
    "none_held": (ABSENT, 64, 32, 0),
    "live_fills_two_chunks": ([4, 5, 6, 7], 16, 32, 2),
    "live_fills_one_chunk": ([4, 5, 6, 7], 16, 64, 1),
}


@pytest.mark.parametrize("case", sorted(WALKS))
def test_walk_in_row_chunks_is_the_masked_dense_loop(case, ref):
    """``moe_apply_held`` with a small ``chunk_rows``: whatever the trips
    the routing asks for, value and the five gradients are the masked
    dense loop's; with no entry held, exact zeros."""
    from mxnet_tpu.parallel import moe
    t, k = 64, 4
    experts, first_tokens, rows, trips = WALKS[case]
    if experts == "random":               # some 64 live rows, 24 a trip
        idx = jax.random.randint(jax.random.key(37), (t, k), 0, 16,
                                 jnp.int32)
    else:
        idx = jnp.tile(jnp.array(ABSENT, jnp.int32), (t, 1)) \
            .at[:first_tokens].set(jnp.array(experts, jnp.int32))
    live = int(jnp.sum((idx >= 4) & (idx < 8)))
    if trips is None:
        assert live % rows and live > 2 * rows
    else:
        assert moe.row_chunks(live, rows) == trips
    x, seed = rnd(38, t, 32), rnd(39, t, 32)
    wg, wu, wd = expert_weights(jnp.float32)
    wt = 0.1 + jax.random.uniform(jax.random.key(40), (t, k))

    def op(x, wt, wg, wu, wd):
        return moe.moe_apply_held(x, idx, wt, wg, wu, wd, 4, 16,
                                  chunk_rows=rows)[0]

    def dense(x, wt, wg, wu, wd):
        return ref.routed_part(x, idx, wt, wg, wu, wd, 4)

    got, want = (jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * seed), argnums=(0, 1, 2, 3, 4)))(
            x, wt, wg, wu, wd) for fn in (op, dense))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if case == "none_held":
            assert not np.asarray(a).any() and not np.asarray(b).any()
        close(a, b, jnp.float32)


# a chunk's rows summed by token: (rows, tokens, which rows): every row
# an entry of its own, so that a token holds at most K of them
K = 4
SUMS = {
    "k_rows_a_token": (24, 16, "whole"),          # tokens 0..5, 4 rows each
    "tokens_with_none": (12, 40, "live"),         # R < T: most have none
    "dead_rows_interleaved": (32, 16, "half"),
    "every_row_dead": (16, 8, "dead"),
    "more_rows_than_tokens": (56, 12, "padded"),  # R > T, 8 dead at the end
}
ROUTES = ["sorted", "onehot"]


def sum_case(case):
    """``tok`` (R,) int32 and ``live`` (R, 1) of a chunk, and its tokens."""
    rows, tokens, which = SUMS[case]
    rng = np.random.default_rng(60)
    if which == "whole":
        ent = rng.permutation(rows)
    else:
        ent = np.pad(rng.permutation(tokens * K)[:rows],
                     (0, max(0, rows - tokens * K)))
    live = {"whole": np.ones(rows, bool), "live": np.ones(rows, bool),
            "half": rng.random(rows) < 0.5, "dead": np.zeros(rows, bool),
            "padded": np.arange(rows) < tokens * K}[which]
    return (jnp.asarray(ent // K, jnp.int32), jnp.asarray(live)[:, None],
            tokens)


def summed_by_onehot(rows, tok, live, tokens):
    """The form the sorted sum replaced, kept as its oracle: the product
    of the rows with the chunk's (rows x tokens) one-hot."""
    onehot = (tok[:, None] == jnp.arange(tokens)) & live
    exact = jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        onehot.astype(rows.dtype), rows, (((0,), (0,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32)


def sums_taken():
    c = obs.snapshot()["counters"]
    return {r: c.get("moe.sum_by_token." + r, 0) for r in ROUTES}


def take_route(monkeypatch, route):
    from mxnet_tpu.parallel import moe
    monkeypatch.setattr(moe, "_sorted_route",
                        lambda rows, tokens: route == "sorted")
    return moe


@pytest.mark.parametrize("route", ROUTES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", sorted(SUMS))
def test_sum_by_token_is_the_onehot_product(monkeypatch, case, dtype, route):
    """Both moves between a chunk's rows and its tokens, by either route:
    the sum of a token's live rows (``_sum_rows``) in float32 equals the
    one-hot product, its reverse mode hands each live row its token's
    cotangent; the gather (``_take_rows``) reverses to that sum in the
    rows' type.  Each sum is counted under the route it took."""
    moe = take_route(monkeypatch, route)
    tok, live, tokens = sum_case(case)
    rows = rnd(61, tok.shape[0], 8, dtype=dtype)
    before = sums_taken()
    got, back = jax.vjp(lambda r: moe._sum_rows(r, tok, live, tokens, K),
                        rows)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        summed_by_onehot(rows, tok, live, tokens)), rtol=1e-6, atol=1e-6)
    g = rnd(62, tokens, 8)
    np.testing.assert_array_equal(
        np.asarray(jnp.where(live, back(g)[0], 0).astype(jnp.float32)),
        np.asarray(jnp.where(live, g.astype(dtype)[tok], 0)
                   .astype(jnp.float32)))
    x = rnd(63, tokens, 8, dtype=dtype)
    took, back = jax.vjp(lambda x: moe._take_rows(x, tok, live, tokens, K), x)
    np.testing.assert_array_equal(np.asarray(took, np.float32),
                                  np.asarray(x[tok], np.float32))
    g = rnd(64, tok.shape[0], 8, dtype=dtype)
    (dx,) = back(g)
    assert dx.dtype == dtype
    close(dx, summed_by_onehot(g, tok, live, tokens), dtype)
    if case == "every_row_dead":
        assert not np.asarray(got).any() and not np.asarray(dx, np.float32).any()
    after = sums_taken()
    assert after[route] - before[route] == 2
    assert {r: after[r] - before[r] for r in ROUTES if r != route} \
        == {r: 0 for r in ROUTES if r != route}


@pytest.mark.parametrize("route", ROUTES)
def test_sum_by_token_accumulates_in_float32(monkeypatch, route):
    """bfloat16 rows summed in float32: 256 + 1 + 1 + 1 is 259, which no
    bfloat16 holds (258 or 260), and 1 + 3 x 2^-9 keeps its last bits."""
    moe = take_route(monkeypatch, route)
    rows = jnp.array([256, 1, 1, 1, 1, 2 ** -9, 2 ** -9, 2 ** -9],
                     jnp.bfloat16)[:, None]
    tok = jnp.array([0, 0, 0, 0, 1, 1, 1, 1], jnp.int32)
    got = moe._sum_by_token(rows[::-1], tok[::-1], jnp.ones((8, 1), bool),
                            2, K)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got)[:, 0],
                                  [259.0, 1 + 3 * 2.0 ** -9])


# (tokens, chunk rows, the route the rule takes for their sums)
WALK_ROUTES = [(64, 32, "onehot"), (4096, 4096, "sorted")]


def test_walk_builds_nothing_of_all_entries_rows_and_scatters_nothing():
    """Value and gradient of the op, tokens x 4 entries walked ``rows`` a
    trip, at a shape of each route: no array of all the entries' rows by
    d = 32 or h = 24 columns, no scatter, and on the sorted route no
    product with an operand of rows x tokens; the route every sum took
    is read off the counters.  The gather that autodiff would reverse
    has the first two, and the one-hot route the third, so the search
    sees."""
    from mxnet_tpu.analysis.jaxpr_passes import iter_eqns
    from mxnet_tpu.parallel import moe
    wg, wu, wd = expert_weights(jnp.float32)
    for tokens, rows, route in WALK_ROUTES:
        entries = tokens * 4
        x, wt = rnd(41, tokens, 32), rnd(42, tokens, 4)
        idx = jax.random.randint(jax.random.key(43), (tokens, 4), 0, 16,
                                 jnp.int32)

        def held(x, wt, wg, wu, wd):
            return jnp.sum(moe.moe_apply_held(
                x, idx, wt, wg, wu, wd, 4, 16, chunk_rows=rows)[0] ** 2)

        def spread(x, wt, wg, wu, wd):
            return jnp.sum(x[jnp.argsort(idx.reshape(-1)) // 4] ** 2)

        def found(fn):
            before = sums_taken()
            eqns = list(iter_eqns(jax.make_jaxpr(jax.value_and_grad(
                fn, argnums=(0, 1, 2, 3, 4)))(x, wt, wg, wu, wd)))
            after = sums_taken()
            shapes = {tuple(v.aval.shape) for e in eqns for v in e.outvars}
            names = {e.primitive.name for e in eqns}
            products = {tuple(v.aval.shape) for e in eqns
                        if e.primitive.name == "dot_general"
                        for v in e.invars}
            return ({s for s in shapes if len(s) == 2 and s[0] == entries
                     and s[1] in (32, 24)},
                    {n for n in names if "scatter" in n},
                    {"while", "ragged_dot_general"} <= names,
                    (rows, tokens) in products,
                    {r for r in ROUTES if after[r] > before[r]})

        assert found(spread) == ({(entries, 32)}, {"scatter-add"}, False,
                                 False, set())
        assert found(held) == (set(), set(), True, route == "onehot",
                               {route})


def test_all_absent_leaves_the_shared_experts_part(ref):
    """The whole layer when the router sends nothing here: the shared
    expert's output alone, in the program and in the reference."""
    cfg = tiny_cfg()
    z = ref._sizes(cfg)
    params, _ = ref.init(cfg, jax.random.key(40))
    p = lambda n: params["l1_" + n]                           # noqa: E731
    x = rnd(41, 1, 32, 64)
    bias = jnp.where(jnp.arange(16) >= 8, 5.0, 0.0)   # only 8..15 chosen
    y, count = ref._expert_layer(x, p, bias, z, cfg, None)
    shared = ref._ffn(x, p("moe_shared_gate_weight"),
                      p("moe_shared_up_weight"), p("moe_shared_down_weight"),
                      None)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(shared))
    assert float(count[:8].sum()) == 0 and float(count.sum()) == 32 * 4


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """16 experts in shares of 4: the four shares' routed parts (the
    program's op), plus what every chip computes alike, the shared
    expert, counted once, are the reference's uncut layer."""
    cfg = tiny_cfg(n_routed_experts=16)          # the reference holds all
    z = ref._sizes(cfg)
    assert z["held"] == z["experts"] == 16
    params, aux = ref.init(cfg, jax.random.key(50))
    p = lambda n: params["l1_" + n]                           # noqa: E731
    x = rnd(51, 1, 40, 64)
    bias = aux["l1_moe_router_bias"]
    whole, count = ref._expert_layer(x, p, bias, z, cfg, None)

    rows = x[0]
    idx, wt, _, _ = router(rows, p("moe_router_weight"), bias)
    total = ref._ffn(rows, p("moe_shared_gate_weight"),
                     p("moe_shared_up_weight"), p("moe_shared_down_weight"),
                     None)
    for first in (0, 4, 8, 12):
        part, c = experts_op(
            rows, idx, wt, *(p("moe_experts_%s_weight" % n)[first:first + 4]
                             for n in ("gate", "up", "down")), first=first)
        np.testing.assert_array_equal(np.asarray(c), np.asarray(count))
        total = total + part
    close(total, whole[0], jnp.float32)


# ----------------------------------------------------------------------
# the builder
def test_published_configuration_by_shapes_alone(ref):
    """At the published widths nothing is allocated: the Symbol's
    arguments and auxiliary states are the reference's ``param_shapes``,
    706.5M parameters in leaves of up to three dimensions."""
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
    assert have["l3_moe_experts_gate_weight"] == (8, 1536, 2048)
    assert have["l3_moe_experts_down_weight"] == (8, 2048, 1536)
    assert have["l2_moe_router_weight"] == (64, 2048)
    total = sum(int(np.prod(s)) for s in have.values())
    assert round(total / 1e6, 1) == 706.5
    assert out_s == [(4096, 19360), (4096, 19360)]
    assert sym.list_outputs() == ["softmax_output", "mtp_softmax_output"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok"):
        assert key not in cfg["reduced"]
    assert sorted(cfg["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]


def test_costs_of_one_expert_layer_by_hand(ref):
    """Layer 2 at the cell's 1 x 4,096 tokens: five latent-attention
    projections, the shared expert, the router, and the experts at the
    expected 4,096 x 4 x 8/64 = 2,048 entries."""
    c = ref.costs(published(), 1)
    by, rows = c["by_layer"], 4096
    proj = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert proj == 21_757_952
    assert sum(by["l2_attn_" + n] for n in ("qa", "qb", "kva", "kvb", "o")) \
        == 6 * rows * proj
    assert by["l2_moe_router"] == 6 * rows * 2048 * 64
    assert sum(by["l2_moe_shared_" + n] for n in ("gate", "up", "down")) \
        == 6 * rows * 3 * 2048 * 1536
    assert by["l2_moe_experts"] == 6 * 2048 * 3 * 2048 * 1536
    assert by["l2_attn"] == 6 * 2 * 1 * 20 * 256 * (4096 * 4096 // 2)
    assert by["head"] == by["mtp_head"] == 6 * rows * 2048 * 19360
    assert c["matmul"]["flops"] / 6 / rows == pytest.approx(328.99e6,
                                                            rel=1e-4)
    assert c["model_flops"] == c["matmul"]["flops"] + \
        c["experts"]["flops"] + c["attention"]["flops"]


# ----------------------------------------------------------------------
# the tiny model through Module's fused step against the reference
B, T, LR = 4, 32, 0.02


def tiny_module(cfg, params, aux, compute_dtype):
    sym = models.get_symbol("glm-moe", vocab_size=512)
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


def total_loss(outs, label):
    """main + 0.3 x module, from the two softmax outputs, as the
    reference sums them: over positions, divided by their number."""
    def nll(probs, target):
        p = jnp.take_along_axis(probs.astype(jnp.float32),
                                jnp.maximum(target, 0)[:, None], axis=1)
        return -jnp.sum(jnp.where(target[:, None] >= 0, jnp.log(p), 0.0))
    after = jnp.concatenate([label[:, 1:], -jnp.ones((B, 1), jnp.int32)], 1)
    return float((nll(outs[0], label.reshape(-1))
                  + 0.3 * nll(outs[1], after.reshape(-1))) / (B * T))


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
        losses.append(total_loss([o.data for o in mod.get_outputs()], label))
        if i == 0:
            grad = {n: np.asarray(v) / -LR
                    for n, v in mod._trainer.opt_state.items()}
    assert metric.num_inst == 3 * B * T        # the main head alone
    return losses, grad, {n: np.asarray(v)
                          for n, v in mod._trainer.params.items()}


def reference_steps(ref, refsteps, cfg, params, aux, feed, cast=None):
    opt = {"learning_rate": LR, "momentum": 0.9}
    step = refsteps.make_step(ref, cfg, opt, refsteps.CASTS[cast])
    p, a = jax.tree.map(jnp.copy, (params, aux))
    mom = jax.tree.map(jnp.zeros_like, p)
    losses, grad = [], None
    for i, (data, label) in enumerate(feed):
        g = jax.grad(lambda q: ref.loss(cfg, q, a, data, label,
                                        refsteps.CASTS[cast])[0])(p) \
            if i == 0 else None
        grad = grad or {n: np.asarray(v) for n, v in g.items()}
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
    mod = tiny_module(cfg, params, aux, None)
    losses, grad, after = program_steps(mod, feed)
    np.testing.assert_allclose(losses, want_l, rtol=1e-4)
    assert set(grad) == set(want_g) == set(params)
    for n in sorted(params):
        start = np.asarray(params[n])
        for got, want in ((grad[n], want_g[n]),
                          (after[n] - start, want_p[n] - start)):
            assert np.linalg.norm(got - want) \
                <= 1e-4 * np.linalg.norm(want), n
    # b is held fixed, and the counts are the last step's
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
    control (the reference with every product's operands in float8),
    which is the nearest precision below and has to read larger."""
    cfg, params, aux, feed, want = tiny
    start = {n: np.asarray(v) for n, v in params.items()}
    mod = tiny_module(cfg, params, aux, "bfloat16")
    got = gaps(refsteps, program_steps(mod, feed), want, start)
    control = gaps(refsteps, reference_steps(ref, refsteps, cfg, params, aux,
                                             feed, cast="fp8"), want, start)
    for name in ("grad_norm_gap_median", "change_norm_gap_median",
                 "grad_norm_gap_big_median", "change_norm_gap_big_median"):
        assert got[name] < control[name], (name, got[name], control[name])
    for i in (1, 2, 3):
        assert got["loss_gap_step%d" % i] < 2e-3


def test_obs_gauges_after_two_steps_equal_counts_made_by_hand(tiny):
    cfg, params, aux, feed, _ = tiny
    mod = tiny_module(cfg, params, aux, None)
    gauges = obs.snapshot()["gauges"]
    assert gauges["moe.experts_held"] == 4
    assert gauges["moe.entries_per_step"] == 2 * B * T * 4   # two layers
    for data, label in feed[:2]:
        batch = mx.io.DataBatch(data=[mx.nd.NDArray(data)],
                                label=[mx.nd.NDArray(label)], pad=0)
        mod.forward(batch, is_train=True)
        mod.update()
    counts = [np.asarray(mod._trainer.aux[n]) for n in
              ("l1_moe_experts_count", "mtp_moe_experts_count")]
    held = np.concatenate([c[:4] for c in counts])
    gauges = obs.snapshot()["gauges"]
    assert gauges["moe.held_entries_share"] == pytest.approx(
        held.sum() / (2 * B * T * 4))
    assert gauges["moe.load_max_over_mean"] == pytest.approx(
        held.max() / held.mean())
    # the chunks of sorted rows that held a live entry, a layer: the
    # chunk is twice the expected entries up to a multiple of 512, 1,024
    # rows in ling3flash_train and 4,096 in glm47flash_train, and all
    # 512 entries here; then with chunks of 16 rows in its place
    from mxnet_tpu.parallel.moe import held_chunk_rows
    assert held_chunk_rows(4096 * 8, 8, 512) == 1024
    assert held_chunk_rows(4096 * 4, 8, 64) == 4096
    assert held_chunk_rows(B * T * 4, 4, 16) == B * T * 4 == 512
    layers = mod._trainer._moe_layers
    assert [rows for _, _, _, rows in layers] == [512, 512]
    assert gauges["moe.row_chunks_per_layer"] == 1.0
    mod._trainer._moe_layers = [layer[:3] + (16,) for layer in layers]
    by_hand = np.mean([np.ceil(c[:4].sum() / 16) for c in counts])
    assert by_hand > 4
    assert obs.snapshot()["gauges"]["moe.row_chunks_per_layer"] \
        == pytest.approx(by_hand)
    # a trainer that goes away leaves its last reading
    del mod
    import gc
    gc.collect()
    assert obs.snapshot()["gauges"]["moe.load_max_over_mean"] \
        == pytest.approx(held.max() / held.mean())


# ----------------------------------------------------------------------
# a parameter used twice, and ids that are not rounded
def two_uses(tied):
    """One table looked up by the data and by the label, as the trunk
    and the prediction module do, into two loss heads."""
    data, label = mx.sym.Variable("data"), mx.sym.Variable("softmax_label")
    w1 = mx.sym.Variable("embed_weight")
    w2 = w1 if tied else mx.sym.Variable("embed2_weight")
    head = mx.sym.Variable("head_weight")
    a = mx.sym.Embedding(data, weight=w1, input_dim=50, output_dim=8,
                         name="a")
    b = mx.sym.Embedding(label, weight=w2, input_dim=50, output_dim=8,
                         name="b")
    one = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        a, weight=head, num_hidden=50, no_bias=True, name="h1"), label,
        name="softmax")
    two = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        a + b, weight=head, num_hidden=50, no_bias=True, name="h2"), label,
        grad_scale=0.3, name="mtp_softmax")
    return mx.sym.Group([one, two])


@pytest.mark.parametrize("dtype", [None, "bfloat16"])
def test_a_parameter_used_twice_gets_the_sum_of_its_uses(dtype):
    """The embedding's gradient with both uses tied is the trunk's use
    alone plus the module's use alone; the head's likewise."""
    table = rnd(70, 50, 8, scale=0.5)
    head = rnd(71, 50, 8, scale=0.5)
    ids = jax.random.randint(jax.random.key(72), (2, 16), 0, 50, jnp.int32)

    def grads(tied):
        mod = mx.mod.Module(context=mx.tpu(), symbol=two_uses(tied),
                            compute_dtype=dtype)
        mod.bind(data_shapes=[("data", (16,))],
                 label_shapes=[("softmax_label", (16,))])
        args = {"embed_weight": table, "head_weight": head}
        if not tied:
            args["embed2_weight"] = table
        mod.init_params(initializer=None, force_init=True,
                        arg_params={n: mx.nd.NDArray(v)
                                    for n, v in args.items()})
        mod.init_optimizer(kvstore=mx.kvstore.create("dist_sync_tpu"),
                           optimizer="sgd", optimizer_params={
                               "learning_rate": 1.0, "momentum": 0.9})
        mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(ids[0])],
                                    label=[mx.nd.NDArray(ids[1])], pad=0),
                    is_train=True)
        mod.update()
        return {n: -np.asarray(v) for n, v in mod._trainer.opt_state.items()}

    both, apart = grads(True), grads(False)
    assert np.abs(apart["embed2_weight"]).max() > 0
    tol = 1e-6 if dtype is None else 2e-2
    np.testing.assert_allclose(
        both["embed_weight"], apart["embed_weight"] + apart["embed2_weight"],
        rtol=tol, atol=tol * np.abs(both["embed_weight"]).max())
    np.testing.assert_allclose(both["head_weight"], apart["head_weight"],
                               rtol=tol, atol=tol)


def test_token_ids_fed_as_float32_are_not_rounded_to_bfloat16():
    """MXNet's convention feeds ids as float32; the fused step casts
    floating inputs to the compute type, and bfloat16 is exact to 256
    only.  Inputs a graph consumes as indices pass uncast: a tiny GPT
    with ids to 2,000 gives the same loss either way."""
    from mxnet_tpu.parallel.trainer import _index_inputs
    from mxnet_tpu.executor import _GraphProgram
    sym = models.get_symbol("transformer", seq_len=16, num_hidden=32,
                            num_heads=2, num_layers=1, vocab_size=2000)
    assert _index_inputs(_GraphProgram(sym).nodes) \
        == {"data", "softmax_label"}
    assert _index_inputs(_GraphProgram(
        models.get_symbol("glm-moe", vocab_size=512)).nodes) \
        == {"data", "softmax_label"}
    assert _index_inputs(_GraphProgram(models.get_symbol(
        "mlp", num_classes=10)).nodes) == {"softmax_label"}
    ids = jax.random.randint(jax.random.key(80), (4, 17), 300, 2000,
                             jnp.int32)

    def first_loss(dtype):
        mx.random.seed(7)
        mod = mx.mod.Module(context=mx.tpu(), symbol=sym,
                            compute_dtype="bfloat16")
        mod.bind(data_shapes=[("data", (4, 16))],
                 label_shapes=[("softmax_label", (4, 16))])
        mod.init_params(mx.init.Normal(0.05))
        mod.init_optimizer(kvstore=mx.kvstore.create("dist_sync_tpu"),
                           optimizer="sgd",
                           optimizer_params={"learning_rate": 0.1})
        data, label = ids[:, :-1].astype(dtype), ids[:, 1:].astype(dtype)
        mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(data)],
                                    label=[mx.nd.NDArray(label)], pad=0),
                    is_train=True)
        mod.update()
        probs = mod.get_outputs()[0].data.astype(jnp.float32)
        p = jnp.take_along_axis(probs, ids[:, 1:].reshape(-1, 1), axis=1)
        return float(-jnp.mean(jnp.log(p))), \
            np.asarray(mod._trainer.params["tok_embed_weight"])

    (as_int, emb_int), (as_float, emb_float) = \
        first_loss(jnp.int32), first_loss(jnp.float32)
    assert as_float == as_int
    np.testing.assert_array_equal(emb_float, emb_int)
