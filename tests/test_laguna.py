"""Laguna-XS.2 through the Symbol path: YaRN in ``RotaryEmbedding``, the
gate a head on the attention's result, a chip's share of the experts
with a shared expert against the whole layer, the configuration at its
published widths by shapes, and the tiny model through ``Module``'s
fused step against the benchmark's plain reference
(``benchmark/reference/laguna-xs.2.py``, loaded by path).  The windowed
kernels are ``tests/test_laguna_window.py``'s."""
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, obs
from mxnet_tpu import name as mxname
from mxnet_tpu.models import laguna
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
    return _load(os.path.join(BENCH, "reference", "laguna-xs.2.py"),
                 "laguna_reference")


@pytest.fixture(scope="module")
def refsteps():
    return _load(os.path.join(BENCH, "lib", "refsteps.py"),
                 "laguna_refsteps")


def published():
    with open(os.path.join(BENCH, "configs", "laguna-xs.2.json")) as f:
        return json.load(f)


B, T, LR = 2, 64, 0.02
# ``laguna``'s defaults: three blocks (a dense one attending fully at 4
# query heads, two expert ones in a window of 16 at 8), d 64, heads of
# 16 over 2 key/value heads, 16 experts of which 4 are held
TINY_ROPE = laguna._TOY_ROPE


def tiny_cfg(**over):
    """The published file cut to ``laguna``'s defaults: published layers
    0 (full, dense), 1 and 2 (sliding, experts) kept, 4 of 16 experts
    held, 64 positions."""
    cfg = published()
    heads = [4 if k == "full_attention" else 8 for k in cfg["layer_types"]]
    cfg.update(hidden_size=64, num_key_value_heads=2, head_dim=16,
               sliding_window=16, intermediate_size=160,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_experts=4, num_experts_per_tok=4, num_hidden_layers=3,
               vocab_size=512,
               num_attention_heads_per_layer=heads,
               rope_parameters=TINY_ROPE)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], layers_kept=[0, 1, 2])
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


def rnd(seed, *shape, scale=1.0):
    return scale * jax.random.normal(jax.random.key(seed), shape,
                                     jnp.float32)


def close(got, want, tol=2e-5):
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * max(np.abs(want).max(), 1e-6))


# ----------------------------------------------------------------------
# YaRN
def yarn_by_hand(theta, r, factor, original, beta_fast, beta_slow):
    """transformers' ``_compute_yarn_parameters`` written out again, in
    float64: (inverse frequencies, the first and last dim of the ramp)."""
    def correction_dim(turns):
        return r * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), r - 1)
    pos = theta ** (np.arange(0, r, 2) / r)
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0, 1)
    extrapolation = 1 - ramp
    inv = (1 / (factor * pos)) * (1 - extrapolation) \
        + (1 / pos) * extrapolation
    return inv, low, high


def test_yarn_frequencies_are_the_formula_on_half_a_head():
    """Laguna's full-layer rotary (theta 5e5, factor 64 over 4,096
    original positions, beta 64 and 1, attention factor 1.41589) on the
    first 64 of 128 dims: the ramp runs from pair 5 to pair 16, the
    rotated dims are the formula's angles with cos and sin scaled, and
    dims 64 to 127 pass through untouched."""
    rope = published()["rope_parameters"]["full_attention"]
    af = rope["attention_factor"]
    inv, low, high = yarn_by_hand(rope["rope_theta"], 64, rope["factor"],
                                  rope["original_max_position_embeddings"],
                                  rope["beta_fast"], rope["beta_slow"])
    assert (low, high) == (5, 16)
    assert af == pytest.approx(0.1 * math.log(64) + 1)
    t = 300
    x = rnd(1, 1, t, 2, 128)
    got = op_fn("RotaryEmbedding", base=rope["rope_theta"], dim=64,
                rope_type="yarn", factor=rope["factor"],
                original_max_position=rope[
                    "original_max_position_embeddings"],
                beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
                attention_factor=af)(x)
    xs = np.asarray(x, np.float64)
    ang = np.arange(t)[:, None] * inv
    cos, sin = af * np.cos(ang)[:, None], af * np.sin(ang)[:, None]
    x1, x2 = xs[..., :32], xs[..., 32:64]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xs[..., 64:]], -1)
    close(got, want, 2e-5)
    np.testing.assert_array_equal(np.asarray(got[..., 64:]),
                                  np.asarray(x[..., 64:]))
    # with no attention factor given yarn takes 0.1 ln(factor) + 1
    dflt = op_fn("RotaryEmbedding", base=rope["rope_theta"], dim=64,
                 rope_type="yarn", factor=64.0, original_max_position=4096,
                 beta_fast=64.0, beta_slow=1.0)(x)
    close(dflt, got, 1e-5)


def test_default_rotary_is_unchanged_by_the_new_parameters():
    """The sliding layers' rope (theta 1e4 on all 128 dims) is the plain
    angle t theta^(-2i/128), cos and sin unscaled: the parameters' defaults
    keep every earlier model's angles."""
    t = 40
    x = rnd(2, 1, t, 2, 128)
    got = op_fn("RotaryEmbedding", base=10000.0)(x)
    inv = 10000.0 ** (-np.arange(64) * 2.0 / 128)
    ang = np.arange(t)[:, None] * inv
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    xs = np.asarray(x, np.float64)
    want = np.concatenate([xs[..., :64] * cos - xs[..., 64:] * sin,
                           xs[..., 64:] * cos + xs[..., :64] * sin], -1)
    close(got, want, 2e-5)


def test_yarn_of_the_reference_is_the_formula(ref):
    rope = published()["rope_parameters"]["full_attention"]
    inv, _, _ = yarn_by_hand(rope["rope_theta"], 64, rope["factor"],
                             rope["original_max_position_embeddings"],
                             rope["beta_fast"], rope["beta_slow"])
    got, scale = ref.rope_frequencies(rope, 64)
    close(got, inv, 1e-6)
    assert scale == rope["attention_factor"]


# ----------------------------------------------------------------------
# the gated attention mixer
def mixer_out(kind, h, arrays, x, t=32):
    """``laguna``'s attention mixer of ``kind`` at ``h`` query heads,
    bound on ``arrays`` (the leaves without their prefix), for the rows
    ``x``."""
    cfg = dict(seq_len=t, hidden=64, num_kv_heads=2, head_dim=16, window=8,
               rope=TINY_ROPE)
    with mxname.Prefix("m_"):
        net = laguna._attention(mx.sym.Variable("x"), cfg, kind, h)
    args = {"x": x}
    args.update({"m_" + n: v for n, v in arrays.items()})
    assert set(net.list_arguments()) == set(args)
    ex = net.bind(mx.cpu(), {n: mx.nd.NDArray(v) for n, v in args.items()})
    return ex.forward(is_train=False)[0].data


def attn_leaves(ref, layer):
    cfg = tiny_cfg()
    params, _ = ref.init(cfg, jax.random.key(20 + layer))
    pre = "l%d_" % layer
    return cfg, {n[len(pre):]: v * (5.0 if "gate" in n else 1.0)
                 for n, v in params.items() if n.startswith(pre + "attn_")}


@pytest.mark.parametrize("layer,kind,h", [(0, "full_attention", 4),
                                          (1, "sliding_attention", 8)])
def test_gated_mixer_is_the_references(ref, layer, kind, h):
    """The mixer, rotary, grouped heads, window and gate together, on
    two rows against the reference's (the gate's weights scaled up so
    that its sigmoid is far from one half)."""
    cfg, leaves = attn_leaves(ref, layer)
    cfg["sliding_window"] = 8
    z = ref._sizes(cfg)
    x = rnd(21, 2, 32, 64)
    got = mixer_out(kind, h, leaves, x.reshape(-1, 64)).reshape(2, 32, 64)
    want = ref._attention(x, lambda n: leaves[n], z, cfg, None, kind, h)
    close(got, want, 1e-4)


def test_the_gate_is_one_scalar_a_head():
    """A head whose gate is shut adds nothing: with head 3's gate logit
    at -1e4 the mixer's output is the one with head 3's columns of W_o
    zeroed, whatever head 3 computes; W_g has one row a head."""
    h, n = 8, 16
    leaves = {"attn_q_weight": rnd(1, h * n, 64, scale=0.2),
              "attn_k_weight": rnd(2, 2 * n, 64, scale=0.2),
              "attn_v_weight": rnd(3, 2 * n, 64, scale=0.2),
              "attn_gate_weight": rnd(4, h, 64, scale=0.2),
              "attn_o_weight": rnd(5, 64, h * n, scale=0.2)}
    x = rnd(6, 32, 64)
    shut = dict(leaves, attn_gate_weight=leaves["attn_gate_weight"].at[3]
                .set(0.0))
    x_bias = x.at[:, 0].set(1.0)              # the logit is -1e4 x[:, 0]
    shut["attn_gate_weight"] = shut["attn_gate_weight"].at[3, 0].set(-1e4)
    zeroed = dict(shut, attn_o_weight=leaves["attn_o_weight"]
                  .at[:, 3 * n:4 * n].set(0.0))
    a = mixer_out("sliding_attention", h, shut, x_bias)
    b = mixer_out("sliding_attention", h, zeroed, x_bias)
    close(a, b, 1e-6)
    c = mixer_out("sliding_attention", h, leaves, x_bias)
    assert np.abs(np.asarray(c - a)).max() > 1e-3


def test_nodes_are_named_for_the_metrics():
    """Full layers under ``l<i>_attn_attn`` (the flash roofline's scope),
    window layers under ``l<i>_attn_window`` (the window roofline's), the
    gate's product ``l<i>_attn_gate``."""
    net = models.get_symbol("laguna", vocab_size=512, seq_len=T)
    names = {n.rsplit("_output", 1)[0]
             for n in net.get_internals().list_outputs()}
    assert {"l0_attn_attn", "l1_attn_window", "l2_attn_window",
            "l0_attn_gate", "l1_attn_gate"} <= names
    assert "l0_attn_window" not in names and "l1_attn_attn" not in names
    args = dict(zip(net.list_arguments(), net.infer_shape(
        data=(B, T), softmax_label=(B, T))[0]))
    assert args["l0_attn_gate_weight"] == (4, 64)
    assert args["l1_attn_gate_weight"] == (8, 64)
    assert args["l1_attn_q_weight"] == (128, 64)
    with pytest.raises(ValueError, match="layer_types"):
        models.get_symbol("laguna", layer_types="full_attention,mamba",
                          heads_per_layer="4,4")
    with pytest.raises(ValueError, match="key/value heads"):
        models.get_symbol("laguna", heads_per_layer="4,8,7")
    with pytest.raises(ValueError, match="shared expert"):
        models.get_symbol("laguna", shared_expert_intermediate_size=64)


# ----------------------------------------------------------------------
# a chip's share of the experts
def test_the_expert_shares_add_up_to_the_uncut_expert_layer(ref):
    """32 experts in 16 shares of 2, as the deployment splits 256 in 16
    shares of 16: the shares' routed parts (the program's router and
    experts) with the shared expert, which every chip computes alike,
    counted once, are the reference's uncut layer."""
    cfg = tiny_cfg(num_experts=32)               # the reference holds all
    cfg["published"] = dict(cfg["published"], num_experts=32)
    z = ref._sizes(cfg)
    assert z["held"] == z["experts"] == 32
    params, aux = ref.init(cfg, jax.random.key(30))
    p = lambda n: params["l1_" + n]                           # noqa: E731
    x = rnd(31, 1, 40, 64)
    bias = aux["l1_moe_router_bias"]
    whole, count = ref.expert_layer(x, p, bias, z, cfg)
    rows = x[0]
    (idx, wt, _), _ = op_fn("MoERouter", num_experts=32, top_k=4,
                            scale=2.5)(rows, p("moe_router_weight"), bias)
    shared = op_fn("FullyConnected", num_hidden=64, no_bias=True)
    act = jax.nn.silu(rows @ p("moe_shared_gate_weight").T) \
        * (rows @ p("moe_shared_up_weight").T)
    total, shares = shared(act, p("moe_shared_down_weight")), 0
    for first in range(0, 32, 2):
        (part,), (c,) = op_fn(
            "MoEExperts", num_experts=32, experts_held=2,
            first_expert=first, num_hidden=32)(
                rows, idx, wt, *(p("moe_experts_%s_weight" % n)
                                 [first:first + 2]
                                 for n in ("gate", "up", "down")),
                jnp.zeros(32))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(count))
        total, shares = total + part, shares + 1
    assert shares == 16
    close(total, whole[0], 1e-4)
    assert float(count.sum()) == 40 * 4


# ----------------------------------------------------------------------
# the network at the published widths
def test_published_configuration_by_shapes_alone(ref):
    """At the published widths nothing is allocated: the Symbol's
    arguments and auxiliary states are the reference's ``param_shapes``,
    490.3M parameters; the kept layers are one whole period after the
    dense layer, and no width, head count, window or rope setting is
    among the keys cut."""
    cfg = published()
    net = models.get_symbol(cfg["symbol"]["network"],
                            **cfg["symbol"]["kwargs"])
    arg_s, out_s, aux_s = net.infer_shape(data=(1, 8192),
                                          softmax_label=(1, 8192))
    have = {n: tuple(s) for n, s in zip(net.list_arguments(), arg_s)
            if n not in ("data", "softmax_label")}
    want_p, want_a = ref.param_shapes(cfg)
    assert have == {n: tuple(s) for n, s in want_p.items()}
    assert dict(zip(net.list_auxiliary_states(), map(tuple, aux_s))) \
        == {n: tuple(s) for n, s in want_a.items()}
    assert have["l0_attn_q_weight"] == (48 * 128, 2048)
    assert have["l1_attn_q_weight"] == (64 * 128, 2048)
    assert have["l1_attn_k_weight"] == have["l1_attn_v_weight"] \
        == (1024, 2048)
    assert have["l1_attn_gate_weight"] == (64, 2048)
    assert have["l4_attn_gate_weight"] == (48, 2048)
    assert have["l2_moe_experts_gate_weight"] == (16, 512, 2048)
    assert have["l2_moe_router_weight"] == (256, 2048)
    assert have["l2_moe_shared_up_weight"] == (512, 2048)
    assert have["l0_mlp_gate_weight"] == (8192, 2048)
    total = sum(int(np.prod(s)) for s in have.values())
    assert round(total / 1e6, 1) == 490.3
    assert out_s == [(8192, 12544)]
    z = ref._sizes(cfg)
    assert z["kinds"] == ["full_attention"] + ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert z["heads"] == [48, 64, 64, 64, 48]
    assert z["is_dense"] == [True] + [False] * 4
    kw = cfg["symbol"]["kwargs"]
    assert kw["layer_types"].split(",") == z["kinds"]
    assert kw["rope_parameters"]["full_attention"] \
        == cfg["rope_parameters"]["full_attention"]
    assert len(cfg["layer_types"]) == cfg["published"]["num_hidden_layers"]
    assert sorted(cfg["reduced"]) == sorted(cfg["published"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    dep = cfg["deployment"]
    assert (dep["chips_per_layer"], dep["chips_per_vocabulary"]) == (16, 8)
    assert cfg["published"]["num_experts"] // dep["chips_per_layer"] \
        == cfg["num_experts"] == kw["experts_held"]
    assert cfg["published"]["vocab_size"] // dep["chips_per_vocabulary"] \
        == cfg["vocab_size"]
    for key in ("gating", "router", "selection_bias", "qk_norm",
                "initializer_range", "seq_len", "optimizer", "precision"):
        assert key in cfg["assumed"]


def test_costs_by_hand(ref):
    """At the cell's 1 x 8,192 tokens: 19.40 TFLOP a step; the window
    layers' cores a fifth of the full layers' though they have more
    heads; a kernel that computed every causal pair of them would do
    8.3 times the window layers' core work."""
    c = ref.costs(published(), 1)
    by = c["by_layer"]
    assert round(c["model_flops"] / 1e12, 2) == 19.40
    full_pairs, live = 8192 * 8192 // 2, 8192 * 512 - 512 * 511 // 2
    assert by["l0_attn"] == by["l4_attn"] == 12 * 48 * full_pairs * 128
    assert c["window"]["flops"] == 3 * 12 * 64 * live * 128
    assert round(full_pairs / live, 1) == 8.3
    assert by["l3_moe_experts"] == 6 * (8192 * 8 * 16 // 256) * 3 * 2048 * 512
    assert c["model_flops"] == sum(c[k]["flops"] for k in
                                   ("matmul", "experts", "attention",
                                    "window"))


# ----------------------------------------------------------------------
# the tiny model through Module's fused step against the reference
def tiny_module(params, aux, compute_dtype):
    net = models.get_symbol("laguna", vocab_size=512, seq_len=T)
    mod = mx.mod.Module(context=mx.tpu(), symbol=net,
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
    # the gates' weights scaled up, so that a gate is far from one half
    params = {n: v * (5.0 if n.endswith("attn_gate_weight") else 1.0)
              for n, v in params.items()}
    feed = batches(61)
    return cfg, params, aux, feed, reference_steps(ref, refsteps, cfg,
                                                   params, aux, feed)


@pytest.fixture(scope="module")
def tiny_program(tiny):
    """The program's three steps in float32 on the tiny model's weights."""
    _, params, aux, feed, _ = tiny
    return program_steps(tiny_module(params, aux, None), feed)


BLOCKS = {"full/dense": "l0_", "window/expert": "l1_",
          "window/expert again": "l2_"}


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_tiny_model_float32_matches_the_reference_leaf_by_leaf(
        tiny, tiny_program, block):
    """Three losses, and for every leaf of the block (and of the
    embedding, final norm and head, with the first) the first gradient
    and the three-step change: the norm of the difference within 1e-4 of
    the leaf's norm; the window and full layers, their rotary and gates,
    the routed and shared experts all in it."""
    cfg, params, aux, feed, (want_l, want_g, want_p) = tiny
    losses, grad, after = tiny_program
    np.testing.assert_allclose(losses, want_l, rtol=1e-4)
    assert set(grad) == set(want_g) == set(params)
    pre = BLOCKS[block]
    leaves = [n for n in params if n.startswith(pre)
              or (pre == "l0_" and not n.startswith("l"))]
    assert len(leaves) >= 8
    for n in sorted(leaves):
        start = np.asarray(params[n])
        for got, want in ((grad[n], want_g[n]),
                          (after[n] - start, want_p[n] - start)):
            assert np.linalg.norm(want) > 0, n
            assert np.linalg.norm(got - want) \
                <= 1e-4 * np.linalg.norm(want), n


def test_tiny_model_trains_through_module_fit_and_counts_its_windows(tiny):
    """``Module.fit`` on a ``tpu`` context, no side script: five epochs
    on one batch of a repeating sequence, and the loss falls.  Tracing
    the step counted its two window nodes and left the live share of
    their tiles."""
    cfg, params, aux, _, _ = tiny
    before = obs.snapshot()["counters"].get("attention.window.nodes", 0)
    ids = np.tile(np.arange(T + 1) % 7, (B, 1)).astype(np.int32)
    it = mx.io.NDArrayIter(ids[:, :-1], ids[:, 1:], batch_size=B)
    mod = mx.mod.Module(context=mx.tpu(), symbol=models.get_symbol(
        "laguna", vocab_size=512, seq_len=T))
    metric = mx.metric.create("ce")
    seen = []
    nd = mx.nd.NDArray
    mod.fit(it, num_epoch=5, eval_metric=metric, optimizer="sgd",
            arg_params={n: nd(v) for n, v in params.items()},
            aux_params={n: nd(v) for n, v in aux.items()},
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "rescale_grad": 1.0 / (B * T)},
            batch_end_callback=lambda p: seen.append(
                p.eval_metric.get()[1]))
    assert mod._trainer is not None
    assert len(seen) == 5 and seen[-1] < seen[0] - 1, seen
    snap = obs.snapshot()
    nodes = snap["counters"]["attention.window.nodes"] - before
    assert nodes >= 2 and nodes % 2 == 0
    # 64 positions in one 128 x 128 tile: 64 x 16 - 16 x 15 / 2 live
    # pairs of its 16,384
    assert snap["gauges"]["attention.window.live_share"] \
        == pytest.approx((64 * 16 - 120) / 128 ** 2)
