"""LFM2-24B-A2B through the Symbol path: the gated short convolution,
grouped key/value heads in the attention op, the expert layer with no
shared expert, a chip's share of the experts against the whole layer,
and the tiny model through ``Module``'s fused step against the
benchmark's plain reference (``benchmark/reference/lfm2-24b-a2b.py``,
loaded by path)."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, obs, program
from mxnet_tpu import name as mxname
from mxnet_tpu import symbol as sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import glm_moe, lfm2_moe
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
    return _load(os.path.join(BENCH, "reference", "lfm2-24b-a2b.py"),
                 "lfm2_reference")


@pytest.fixture(scope="module")
def refsteps():
    return _load(os.path.join(BENCH, "lib", "refsteps.py"), "lfm2_refsteps")


def published(name="lfm2-24b-a2b"):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


B, T, LR = 2, 64, 0.02
# ``lfm2-moe``'s defaults: three blocks (a dense one mixing by the gated
# short convolution, an expert one by attention over grouped heads, an
# expert one by the convolution), d 64, 4 query heads of 16 over 2
# key/value heads, 16 experts of which 4 are held
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=160, moe_intermediate_size=48, num_experts=4,
            num_hidden_layers=3, vocab_size=512)


def tiny_cfg(**over):
    """The published file cut to ``lfm2-moe``'s defaults: published
    layers 0 (convolution, dense), 2 (attention) and 3 (convolution)
    kept, 4 of 16 experts held, 64 positions."""
    cfg = published()
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], layers_kept=[0, 2, 3])
    cfg["input"] = {"kind": "tokens", "seq_len": T, "vocab": 512}
    cfg.update(over)
    return cfg


def op_fn(name, platform="cpu", **kwargs):
    """The registered op's body as a function of arrays."""
    op = registry.get(name)
    params = op.parse_params(kwargs)
    ctx = registry.OpContext(is_train=True, platform=platform)

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


def counters():
    c = obs.snapshot()["counters"]
    return (c.get("attention.grouped_kv.nodes", 0),
            c.get("attention.grouped_kv.repeat_bytes", 0))


# ----------------------------------------------------------------------
# grouped key/value heads in the attention op
def grouped_einsum(q, k, v, scale):
    """Causal softmax attention with query head j reading key/value head
    j // (h / h_kv), as one einsum over the groups: nothing repeated."""
    b, t, h, d = q.shape
    g = k.shape[2]
    qg = q.reshape(b, t, g, h // g, d)
    s = jnp.einsum("bqgjd,bkgd->bgjqk", qg, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("bgjqk,bkgd->bqgjd", jax.nn.softmax(s, -1), v)
    return o.reshape(b, t, h, v.shape[-1])


@pytest.mark.parametrize("kv_heads", [1, 2])
@pytest.mark.parametrize("flash", [True, False], ids=["flash", "oracle"])
def test_grouped_kv_heads_are_the_einsum_over_groups(flash, kv_heads, ref):
    """Four query heads over one or two key/value heads, the flash path
    interpreted and the oracle: value and the gradients of q, k and v
    against the einsum over grouped heads (autodiff sums dk and dv over
    each group); the reference's blocked attention is the same."""
    q = rnd(1, 2, 128, 4, 16)
    k, v = rnd(2, 2, 128, kv_heads, 16), rnd(3, 2, 128, kv_heads, 16)
    attn = op_fn("_contrib_DotProductAttention", causal=True, flash=flash,
                 scale=0.25)
    out = attn(q, k, v)
    assert out.shape == (2, 128, 4, 16)
    close(out, grouped_einsum(q, k, v, 0.25))
    close(ref.grouped_attention(q, k, v, block=32),
          grouped_einsum(q, k, v, 0.25))
    seed = rnd(4, 2, 128, 4, 16)
    got = jax.grad(lambda *a: jnp.sum(attn(*a) * seed), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(grouped_einsum(*a, 0.25) * seed),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        close(a, b)
    node = mx.sym._contrib_DotProductAttention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"))
    assert node.infer_shape(q=q.shape, k=k.shape, v=v.shape)[1] \
        == [(2, 128, 4, 16)]


def _attention_before_groups(p, c, q, k, v):
    """``op/attention.py``'s body as it stood before grouped heads,
    line for line."""
    from mxnet_tpu.op.attention import _LANES
    scale = None if p["scale"] <= 0 else p["scale"]
    if p["flash"]:
        from mxnet_tpu.op.pallas import flash_attention
        interpret = (c.platform or jax.default_backend()) != "tpu"
        kw = {}
        if p["block_q"]:
            kw["block_q"] = p["block_q"]
        if p["block_k"]:
            kw["block_k"] = p["block_k"]
        d_qk, d_v = q.shape[-1], v.shape[-1]
        if d_qk != d_v:
            width = -(-max(d_qk, d_v) // _LANES) * _LANES
            q, k, v = (jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))
                       for x in (q, k, v))
            scale = scale or d_qk ** -0.5
        out = flash_attention(q, k, v, causal=p["causal"], scale=scale,
                              interpret=interpret, **kw)
        return out if d_qk == d_v else out[..., :d_v]
    from mxnet_tpu.parallel.ring_attention import attention_reference
    return attention_reference(q, k, v, causal=p["causal"], scale=scale)


@pytest.mark.parametrize("flash,dv", [(True, 16), (True, 24), (False, 16)],
                         ids=["flash", "flash-padded", "oracle"])
def test_equal_heads_lower_as_before_bit_for_bit(flash, dv):
    """Where k and v carry the query's heads the op is what it was before grouped heads:
    the same jaxpr, value and gradient, equation for equation, and no
    counter moves."""
    q, k, v = rnd(5, 1, 64, 2, 16), rnd(6, 1, 64, 2, 16), rnd(7, 1, 64, 2, dv)
    kw = dict(causal=True, flash=flash)
    op = registry.get("_contrib_DotProductAttention")
    params = op.parse_params(kw)
    ctx = registry.OpContext(is_train=True, platform="cpu")
    start = counters()

    def now(*a):
        return op.fn(params, ctx, *a)

    def before(*a):
        return _attention_before_groups(params, ctx, *a)

    for f in (lambda fn: fn,
              lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) ** 2), (0, 1, 2))):
        assert str(jax.make_jaxpr(f(now))(q, k, v)) \
            == str(jax.make_jaxpr(f(before))(q, k, v))
    np.testing.assert_array_equal(np.asarray(now(q, k, v)),
                                  np.asarray(before(q, k, v)))
    assert counters() == start


def test_key_value_heads_that_do_not_divide_are_refused():
    attn = op_fn("_contrib_DotProductAttention", causal=True)
    q = rnd(8, 1, 32, 4, 16)
    with pytest.raises(MXNetError, match="3 key/value heads do not divide 4"):
        attn(q, rnd(9, 1, 32, 3, 16), rnd(10, 1, 32, 3, 16))
    with pytest.raises(MXNetError, match="key and value carry 2 and 1"):
        attn(q, rnd(9, 1, 32, 2, 16), rnd(10, 1, 32, 1, 16))
    node = mx.sym._contrib_DotProductAttention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"))
    with pytest.raises(MXNetError, match="do not divide"):
        node.infer_shape(q=(1, 32, 4, 16), k=(1, 32, 3, 16),
                         v=(1, 32, 3, 16))


def test_counters_of_grouped_nodes_and_their_repeated_bytes():
    """``attention.grouped_kv.nodes`` rises by one a grouped node traced
    and ``attention.grouped_kv.repeat_bytes`` by the bytes of k and v
    repeated to the query's heads: 4 heads of 16 over 64 positions in
    bfloat16, twice, from one or two key/value heads."""
    attn = op_fn("_contrib_DotProductAttention", causal=True, flash=False)
    q = rnd(11, 1, 64, 4, 16, dtype=jnp.bfloat16)
    for kv_heads in (1, 2):
        k = rnd(12, 1, 64, kv_heads, 16, dtype=jnp.bfloat16)
        before = counters()
        attn(q, k, k)
        assert counters() == (before[0] + 1,
                              before[1] + 2 * 64 * 4 * 16 * 2)
    before = counters()
    attn(q, q, q)                       # equal heads: nothing repeated
    assert counters() == before


# ----------------------------------------------------------------------
# the gated short convolution
def mixer_out(mixer, cfg, arrays, x):
    """One of ``lfm2_moe``'s mixers bound on ``arrays`` (the leaves
    without their prefix), its output for the rows ``x``."""
    with mxname.Prefix("m_"):
        net = mixer(mx.sym.Variable("x"), cfg)
    args = {"x": x}
    args.update({"m_" + n: v for n, v in arrays.items()})
    assert set(net.list_arguments()) == set(args)
    ex = net.bind(mx.cpu(), {n: mx.nd.NDArray(v) for n, v in args.items()})
    return ex.forward(is_train=False)[0].data


def mixer_cfg(t=T):
    return dict(seq_len=t, hidden=64, num_heads=4, num_kv_heads=2,
                head_dim=16, conv_kernel=3, rope_theta=1e6, eps=1e-5)


def block_leaves(ref, cfg, seed, layer, part):
    params, aux = ref.init(cfg, jax.random.key(seed))
    pre = "l%d_" % layer
    return {n[len(pre):]: v for n, v in params.items()
            if n.startswith(pre + part)}, params, aux


def test_conv_mixer_is_the_reference_causal_and_within_its_row(ref):
    """``lfm2_moe``'s gated short convolution on two rows against the
    reference's; an input moved at position 6 of row 0 moves no output
    before 6 and nothing of row 1, and moves outputs 6 to 8 (three
    taps) and on through nothing else: the mixer has no state past its
    taps."""
    cfg = tiny_cfg()
    z = ref._sizes(cfg)
    leaves, _, _ = block_leaves(ref, cfg, 20, 0, "sconv_")
    assert sorted(leaves) == ["sconv_in_weight", "sconv_out_weight",
                              "sconv_taps_weight"]
    x = rnd(21, 2, 16, 64)
    run = lambda x: mixer_out(lfm2_moe._sconv, mixer_cfg(16), leaves,  # noqa
                              x.reshape(-1, 64)).reshape(2, 16, 64)
    y = run(x)
    close(y, ref._sconv(x, lambda n: leaves[n], z, cfg, None))
    moved = run(x.at[0, 6].add(1.0))
    np.testing.assert_array_equal(np.asarray(moved[0, :6]),
                                  np.asarray(y[0, :6]))
    np.testing.assert_array_equal(np.asarray(moved[1]), np.asarray(y[1]))
    np.testing.assert_array_equal(np.asarray(moved[0, 9:]),
                                  np.asarray(y[0, 9:]))
    assert (np.abs(np.asarray(moved[0, 6:9] - y[0, 6:9])).max(axis=1)
            > 0).all()


def test_conv_mixer_nodes_are_named_for_the_metrics():
    """The nodes the benchmark's scopes read: the whole mixer under
    ``_sconv_`` and its gated core as three nodes ending in ``bx``,
    ``taps`` and ``cz``."""
    net = models.get_symbol("lfm2-moe", vocab_size=512, seq_len=T)
    names = [n for n in net.attr_dict()] + net.get_internals().list_outputs()
    sconv = sorted({n.rsplit("_output", 1)[0] for n in names
                    if n.startswith("l0_sconv_")
                    and not n.endswith("_weight")})
    for node in ("l0_sconv_in", "l0_sconv_bx", "l0_sconv_taps",
                 "l0_sconv_cz", "l0_sconv_out"):
        assert node in sconv, sconv
    assert any(n.startswith("l1_attn_attn") for n in names)


# ----------------------------------------------------------------------
# the expert layer with no shared expert; glm_moe's with one as before
def _expert_layer_before_n_shared(x, cfg):
    """``models/glm_moe.py: _expert_layer`` before ``n_shared``, line for
    line."""
    router = sym.MoERouter(x, num_experts=cfg["n_experts"],
                           top_k=cfg["top_k"], scale=cfg["scaling"],
                           n_group=cfg.get("n_group", 1),
                           topk_group=cfg.get("topk_group", 1),
                           name="moe_router")
    routed = sym.MoEExperts(x, router[0], router[1],
                            num_experts=cfg["n_experts"],
                            experts_held=cfg["held"],
                            first_expert=cfg["first_expert"],
                            num_hidden=cfg["moe_width"],
                            name="moe_experts")
    return glm_moe._gated_ffn(x, cfg["moe_width"], cfg["hidden"],
                              "moe_shared_") + routed


@pytest.mark.parametrize("config", ["glm-4.7-flash", "ling-3.0-flash"])
def test_a_shared_expert_builds_todays_symbol(config, monkeypatch):
    """The two configurations with a shared expert build the Symbol they
    built before ``n_shared``, digest for digest; ``lfm2-moe``'s has none."""
    cfg = published(config)

    def build():
        return models.get_symbol(cfg["symbol"]["network"],
                                 **cfg["symbol"]["kwargs"])

    args = models.get_symbol("lfm2-moe").list_arguments()
    assert not [n for n in args if "shared" in n]
    assert "l1_moe_experts_gate_weight" in args
    now = program.symbol_digest(build())
    monkeypatch.setattr(glm_moe, "_expert_layer", _expert_layer_before_n_shared)
    assert program.symbol_digest(build()) == now


def test_the_expert_shares_add_up_to_the_uncut_expert_layer(ref):
    """32 experts in eight shares of 4: the shares' routed parts (the
    program's router and experts) are the reference's uncut layer, with
    nothing that every chip computes alike: there is no shared expert."""
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
                            scale=1.0)(rows, p("moe_router_weight"), bias)
    total, shares = 0, 0
    for first in range(0, 32, 4):
        (part,), (c,) = op_fn(
            "MoEExperts", num_experts=32, experts_held=4,
            first_expert=first, num_hidden=48)(
                rows, idx, wt, *(p("moe_experts_%s_weight" % n)
                                 [first:first + 4]
                                 for n in ("gate", "up", "down")),
                jnp.zeros(32))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(count))
        total, shares = total + part, shares + 1
    assert shares == 8
    close(total, whole[0])
    assert float(count.sum()) == 40 * 4
    # one share alone is not the layer
    assert np.abs(np.asarray(total - part)).max() > 1e-4


# ----------------------------------------------------------------------
# the network at the published widths
def test_published_configuration_by_shapes_alone(ref):
    """At the published widths nothing is allocated: the Symbol's
    arguments and auxiliary states are the reference's ``param_shapes``,
    486.1M parameters; no width is among the keys cut, and the kept
    layers are one whole period of the published pattern."""
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
    assert have["l0_sconv_in_weight"] == (6144, 2048)
    assert have["l0_sconv_taps_weight"] == (2048, 3)
    assert have["l1_attn_q_weight"] == (2048, 2048)
    assert have["l1_attn_k_weight"] == have["l1_attn_v_weight"] == (512, 2048)
    assert have["l1_attn_q_norm_gamma"] == (64,)
    assert have["l2_moe_experts_gate_weight"] == (8, 1536, 2048)
    assert have["l2_moe_router_weight"] == (64, 2048)
    assert have["l0_mlp_gate_weight"] == (11776, 2048)
    total = sum(int(np.prod(s)) for s in have.values())
    assert round(total / 1e6, 1) == 486.1

    def layer(i):
        return sum(int(np.prod(s)) for n, s in have.items()
                   if n.startswith("l%d_" % i)) / 1e6
    assert [round(layer(i), 1) for i in range(5)] \
        == [89.1, 86.1, 92.4, 92.4, 92.4]
    assert out_s == [(8192, 8192)]
    z = ref._sizes(cfg)
    assert z["kinds"] == cfg["symbol"]["kwargs"]["layer_types"].split(",") \
        == ["conv", "attention", "conv", "conv", "conv"]
    assert z["is_dense"] == [True] + [False] * 4
    kept = cfg["deployment"]["layers_kept"]
    assert [i < cfg["published"]["num_dense_layers"] for i in kept] \
        == z["is_dense"]
    assert cfg["layer_types"][:4] == ["conv", "conv", "full_attention",
                                      "conv"]
    assert len(cfg["layer_types"]) == cfg["published"]["num_hidden_layers"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "conv_L_cache"):
        assert key not in cfg["reduced"]
    assert sorted(cfg["reduced"]) == sorted(cfg["published"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    with pytest.raises(ValueError, match="layer_types"):
        models.get_symbol("lfm2-moe", layer_types="conv,mamba")
    with pytest.raises(ValueError, match="key/value heads"):
        models.get_symbol("lfm2-moe", num_heads=4, num_kv_heads=3)


def test_costs_by_hand(ref):
    """At the cell's 1 x 8,192 tokens: a convolution mixer's core reads
    and writes 11 x 8,192 x 2,048 elements of two bytes; attention at
    32 query heads counts k and v at their 8 heads; the experts at the
    expected 8,192 x 4 x 8/64 = 4,096 entries; 9.97 TFLOP a step."""
    c = ref.costs(published(), 1)
    by, rows = c["by_layer"], 8192
    assert c["sconv"]["bytes"] == 4 * 2 * 11 * rows * 2048
    assert by["l0_sconv"] == 3 * 8 * rows * 2048
    assert by["l0_sconv_in"] == 6 * rows * 2048 * 6144
    assert by["l1_attn"] == 6 * 2 * 32 * 64 * (rows * rows // 2)
    assert c["attention"] == {"flops": by["l1_attn"],
                              "bytes": 2 * 4 * rows * 64 * (32 + 8)}
    assert sum(by["l1_attn_" + n] for n in "qkvo") \
        == 6 * rows * 2048 * (2048 + 512 + 512 + 2048)
    assert by["l2_moe_experts"] == 6 * 4096 * 3 * 2048 * 1536
    assert c["experts"]["flops"] == 4 * by["l2_moe_experts"]
    assert by["head"] == 6 * rows * 2048 * 8192
    assert c["model_flops"] == sum(c[k]["flops"] for k in
                                   ("matmul", "experts", "attention", "sconv"))
    assert round(c["model_flops"] / 1e12, 2) == 9.97


# ----------------------------------------------------------------------
# the tiny model through Module's fused step against the reference
def tiny_module(params, aux, compute_dtype):
    net = models.get_symbol("lfm2-moe", vocab_size=512, seq_len=T)
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


BLOCKS = {"conv/dense": "l0_", "attention/expert": "l1_",
          "conv/expert": "l2_"}


@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_tiny_model_float32_matches_the_reference_leaf_by_leaf(tiny, block):
    """Three losses, and for every leaf of the block (and of the
    embedding, final norm and head, with the first) the first gradient
    and the three-step change: the norm of the difference within 1e-4 of
    the leaf's norm."""
    cfg, params, aux, feed, (want_l, want_g, want_p) = tiny
    mod = tiny_module(params, aux, None)
    losses, grad, after = program_steps(mod, feed)
    np.testing.assert_allclose(losses, want_l, rtol=1e-4)
    assert set(grad) == set(want_g) == set(params)
    pre = BLOCKS[block]
    leaves = [n for n in params if n.startswith(pre)
              or (pre == "l0_" and not n.startswith("l"))]
    assert len(leaves) >= 7
    for n in sorted(leaves):
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


def test_tiny_model_trains_through_module_fit(tiny):
    """``Module.fit`` on a ``tpu`` context, no side script: five epochs
    on one batch of a repeating sequence, and the loss falls."""
    cfg, params, aux, _, _ = tiny
    ids = np.tile(np.arange(T + 1) % 7, (B, 1)).astype(np.int32)
    it = mx.io.NDArrayIter(ids[:, :-1], ids[:, 1:], batch_size=B)
    mod = mx.mod.Module(context=mx.tpu(), symbol=models.get_symbol(
        "lfm2-moe", vocab_size=512, seq_len=T))
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


def test_obs_counters_of_the_grouped_node_after_two_steps(tiny):
    """The tiny model's one attention node groups 4 query heads over 2
    key/value heads: a trace counts one grouped node (two where the
    value and gradient are traced apart) and its repeated k and v in
    float32; steps of a compiled program trace, and count, nothing."""
    cfg, params, aux, feed, _ = tiny
    start = counters()
    mod = tiny_module(params, aux, None)

    def step(data, label):
        mod.forward(mx.io.DataBatch(data=[mx.nd.NDArray(data)],
                                    label=[mx.nd.NDArray(label)], pad=0),
                    is_train=True)
        mod.update()

    step(*feed[0])
    nodes, nbytes = (a - b for a, b in zip(counters(), start))
    assert nodes >= 1
    assert nbytes == nodes * 2 * 2 * (B * T * 2 * 16 * 4)
    step(*feed[1])
    assert counters() == (start[0] + nodes, start[1] + nbytes)
