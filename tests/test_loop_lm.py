"""The looped language model (``models/loop_lm.py``) through the Symbol
path: the per-row cross-entropy and the exit distribution against their
formulas, a leaf shared by four passes against the same model with the
leaf untied, and the tiny model through ``Module``'s fused step against
the benchmark's plain reference (``benchmark/reference/ouro-2.6b.py``,
loaded by path), with its passes as recomputation segments and without."""
import gc
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, obs
from mxnet_tpu.models import loop_lm
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
    return _load(os.path.join(BENCH, "reference", "ouro-2.6b.py"),
                 "ouro_reference")


@pytest.fixture(scope="module")
def refsteps():
    return _load(os.path.join(BENCH, "lib", "refsteps.py"), "ouro_refsteps")


def published():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


B, T, LR, VOCAB = 4, 32, 0.02, 512
# of the configuration as committed: the layers it holds of the 48, and
# the model's operations a step of 4,096 tokens at that many
LAYERS, MODEL_TFLOP = 4, 33.4


def tiny_cfg(**over):
    """The published file cut to the builder's defaults: 2 layers, d 64,
    2 heads of 32, width 160, vocabulary 512, 32 positions, 4 passes."""
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=2, num_key_value_heads=2,
               head_dim=32, intermediate_size=160, num_hidden_layers=2,
               vocab_size=VOCAB)
    cfg["input"] = {"kind": "tokens", "seq_len": T, "vocab": VOCAB}
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


# ----------------------------------------------------------------------
# the two ops against their formulas
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_row_cross_entropy_is_log_softmax_and_pick(dtype):
    """Value and gradient against ``log_softmax`` + ``pick`` in float32
    on the same (rounded) logits; a float32 result whatever the logits'
    type, and a gradient in the logits' type."""
    logits = rnd(0, 24, 200, scale=3.0, dtype=dtype)
    label = jax.random.randint(jax.random.key(1), (24,), 0, 200, jnp.int32)
    weight = 0.5 + jax.random.uniform(jax.random.key(2), (24,))
    rows = op_fn("_contrib_RowCrossEntropy")

    def formula(x):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, label[:, None], axis=-1)[:, 0]

    got = rows(logits, label)
    assert got.dtype == jnp.float32 and got.shape == (24,)
    np.testing.assert_allclose(got, formula(logits), rtol=2e-6, atol=2e-6)
    g = jax.grad(lambda x: jnp.sum(rows(x, label) * weight))(logits)
    want = jax.grad(lambda x: jnp.sum(formula(x) * weight))(
        logits.astype(jnp.float32))
    assert g.dtype == dtype
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(np.asarray(g, np.float32), want, rtol=tol,
                               atol=tol * float(jnp.abs(want).max()))
    # MXNet feeds class ids as float32: the same rows
    np.testing.assert_array_equal(rows(logits, label.astype(jnp.float32)),
                                  got)


def test_row_cross_entropy_keeps_no_float32_copy_of_the_logits():
    """Between the passes the reverse mode holds the logits as given
    and one float32 a row: no residual of rows x classes in float32."""
    rows = op_fn("_contrib_RowCrossEntropy")
    logits = rnd(3, 64, 1000, dtype=jnp.bfloat16)
    label = jnp.zeros((64,), jnp.int32)
    _, pull = jax.vjp(lambda x: rows(x, label), logits)
    kept = [x for x in jax.tree.leaves(pull) if hasattr(x, "shape")]
    assert [(x.shape, x.dtype) for x in kept if x.size >= 64 * 1000] \
        == [((64, 1000), jnp.bfloat16)]
    sym = mx.sym._contrib_RowCrossEntropy(mx.sym.Variable("data"),
                                          mx.sym.Variable("label"))
    arg_s, out_s, _ = sym.infer_shape(data=(64, 1000))
    assert arg_s == [(64, 1000), (64,)] and out_s == [(64,)]
    assert registry.get("_contrib_RowCrossEntropy").infer_shape is not None


@pytest.mark.parametrize("steps", [2, 4, 7])
def test_exit_distribution_sums_to_one_and_is_the_product_formula(steps, ref):
    lam = jax.random.uniform(jax.random.key(steps), (50, steps - 1),
                             minval=0.02, maxval=0.98)
    (dist,), (mean,) = op_fn("_contrib_ExitDistribution")(
        lam, jnp.zeros((steps,)))
    assert dist.shape == (50, steps) and dist.dtype == jnp.float32
    np.testing.assert_allclose(dist.sum(axis=1), 1.0, rtol=1e-6)
    by_hand = np.ones((50, steps))
    lam64 = np.asarray(lam, np.float64)
    for t in range(steps):
        stay = np.prod(1 - lam64[:, :t], axis=1)
        by_hand[:, t] = stay * (lam64[:, t] if t < steps - 1 else 1.0)
    np.testing.assert_allclose(dist, by_hand, rtol=1e-5)
    np.testing.assert_allclose(dist, ref.exit_distribution(lam), rtol=1e-6)
    np.testing.assert_allclose(mean, by_hand.mean(axis=0), rtol=1e-5)
    # every gate one half: 1/2, 1/4, ... and the rest; 1.875 passes of 4
    half = op_fn("_contrib_ExitDistribution")(
        jnp.full((3, 3), 0.5), jnp.zeros((4,)))[0][0]
    np.testing.assert_allclose(half[0], [0.5, 0.25, 0.125, 0.125])
    gauges = registry.get("_contrib_ExitDistribution").gauges(
        {}, {"pass_share": np.asarray(half[0])})
    assert gauges == {"loop.expected_steps": 1.875}


# ----------------------------------------------------------------------
# the builder
def test_published_configuration_by_shapes_alone(ref):
    """At the published widths nothing is allocated: the Symbol's
    arguments and auxiliary state are the reference's ``param_shapes``,
    a block's 51.39M parameters used by four nodes each beside 201.3M of
    embedding and head; every width is the catalog's and depth alone is
    reduced."""
    cfg = published()
    layers = cfg["num_hidden_layers"]
    assert layers == cfg["symbol"]["kwargs"]["num_layers"] == LAYERS
    sym = models.get_symbol(cfg["symbol"]["network"],
                            **cfg["symbol"]["kwargs"])
    arg_s, out_s, aux_s = sym.infer_shape(data=(1, 4096),
                                          softmax_label=(1, 4096))
    have = {n: tuple(s) for n, s in zip(sym.list_arguments(), arg_s)
            if n not in ("data", "softmax_label")}
    want_p, want_a = ref.param_shapes(cfg)
    assert have == {n: tuple(s) for n, s in want_p.items()}
    assert dict(zip(sym.list_auxiliary_states(), map(tuple, aux_s))) \
        == {n: tuple(s) for n, s in want_a.items()} \
        == {"exit_loss_dist_pass_share": (4,)}
    assert have["l%d_mlp_gate_weight" % (layers - 1)] == (5632, 2048)
    assert "l%d_mlp_gate_weight" % layers not in have
    assert have["l0_attn_k_weight"] == (2048, 2048)
    assert have["head_weight"] == have["tok_embed_weight"] == (49152, 2048)
    block = sum(int(np.prod(s)) for n, s in have.items()
                if n.startswith("l3_"))
    assert block == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    total = sum(int(np.prod(s)) for s in have.values())
    assert total == layers * block + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert out_s == [(4096, 49152), (1,)]
    assert sym.list_outputs() == ["softmax_output", "exit_loss_output"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 48}
    for key, value in (("hidden_size", 2048), ("head_dim", 128),
                       ("num_attention_heads", 16),
                       ("num_key_value_heads", 16),
                       ("intermediate_size", 5632), ("vocab_size", 49152),
                       ("total_ut_steps", 4), ("rope_theta", 1000000),
                       ("rms_norm_eps", 1e-6), ("early_exit_threshold", 1)):
        assert cfg[key] == value, key
    for point in ("norm_placement", "final_norm_in_loop", "exit_gate",
                  "exit_beta", "objective", "rotary_pairing",
                  "initializer_range", "seq_len", "optimizer"):
        assert point in cfg["assumed"], point


def test_every_weight_is_one_variable_used_by_every_pass():
    """Node names are unique and say their pass; a block's eleven leaves
    are each read by four nodes, the head by four, the gate by three;
    all 24 attention nodes end in ``attn_attn``."""
    sym = models.get_symbol("loop-lm", vocab_size=VOCAB, num_layers=6)
    graph = json.loads(sym.tojson())
    nodes = graph["nodes"]
    names = [n["name"] for n in nodes]
    assert len(names) == len(set(names))
    uses = {}
    for n in nodes:
        for src, _, _ in n["inputs"]:
            if nodes[src]["op"] == "null":
                uses.setdefault(nodes[src]["name"], []).append(n["name"])
    assert sorted(uses["l2_attn_q_weight"]) == [
        "u%d_l2_attn_q" % t for t in (1, 2, 3, 4)]
    assert sorted(uses["head_weight"]) == [
        "u%d_exit_head" % t for t in (1, 2, 3, 4)]
    # the gate's two leaves through a cast to float32 in every pass
    # that has a gate
    assert sorted(uses["exit_weight"]) == [
        "u%d_exit_weight32" % t for t in (1, 2, 3)]
    assert sorted(uses["exit_bias"]) == [
        "u%d_exit_bias32" % t for t in (1, 2, 3)]
    assert sorted(uses["norm_gamma"]) == ["u%d_norm" % t for t in range(1, 5)]
    assert len(uses["tok_embed_weight"]) == 1
    for leaf, readers in uses.items():
        if leaf[0] == "l" and leaf[1].isdigit():
            assert len(readers) == 4, leaf
    attn = [n for n in names if n.endswith("attn_attn")]
    assert len(attn) == 24 and attn[0] == "u1_l0_attn_attn"
    # a pass's nodes carry its mark, its head, gate, label and row loss
    # too; the embedding and the combination carry none
    marks = {n["name"]: (n.get("attrs") or {}).get("remat_segment")
             for n in nodes if n["op"] != "null"}
    assert marks["u3_l4_mlp_down"] == marks["u3_exit_rowloss"] \
        == marks["u3_exit_lambda"] == marks["u3_norm"] \
        == marks["u3_exit_label"] == marks["u3_exit_bias32"] == "u3"
    assert marks["u4_exit_head"] == marks["u4_exit_rowloss"] == "u4"
    # what a metric is shown is behind no gradient, and in no segment
    assert marks["u4_exit_prob"] is None and marks["softmax"] is None
    assert marks["tok_embed"] is None and marks["exit_loss"] is None
    assert marks["exit_loss_dist"] is None
    plain = json.loads(models.get_symbol(
        "loop-lm", vocab_size=VOCAB, segments=False).tojson())
    assert not any("remat_segment" in (n.get("attrs") or {})
                   for n in plain["nodes"])


def test_costs_by_hand(ref):
    """The cell's 1 x 4,096 tokens: four passes of every block, 4 head
    passes, a causal attention node a block pass, nothing recomputed."""
    c = ref.costs(published(), 1)
    by, rows, passes = c["by_layer"], 4096, 4 * LAYERS
    block = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert sum(v for n, v in by.items() if n.startswith("u2_l3_")
               and not n.endswith("attn")) == 6 * rows * block
    assert by["u1_exit_head"] == by["u4_exit_head"] \
        == 6 * rows * 2048 * 49152
    assert by["u3_exit_gate"] == 6 * rows * 2048 and "u4_exit_gate" not in by
    assert by["u4_l%d_attn" % (LAYERS - 1)] \
        == 6 * 2 * 1 * 16 * 128 * (4096 * 4096 // 2)
    assert sum(1 for n in by if n.endswith("_attn")) == passes
    assert c["attention"]["flops"] == passes * by["u1_l0_attn"]
    assert c["attention"]["bytes"] == passes * 2 * 8 * rows * 2048
    assert c["matmul"]["flops"] == 6 * rows * (
        passes * block + 4 * 2048 * 49152 + 3 * 2048)
    assert c["model_flops"] == c["matmul"]["flops"] + c["attention"]["flops"]
    assert round(c["model_flops"] / 1e12, 1) == MODEL_TFLOP
    assert round(4 * by["u1_exit_head"] / 1e12, 1) == 9.9


# ----------------------------------------------------------------------
# the tiny model through Module's fused step against the reference
def tiny_module(params, aux, compute_dtype, **kwargs):
    sym = models.get_symbol("loop-lm", vocab_size=VOCAB, seq_len=T, **kwargs)
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
                             VOCAB, jnp.int32)
    return [(ids[i, :, :-1], ids[i, :, 1:]) for i in range(steps)]


def program_steps(mod, feed):
    """Three steps through forward / update / update_metric; each step's
    loss (output 1, the sum over positions, over their number), the
    first gradient (momentum after one step over minus the rate) and
    the parameters after the three."""
    metric = mx.metric.create("acc")
    losses, grad = [], None
    for i, (data, label) in enumerate(feed):
        batch = mx.io.DataBatch(data=[mx.nd.NDArray(data)],
                                label=[mx.nd.NDArray(label)], pad=0)
        mod.forward(batch, is_train=True)
        mod.update()
        mod.update_metric(metric, batch.label)
        outs = mod.get_outputs()
        assert outs[0].shape == (B * T, VOCAB) and outs[1].shape == (1,)
        losses.append(float(outs[1].data[0]) / (B * T))
        if i == 0:
            grad = {n: np.asarray(v) / -LR
                    for n, v in mod._trainer.opt_state.items()}
    assert metric.num_inst == 3 * B * T        # the last pass's softmax
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
    return losses, grad, {n: np.asarray(v) for n, v in p.items()}, \
        {n: np.asarray(v) for n, v in a.items()}


@pytest.fixture(scope="module")
def tiny(ref, refsteps):
    cfg = tiny_cfg()
    params, aux = ref.init(cfg, jax.random.key(60))
    # gates that differ from one half and from one another
    params["exit_bias"] = jnp.asarray([0.4], jnp.float32)
    feed = batches(61)
    return cfg, params, aux, feed, reference_steps(ref, refsteps, cfg,
                                                   params, aux, feed)


@pytest.mark.parametrize("segments", [True, False])
def test_tiny_model_float32_matches_the_reference_leaf_by_leaf(tiny,
                                                               segments):
    """Three losses, the first gradient and the three-step change, every
    leaf: the norm of the difference within 1e-4 of the leaf's norm, the
    tolerance ``test_glm_moe.py`` and ``test_bailing_hybrid.py`` hold
    their models to (the largest read here is 3.03e-5, on a key
    projection whose gradient's norm is 1.6e-4), with a pass a
    recomputation segment and without; the auxiliary state is the last
    step's mean exit distribution."""
    cfg, params, aux, feed, (want_l, want_g, want_p, want_a) = tiny
    mod = tiny_module(params, aux, None, segments=segments)
    losses, grad, after = program_steps(mod, feed)
    np.testing.assert_allclose(losses, want_l, rtol=3e-5)
    assert set(grad) == set(want_g) == set(params)
    for n in sorted(params):
        start = np.asarray(params[n])
        # (a change is a difference of float32 values: an ulp of the
        # gate's weight, of deviation 1, is a seven-thousandth of its)
        ulp = np.finfo(np.float32).eps * np.abs(start).max()
        for got, want, slack in ((grad[n], want_g[n], 0.0),
                                 (after[n] - start, want_p[n] - start, ulp)):
            assert np.linalg.norm(want) > 0, n
            assert np.linalg.norm(got - want) \
                <= 1e-4 * np.linalg.norm(want) + slack, n
    got = np.asarray(mod._trainer.aux["exit_loss_dist_pass_share"])
    np.testing.assert_allclose(got, want_a["exit_loss_dist_pass_share"],
                               rtol=1e-5)
    assert abs(got.sum() - 1) < 1e-6 and got.min() > 0.01


def gaps(refsteps, got, want, start):
    """``refsteps.compare``'s numbers from (losses, gradient, params)."""
    def norms(tree):
        return {n: float(np.linalg.norm(v)) for n, v in tree.items()}

    def pack(run):
        losses, grad, after = run[:3]
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
        assert got["loss_gap_step%d" % i] < 5e-3


def test_expected_steps_gauge_is_the_ops_own_and_outlives_the_trainer(tiny):
    """``loop.expected_steps`` after two steps is sum of t x the mean
    exit distribution the second step left; the trainer publishes it
    because the op declares it, and a trainer that goes away leaves its
    last reading."""
    cfg, params, aux, feed, _ = tiny
    mod = tiny_module(params, aux, None)
    for data, label in feed[:2]:
        batch = mx.io.DataBatch(data=[mx.nd.NDArray(data)],
                                label=[mx.nd.NDArray(label)], pad=0)
        mod.forward(batch, is_train=True)
        mod.update()
    mean = np.asarray(mod._trainer.aux["exit_loss_dist_pass_share"], np.float64)
    by_hand = float(np.sum(mean * [1, 2, 3, 4]))
    assert 1.2 < by_hand < 3.0
    assert obs.snapshot()["gauges"]["loop.expected_steps"] \
        == pytest.approx(by_hand)
    del mod
    gc.collect()
    assert obs.snapshot()["gauges"]["loop.expected_steps"] \
        == pytest.approx(by_hand)


def grad_req(sym):
    """Gradients for the parameters; the ids have none."""
    return {n: "null" if n in ("data", "softmax_label") else "write"
            for n in sym.list_arguments()}


def test_one_pass_is_the_plain_next_token_loss(ref):
    """``loop_steps`` 1: no gate, no exit distribution, and the loss is
    the mean cross-entropy of a one-pass model, which ``SoftmaxOutput``
    on the same head gives the same gradients for."""
    cfg = tiny_cfg(total_ut_steps=1)
    params, aux = ref.init(cfg, jax.random.key(5))
    assert aux == {}
    (data, label), = batches(6, steps=1)
    loss, _ = ref.loss(cfg, params, aux, data, label)
    logits = ref.row_losses(cfg, params, data, label)[0]
    np.testing.assert_allclose(loss, jnp.mean(logits[0]), rtol=1e-6)
    sym = models.get_symbol("loop-lm", vocab_size=VOCAB, seq_len=T,
                            loop_steps=1)
    assert sym.list_auxiliary_states() == []
    assert "exit_weight" not in sym.list_arguments()
    used = {n: v for n, v in params.items()
            if n not in ("exit_weight", "exit_bias")}
    want = jax.grad(lambda p: ref.loss(cfg, dict(params, **p), aux, data,
                                       label)[0])(used)
    ex = sym.simple_bind(mx.cpu(), grad_req=grad_req(sym), data=(B, T),
                         softmax_label=(B, T))
    for n, v in used.items():
        ex.arg_dict[n][:] = np.asarray(v)
    ex.arg_dict["data"][:] = np.asarray(data)
    ex.arg_dict["softmax_label"][:] = np.asarray(label)
    outs = ex.forward(is_train=True)
    ex.backward()
    np.testing.assert_allclose(outs[1].asnumpy()[0] / (B * T), loss,
                               rtol=2e-6)
    probs = outs[0].asnumpy()
    picked = probs[np.arange(B * T), np.asarray(label).reshape(-1)]
    np.testing.assert_allclose(-np.log(picked).mean(), loss, rtol=2e-5)
    for n, g in want.items():
        got = ex.grad_dict[n].asnumpy() / (B * T)
        assert np.linalg.norm(got - g) <= 3e-5 * np.linalg.norm(g), n


@pytest.mark.parametrize("leaf", ["l0_attn_q_weight", "l1_mlp_down_weight",
                                  "l1_norm4_gamma"])
def test_a_shared_leafs_gradient_is_the_sum_over_its_four_uses(
        tiny, leaf, monkeypatch):
    """The same model with ``leaf`` untied, a Variable a pass holding
    the same values: the four gradients add up to the shared leaf's, and
    every other leaf's gradient is what it was."""
    cfg, params, aux, feed, _ = tiny
    data, label = feed[0]

    def grads(sym, values):
        ex = sym.simple_bind(mx.cpu(), grad_req=grad_req(sym), data=(B, T),
                             softmax_label=(B, T))
        for n, v in values.items():
            ex.arg_dict[n][:] = np.asarray(v)
        ex.arg_dict["data"][:] = np.asarray(data)
        ex.arg_dict["softmax_label"][:] = np.asarray(label)
        ex.forward(is_train=True)
        ex.backward()
        return {n: ex.grad_dict[n].asnumpy() for n in values}

    kw = dict(vocab_size=VOCAB, seq_len=T)
    tied = grads(models.get_symbol("loop-lm", **kw), params)
    block, name = leaf.split("_", 1)
    whole = loop_lm._block

    def untied_block(x, w, cfg, prefix):
        if prefix.endswith("_%s_" % block):
            w = dict(w, **{name: mx.sym.Variable(prefix + name)})
        return whole(x, w, cfg, prefix)

    monkeypatch.setattr(loop_lm, "_block", untied_block)
    sym = models.get_symbol("loop-lm", **kw)
    copies = ["u%d_%s" % (t, leaf) for t in (1, 2, 3, 4)]
    assert set(copies) < set(sym.list_arguments())
    assert leaf not in sym.list_arguments()
    values = {n: v for n, v in params.items() if n != leaf}
    values.update({c: params[leaf] for c in copies})
    apart = grads(sym, values)
    parts = [apart[c] for c in copies]
    assert all(np.linalg.norm(p) > 0 for p in parts)
    assert np.linalg.norm(parts[0] - parts[3]) > 0.1 * np.linalg.norm(parts[0])
    total = np.sum(parts, axis=0)
    assert np.linalg.norm(tied[leaf] - total) \
        <= 1e-5 * np.linalg.norm(total)
    for n in values:
        if n not in copies:
            assert np.linalg.norm(tied[n] - apart[n]) \
                <= 1e-5 * np.linalg.norm(tied[n]), n


def test_label_ids_fed_as_float32_are_not_rounded_to_bfloat16():
    """The row loss's label is an index input: fed as float32, MXNet's
    way, it reaches the op uncast under bfloat16 compute."""
    from mxnet_tpu.executor import _GraphProgram
    from mxnet_tpu.parallel.trainer import _index_inputs
    sym = models.get_symbol("loop-lm", vocab_size=2000, seq_len=16)
    assert _index_inputs(_GraphProgram(sym).nodes) \
        == {"data", "softmax_label"}
