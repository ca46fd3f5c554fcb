"""Graph linter: one crafted graph per rule (asserting finding kind +
node provenance), the bench-graph zero-error sweep, the baseline-gate
CLI, and the satellite regressions (parse_params did-you-mean, _topo
cycle detection, debug_str annotation agreement)."""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import analysis, models

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=240, **kw):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, cwd=_ROOT, timeout=timeout, **kw)


def _find(report, rule, severity=None):
    return [f for f in report.findings if f.rule == rule
            and (severity is None or f.severity == severity)]


# ----------------------------------------------------------------------
# symbol-level rules
def test_shape_infer_failure_has_node_provenance():
    a = mx.sym.Variable("a", shape=(4, 5))
    b = mx.sym.Variable("b", shape=(4, 6))
    bad = a + b
    rep = analysis.lint_symbol(bad, trace=False)
    errs = _find(rep, "shape-infer", "error")
    assert len(errs) == 1
    f = errs[0]
    assert f.op == "_plus"
    # the message carries the conflicting input shapes AND the
    # producing nodes — the provenance infer_shape's deep throw lacks
    assert "(4, 5)" in f.message and "(4, 6)" in f.message
    assert "a" in f.detail["inputs"] and "b" in f.detail["inputs"]


def test_shape_conflict_names_both_consumers():
    w = mx.sym.Variable("w")
    d1 = mx.sym.Variable("d1", shape=(16, 32))
    d2 = mx.sym.Variable("d2", shape=(16, 64))
    fc1 = mx.sym.FullyConnected(d1, weight=w, num_hidden=10, no_bias=True,
                                name="fc1")
    fc2 = mx.sym.FullyConnected(d2, weight=w, num_hidden=10, no_bias=True,
                                name="fc2")
    rep = analysis.lint_symbol(mx.sym.Group([fc1, fc2]), trace=False)
    errs = _find(rep, "shape-conflict", "error")
    assert len(errs) == 1
    assert errs[0].node == "w"
    assert errs[0].detail["consumer"] in ("fc1", "fc2")
    assert "(10, 32)" in errs[0].message and "(10, 64)" in errs[0].message


def test_dead_code_in_json():
    data = mx.sym.Variable("data", shape=(4, 8))
    live = mx.sym.Activation(data, act_type="relu", name="live")
    j = json.loads(live.tojson())
    # graft an unreachable compute node and an unused argument into the
    # JSON (exactly what load_json would silently drop)
    j["nodes"].append({"op": "null", "name": "orphan_arg", "inputs": []})
    j["nodes"].append({"op": "Activation", "name": "dead_relu",
                       "attrs": {"act_type": "relu"},
                       "inputs": [[len(j["nodes"]) - 1, 0, 0]]})
    j["arg_nodes"].append(len(j["nodes"]) - 2)
    rep = analysis.lint_json(json.dumps(j), trace=False)
    dead = {f.node: f for f in _find(rep, "dead-code", "warn")}
    assert "dead_relu" in dead and "subgraph" in dead["dead_relu"].message
    assert "orphan_arg" in dead
    assert "unused argument" in dead["orphan_arg"].message


def test_reference_json_aux_inputs_are_not_dead_code():
    # reference-style nnvm JSON lists BN aux states (moving_mean/var) as
    # node INPUTS; the load path drops those edges, which must not make
    # the aux variables look like unused arguments
    j = {"nodes": [
        {"op": "null", "name": "data", "inputs": []},
        {"op": "null", "name": "bn_gamma", "inputs": []},
        {"op": "null", "name": "bn_beta", "inputs": []},
        {"op": "null", "name": "bn_moving_mean", "inputs": []},
        {"op": "null", "name": "bn_moving_var", "inputs": []},
        {"op": "BatchNorm", "name": "bn",
         "inputs": [[0, 0, 0], [1, 0, 0], [2, 0, 0], [3, 0, 0],
                    [4, 0, 0]]},
    ], "arg_nodes": [0, 1, 2, 3, 4], "heads": [[5, 0, 0]]}
    rep = analysis.lint_json(json.dumps(j),
                             shapes={"data": (4, 8, 8, 16)}, trace=False)
    assert not _find(rep, "dead-code")


def test_duplicate_subgraph_cse():
    d = mx.sym.Variable("data", shape=(16, 32))
    w = mx.sym.Variable("w")
    b = mx.sym.Variable("b")
    fc_a = mx.sym.FullyConnected(d, weight=w, bias=b, num_hidden=8,
                                 name="twin_a")
    fc_b = mx.sym.FullyConnected(d, weight=w, bias=b, num_hidden=8,
                                 name="twin_b")
    rep = analysis.lint_symbol(mx.sym.Group([fc_a, fc_b]), trace=False)
    dups = _find(rep, "duplicate-subgraph", "info")
    assert len(dups) == 1
    assert set(dups[0].detail["nodes"]) == {"twin_a", "twin_b"}


def _unrolled_lstm(steps):
    cell = mx.rnn.LSTMCell(32, prefix="l_")
    outs, _ = cell.unroll(steps, inputs=mx.sym.Variable("data"),
                          merge_outputs=True)
    return outs


def _residual_chain(depth):
    h = mx.sym.Variable("data")
    for i in range(depth):      # every sum has two consumers
        h = h + mx.sym.Activation(h, act_type="relu", name="r%d" % i)
    return mx.sym.Group([mx.sym.Activation(h, act_type="tanh", name=n)
                         for n in ("twin_a", "twin_b")])


@pytest.mark.parametrize("build,small,large,expect", [
    (_unrolled_lstm, 4, 32, {"l_begin_state_0", "l_begin_state_1"}),
    (_residual_chain, 3, 40, {"twin_a", "twin_b"}),
])
def test_duplicate_subgraph_on_shared_inputs(build, small, large, expect):
    """A node's signature holds its inputs' interned ids, not their
    signatures: with those nested, hashing one walked the graph beneath
    as a tree, twice as long for every node with two consumers (an
    unrolled LSTM took 24 s to lint at 10 steps, and every bind lints)."""
    import time
    dups = {}
    for size in (small, large):
        t0 = time.monotonic()
        rep = analysis.lint_symbol(build(size), trace=False)
        assert time.monotonic() - t0 < 5.0
        dups[size] = [set(f.detail["nodes"])
                      for f in _find(rep, "duplicate-subgraph", "info")]
    assert dups[small] == dups[large] == [expect]


def test_tpu_layout_misaligned_matmul():
    d = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(d, num_hidden=100, name="fc_off")
    rep = analysis.lint_symbol(fc, shapes={"data": (16, 256)}, trace=False)
    warns = _find(rep, "tpu-layout", "warn")
    assert len(warns) == 1
    f = warns[0]
    assert f.node == "fc_off" and f.op == "FullyConnected"
    assert "pads" in f.message and "waste" in f.message
    assert f.detail["params"]["num_hidden"] == "100"
    # aligned graph: no layout findings
    ok = mx.sym.FullyConnected(mx.sym.Variable("x"), num_hidden=256,
                               no_bias=True, name="fc_ok")
    rep2 = analysis.lint_symbol(ok, shapes={"x": (16, 256)}, trace=False)
    assert not _find(rep2, "tpu-layout")


def test_dtype_promotion_blames_declaring_variable():
    d = mx.sym.Variable("data", dtype="float64")
    fc = mx.sym.FullyConnected(d, num_hidden=128, name="fc64")
    rep = analysis.lint_symbol(fc, shapes={"data": (16, 128)}, trace=False)
    errs = _find(rep, "dtype-promotion", "error")
    assert [f.node for f in errs] == ["data"]       # one leak = one error
    carriers = _find(rep, "dtype-promotion", "info")
    assert any(f.node == "fc64" for f in carriers)  # propagation is info


# ----------------------------------------------------------------------
# jaxpr-level rules
def test_f64_cast_caught_at_both_levels_with_provenance():
    d = mx.sym.Variable("data")
    c = mx.sym.Cast(d, dtype="float64", name="widen")
    s = mx.sym.sum(c, name="reduce") if hasattr(mx.sym, "sum") else c
    rep = analysis.lint_symbol(s, shapes={"data": (8, 128)}, trace=False)
    errs = _find(rep, "dtype-promotion", "error")
    assert len(errs) == 1 and errs[0].node == "widen"
    assert errs[0].op == "Cast"
    # jaxpr level: run only the f64 pass (symbol level already errors,
    # which would veto the trace)
    rep2 = analysis.lint_symbol(
        c, shapes={"data": (8, 128)}, trace=True, is_train=False,
        only={"f64-widening"})
    wide = _find(rep2, "f64-widening", "error")
    assert wide and wide[0].layer == "widen"      # named-scope provenance


def test_jaxpr_passes_see_inside_shard_map_with_provenance():
    """The sub-jaxpr recursion fix: a hazard INSIDE a shard_map body is
    (a) visible to the jaxpr rules and (b) attributed to the scope
    applied AROUND the shard_map call — before the scoped recursion,
    sub-jaxpr equations only carried their body-relative name stack and
    everything under an outer scope reported ``(unattributed)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh
    from jax import shard_map

    mesh = make_mesh({"data": 2}, jax.devices()[:2])

    def body(x):
        return jax.lax.psum(x.astype(jnp.float64), "data")

    def prog(x):
        with jax.named_scope("commlayer"):
            y = shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data"), check_vma=False)(x)
        return y.astype(jnp.float32)

    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(prog)(
            jax.ShapeDtypeStruct((4, 8), np.float32))
    out = list(analysis.get_pass("f64-widening").run(
        analysis.PassContext(jaxpr=jaxpr)))
    assert out, "the widening inside the shard_map body must be seen"
    assert out[0].layer == "commlayer"         # outer-scope provenance


def test_host_callback_pass():
    import jax

    def f(x):
        return jax.pure_callback(
            np.sin, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    jaxpr = jax.make_jaxpr(f)(np.ones((4,), np.float32))
    ctx = analysis.PassContext(jaxpr=jaxpr)
    out = list(analysis.get_pass("host-callback").run(ctx))
    assert len(out) == 1 and out[0].severity == "error"
    assert "pure_callback" in out[0].message


def _pool_scope_jaxpr(policy, scattered):
    """The gradient of a 2x2 max pool traced under the scope of the
    ``Pooling`` node ``pool0``, and that node's graph view: the op's own
    body under ``policy``, or (``scattered``) a hand-written backward
    that scatter-adds the cotangent at indices the forward saved."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.op.registry import OpContext, get

    sym = mx.sym.Pooling(mx.sym.Variable("data"), kernel=(2, 2),
                         stride=(2, 2), pool_type="max", layout="NHWC",
                         name="pool0")
    op = get("Pooling")
    params = op.parse_params(dict(kernel=(2, 2), stride=(2, 2),
                                  pool_type="max", layout="NHWC"))
    ctx = OpContext(is_train=True, dtype_policy=policy)

    def pool(x):
        return op.apply(params, ctx, x)[0][0]

    @jax.custom_vjp
    def pool_by_scatter(x):
        return pool(x)

    def fwd(x):
        k = jnp.argmax(x.reshape(2, 2, 2, 2).transpose(0, 2, 1, 3)
                       .reshape(4, 4), axis=-1)       # [window, offset]
        oh, ow = jnp.divmod(jnp.arange(4), 2)
        return pool(x), (oh * 2 + k // 2) * 4 + ow * 2 + k % 2

    def bwd(idx, g):
        return (jnp.zeros((16,), g.dtype).at[idx].add(g.ravel())
                .reshape(1, 4, 4, 1),)

    pool_by_scatter.defvjp(fwd, bwd)

    def grad(x):
        def loss(y):
            with jax.named_scope("pool0"):
                return jnp.sum((pool_by_scatter if scattered else pool)(y))
        return jax.grad(loss)(x)

    return (jax.make_jaxpr(grad)(np.ones((1, 4, 4, 1), np.float32)),
            analysis.GraphView.from_symbol(sym))


@pytest.mark.parametrize("policy,scattered", [
    ("bytediet", False), ("legacy", False), ("bytediet", True)])
def test_pooling_backward_by_scatter_warns(policy, scattered):
    """Max ``Pooling``'s backward is ``select_and_scatter_add`` under
    either dtype policy and is no finding; a scatter-add traced under a
    ``Pooling`` node's scope is the formulation PR 28 removed."""
    jaxpr, view = _pool_scope_jaxpr(policy, scattered)
    prims = {e.primitive.name for e in analysis.jaxpr_passes.iter_eqns(jaxpr)}
    assert ("select_and_scatter_add" in prims) != scattered
    gs = analysis.get_pass("gather-scatter")
    out = list(gs.run(analysis.PassContext(jaxpr=jaxpr, view=view)))
    if not scattered:
        assert not out
        return
    assert [f.severity for f in out] == ["warn"]
    assert out[0].node == "pool0 (bwd)"
    assert out[0].detail["layers"] == {"pool0 (bwd)": ["scatter-add"]}
    # without a view a scope is only a name: counted, not judged
    blind = list(gs.run(analysis.PassContext(jaxpr=jaxpr)))
    assert [f.severity for f in blind] == ["info"]


def test_donation_pass_flags_undonated_state():
    import jax
    import jax.numpy as jnp

    def step(params, batch):
        return {"w": params["w"] - 0.1 * batch["x"].sum() * params["w"]}

    args = ({"w": jnp.zeros((512, 1024), np.float32)},
            {"x": jnp.ones((4, 4), np.float32)})
    pass_ = analysis.get_pass("donation")

    def ctx_for(fn):
        closed = jax.make_jaxpr(fn)(*args)
        eqn = closed.jaxpr.eqns[0]
        assert eqn.primitive.name == "jit"
        return analysis.PassContext(
            jaxpr=eqn.params["jaxpr"],
            donated_invars=eqn.params["donated_invars"],
            invar_labels=["params['w']", "batch['x']"])

    bad = list(pass_.run(ctx_for(jax.jit(step))))
    assert len(bad) == 1 and bad[0].severity == "warn"
    assert "params['w']" in bad[0].message
    good = list(pass_.run(ctx_for(jax.jit(step, donate_argnums=0))))
    assert not good


def test_trainer_step_lint_is_clean(monkeypatch):
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "always")
    sym = models.get_symbol("lenet", num_classes=10)
    mod = mx.mod.Module(context=mx.cpu(), symbol=sym)
    mod.bind(data_shapes=[("data", (8, 1, 28, 28))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    rep = mod._trainer.lint()
    assert rep.traced
    # the fused step donates params/aux/opt_state and runs no host
    # callbacks or f64 math: zero error AND zero warn findings
    assert rep.counts()["error"] == 0 and rep.counts()["warn"] == 0
    # ...and neither pooling node scatters its gradient
    assert not _find(rep, "gather-scatter")


def test_trainer_step_pools_by_select_and_scatter(monkeypatch):
    """The fused step of conv-BN-ReLU-maxpool (the ResNet stem's
    geometry): the pool's backward is the compiler's window op; no sort
    and no scatter is traced anywhere in the step."""
    monkeypatch.setenv("MXTPU_MODULE_FUSED", "always")
    net = mx.sym.Convolution(mx.sym.Variable("data"), kernel=(3, 3),
                             pad=(1, 1), num_filter=8, no_bias=True,
                             layout="NHWC", name="conv0")
    net = mx.sym.BatchNorm(net, fix_gamma=False, axis=3, name="bn0")
    net = mx.sym.Activation(net, act_type="relu", name="relu0")
    net = mx.sym.Pooling(net, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                         pool_type="max", layout="NHWC", name="pooling0")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(context=mx.cpu(), symbol=net)
    mod.bind(data_shapes=[("data", (4, 8, 8, 3))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    closed = mod._trainer.step_jaxpr()
    where = {}
    for eqn, prefix, _ in analysis.jaxpr_passes.iter_eqns_scoped(closed):
        where.setdefault(eqn.primitive.name, set()).add(
            analysis.jaxpr_passes.layer_of_eqn(eqn, prefix))
    assert where["select_and_scatter_add"] == {("pooling0", True)}
    assert not {"sort", "scatter-add", "scatter"} & set(where)
    assert not _find(mod._trainer.lint(), "gather-scatter")


# ----------------------------------------------------------------------
# sweep + CLI gate
def test_bench_graphs_have_zero_errors():
    rep = analysis.lint_symbol(
        models.get_symbol("resnet-50", num_classes=1000, layout="NHWC"),
        shapes={"data": (4, 64, 64, 3), "softmax_label": (4,)},
        model="resnet-50")
    assert rep.traced and rep.counts()["error"] == 0
    rep2 = analysis.lint_symbol(
        models.get_symbol("transformer", num_classes=100, seq_len=32,
                          num_hidden=64, num_heads=2),
        shapes={"data": (2, 32), "softmax_label": (2, 32)},
        dtypes={"data": np.int32}, model="transformer")
    assert rep2.traced and rep2.counts()["error"] == 0


def test_cli_check_passes_at_head():
    r = _run(["tools/graph_lint.py", "--check"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "baseline gate OK" in r.stdout


def test_cli_check_fails_on_injected_hazard(tmp_path):
    d = mx.sym.Variable("data", shape=(8, 128))
    bad = mx.sym.Cast(d, dtype="float64", name="widen")
    p = tmp_path / "hazard-symbol.json"
    p.write_text(bad.tojson())
    r = _run(["tools/graph_lint.py", str(p), "--check"])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "dtype-promotion" in r.stdout and "widen" in r.stdout


# ----------------------------------------------------------------------
# satellites
def test_parse_params_did_you_mean():
    with pytest.raises(mx.MXNetError, match="did you mean 'num_hidden'"):
        mx.sym.FullyConnected(mx.sym.Variable("d"), num_hiden=10)
    # dunder group attrs ride through untouched (escape hatch)
    from mxnet_tpu.op import registry as reg
    p = reg.get("FullyConnected").parse_params(
        {"num_hidden": 8, "__lr_mult__": "2"})
    assert p["__lr_mult__"] == "2" and p["num_hidden"] == 8


def test_topo_cycle_raises_with_node_names():
    from mxnet_tpu.op import registry as reg
    from mxnet_tpu.symbol import _Node, _topo
    op = reg.get("Activation")
    a = _Node(op, "cyc_a", params={"act_type": "relu"})
    b = _Node(op, "cyc_b", params={"act_type": "relu"})
    a.inputs = [(b, 0)]
    b.inputs = [(a, 0)]
    with pytest.raises(mx.MXNetError, match="cycle"):
        _topo([a])
    try:
        _topo([a])
    except mx.MXNetError as e:
        assert "cyc_a" in str(e) and "cyc_b" in str(e)
    # a diamond (shared subexpression) is NOT a cycle
    d = mx.sym.Variable("d", shape=(4, 4))
    r = mx.sym.Activation(d, act_type="relu")
    assert (r + r).list_arguments() == ["d"]


def test_simple_bind_surfaces_warns_and_debug_str_annotates(monkeypatch):
    d = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(d, num_hidden=100, name="fc_off")
    with warnings.catch_warnings(record=True) as got:
        warnings.simplefilter("always")
        exe = fc.simple_bind(ctx=mx.cpu(), data=(16, 256))
    assert any(issubclass(w.category, analysis.GraphLintWarning)
               for w in got)
    dbg = exe.debug_str()
    # per-node inferred shape/dtype from the analyzer's annotated graph
    assert "Variable:data, out=[float32 (16, 256)]" in dbg
    assert "Name=fc_off, out=[float32 (16, 100)]" in dbg
    # ...and the findings themselves, so debug output and lint agree
    assert "Graph lint findings:" in dbg and "tpu-layout" in dbg
    # the env kill switch
    monkeypatch.setenv("MXTPU_GRAPH_LINT", "0")
    with warnings.catch_warnings(record=True) as got2:
        warnings.simplefilter("always")
        exe2 = fc.simple_bind(ctx=mx.cpu(), data=(16, 256))
    assert not any(issubclass(w.category, analysis.GraphLintWarning)
                   for w in got2)
    assert exe2._lint_report is None
