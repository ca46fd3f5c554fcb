"""Executor tests (reference ``tests/python/unittest/test_executor.py``)."""
import numpy as np
import pytest

import mxnet_tpu as mx


def test_bind_forward_backward():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    c = a * b + a
    av = np.random.randn(3, 4).astype("f")
    bv = np.random.randn(3, 4).astype("f")
    exe = c.bind(mx.cpu(), {"a": mx.nd.array(av), "b": mx.nd.array(bv)},
                 args_grad={"a": mx.nd.zeros((3, 4)),
                            "b": mx.nd.zeros((3, 4))})
    out = exe.forward(is_train=True)[0].asnumpy()
    assert np.allclose(out, av * bv + av, atol=1e-6)
    og = np.random.randn(3, 4).astype("f")
    exe.backward(mx.nd.array(og))
    assert np.allclose(exe.grad_dict["a"].asnumpy(), og * (bv + 1), atol=1e-5)
    assert np.allclose(exe.grad_dict["b"].asnumpy(), og * av, atol=1e-5)


def test_grad_req_add():
    a = mx.sym.Variable("a")
    out = mx.symbol.square(a)
    av = np.random.randn(2, 2).astype("f")
    ga = mx.nd.ones((2, 2))
    exe = out.bind(mx.cpu(), {"a": mx.nd.array(av)}, args_grad={"a": ga},
                   grad_req="add")
    exe.forward(is_train=True)
    exe.backward(mx.nd.ones((2, 2)))
    assert np.allclose(ga.asnumpy(), 1 + 2 * av, atol=1e-5)


def test_grad_req_null():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    out = a * b
    exe = out.bind(mx.cpu(), {"a": mx.nd.ones((2,)), "b": mx.nd.ones((2,))},
                   args_grad={"a": mx.nd.zeros((2,))},
                   grad_req={"a": "write", "b": "null"})
    exe.forward(is_train=True)
    exe.backward(mx.nd.ones((2,)))
    assert np.allclose(exe.grad_dict["a"].asnumpy(), [1, 1])


def test_simple_bind():
    x = mx.sym.Variable("x")
    fc = mx.symbol.FullyConnected(x, num_hidden=4, name="fc")
    exe = fc.simple_bind(ctx=mx.cpu(), x=(2, 3))
    assert exe.arg_dict["fc_weight"].shape == (4, 3)
    exe.forward()
    assert exe.outputs[0].shape == (2, 4)


def test_forward_kwargs_update():
    x = mx.sym.Variable("x")
    out = mx.symbol.square(x)
    exe = out.simple_bind(ctx=mx.cpu(), x=(2, 2))
    o1 = exe.forward(x=np.full((2, 2), 2.0, dtype="f"))[0].asnumpy()
    assert np.allclose(o1, 4)
    o2 = exe.forward(x=np.full((2, 2), 3.0, dtype="f"))[0].asnumpy()
    assert np.allclose(o2, 9)


def test_reshape():
    x = mx.sym.Variable("x")
    fc = mx.symbol.FullyConnected(x, num_hidden=4, name="fc")
    exe = fc.simple_bind(ctx=mx.cpu(), x=(2, 3))
    exe.arg_dict["fc_weight"][:] = 1.0
    new_exe = exe.reshape(x=(5, 3))
    assert new_exe.arg_dict["x"].shape == (5, 3)
    # weights carried over
    assert np.allclose(new_exe.arg_dict["fc_weight"].asnumpy(), 1.0)
    new_exe.forward()
    assert new_exe.outputs[0].shape == (5, 4)


def test_output_dict():
    x = mx.sym.Variable("x")
    out = mx.symbol.tanh(x, name="t")
    exe = out.simple_bind(ctx=mx.cpu(), x=(2, 2))
    exe.forward()
    assert "t_output" in exe.output_dict


def test_monitor_callback():
    x = mx.sym.Variable("x")
    h = mx.symbol.tanh(x, name="t")
    out = mx.symbol.square(h, name="s")
    exe = out.simple_bind(ctx=mx.cpu(), x=(2, 2))
    seen = []
    exe.install_monitor(lambda name, arr: seen.append(name))
    exe.forward()
    assert "t_output" in seen and "s_output" in seen


def test_partial_forward():
    """PartialForward contract (reference ``executor.h:44-51``): issue
    one forward node per call with increasing step until 0 left; final
    outputs match a whole forward()."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=4,
                                name="fc")
    net = mx.sym.Activation(net, act_type="tanh")
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc2")
    rng = np.random.RandomState(0)
    args = {"data": mx.nd.array(rng.randn(3, 5).astype("f")),
            "fc_weight": mx.nd.array(rng.randn(4, 5).astype("f")),
            "fc_bias": mx.nd.zeros((4,)),
            "fc2_weight": mx.nd.array(rng.randn(2, 4).astype("f")),
            "fc2_bias": mx.nd.zeros((2,))}
    ex = net.bind(mx.cpu(), args=args)
    want = ex.forward(is_train=False)[0].asnumpy()

    step = 0
    left = ex.partial_forward(is_train=False, step=step)
    steps = 1
    while left:
        step += 1
        left = ex.partial_forward(is_train=False, step=step)
        steps += 1
    assert steps == 3            # fc, tanh, fc2
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), want, rtol=1e-6)


def test_partial_forward_ordering_and_invalidation():
    """Out-of-order steps raise; a full forward() supersedes an
    in-flight partial sequence (no stale mixed-state outputs)."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                name="fc")
    net = mx.sym.Activation(net, act_type="relu")
    rng = np.random.RandomState(1)
    args = {"data": mx.nd.array(rng.randn(2, 4).astype("f")),
            "fc_weight": mx.nd.array(rng.randn(3, 4).astype("f")),
            "fc_bias": mx.nd.zeros((3,))}
    ex = net.bind(mx.cpu(), args=args)

    # steps must be issued in order from 0
    with pytest.raises(Exception):
        ex.partial_forward(is_train=False, step=1)

    # start a partial run, then interrupt it with a full forward on new
    # data; the old sequence must not resume silently
    ex.partial_forward(is_train=False, step=0)
    args["data"][:] = rng.randn(2, 4).astype("f")
    want = ex.forward(is_train=False)[0].asnumpy()
    with pytest.raises(Exception):
        ex.partial_forward(is_train=False, step=1)   # stale sequence gone
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), want, rtol=1e-6)


def test_partial_forward_cold_out_of_range_raises():
    """A too-large step with no active sequence is an ordering error,
    not 'done' — returning 0 would let the caller read stale outputs."""
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3,
                                name="fc")
    rng = np.random.RandomState(2)
    args = {"data": mx.nd.array(rng.randn(2, 4).astype("f")),
            "fc_weight": mx.nd.array(rng.randn(3, 4).astype("f")),
            "fc_bias": mx.nd.zeros((3,))}
    ex = net.bind(mx.cpu(), args=args)
    with pytest.raises(Exception):
        ex.partial_forward(is_train=False, step=99)
    # after a completed sequence, an off-the-end step still reads as done
    left, step = 1, 0
    left = ex.partial_forward(is_train=False, step=0)
    while left:
        step += 1
        left = ex.partial_forward(is_train=False, step=step)
    assert ex.partial_forward(is_train=False, step=step + 1) == 0
    # ...but a full forward invalidates that too
    ex.forward(is_train=False)
    with pytest.raises(Exception):
        ex.partial_forward(is_train=False, step=99)


def test_eval_forward_skips_key_derivation(monkeypatch):
    """Train-only noise ops (Dropout) must not cost per-forward PRNG
    derivation at is_train=False — every eager key op is a dispatch
    of its own (the round-4 inference fix).  Samplers
    (rng_in_eval) must still draw fresh keys every forward."""
    from mxnet_tpu import random as mxrandom

    calls = {"n": 0}
    real = mxrandom.next_key

    def counting_next_key():
        calls["n"] += 1
        return real()
    monkeypatch.setattr(mxrandom, "next_key", counting_next_key)

    net = mx.sym.Dropout(mx.sym.Variable("data"), p=0.5)
    ex = net.simple_bind(mx.cpu(), grad_req="null", data=(2, 8))
    ex.arg_dict["data"][:] = np.ones((2, 8), "f")
    for _ in range(3):
        ex.forward(is_train=False)
    assert calls["n"] == 0, "eval forward of a train-only-noise " \
        "program must reuse the cached const key"
    ex.forward(is_train=True)
    assert calls["n"] == 1, "train forward must derive a fresh key"

    calls["n"] = 0
    samp = mx.sym.Group([mx.sym.uniform(shape=(2, 2))])
    sex = samp.simple_bind(mx.cpu(), grad_req="null")
    a = sex.forward(is_train=False)[0].asnumpy().copy()
    b = sex.forward(is_train=False)[0].asnumpy().copy()
    assert calls["n"] == 2, "sampler eval forwards must draw fresh keys"
    assert not np.allclose(a, b), "sampler eval draws must differ"


def test_load_general_adopts_whole_batch_buffer():
    """Whole-batch same-dtype same-device loads must adopt the source
    buffer (zero dispatched ops) instead of slicing — the other half of
    the round-4 dispatch fix (executor_manager._load_general)."""
    from mxnet_tpu import io
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=2,
                              name="fc"), name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (4, 8))],
             label_shapes=[("softmax_label", (4,))], for_training=False)
    mod.init_params(mx.init.Uniform(0.1))
    src = mx.nd.array(np.random.RandomState(0).rand(4, 8).astype("f"))
    mod.forward(io.DataBatch(data=[src],
                             label=[mx.nd.zeros((4,))]), is_train=False)
    bound = mod._exec_group.execs[0].arg_dict["data"]
    assert bound.data is src.data, \
        "fast path must alias the caller's buffer, not copy"
    # mismatched dtype still goes through the casting copy path
    src16 = src.astype("float16")
    mod.forward(io.DataBatch(data=[src16],
                             label=[mx.nd.zeros((4,))]), is_train=False)
    bound = mod._exec_group.execs[0].arg_dict["data"]
    assert bound.data is not src16.data
    assert str(bound.dtype) == "float32"


# ----------------------------------------------------------------------
# recomputation segments (``remat_segment``, executor.SEGMENT_ATTR)
def _stack(mark, rate=0.3, blocks=3, width=64):
    """FullyConnected / BatchNorm / tanh / Dropout blocks, each one
    segment when ``mark``."""
    import contextlib
    x = mx.sym.Variable("data")
    for i in range(blocks):
        scope = mx.AttrScope(remat_segment="b%d" % i) if mark \
            else contextlib.nullcontext()
        with scope:
            x = mx.sym.FullyConnected(x, num_hidden=width, name="fc%d" % i)
            x = mx.sym.BatchNorm(x, fix_gamma=False, name="bn%d" % i)
            x = mx.sym.Activation(x, act_type="tanh", name="act%d" % i)
            if rate:
                x = mx.sym.Dropout(x, p=rate, name="drop%d" % i)
    x = mx.sym.FullyConnected(x, num_hidden=10, name="out")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _one_step(sym, seed=7, is_train=True):
    """Outputs, gradients and new auxiliary states of one pass on fixed
    values and a fixed key."""
    ex = sym.simple_bind(mx.cpu(), data=(32, 16), softmax_label=(32,))
    rs = np.random.RandomState(0)
    for n, a in ex.arg_dict.items():
        a[:] = rs.randint(0, 10, a.shape) if n == "softmax_label" \
            else rs.normal(0, 0.3, a.shape)
    mx.random.seed(seed)
    out = ex.forward(is_train=is_train)[0].asnumpy()
    grads = {}
    if is_train:
        ex.backward()
        grads = {n: g.asnumpy() for n, g in ex.grad_dict.items()}
    return out, grads, {n: a.asnumpy() for n, a in ex.aux_dict.items()}


def test_a_marked_symbol_gives_the_unmarked_ones_outputs_and_gradients():
    """With every block a segment the outputs are the same bits and the
    gradients equal to round-off; BatchNorm inside a segment leaves the
    same moving statistics and Dropout draws the same mask, in the
    forward pass and in the recomputation: both are carried through
    exactly."""
    plain, marked = _one_step(_stack(False)), _one_step(_stack(True))
    np.testing.assert_array_equal(plain[0], marked[0])
    assert set(plain[1]) == set(marked[1])
    scale = max(np.abs(g).max() for g in plain[1].values())
    for n, g in plain[1].items():
        # (a bias before a BatchNorm has no gradient but round-off)
        assert np.linalg.norm(marked[1][n] - g) \
            <= 1e-5 * np.linalg.norm(g) + 1e-6 * scale, n
    assert np.linalg.norm(plain[1]["fc1_weight"]) > 0.1 * scale
    for n, a in plain[2].items():          # moving_mean / moving_var
        np.testing.assert_array_equal(marked[2][n], a)
        assert np.abs(a - (1.0 if n.endswith("var") else 0.0)).max() > 0
    # another key, another mask, and the marked walk follows it
    other = _one_step(_stack(True), seed=8)
    assert np.abs(other[0] - marked[0]).max() > 1e-3
    np.testing.assert_array_equal(other[0], _one_step(_stack(False),
                                                      seed=8)[0])
    # out of training nothing is recomputed and the plain walk runs
    np.testing.assert_array_equal(_one_step(_stack(True), is_train=False)[0],
                                  _one_step(_stack(False), is_train=False)[0])


def test_the_segment_attribute_is_part_of_the_symbols_digest():
    """The attribute is in ``tojson()``, so the program cache tells a
    marked graph from an unmarked one and two markings apart."""
    from mxnet_tpu import program
    from mxnet_tpu.executor import SEGMENT_ATTR
    assert SEGMENT_ATTR == "remat_segment"
    plain, marked = _stack(False), _stack(True)
    assert '"remat_segment": "b1"' in marked.tojson()
    assert "remat_segment" not in plain.tojson()
    assert program.symbol_digest(plain) != program.symbol_digest(marked)
    assert program.symbol_digest(marked) == program.symbol_digest(_stack(True))
    again = mx.sym.load_json(marked.tojson())
    assert program.symbol_digest(again) == program.symbol_digest(marked)
    assert again.attr_dict()["fc1"]["remat_segment"] == "b1"


def _old_walk(prog, arg_vals, aux_vals, rng_key, is_train):
    """``_GraphProgram._eval`` as it was before segments existed."""
    import jax
    from mxnet_tpu.op.registry import OpContext
    env = {}
    aux_out = list(aux_vals)
    for n in prog.nodes:
        if n.is_variable:
            env[(id(n), 0)] = arg_vals[prog._arg_index[n.name]]
            continue
        in_vals = [env[(id(c), i)] for c, i in n.inputs]
        aux_names = n.aux_names()
        aux_slots = [prog._aux_index["%s_%s" % (n.name, a)]
                     for a in aux_names]
        node_aux = [aux_vals[s] for s in aux_slots]
        if aux_names:
            node_aux = [jax.lax.stop_gradient(v) for v in node_aux]
        rng = None
        if n.op.uses_rng:
            rng = jax.random.fold_in(rng_key, len(env))
        ctx = OpContext(is_train=is_train, rng=rng, platform=prog.platform,
                        dtype_policy=prog.dtype_policy)
        with jax.named_scope(n.name):
            outs, aux_updates = n.op.apply(n.params, ctx,
                                           *(in_vals + node_aux))
        for i, v in enumerate(outs):
            env[(id(n), i)] = v
        for s, v in zip(aux_slots, aux_updates):
            aux_out[s] = v
    outputs = tuple(env[(id(nd), i)] for nd, i in prog.output_entries)
    return outputs, tuple(aux_out)


@pytest.mark.parametrize("network,kwargs", [
    ("transformer", dict(seq_len=16, num_hidden=32, num_heads=2,
                         num_layers=2, vocab_size=64, dropout=0.1)),
    ("glm-moe", dict(vocab_size=64, seq_len=16)),
    ("loop-lm", dict(vocab_size=64, seq_len=16, segments=False)),
])
def test_an_unmarked_symbol_traces_the_program_it_traced_before(network,
                                                                kwargs):
    """No marked node, no plan: the walk's jaxpr, forward and backward,
    is the old walk's, equation for equation."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.executor import _GraphProgram
    sym = models.get_symbol(network, **kwargs)
    prog = _GraphProgram(sym)
    assert prog._plan is None
    arg_s, _, aux_s = sym.infer_shape(data=(2, 16), softmax_label=(2, 16))
    args = [jnp.zeros(s, jnp.int32 if n in ("data", "softmax_label")
                      else jnp.float32)
            for n, s in zip(prog.arg_names, arg_s)]
    aux = [jnp.zeros(s, jnp.float32) for s in aux_s]
    key = jax.random.key(0)

    def step(walk):
        def fn(a, x):
            (outs, new_aux), pull = jax.vjp(
                lambda a: walk(list(a), list(x), key, True), a)
            seeds = (tuple(jnp.ones_like(o) for o in outs),
                     tuple(jnp.zeros_like(v) for v in new_aux))
            return outs, new_aux, pull(seeds)
        return str(jax.make_jaxpr(fn)(args, aux))

    new = step(prog._eval)
    old = step(lambda *a: _old_walk(prog, *a))
    assert new == old and len(new) > 1000


def test_a_segment_is_a_run_of_the_walk_and_one_function_of_what_it_reads():
    """The looped model's passes: the walk reaches a pass's gate, head
    and row loss only after every block of every pass (output 0 is the
    last pass's softmax), so the nodes marked ``u1`` are three runs and
    three segments; each gives the rest only what the rest reads (the
    carried state; the gate; the row loss), never a pass's logits."""
    from mxnet_tpu import models
    from mxnet_tpu.executor import _GraphProgram, _Segment
    sym = models.get_symbol("loop-lm", vocab_size=64, seq_len=16)
    prog = _GraphProgram(sym)
    steps, rng_index = prog._plan
    # the walk's own order, nothing moved
    assert [n.name for n in prog.nodes if not n.is_variable] \
        == [n.name for s in steps
            for n in (s.nodes if isinstance(s, _Segment) else [s])]
    by_id = {id(n): n.name for n in prog.nodes}
    segs = [s for s in steps if isinstance(s, _Segment)]
    assert [(s.name, s.nodes[0].name, [by_id[i] for i, _ in s.gives])
            for s in segs] == [
        ("u1", "u1_l0_norm1", ["u1_norm"]),
        ("u2", "u2_l0_norm1", ["u2_norm"]),
        ("u3", "u3_l0_norm1", ["u3_norm"]),
        ("u4", "u4_l0_norm1", ["u4_exit_head"]),
        ("u1", "u1_exit_gate_in", ["u1_exit_lambda"]),
        ("u2", "u2_exit_gate_in", ["u2_exit_lambda"]),
        ("u3", "u3_exit_gate_in", ["u3_exit_lambda"]),
        ("u1", "u1_exit_head", ["u1_exit_rowloss_col"]),
        ("u2", "u2_exit_head", ["u2_exit_rowloss_col"]),
        ("u3", "u3_exit_head", ["u3_exit_rowloss_col"]),
        ("u4", "u4_exit_label", ["u4_exit_rowloss_col"])]
    read = {by_id[i] for i, _ in segs[0].reads}
    assert {"tok_embed_rows", "norm_gamma", "l1_mlp_up_weight"} <= read \
        and "data" not in read and "head_weight" not in read
    assert {by_id[i] for i, _ in segs[7].reads} \
        == {"u1_norm", "head_weight", "softmax_label"}
    assert sorted(rng_index.values()) == sorted(set(rng_index.values()))


def _lowered_scopes(sym, wrap=None):
    """The scopes ``benchmark/lib/tracered.py`` would give the
    operations of one gradient of ``sym``'s walk (its rule, copied: the
    first part of an ``op_name`` that is neither a transformation's
    wrapper nor a call), with the lowered text."""
    import re
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.executor import _GraphProgram
    prog = _GraphProgram(sym)
    arg_s, _, aux_s = sym.infer_shape(data=(32, 16), softmax_label=(32,))
    args = [jnp.ones(s, jnp.float32) for s in arg_s]
    aux = [jnp.ones(s, jnp.float32) for s in aux_s]
    walk = (wrap or (lambda f: f))(
        lambda a: prog._eval(list(a), aux, jax.random.key(0), True)[0][0])
    text = jax.jit(jax.grad(lambda a: jnp.sum(walk(a) ** 2))).lower(
        args).as_text(debug_info=True)
    wrappers = re.compile(r"^(transpose|jvp|checkpoint|remat|vmap|rematted_"
                          r"computation|custom_vjp_call|custom_jvp_call)"
                          r"\((.*)\)$")
    scopes = set()
    for name in re.findall(r'"jit\(<lambda>\)/([^"]*)/[\w\-]+"', text):
        for part in name.split("/"):
            while wrappers.match(part):
                part = wrappers.match(part).group(2)
            if part and not re.match(r"^[\w.\-]+\(.*\)$", part):
                scopes.add(part)
                break
    return scopes, text


def test_the_backward_pass_of_a_segment_is_named_node_by_node():
    """Every node keeps its scope in the compiled step.  In its
    segment's backward pass the forward operations run again under
    ``remat.<node>`` and their reverse modes under ``remat_bwd.<node>``:
    a device trace tells the second run from the first and from the
    reverse modes beside it.  The second run stays behind a barrier that
    keeps it from merging with the first."""
    scopes, text = _lowered_scopes(_stack(True, rate=0.0))
    for node in ("fc0", "bn1", "act2"):
        assert {node, "remat." + node, "remat_bwd." + node} <= scopes
    assert {"out", "softmax"} <= scopes
    assert not any(s.startswith("remat") and s.endswith(("out", "softmax"))
                   for s in scopes)
    assert "checkpoint" not in scopes
    assert text.count("stablehlo.optimization_barrier") == 3


def test_jax_checkpoint_would_hide_a_segments_nodes_from_a_trace():
    """Why a segment is not ``jax.checkpoint``: its body is lowered
    under the one name ``checkpoint``, which comes before the nodes'
    scopes, so the trace reduction would read ``checkpoint`` for every
    operation of every segment's backward pass, the attention kernels
    among them.  (If this fails, JAX names them apart now and
    ``_eval_segment`` can be ``jax.checkpoint``.)"""
    import jax
    scopes, _ = _lowered_scopes(_stack(False, rate=0.0), jax.checkpoint)
    assert "checkpoint" in scopes
    assert not any("fc1" in s for s in scopes if s != "fc1")


def test_segments_lower_the_compiled_steps_temporaries():
    """The looped model at four passes of four layers: with every pass
    marked ``compiled.memory_analysis()`` gives a smaller temporary
    size for the gradient's program than without, on the CPU, whose
    compiler schedules little for memory.  (The v5e's rematerializes by
    itself and keeps every pass's partial gradient of a shared weight
    to the end, so there the marked step is the larger one until a leaf
    is handed from segment to segment: PERF.md section 6, PR 37.)"""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.executor import _GraphProgram

    def temporaries(segments):
        sym = models.get_symbol("loop-lm", vocab_size=512, seq_len=128,
                                hidden_size=64, num_layers=4,
                                intermediate_size=256, segments=segments)
        prog = _GraphProgram(sym)
        prog.platform = "cpu"
        arg_s, _, aux_s = sym.infer_shape(data=(2, 128),
                                          softmax_label=(2, 128))
        args = [jax.ShapeDtypeStruct(s, jnp.int32 if n in (
            "data", "softmax_label") else jnp.float32)
            for n, s in zip(prog.arg_names, arg_s)]
        aux = [jax.ShapeDtypeStruct(s, jnp.float32) for s in aux_s]

        def loss(a, x):
            outs, _ = prog._eval(list(a), list(x), jax.random.key(0), True)
            return outs[1][0]

        compiled = jax.jit(jax.grad(loss, allow_int=True)).lower(
            args, aux).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    assert temporaries(True) < temporaries(False)


def test_what_a_segment_cannot_hold_is_refused_by_name():
    """Nodes of one value that the walk does not reach one after the
    other are two segments; a node that calls back into Python is
    refused at bind, the node named."""
    from mxnet_tpu.executor import _GraphProgram, _Segment
    x = mx.sym.Variable("data")
    with mx.AttrScope(remat_segment="s"):
        a = mx.sym.FullyConnected(x, num_hidden=8, name="inside_a")
    b = mx.sym.Activation(a, act_type="relu", name="outside")
    with mx.AttrScope(remat_segment="s"):
        c = mx.sym.FullyConnected(b, num_hidden=8, name="inside_c")
    steps = _GraphProgram(c)._plan[0]
    assert [s.name if isinstance(s, _Segment) else "-" + s.name
            for s in steps] == ["s", "-outside", "s"]

    class Twice(mx.operator.CustomOp):
        def forward(self, is_train, req, in_data, out_data, aux):
            self.assign(out_data[0], req[0], in_data[0] * 2)

        def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
            self.assign(in_grad[0], req[0], out_grad[0] * 2)

    @mx.operator.register("twice_in_a_segment")
    class TwiceProp(mx.operator.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

        def create_operator(self, ctx, shapes, dtypes):
            return Twice()

    with mx.AttrScope(remat_segment="s"):
        e = mx.sym.Custom(x, op_type="twice_in_a_segment", name="calls_back")
    with pytest.raises(mx.base.MXNetError, match="calls_back.*twice"):
        _GraphProgram(e)
    assert _GraphProgram(mx.sym.Custom(
        x, op_type="twice_in_a_segment", name="free"))._plan is None
