"""The main path's Pallas kernel, compiled for the chip with no chip.

The TPU's compiler is installed here and compiles for a device that is
described and not attached (``jax.experimental.topologies``).  Every
other test of ``flash_attention`` runs it in interpret mode on the CPU;
these hand the same kernel, ``interpret=False``, to the v5e's compiler
at the widths the main path uses, so a kernel the chip would refuse
(tiling, VMEM) fails here at no chip time.  A compile that passes is a
compile, not a run: ``chip_smoke.py`` is the run.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.op.pallas.flash_attention import flash_attention

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or it logs under /tmp


@pytest.fixture(scope="module")
def v5e():
    """One device of a described v5e 2x2, persistent cache off: a
    compile for a described device is written to the cache and cannot
    be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                          # noqa: BLE001
        pytest.skip("cannot describe a v5e:2x2 here: %s" % e)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (shape, dtype, causal, in place): the kernels read q, k, v, o and dO
# where the graph has them unless the time length needs padding
SHAPES = [
    pytest.param((8, 1024, 16, 64), jnp.bfloat16, True, True,
                 id="gpt2-medium"),
    pytest.param((2, 8192, 8, 64), jnp.bfloat16, True, True,
                 id="long-context"),
    pytest.param((4, 2048, 8, 128), jnp.bfloat16, True, True,
                 id="head-dim-128"),
    pytest.param((4, 1000, 8, 64), jnp.float32, False, False,
                 id="ragged-f32"),
    pytest.param((1, 4096, 20, 256), jnp.bfloat16, True, True,
                 id="glm-4.7-flash"),
    pytest.param((1, 4096, 16, 128), jnp.bfloat16, True, True,
                 id="ouro-2.6b"),
    pytest.param((1, 8192, 32, 64), jnp.bfloat16, True, True,
                 id="lfm2-24b-a2b"),
    pytest.param((1, 8192, 48, 128), jnp.bfloat16, True, True,
                 id="laguna-xs.2"),
]


def _node(q, k, v, shape, causal):
    """The attention node between the products that feed it and the one
    it feeds, which hold ``[b, t, h*d]``: the reshapes to and from the
    op's ``[b, t, h, d]`` are bitcasts."""
    o = flash_attention(*(a.reshape(shape) for a in (q, k, v)),
                        causal=causal, interpret=False)
    return o.reshape(q.shape)


def _compiled_text(fn, shape, dtype, sharding):
    b, t, h, d = shape
    x = jax.ShapeDtypeStruct((b, t, h * d), dtype, sharding=sharding)
    return jax.jit(fn).lower(x, x, x).compile().as_text()


def _kernels(text):
    """The names of the module's Pallas kernels, in order."""
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    return [re.search(r"flash_attention_(?:fwd|bwd)", line).group(0)
            for line in calls]


def _layout_passes(text, shape, scope=""):
    """The instructions, fused or not, that move a whole operand of the
    kernels about: a ``transpose``, a ``pad``, or the ``copy`` a
    transpose is once the compiler has assigned layouts.  ``scope``
    keeps those traced under a node of that name."""
    whole = int(np.prod(shape))
    found = []
    for line in text.splitlines():
        if scope not in line:
            continue
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"(transpose|pad|copy)\(", line)
        if m and np.prod([int(n) for n in m.group(1).split(",") if n]) \
                >= whole:
            found.append(line.strip()[:120])
    return found


@pytest.mark.parametrize("shape,dtype,causal,in_place", SHAPES)
def test_flash_forward_compiles_for_v5e(v5e, shape, dtype, causal, in_place):
    text = _compiled_text(lambda q, k, v: _node(q, k, v, shape, causal),
                          shape, dtype, v5e)
    assert _kernels(text) == ["flash_attention_fwd"]
    # folded, the detector has something to find: it is not blind
    assert bool(_layout_passes(text, shape)) != in_place


@pytest.mark.parametrize("shape,dtype,causal,in_place", SHAPES)
def test_flash_gradient_compiles_for_v5e(v5e, shape, dtype, causal, in_place):
    """The reverse mode is a kernel of its own, not a loop of einsums:
    the forward's custom call alone does not pass, and a tile the v5e's
    compiler refuses (VMEM at head 256 over 4,096 positions) fails
    here.  Where the kernels read in place, nothing in the module
    transposes, pads or copies q, k, v, o or dO."""
    def loss(q, k, v):
        return jnp.sum(_node(q, k, v, shape, causal).astype(jnp.float32)
                       ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), shape, dtype,
                          v5e)
    assert _kernels(text) == ["flash_attention_fwd", "flash_attention_bwd"]
    assert " while(" not in text
    passes = _layout_passes(text, shape)
    assert bool(passes) != in_place, passes


def test_grouped_kv_heads_reach_the_kernels_on_the_v5e(v5e):
    """The attention op at lfm2-24b-a2b's shapes, 32 query heads of 64
    over 8 key/value heads and 8,192 positions, on its compiled path:
    value and gradient compile for the v5e, k and v are repeated to the
    query's heads before the two kernels, and dk and dv come back at 8
    heads."""
    from mxnet_tpu.op import registry
    op = registry.get("_contrib_DotProductAttention")
    params = op.parse_params({"causal": True, "scale": 0.125})
    ctx = registry.OpContext(is_train=True, platform="tpu")

    def loss(q, k, v):
        out = op.fn(params, ctx, q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def spec(heads):
        return jax.ShapeDtypeStruct((1, 8192, heads, 64), jnp.bfloat16,
                                    sharding=v5e)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        spec(32), spec(8), spec(8)).compile()
    assert _kernels(compiled.as_text()) == ["flash_attention_fwd",
                                            "flash_attention_bwd"]
    assert [tuple(o.shape) for o in compiled.out_info] \
        == [(1, 8192, 32, 64), (1, 8192, 8, 64), (1, 8192, 8, 64)]


@pytest.mark.parametrize("heads,window", [(48, 0), (64, 512)],
                         ids=["full", "window"])
def test_laguna_attention_nodes_compile_for_v5e(v5e, heads, window):
    """The attention op at laguna-xs.2's shapes on its compiled path: 48
    (full) or 64 (window of 512) query heads of 128 over 8 key/value
    heads and 8,192 positions.  Value and gradient compile for the v5e
    to the two kernels and no loop, and the window's grids step over the
    2 blocks a 512 x 512 tile's window reaches, not the 16 of the
    sequence."""
    from mxnet_tpu.op import registry
    op = registry.get("_contrib_DotProductAttention")
    params = op.parse_params({"causal": True, "scale": 128 ** -0.5,
                              "window": window})
    ctx = registry.OpContext(is_train=True, platform="tpu")

    def loss(q, k, v):
        out = op.fn(params, ctx, q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def spec(h):
        return jax.ShapeDtypeStruct((1, 8192, h, 128), jnp.bfloat16,
                                    sharding=v5e)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = grad.lower(spec(heads), spec(8), spec(8)).compile().as_text()
    assert _kernels(text) == ["flash_attention_fwd", "flash_attention_bwd"]
    assert " while(" not in text
    jaxpr = str(jax.make_jaxpr(grad)(spec(heads), spec(8), spec(8)))
    steps = 2 if window else 16
    assert re.findall(r"grid=\(([\d, ]+)\)", jaxpr) \
        == ["1, %d, 16, %d" % (heads, steps)] * 2


def test_transformer_step_feeds_the_kernels_with_no_layout_pass(v5e):
    """The fused training step of ``models.get_symbol("transformer")``,
    two layers at GPT-2 medium's width and batch, compiled for the v5e:
    q, k and v reach the forward kernel, and dq, dk and dv leave the
    backward kernel, with no transpose, pad or copy of a whole
    ``[8, 1024, 1024]`` array.  (Sliced along the "3" of a
    ``[b, t, 3, h, d]`` view of the qkv product, XLA laid the product out
    with that axis major and put six such copies a layer around the
    kernels.)"""
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel.trainer import Trainer

    sym = models.get_symbol("transformer", seq_len=1024, num_hidden=1024,
                            num_heads=16, num_layers=2, vocab_size=2048)
    trainer = Trainer(sym, mx.optimizer.SGD(learning_rate=0.02, momentum=0.9),
                      compute_dtype="bfloat16")      # no mesh: one chip
    trainer.bind(data_shapes={"data": (8, 1024)},
                 label_shapes={"softmax_label": (8, 1024)})
    trainer.init_params(mx.init.Normal(0.02))
    trainer.prog.platform = "tpu"       # the op takes its compiled path
    args = trainer.abstract_step_args({"data": np.int32,
                                       "softmax_label": np.int32})
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a),
                                       sharding=v5e), args)
    text = trainer._step_fn.lower(*args).compile().as_text()
    assert _kernels(text) == ["flash_attention_fwd"] * 2 \
        + ["flash_attention_bwd"] * 2
    assert _layout_passes(text, (8, 1024, 16, 64), scope="_attn_attn") == []


@pytest.mark.parametrize("segments,again", [(True, 4), (False, 0)])
def test_a_segments_forward_runs_again_on_the_v5e(v5e, segments, again):
    """The fused step of the looped model (two layers run twice at
    128-wide heads, 1,024 positions) compiled for the v5e: with a pass
    a recomputation segment every attention node's forward kernel is
    in the program twice, once for the forward pass and once behind the
    barrier of its segment's backward pass (the compiler has not merged
    the two), under the node's scope and under ``remat.<node>``; with
    no segment it is there once.  The backward kernel is there once
    either way."""
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel.trainer import Trainer

    sym = models.get_symbol("loop-lm", vocab_size=2048, seq_len=1024,
                            hidden_size=512, num_layers=2, num_heads=4,
                            head_dim=128, intermediate_size=1408,
                            loop_steps=2, segments=segments)
    trainer = Trainer(sym, mx.optimizer.SGD(learning_rate=0.02, momentum=0.9),
                      compute_dtype="bfloat16")
    trainer.bind(data_shapes={"data": (1, 1024)},
                 label_shapes={"softmax_label": (1, 1024)})
    trainer.init_params(mx.init.Normal(0.02))
    trainer.prog.platform = "tpu"
    args = trainer.abstract_step_args({"data": np.int32,
                                       "softmax_label": np.int32})
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a),
                                       sharding=v5e), args)
    text = trainer._step_fn.lower(*args).compile().as_text()
    kernels = _kernels(text)
    assert kernels.count("flash_attention_fwd") == 4 + again
    assert kernels.count("flash_attention_bwd") == 4
    marked = set(re.findall(r"remat\.(u\d_l\d_attn_attn)", text))
    assert len(marked) == again
    assert _layout_passes(text, (1, 1024, 4, 128), scope="_attn_attn") == []


# (tokens, entries a token, experts, held, d, h) of the three expert cells
EXPERT_SHAPES = [
    pytest.param(4096, 4, 64, 8, 2048, 1536, id="glm-4.7-flash"),
    pytest.param(4096, 8, 512, 8, 2560, 768, id="ling-3.0-flash"),
    pytest.param(8192, 4, 64, 8, 2048, 1536, id="lfm2-24b-a2b"),
]


@pytest.mark.parametrize("t,k,experts,held,d,h", EXPERT_SHAPES)
def test_expert_layer_compiles_to_the_chips_grouped_kernels(
        v5e, t, k, experts, held, d, h):
    """``MoEExperts``' mathematics at the cells' widths (4,096 rows x 4
    entries, 8 of 64 experts held; x 8 entries, 8 of 512; 8,192 rows x 4
    entries, 8 of 64): value and
    gradient compile for the v5e, and the three grouped products are the
    compiler's own ragged-dot kernels, which walk the live row tiles,
    not a dense product an expert.  The sorted entries are walked in
    chunks by one loop a pass, and no buffer of all of their rows by d
    columns exists; where the chunk's sums by token take the sorted route
    no array of the chunk's rows by the tokens does either."""
    from mxnet_tpu.parallel import moe

    def loss(x, idx, w, wg, wu, wd):
        y, count = moe.moe_apply_held(x, idx, w, wg, wu, wd, 0, experts)
        return jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(count)

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 2, 3, 4, 5))).lower(
        spec((t, d)), spec((t, k), jnp.int32), spec((t, k), jnp.float32),
        spec((held, h, d)), spec((held, h, d)), spec((held, d, h))
    ).compile().as_text()
    assert text.count("ragged-dot-metadata") >= 3
    assert "tpu_custom_call" in text
    assert len(re.findall(r" while\(", text)) == 2
    assert "[%d,%d]" % (t * k, d) not in text \
        and "[%d,%d]" % (t * k, h) not in text
    rows = moe.held_chunk_rows(t * k, held, experts)
    assert "bf16[%d,%d]" % (rows, d) in text
    # where the chunk's sums take the sorted route, no rows x tokens array
    assert ("[%d,%d]" % (rows, t) in text) != moe._sorted_route(rows, t)


# (activation, policy, with its gradient): ResNet-50's stage-1 and
# stage-3 BatchNorm inputs, NHWC, in the trainer's compute dtype
BN_CASES = [
    pytest.param((256, 56, 56, 256), "bytediet", False, id="56x56-forward"),
    pytest.param((256, 14, 14, 256), "bytediet", False, id="14x14-forward"),
    pytest.param((256, 56, 56, 256), "legacy", False, id="56x56-legacy"),
    pytest.param((256, 14, 14, 256), "legacy", False, id="14x14-legacy"),
    pytest.param((256, 56, 56, 256), "bytediet", True, id="56x56-gradient"),
    pytest.param((256, 14, 14, 256), "bytediet", True, id="14x14-gradient"),
]


@pytest.mark.parametrize("shape,policy,grad", BN_CASES)
def test_batchnorm_statistics_write_no_float32_copy(v5e, shape, policy, grad):
    """Train-mode ``BatchNorm`` through the op registry, then ``relu``,
    compiled for the v5e in bfloat16: the statistics read the activation
    as it is, so the program's entry holds no float32 array of its shape
    (an operand of the cancellation fallback's conditional is a buffer:
    widened there, it is a copy written every step, 1.2 GB of traffic
    at 56x56).  The entry holds the bfloat16 output of that shape: the
    detector reads the text."""
    from mxnet_tpu.op import bytediet
    from mxnet_tpu.op.registry import OpContext, get

    op = get("BatchNorm")
    params = op.parse_params(dict(eps=2e-5, momentum=0.9, fix_gamma=False,
                                  axis=3))
    ctx = OpContext(is_train=True, dtype_policy=policy)

    def forward(x, gamma, beta, moving_mean, moving_var):
        (out,), aux = op.apply(params, ctx, x, gamma, beta, moving_mean,
                               moving_var)
        return bytediet.relu_save_output(out), aux

    def step(x, gamma, beta, moving_mean, moving_var):
        def loss(x, gamma, beta):
            y, aux = forward(x, gamma, beta, moving_mean, moving_var)
            return jnp.sum(y.astype(jnp.float32) ** 2), aux
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            x, gamma, beta)

    def spec(s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=v5e)

    c = (shape[-1],)
    text = jax.jit(step if grad else forward).lower(
        spec(shape), spec(c), spec(c), spec(c), spec(c)).compile().as_text()
    entry = text[text.index("\nENTRY"):]
    dims = ",".join(str(n) for n in shape)
    assert re.search(r"= bf16\[%s\]" % dims, entry)
    widened = [line.strip()[:120] for line in entry.splitlines()
               if re.search(r"= f32\[%s\]" % dims, line)]
    assert widened == []
