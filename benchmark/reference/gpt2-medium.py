"""Plain reference for the ``gpt2-medium`` configuration.

A decoder-only transformer language model at GPT-2 medium's published
sizes (``openai-community/gpt2-medium`` ``config.json``): learned token
and position embeddings, pre-norm blocks x + Attn(LN(x)),
x + MLP(LN(x)) with causal multi-head attention and a tanh-GELU MLP of
four times the width, a final LayerNorm, a dense output head, softmax
cross-entropy on the next token.

Departure from the published model, the program's and so this file's:
the output head is a matrix of its own with a bias, not the transposed
token embedding.

Straightforward ``jax.numpy`` in float32: attention materializes its
scores.  It imports nothing of the program under test: parameter names
are the program symbol's public names, which is how the harness hands
one set of seeded weights to both sides.  ``jax.checkpoint`` around
each block only bounds the memory of the backward pass; it changes no
value.

``cast`` is the hook of the lower-precision control: it is applied to
both operands of every matrix product, attention's two included, as
float8 training computes its products (what ``cast`` does to the
cotangents that come back through it is its own affair); accumulation,
the products' results, LayerNorm, softmax, GELU, the residual sums and
the loss stay in float32.  The reference itself passes ``None``.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

LN_EPS = 1e-5
HI = lax.Precision.HIGHEST


def param_shapes(cfg):
    """({parameter: shape}, {}): no auxiliary state."""
    c, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    inner = cfg["n_inner"]
    p = {"tok_embed_weight": (v, c), "pos_embed_weight": (t, c),
         "ln_f_gamma": (c,), "ln_f_beta": (c,),
         "head_weight": (v, c), "head_bias": (v,)}
    for i in range(cfg["n_layer"]):
        pre = "l%d_" % i
        p.update({
            pre + "ln1_gamma": (c,), pre + "ln1_beta": (c,),
            pre + "attn_qkv_weight": (3 * c, c),
            pre + "attn_qkv_bias": (3 * c,),
            pre + "attn_proj_weight": (c, c), pre + "attn_proj_bias": (c,),
            pre + "ln2_gamma": (c,), pre + "ln2_beta": (c,),
            pre + "mlp1_weight": (inner, c), pre + "mlp1_bias": (inner,),
            pre + "mlp2_weight": (c, inner), pre + "mlp2_bias": (c,)})
    return p, {}


def init(cfg, key):
    """Seeded float32 weights as GPT-2 initializes them: normal of
    deviation ``initializer_range`` for matrices and embeddings, the
    residual projections scaled down by sqrt(2 n_layer), gamma 1, beta
    and bias 0."""
    std = cfg["initializer_range"]
    pshapes, _ = param_shapes(cfg)
    params = {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        if name.endswith("_weight"):
            s = std
            if name.endswith(("attn_proj_weight", "mlp2_weight")):
                s = std / (2.0 * cfg["n_layer"]) ** 0.5
            params[name] = s * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        elif name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        else:
            params[name] = jnp.zeros(shape, jnp.float32)
    return params, {}


def _dense(x, w, b, cast):
    if cast is not None:
        x, w = cast(x), cast(w)
    return jnp.dot(x, w.T, precision=HI) + b


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * g + b


def _attention(q, k, v, cast):
    """Causal attention over (B, T, H, D), scores in full."""
    t, d = q.shape[1], q.shape[3]
    if cast is not None:
        q, k = cast(q), cast(k)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / (d ** 0.5)
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if cast is not None:
        p, v = cast(p), cast(v)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)


def _block(x, params, pre, heads, cast):
    b, t, c = x.shape
    p = lambda n: params[pre + n]                           # noqa: E731
    h = _ln(x, p("ln1_gamma"), p("ln1_beta"))
    qkv = _dense(h, p("attn_qkv_weight"), p("attn_qkv_bias"), cast)
    qkv = qkv.reshape(b, t, 3, heads, c // heads)
    a = _attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], cast)
    x = x + _dense(a.reshape(b, t, c), p("attn_proj_weight"),
                   p("attn_proj_bias"), cast)
    h = _ln(x, p("ln2_gamma"), p("ln2_beta"))
    h = jax.nn.gelu(_dense(h, p("mlp1_weight"), p("mlp1_bias"), cast),
                    approximate=True)
    return x + _dense(h, p("mlp2_weight"), p("mlp2_bias"), cast)


def loss(cfg, params, aux, data, label, cast=None):
    """Mean next-token cross-entropy over every position of the batch,
    and the (empty) auxiliary state.  ``data`` and ``label`` (N, T)
    int32."""
    t = data.shape[1]
    x = params["tok_embed_weight"][data] + params["pos_embed_weight"][:t]
    for i in range(cfg["n_layer"]):
        x = jax.checkpoint(functools.partial(
            _block, pre="l%d_" % i, heads=cfg["n_head"], cast=cast))(
                x, params)
    x = _ln(x, params["ln_f_gamma"], params["ln_f_beta"])
    logits = _dense(x, params["head_weight"], params["head_bias"], cast)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, label[..., None], axis=-1)
    return jnp.mean(nll), {}


# ----------------------------------------------------------------------
# operations and bytes, from shapes
def costs(cfg, batch):
    """What the algorithm needs for one step of ``batch`` sequences.

    ``matmul``: the dense products (qkv, proj, mlp1, mlp2 of every
    block, and the head), forward and backward, two operations a
    multiply-add, and the bytes they must move at ``act_bytes`` an
    element.  ``attention``: causal attention forward and backward at
    (batch, T, heads, head): only the lower triangle counted, so T*T/2
    products for each of QK^T and PV forward and their four backward
    products, nothing recomputed; bytes are q, k, v, o and their
    gradients read or written once.  ``model_flops`` is the two summed.
    """
    act = cfg.get("act_bytes", 2)
    c, v, t = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    inner, layers, heads = cfg["n_inner"], cfg["n_layer"], cfg["n_head"]
    rows = batch * t
    mm_flops = 0
    mm_bytes = 0
    by_layer = {}
    dense = [("l%d_%s" % (i, n), a, b) for i in range(layers)
             for n, a, b in (("attn_qkv", c, 3 * c), ("attn_proj", c, c),
                             ("mlp1", c, inner), ("mlp2", inner, c))]
    for name, fan_in, fan_out in dense + [("head", c, v)]:
        by_layer[name] = 3 * 2 * rows * fan_in * fan_out
        mm_flops += by_layer[name]
        mm_bytes += act * 3 * (rows * fan_in + fan_in * fan_out
                               + rows * fan_out)
    d = c // heads
    # forward: QK^T and PV over the causal half; backward: dV, dP, dQ, dK
    per_layer = (2 + 4) * 2 * batch * heads * (t * t // 2) * d
    by_layer.update({"l%d_attn" % i: per_layer for i in range(layers)})
    at_flops = layers * per_layer
    at_bytes = layers * act * 8 * batch * t * c
    return {"model_flops": mm_flops + at_flops, "by_layer": by_layer,
            "matmul": {"flops": mm_flops, "bytes": mm_bytes},
            "attention": {"flops": at_flops, "bytes": at_bytes}}
