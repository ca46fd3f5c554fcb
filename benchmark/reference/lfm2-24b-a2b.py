"""Plain reference for the ``lfm2-24b-a2b`` configuration.

LFM2-24B-A2B (``LiquidAI/LFM2-24B-A2B`` ``config.json``, ``model_type``
``lfm2_moe``) as one chip of the 8 that share each layer holds it:
pre-norm blocks x + Mixer(RMSNorm(x)), x + FFN(RMSNorm(x)); a published
layer mixes by a gated short convolution or by grouped-query attention
as its ``layer_types`` entry says; the first ``num_dense_layers`` kept
layers have a dense feed-forward, the others the routed experts this
chip holds and no shared expert; a final RMSNorm and an untied head over
the vocabulary share.  With u the block's normed input:

* RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.
* Conv mixer: [B | C | x~] = u W_in (d -> 3d, split in that order);
  y = B * x~; z_t = sum_j w[:, j] y_{t-L+1+j}, depthwise and causal with
  L = ``conv_L_cache`` taps, zero before a row's start, no bias;
  out = (C * z) W_out.  No activation.
* Attention mixer: q = u W_q (H heads of n = d / H), k = u W_k and
  v = u W_v (H_kv heads each); q and k each through an RMSNorm over a
  head's n dims with a gamma of their own, then rotate-half rotary
  embedding over all n dims (theta); causal softmax(q k^T / sqrt(n)) v,
  query head j reading key/value head j // (H / H_kv); then W_o.
* F(x) = (silu(x W_gate) * x W_up) W_down.
* Expert layer: s = sigmoid(x W_r) over all the published experts; the
  chosen are the top k of s + b (lower index first among equals); w_e =
  scaling * s_e / sum of s over the chosen; y = sum over chosen e that
  this chip holds of w_e F_e(x).  Nothing is dropped, nothing stands in
  for the absent experts, and there is no shared expert.
* Loss: mean next-token cross-entropy over the held rows of the
  vocabulary.

Departures from the family's modeling code, the configuration's
``assumed``: its 1e-6 in the renormalization's denominator is left out;
b seeded non-zero and held fixed; the initial weights; momentum SGD.

Straightforward ``jax.numpy`` in float32, every product at the highest
precision: attention in blocks of ``QUERY_BLOCK`` queries against every
key, each block recomputed on the way back, so that the scores of all
positions never live at once; the experts are a loop over the held
experts with a mask, every expert computing every row.  It imports
nothing of the program under test: parameter names are the program
symbol's public names.  ``cast`` is the hook of the lower-precision
control, applied to both operands of every matrix product (router,
experts and attention's two included) and to the convolution's operands;
the reference itself passes ``None``.

The auxiliary state is b, which passes through, and the count of entries
the router sent to each expert, which is compared with nothing.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 1024


def _sizes(cfg):
    dep = cfg["deployment"]
    kept = dep["layers_kept"]
    assert len(kept) == cfg["num_hidden_layers"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(
        d=d, heads=h, kv_heads=cfg["num_key_value_heads"], head_dim=d // h,
        taps=cfg["conv_L_cache"], dense=cfg["intermediate_size"],
        moe=cfg["moe_intermediate_size"], held=cfg["num_experts"],
        experts=cfg["published"]["num_experts"], first=dep["first_expert"],
        top_k=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"],
        layers=len(kept),
        kinds=["attention" if cfg["layer_types"][i] == "full_attention"
               else "conv" for i in kept],
        is_dense=[i < cfg["num_dense_layers"] for i in range(len(kept))])


def _block_shapes(z, pre, kind, dense):
    d, n = z["d"], z["head_dim"]
    p = {pre + "norm1_gamma": (d,), pre + "norm2_gamma": (d,)}
    if kind == "conv":
        p.update({pre + "sconv_in_weight": (3 * d, d),
                  pre + "sconv_taps_weight": (d, z["taps"]),
                  pre + "sconv_out_weight": (d, d)})
    else:
        p.update({pre + "attn_q_weight": (z["heads"] * n, d),
                  pre + "attn_q_norm_gamma": (n,),
                  pre + "attn_k_weight": (z["kv_heads"] * n, d),
                  pre + "attn_k_norm_gamma": (n,),
                  pre + "attn_v_weight": (z["kv_heads"] * n, d),
                  pre + "attn_o_weight": (d, z["heads"] * n)})
    aux = {}
    if dense:
        p.update({pre + "mlp_gate_weight": (z["dense"], d),
                  pre + "mlp_up_weight": (z["dense"], d),
                  pre + "mlp_down_weight": (d, z["dense"])})
    else:
        g, m = z["held"], z["moe"]
        p.update({pre + "moe_router_weight": (z["experts"], d),
                  pre + "moe_experts_gate_weight": (g, m, d),
                  pre + "moe_experts_up_weight": (g, m, d),
                  pre + "moe_experts_down_weight": (g, d, m)})
        aux = {pre + "moe_router_bias": (z["experts"],),
               pre + "moe_experts_count": (z["experts"],)}
    return p, aux


def param_shapes(cfg):
    """({parameter: shape}, {auxiliary state: shape})."""
    z = _sizes(cfg)
    p = {"tok_embed_weight": (z["vocab"], z["d"]), "norm_gamma": (z["d"],),
         "head_weight": (z["vocab"], z["d"])}
    aux = {}
    for i in range(z["layers"]):
        bp, ba = _block_shapes(z, "l%d_" % i, z["kinds"][i],
                               z["is_dense"][i])
        p.update(bp)
        aux.update(ba)
    return p, aux


RESIDUAL = ("attn_o_weight", "sconv_out_weight", "mlp_down_weight",
            "moe_experts_down_weight")


def init(cfg, key):
    """Seeded float32 weights: normal of deviation ``initializer_range``,
    the projections into the residual stream scaled down by
    sqrt(2 num_hidden_layers) as GPT-2 does; gamma 1; the convolution's
    taps uniform in +-1/sqrt(taps) (a depthwise convolution's usual
    start: one input channel a group).  The selection bias b normal of
    deviation ``router_bias_std`` at values bfloat16 holds exactly (the
    program keeps auxiliary state at its compute type inside a step, so
    b stays what it was); the counts 0."""
    std = cfg["initializer_range"]
    pshapes, ashapes = param_shapes(cfg)
    params, aux = {}, {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_taps_weight"):
            bound = shape[1] ** -0.5
            params[name] = jax.random.uniform(k, shape, jnp.float32,
                                              -bound, bound)
        else:
            s = std / (2.0 * cfg["num_hidden_layers"]) ** 0.5 \
                if name.endswith(RESIDUAL) else std
            params[name] = s * jax.random.normal(k, shape, jnp.float32)
    for i, (name, shape) in enumerate(sorted(ashapes.items())):
        if name.endswith("_bias"):
            b = cfg["router_bias_std"] * jax.random.normal(
                jax.random.fold_in(key, 100000 + i), shape, jnp.float32)
            aux[name] = b.astype(jnp.bfloat16).astype(jnp.float32)
        else:
            aux[name] = jnp.zeros(shape, jnp.float32)
    return params, aux


# ----------------------------------------------------------------------
def _mm(x, w, cast):
    """x (.., k) times w (n, k) transposed."""
    if cast is not None:
        x, w = cast(x), cast(w)
    return jnp.dot(x, w.T, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _conv(u, w):
    """Depthwise causal taps: u (B, T, C), w (C, taps)."""
    taps, t = w.shape[1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(w[:, j] * padded[:, j:j + t] for j in range(taps))


def _sconv(x, p, z, cfg, cast):
    d = z["d"]
    bcx = _mm(x, p("sconv_in_weight"), cast)
    b, c, xt = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    y, w = b * xt, p("sconv_taps_weight")
    if cast is not None:
        y, w = cast(y), cast(w)
    return _mm(c * _conv(y, w), p("sconv_out_weight"), cast)


def _rotary(x, theta):
    """Rotate-half rotary embedding over all of the last axis; x
    (B, T, heads, n), position along axis 1."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(n // 2, dtype=jnp.float32) * 2.0 / n)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : n // 2], x[..., n // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def grouped_attention(q, k, v, cast=None, block=QUERY_BLOCK):
    """Causal softmax attention: q (B, T, H, n), k and v (B, T, H_kv,
    n); query head j reads key/value head j // (H / H_kv).  Queries in
    blocks of ``block`` against every key, each block recomputed on the
    way back."""
    b, t, h, n = q.shape
    g = k.shape[2]
    block = min(block, t)
    if cast is not None:
        q, k, v = cast(q), cast(k), cast(v)
    qg = q.reshape(b, t // block, block, g, h // g, n)

    @jax.checkpoint
    def one(args):
        i, qb = args                              # qb (B, block, G, J, n)
        s = jnp.einsum("bqgjn,bkgn->bgjqk", qb, k, precision=HI) * n ** -0.5
        pos = i * block + jnp.arange(block)
        s = jnp.where(pos[:, None] >= jnp.arange(t)[None, :], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if cast is not None:
            pr = cast(pr)
        return jnp.einsum("bgjqk,bkgn->bqgjn", pr, v, precision=HI)

    out = lax.map(one, (jnp.arange(t // block), jnp.moveaxis(qg, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, n)


def _gqa(x, p, z, cfg, cast):
    b, t, _ = x.shape
    n, eps = z["head_dim"], cfg["norm_eps"]
    theta = float(cfg["rope_parameters"]["rope_theta"])

    def heads(name, count):
        return _mm(x, p("attn_%s_weight" % name), cast).reshape(b, t, count, n)

    q = _rotary(_rms(heads("q", z["heads"]), p("attn_q_norm_gamma"), eps),
                theta)
    k = _rotary(_rms(heads("k", z["kv_heads"]), p("attn_k_norm_gamma"), eps),
                theta)
    o = grouped_attention(q, k, heads("v", z["kv_heads"]), cast)
    return _mm(o.reshape(b, t, z["heads"] * n), p("attn_o_weight"), cast)


def _ffn(x, w_gate, w_up, w_down, cast):
    return _mm(jax.nn.silu(_mm(x, w_gate, cast)) * _mm(x, w_up, cast),
               w_down, cast)


def route(scores, bias, top_k, scaling):
    """The chosen experts (T, k) and their weights (T, k): ``top_k``
    rounds of taking the largest of score + bias, the lower index among
    equals, and masking it out."""
    pick = scores + bias
    chosen = []
    for _ in range(top_k):
        e = jnp.argmax(pick, axis=-1)
        chosen.append(e)
        pick = jnp.where(jnp.arange(pick.shape[-1]) == e[:, None],
                         -jnp.inf, pick)
    idx = jnp.stack(chosen, axis=-1)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scaling * w / jnp.sum(w, axis=-1, keepdims=True)


def routed_part(x, idx, w, w_gate, w_up, w_down, first, cast=None):
    """Sum over the held experts e = first + g of (the weight of e where
    a row chose it, else 0) times F_e(x): every expert computes every
    row, the mask keeps its own; a ``lax.scan`` whose body is recomputed
    on the way back."""
    @jax.checkpoint
    def one(y, expert):
        g, wg, wu, wd = expert
        mine = jnp.sum(jnp.where(idx == first + g, w, 0.0), axis=-1)
        return y + mine[:, None] * _ffn(x, wg, wu, wd, cast), None

    held = jnp.arange(w_gate.shape[0])
    return lax.scan(one, jnp.zeros_like(x), (held, w_gate, w_up, w_down))[0]


def expert_layer(x, p, bias, z, cfg, cast=None):
    """The routed experts this chip holds, on (B, T, d); returns (their
    part, the count of entries routed to each published expert)."""
    rows = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(_mm(rows, p("moe_router_weight"), cast))
    idx, w = route(scores, bias, z["top_k"], cfg["routed_scaling_factor"])
    y = routed_part(rows, idx, w, p("moe_experts_gate_weight"),
                    p("moe_experts_up_weight"), p("moe_experts_down_weight"),
                    z["first"], cast)
    count = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(z["experts"]),
                    axis=0).astype(jnp.float32)
    return y.reshape(x.shape), count


def _block(x, params, bias, pre, kind, dense, z, cfg, cast):
    """One block on (B, T, d); returns (x, the experts' count or None)."""
    p = lambda n: params[pre + n]                           # noqa: E731
    eps = cfg["norm_eps"]
    mixer = _sconv if kind == "conv" else _gqa
    x = x + mixer(_rms(x, p("norm1_gamma"), eps), p, z, cfg, cast)
    h = _rms(x, p("norm2_gamma"), eps)
    if dense:
        return x + _ffn(h, p("mlp_gate_weight"), p("mlp_up_weight"),
                        p("mlp_down_weight"), cast), None
    y, count = expert_layer(h, p, bias, z, cfg, cast)
    return x + y, count


def loss(cfg, params, aux, data, label, cast=None):
    """(mean next-token cross-entropy, new auxiliary state).  ``data``
    and ``label`` (N, T) int32, ``label`` the next tokens."""
    z = _sizes(cfg)
    new_aux = dict(aux)
    x = params["tok_embed_weight"][data]
    for i in range(z["layers"]):
        pre, dense = "l%d_" % i, z["is_dense"][i]
        bias = None if dense else aux[pre + "moe_router_bias"]
        x, count = jax.checkpoint(functools.partial(
            _block, pre=pre, kind=z["kinds"][i], dense=dense, z=z, cfg=cfg,
            cast=cast))(x, params, bias)
        if count is not None:
            new_aux[pre + "moe_experts_count"] = count
    logits = _mm(_rms(x, params["norm_gamma"], cfg["norm_eps"]),
                 params["head_weight"], cast)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, label[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), new_aux


# ----------------------------------------------------------------------
# operations and bytes, from shapes
def costs(cfg, batch):
    """What the algorithm needs for one step of ``batch`` rows, forward
    and backward (three times the forward's products), two operations a
    multiply-add, ``act_bytes`` an element; nothing the chip does not do
    is counted: no absent expert, no row of the full vocabulary, no
    repetition of grouped keys and values.

    ``matmul``: what the trace files under convolution/dot outside the
    attention scope: the mixers' projections, the dense feed-forward,
    the router, the head.  ``experts``: the three grouped products of
    every expert layer at the expected number of entries, rows x k x
    held / published experts, each expert's weights read once a pass.
    ``attention``: causal attention at (batch, T, H, n), the lower
    triangle only, forward QK^T and PV and their four backward products;
    bytes q, o and their gradients at H heads, k, v and theirs at H_kv
    heads, once each.  ``sconv``: the gated core of every convolution
    mixer (B * x~, the taps, C * z), what it needs at the least:
    forward reads B, C and x~ and writes the output (4 T d elements),
    backward reads those three and the output's gradient and writes the
    three gradients (7 T d); 2 L + 2 operations an element forward and
    twice that back.  ``model_flops`` is the four summed.
    """
    z = _sizes(cfg)
    act = cfg.get("act_bytes", 2)
    t = cfg["input"]["seq_len"]
    rows = batch * t
    d, h, h_kv, n = z["d"], z["heads"], z["kv_heads"], z["head_dim"]
    by_layer, mm_flops, mm_bytes = {}, 0, 0

    def dense(name, fan_in, fan_out):
        nonlocal mm_flops, mm_bytes
        by_layer[name] = 3 * 2 * rows * fan_in * fan_out
        mm_flops += by_layer[name]
        mm_bytes += act * 3 * (rows * fan_in + fan_in * fan_out
                               + rows * fan_out)

    dense("head", d, z["vocab"])
    entries = rows * z["top_k"] * z["held"] / z["experts"]
    ex_flops = ex_bytes = at_flops = at_bytes = sc_flops = sc_bytes = 0
    for i in range(z["layers"]):
        pre = "l%d_" % i
        if z["kinds"][i] == "conv":
            dense(pre + "sconv_in", d, 3 * d)
            dense(pre + "sconv_out", d, d)
            by_layer[pre + "sconv"] = 3 * (2 * z["taps"] + 2) * rows * d
            sc_flops += by_layer[pre + "sconv"]
            sc_bytes += act * (4 + 7) * rows * d
        else:
            dense(pre + "attn_q", d, h * n)
            dense(pre + "attn_k", d, h_kv * n)
            dense(pre + "attn_v", d, h_kv * n)
            dense(pre + "attn_o", h * n, d)
            by_layer[pre + "attn"] = (2 + 4) * 2 * batch * h * (t * t // 2) \
                * n
            at_flops += by_layer[pre + "attn"]
            at_bytes += act * 4 * rows * n * (h + h_kv)
        if z["is_dense"][i]:
            for nm, a, b in (("gate", d, z["dense"]), ("up", d, z["dense"]),
                             ("down", z["dense"], d)):
                dense(pre + "mlp_" + nm, a, b)
            continue
        dense(pre + "moe_router", d, z["experts"])
        by_layer[pre + "moe_experts"] = int(3 * 2 * entries * 3 * d
                                            * z["moe"])
        ex_flops += by_layer[pre + "moe_experts"]
        ex_bytes += int(act * 3 * (3 * z["held"] * d * z["moe"]
                                   + entries * (2 * d + 3 * z["moe"])))
    return {"model_flops": mm_flops + ex_flops + at_flops + sc_flops,
            "by_layer": by_layer,
            "matmul": {"flops": mm_flops, "bytes": mm_bytes},
            "experts": {"flops": ex_flops, "bytes": ex_bytes},
            "attention": {"flops": at_flops, "bytes": at_bytes},
            "sconv": {"flops": sc_flops, "bytes": sc_bytes}}
