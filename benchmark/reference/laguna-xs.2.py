"""Plain reference for the ``laguna-xs.2`` configuration.

Laguna-XS.2 (``poolside/Laguna-XS.2`` ``config.json``, ``model_type``
``laguna``) as one chip holds it: its experts' share of the 16 chips that
share each layer, its rows' share of the 8 that share the vocabulary.
Pre-norm blocks x + Attn(RMSNorm(x)), x + FFN(RMSNorm(x)); a kept
published layer attends fully or in a sliding window as its
``layer_types`` entry says, with its own count of query heads
(``num_attention_heads_per_layer``); the layers that ``mlp_layer_types``
calls dense have a dense feed-forward, the others the routed experts this
chip holds and one shared expert; a final RMSNorm and an untied head over
the vocabulary share.  With u the block's normed input and H the layer's
query heads of n = ``head_dim``:

* RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.
* q = u W_q (H heads), k = u W_k and v = u W_v (``num_key_value_heads``
  heads each); query head j reads key/value head j // (H / H_kv).
* Rotary, rotate-half, on the first ``partial_rotary_factor`` x n dims of
  every q and k head (dim i pairs with dim i + r/2 of those r), by the
  layer kind's ``rope_parameters`` group; the other dims pass through.
  ``default``: the angle t theta^(-2i/r).  ``yarn`` (arXiv:2309.00071):
  the frequencies theta^(-2i/r) are blended between themselves and
  themselves over ``factor`` by a linear ramp in i from the dim that
  turns ``beta_fast`` times over ``original_max_position_embeddings``
  positions (floored) to the one that turns ``beta_slow`` times
  (ceiled), interpolated where the ramp is 1; cos and sin are times
  ``attention_factor``.
* softmax(q k^T / sqrt(n)) v where query t sees key s for s <= t, and on
  a sliding layer also t - s < ``sliding_window``.
* Gate: o_j <- sigmoid(u W_g)_j o_j, one scalar a head and position;
  then W_o.
* F(x) = (silu(x W_gate) * x W_up) W_down.
* Expert layer: s = sigmoid(x W_r) over all the published experts; the
  chosen are the top k of s (lower index first among equals: there is
  no selection bias); w_e = scaling * s_e / sum of s over the chosen;
  y = F_shared(x) + sum over chosen e that this chip holds of w_e
  F_e(x).  Nothing is dropped and nothing stands in for the absent
  experts.
* Loss: mean next-token cross-entropy over the held rows of the
  vocabulary.

Departures and readings, the configuration's ``assumed``: the gate's
form and place, the sigmoid router, the initial weights, momentum SGD.

Straightforward ``jax.numpy`` in float32, every product at the highest
precision.  Attention runs in blocks of ``QUERY_BLOCK`` queries, each
recomputed on the way back: a full layer's block against every key, a
sliding layer's against the ``QUERY_BLOCK + window`` keys before its
end, under an explicit mask of the window.  The experts are a loop over
the held experts with a mask, every expert computing every row.  It
imports nothing of the program under test: parameter names are the
program symbol's public names.  ``cast`` is the hook of the
lower-precision control, applied to both operands of every matrix
product (router, experts, gates and attention's two included); the
reference itself passes ``None``.

The auxiliary state is the router's selection bias, zero and passed
through, and the count of entries the router sent to each expert, which
is compared with nothing.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
QUERY_BLOCK = 512


def _sizes(cfg):
    dep = cfg["deployment"]
    kept = dep["layers_kept"]
    assert len(kept) == cfg["num_hidden_layers"]
    return dict(
        d=cfg["hidden_size"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], window=cfg["sliding_window"],
        dense=cfg["intermediate_size"], moe=cfg["moe_intermediate_size"],
        shared=cfg["shared_expert_intermediate_size"],
        held=cfg["num_experts"], experts=cfg["published"]["num_experts"],
        first=dep["first_expert"], top_k=cfg["num_experts_per_tok"],
        vocab=cfg["vocab_size"], layers=len(kept),
        kinds=[cfg["layer_types"][i] for i in kept],
        heads=[cfg["num_attention_heads_per_layer"][i] for i in kept],
        is_dense=[cfg["mlp_layer_types"][i] == "dense" for i in kept])


def _block_shapes(z, pre, h, dense):
    d, n = z["d"], z["head_dim"]
    p = {pre + "norm1_gamma": (d,), pre + "norm2_gamma": (d,),
         pre + "attn_q_weight": (h * n, d),
         pre + "attn_k_weight": (z["kv_heads"] * n, d),
         pre + "attn_v_weight": (z["kv_heads"] * n, d),
         pre + "attn_gate_weight": (h, d),
         pre + "attn_o_weight": (d, h * n)}
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
                  pre + "moe_experts_down_weight": (g, d, m),
                  pre + "moe_shared_gate_weight": (z["shared"], d),
                  pre + "moe_shared_up_weight": (z["shared"], d),
                  pre + "moe_shared_down_weight": (d, z["shared"])})
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
        bp, ba = _block_shapes(z, "l%d_" % i, z["heads"][i],
                               z["is_dense"][i])
        p.update(bp)
        aux.update(ba)
    return p, aux


RESIDUAL = ("attn_o_weight", "mlp_down_weight", "moe_experts_down_weight",
            "moe_shared_down_weight")


def init(cfg, key):
    """Seeded float32 weights: normal of deviation ``initializer_range``,
    the projections into the residual stream scaled down by
    sqrt(2 num_hidden_layers) as GPT-2 does; gamma 1; the selection
    bias and the counts 0."""
    std = cfg["initializer_range"]
    pshapes, ashapes = param_shapes(cfg)
    params = {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        if name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
            continue
        s = std / (2.0 * cfg["num_hidden_layers"]) ** 0.5 \
            if name.endswith(RESIDUAL) else std
        params[name] = s * jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)
    aux = {name: jnp.zeros(shape, jnp.float32)
           for name, shape in ashapes.items()}
    return params, aux


# ----------------------------------------------------------------------
def _mm(x, w, cast):
    """x (.., k) times w (n, k) transposed."""
    if cast is not None:
        x, w = cast(x), cast(w)
    return jnp.dot(x, w.T, precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rope_frequencies(rope, r):
    """(inverse frequencies of the r/2 pairs, the factor on cos and sin)
    of one ``rope_parameters`` group over r rotated dims."""
    theta = float(rope["rope_theta"])
    freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    if rope.get("rope_type", "default") != "yarn":
        return freq, 1.0
    factor = float(rope["factor"])
    span = float(rope["original_max_position_embeddings"])

    def dim_turning(turns):     # the dim whose angle turns so many times
        return r * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))
    lo = max(math.floor(dim_turning(rope["beta_fast"])), 0)
    hi = min(math.ceil(dim_turning(rope["beta_slow"])), r - 1)
    hi = hi + 0.001 if hi == lo else hi
    interpolated = jnp.clip((jnp.arange(r // 2, dtype=jnp.float32) - lo)
                            / (hi - lo), 0.0, 1.0)
    freq = freq * (1.0 - interpolated) + freq / factor * interpolated
    return freq, float(rope["attention_factor"])


def _rotary(x, rope):
    """x (B, T, heads, n), position along axis 1."""
    n = x.shape[-1]
    r = int(n * rope.get("partial_rotary_factor", 1.0))
    freq, scale = rope_frequencies(rope, r)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos = scale * jnp.cos(ang)[:, None, :]
    sin = scale * jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention(q, k, v, window=0, cast=None, block=QUERY_BLOCK):
    """Causal softmax attention: q (B, T, H, n), k and v (B, T, H_kv,
    n); query head j reads key/value head j // (H / H_kv); with
    ``window`` query t sees keys t - window < s <= t alone.  Queries in
    blocks of ``block``: a block against every key, or under a window
    against the ``block + window`` keys that end with it, each block
    recomputed on the way back."""
    b, t, h, n = q.shape
    g = k.shape[2]
    block = min(block, t)
    if cast is not None:
        q, k, v = cast(q), cast(k), cast(v)
    qg = q.reshape(b, t // block, block, g, h // g, n)
    span = block + window if window else t
    if window:
        # keys from ``window`` before the first query: zeros, masked
        k = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))

    @jax.checkpoint
    def one(args):
        i, qb = args                              # qb (B, block, G, J, n)
        start = i * block if window else 0        # of the keys, padded
        kb = lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vb = lax.dynamic_slice_in_dim(v, start, span, axis=1)
        s = jnp.einsum("bqgjn,bkgn->bgjqk", qb, kb, precision=HI) * n ** -0.5
        t_q = i * block + jnp.arange(block)
        t_k = start - window + jnp.arange(span) if window else jnp.arange(t)
        live = t_k[None, :] <= t_q[:, None]
        if window:
            live &= (t_q[:, None] - t_k[None, :] < window) \
                & (t_k[None, :] >= 0)
        s = jnp.where(live, s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        if cast is not None:
            pr = cast(pr)
        return jnp.einsum("bgjqk,bkgn->bqgjn", pr, vb, precision=HI)

    out = lax.map(one, (jnp.arange(t // block), jnp.moveaxis(qg, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, n)


def _attention(x, p, z, cfg, cast, kind, h):
    b, t, _ = x.shape
    n, hkv = z["head_dim"], z["kv_heads"]
    rope = cfg["rope_parameters"][kind]

    def heads(name, count):
        return _mm(x, p("attn_%s_weight" % name), cast).reshape(b, t, count, n)

    q = _rotary(heads("q", h), rope)
    k = _rotary(heads("k", hkv), rope)
    window = z["window"] if kind == "sliding_attention" else 0
    o = attention(q, k, heads("v", hkv), window, cast)
    gate = jax.nn.sigmoid(_mm(x, p("attn_gate_weight"), cast))
    o = o * gate[..., None]
    return _mm(o.reshape(b, t, h * n), p("attn_o_weight"), cast)


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
    """The shared expert and the routed experts this chip holds, on
    (B, T, d); returns (their sum, the count of entries routed to each
    published expert)."""
    rows = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(_mm(rows, p("moe_router_weight"), cast))
    idx, w = route(scores, bias, z["top_k"], cfg["moe_routed_scaling_factor"])
    y = routed_part(rows, idx, w, p("moe_experts_gate_weight"),
                    p("moe_experts_up_weight"), p("moe_experts_down_weight"),
                    z["first"], cast)
    y = y + _ffn(rows, p("moe_shared_gate_weight"), p("moe_shared_up_weight"),
                 p("moe_shared_down_weight"), cast)
    count = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(z["experts"]),
                    axis=0).astype(jnp.float32)
    return y.reshape(x.shape), count


def _block(x, params, bias, pre, kind, h, dense, z, cfg, cast):
    """One block on (B, T, d); returns (x, the experts' count or None)."""
    p = lambda n: params[pre + n]                           # noqa: E731
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms(x, p("norm1_gamma"), eps), p, z, cfg, cast,
                       kind, h)
    u = _rms(x, p("norm2_gamma"), eps)
    if dense:
        return x + _ffn(u, p("mlp_gate_weight"), p("mlp_up_weight"),
                        p("mlp_down_weight"), cast), None
    y, count = expert_layer(u, p, bias, z, cfg, cast)
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
            _block, pre=pre, kind=z["kinds"][i], h=z["heads"][i],
            dense=dense, z=z, cfg=cfg, cast=cast))(x, params, bias)
        if count is not None:
            new_aux[pre + "moe_experts_count"] = count
    logits = _mm(_rms(x, params["norm_gamma"], cfg["rms_norm_eps"]),
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
    repetition of grouped keys and values, no pair of positions a mask
    leaves out.

    ``matmul``: what the trace files under convolution/dot outside the
    attention scopes: the projections, the gates' product, the dense
    feed-forward, the shared experts, the router, the head.
    ``experts``: the three grouped products of every expert layer at the
    expected number of entries, rows x k x held / published experts,
    each expert's weights read once a pass.  ``attention``: the full
    layers, the causal pairs t^2 / 2 a head, forward QK^T and PV and
    their four backward products; ``window``: the sliding layers the
    same way over their live pairs, t w - w (w - 1) / 2 a head.  Bytes
    of both: q, o and their gradients at H heads, k, v and theirs at
    H_kv heads, once each.  ``model_flops`` is the four summed.
    """
    z = _sizes(cfg)
    act = cfg.get("act_bytes", 2)
    t = cfg["input"]["seq_len"]
    rows = batch * t
    d, hkv, n, w = z["d"], z["kv_heads"], z["head_dim"], z["window"]
    by_layer, mm_flops, mm_bytes = {}, 0, 0

    def dense(name, fan_in, fan_out):
        nonlocal mm_flops, mm_bytes
        by_layer[name] = 3 * 2 * rows * fan_in * fan_out
        mm_flops += by_layer[name]
        mm_bytes += act * 3 * (rows * fan_in + fan_in * fan_out
                               + rows * fan_out)

    dense("head", d, z["vocab"])
    entries = rows * z["top_k"] * z["held"] / z["experts"]
    cores = {"attention": [0, 0], "window": [0, 0]}
    ex_flops = ex_bytes = 0
    for i in range(z["layers"]):
        pre, h = "l%d_" % i, z["heads"][i]
        for nm, width in (("q", h * n), ("k", hkv * n), ("v", hkv * n),
                          ("gate", h)):
            dense(pre + "attn_" + nm, d, width)
        dense(pre + "attn_o", h * n, d)
        sliding = z["kinds"][i] == "sliding_attention"
        live = t * w - w * (w - 1) // 2 if sliding and w < t else t * t // 2
        name = "window" if sliding else "attention"
        by_layer[pre + "attn"] = (2 + 4) * 2 * batch * h * live * n
        cores[name][0] += by_layer[pre + "attn"]
        cores[name][1] += act * 4 * rows * n * (h + hkv)
        if z["is_dense"][i]:
            for nm, a, b in (("gate", d, z["dense"]), ("up", d, z["dense"]),
                             ("down", z["dense"], d)):
                dense(pre + "mlp_" + nm, a, b)
            continue
        dense(pre + "moe_router", d, z["experts"])
        for nm, a, b in (("gate", d, z["shared"]), ("up", d, z["shared"]),
                         ("down", z["shared"], d)):
            dense(pre + "moe_shared_" + nm, a, b)
        by_layer[pre + "moe_experts"] = int(3 * 2 * entries * 3 * d
                                            * z["moe"])
        ex_flops += by_layer[pre + "moe_experts"]
        ex_bytes += int(act * 3 * (3 * z["held"] * d * z["moe"]
                                   + entries * (2 * d + 3 * z["moe"])))
    out = {"model_flops": mm_flops + ex_flops + cores["attention"][0]
           + cores["window"][0],
           "by_layer": by_layer,
           "matmul": {"flops": mm_flops, "bytes": mm_bytes},
           "experts": {"flops": ex_flops, "bytes": ex_bytes}}
    for name, (flops, nbytes) in cores.items():
        out[name] = {"flops": flops, "bytes": nbytes}
    return out
