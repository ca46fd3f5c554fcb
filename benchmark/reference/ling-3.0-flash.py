"""Plain reference for the ``ling-3.0-flash`` configuration.

Ling-3.0-flash (``inclusionAI/Ling-3.0-flash`` ``config.json``,
``model_type`` ``bailing_hybrid``) as one chip of the 64 that share each
layer holds it: pre-norm blocks x + Mixer(RMSNorm(x)), x +
FFN(RMSNorm(x)); of every ``layer_group_size`` published layers the last
mixes by latent attention (MLA), the others by Kimi delta attention
(KDA, arXiv:2510.26692 sec. 3); the first ``first_k_dense_replace``
published layers have a dense feed-forward, the others a shared expert
plus the routed experts this chip holds; a final RMSNorm and an untied
head over the vocabulary share.  With x the block's normed input and H
the heads held:

* RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.
* KDA: u = SiLU(conv(x W_u)) for u in q, k, v, the convolution depthwise
  and causal with ``short_conv_kernel_size`` taps, y_t = sum_j w[:, j]
  u_{t-3+j}, zero before a row's start; a head's q and k divided by
  their L2 norms (x rsqrt(sum x^2 + 1e-6)), q times d_k^-1/2; the log
  decay a channel g_t = lower_bound * sigmoid(exp(A_log_h) * (x W_f +
  dt_bias)); beta_t = sigmoid(x W_beta), one a head.  The state S (d_k x
  d_v, zero at a row's start) goes **token by token**:
  S' = Diag(exp(g_t)) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
  o_t = S^T q_t.  out = (RMSNorm_dv(o_t) * sigmoid(x W_g)) W_o.
* MLA: q = x W_q -> (H, nope + rope), no bottleneck; [c | k_pe] = x
  W_kva, c <- RMSNorm(c); c W_kvb -> (H, nope + v) = [k_nope | v].
  Rotary embedding (theta, interleaved: dim 2i pairs with 2i + 1) on q's
  last ``rope`` dims and on k_pe, one vector a position shared by the
  heads.  Causal softmax at scale (nope + rope)^-1/2;
  out = (attn * sigmoid(x W_gate)) W_o, W_gate one scalar a head.
* F(x) = (silu(x W_gate) * x W_up) W_down.
* Expert layer: s = sigmoid(x W_r) over all published experts; s' = s +
  b; the experts lie in ``n_group`` runs, a group's score is the sum of
  its two largest s', the ``topk_group`` best groups stay; among their
  experts the ``num_experts_per_tok`` largest s' are chosen (the lower
  index among equals, for groups and experts alike); w_e = scaling * s_e
  / sum of s over the chosen; y = F_shared(x) + sum over chosen e held
  here of w_e F_e(x).  Nothing is dropped and nothing stands in for the
  absent experts, heads or rows.
* Loss: mean next-token cross-entropy over the held rows of the
  vocabulary.  The multi-token-prediction layer is left out: the
  config's ``mtp_loss_scaling_factor`` is 0.

Straightforward ``jax.numpy`` in float32, every product at the highest
precision: the recurrence is a ``lax.scan`` over positions (checkpointed
in blocks of 64 so that the way back fits), attention materializes its
scores, the experts are a loop over the held experts with a mask.  It
imports nothing of the program under test: parameter names are the
program symbol's public names.  ``cast`` is the hook of the
lower-precision control, applied to both operands of every matrix
product: the projections, the router, the experts, attention's two, and
q, k and v where they enter the recurrence (whose state stays float32).

The auxiliary state is b, which passes through, and the count of entries
the router sent to each expert, compared with nothing.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
SCAN_BLOCK = 64


def _sizes(cfg):
    dep = cfg["deployment"]
    kept = dep["layers_kept"]
    assert len(kept) == cfg["num_hidden_layers"]
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        dk=cfg["head_dim"], taps=cfg["short_conv_kernel_size"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vdim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        dense=cfg["intermediate_size"], moe=cfg["moe_intermediate_size"],
        held=cfg["num_experts"],
        experts=cfg["published"]["num_experts"],
        first=dep["first_expert"], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        vocab=cfg["vocab_size"], layers=len(kept),
        kinds=["mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"
               for i in kept],
        is_dense=[i < cfg["first_k_dense_replace"] for i in kept])


def _block_shapes(z, pre, kind, dense):
    d, h, dk = z["d"], z["heads"], z["dk"]
    p = {pre + "norm1_gamma": (d,), pre + "norm2_gamma": (d,)}
    if kind == "kda":
        for n in ("q", "k", "v"):
            p[pre + "kda_%s_weight" % n] = (h * dk, d)
            p[pre + "kda_%s_conv_weight" % n] = (h * dk, z["taps"])
        p.update({pre + "kda_f_weight": (h * dk, d),
                  pre + "kda_g_weight": (h * dk, d),
                  pre + "kda_beta_weight": (h, d),
                  pre + "kda_A_log": (h,), pre + "kda_dt_bias": (h * dk,),
                  pre + "kda_o_norm_gamma": (dk,),
                  pre + "kda_o_weight": (d, h * dk)})
    else:
        p.update({pre + "attn_q_weight": (h * (z["nope"] + z["rope"]), d),
                  pre + "attn_kva_weight": (z["kv_rank"] + z["rope"], d),
                  pre + "attn_kva_norm_gamma": (z["kv_rank"],),
                  pre + "attn_kvb_weight": (h * (z["nope"] + z["vdim"]),
                                            z["kv_rank"]),
                  pre + "attn_gate_weight": (h, d),
                  pre + "attn_o_weight": (d, h * z["vdim"])})
    aux = {}
    if dense:
        p.update({pre + "mlp_gate_weight": (z["dense"], d),
                  pre + "mlp_up_weight": (z["dense"], d),
                  pre + "mlp_down_weight": (d, z["dense"])})
    else:
        g, m = z["held"], z["moe"]
        p.update({pre + "moe_router_weight": (z["experts"], d),
                  pre + "moe_shared_gate_weight": (m, d),
                  pre + "moe_shared_up_weight": (m, d),
                  pre + "moe_shared_down_weight": (d, m),
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


RESIDUAL = ("attn_o_weight", "kda_o_weight", "mlp_down_weight",
            "moe_shared_down_weight", "moe_experts_down_weight")


def init(cfg, key):
    """Seeded float32 weights: normal of deviation ``initializer_range``,
    the projections into the residual stream scaled down by
    sqrt(2 num_hidden_layers); gamma 1.  What the recurrence adds, each
    with its reason under the configuration's ``assumed``: the taps
    uniform in +-1/2 (a convolution's usual start at 4 taps); exp(A_log)
    uniform in [1, 4]; dt_bias such that at x W_f = 0 a channel decays
    by exp(-r) a position, r log-uniform from 1/512 to 1/2, so that the
    state carries across many chunks on some channels and is gone within
    one on others.  A_log, dt_bias and b (normal of deviation
    ``router_bias_std``) at values bfloat16 holds exactly, so that the
    program's bfloat16 copies start as what they are; the counts 0."""
    std = cfg["initializer_range"]
    pshapes, ashapes = param_shapes(cfg)
    bound = -float(cfg["kda_lower_bound"])
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    params, aux = {}, {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_conv_weight"):
            params[name] = jax.random.uniform(k, shape, jnp.float32,
                                              -0.5, 0.5)
        elif name.endswith("_A_log"):
            params[name] = bf16(jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 4.0)))
        elif name.endswith("_dt_bias"):
            continue                       # needs its A_log: below
        else:
            s = std / (2.0 * cfg["num_hidden_layers"]) ** 0.5 \
                if name.endswith(RESIDUAL) else std
            params[name] = s * jax.random.normal(k, shape, jnp.float32)
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        if not name.endswith("_dt_bias"):
            continue
        a_log = params[name[:-len("dt_bias")] + "A_log"]
        r = jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, 50000 + i), shape, jnp.float32,
            jnp.log(1.0 / 512), jnp.log(0.5)))
        logit = jnp.log(r / (bound - r))
        params[name] = bf16(logit.reshape(a_log.shape[0], -1)
                            / jnp.exp(a_log)[:, None]).reshape(shape)
    for i, (name, shape) in enumerate(sorted(ashapes.items())):
        if name.endswith("_bias"):
            b = cfg["router_bias_std"] * jax.random.normal(
                jax.random.fold_in(key, 100000 + i), shape, jnp.float32)
            aux[name] = bf16(b)
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


def delta_rule(q, k, v, g, beta):
    """The recurrence, one position at a time: q, k, g (B, T, H, dk), v
    (B, T, H, dv), beta (B, T, H) -> o (B, T, H, dv).  Blocks of
    ``SCAN_BLOCK`` positions are recomputed on the way back."""
    b, t, h, dk = q.shape

    def token(S, xs):
        q, k, v, g, be = xs
        S = jnp.exp(g)[..., None] * S
        u = v - jnp.einsum("bhkv,bhk->bhv", S, k, precision=HI)
        S = S + be[..., None, None] * k[..., None] * u[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q, precision=HI)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(token, S, xs)

    n = -(-t // SCAN_BLOCK)
    pad = n * SCAN_BLOCK - t     # g = 0, beta = 0: the state passes through

    def blocks(x):
        x = jnp.pad(jnp.moveaxis(x, 1, 0), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n, SCAN_BLOCK) + x.shape[1:])

    S0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(block, S0, tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((n * SCAN_BLOCK,) + o.shape[2:])[:t], 0, 1)


def _kda(x, p, z, cfg, cast):
    b, t, _ = x.shape
    h, dk = p("kda_A_log").shape[0], z["dk"]

    def branch(n):
        u = _conv(_mm(x, p("kda_%s_weight" % n), cast),
                  p("kda_%s_conv_weight" % n))
        return jax.nn.silu(u).reshape(b, t, h, dk)

    def unit(u):
        return u * lax.rsqrt(jnp.sum(jnp.square(u), -1, keepdims=True)
                             + 1e-6)

    q, k, v = unit(branch("q")) * dk ** -0.5, unit(branch("k")), branch("v")
    f = _mm(x, p("kda_f_weight"), cast).reshape(b, t, h, dk)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p("kda_A_log"))[:, None]
        * (f + p("kda_dt_bias").reshape(h, dk)))
    beta = jax.nn.sigmoid(_mm(x, p("kda_beta_weight"), cast))
    if cast is not None:
        q, k, v = cast(q), cast(k), cast(v)
    o = _rms(delta_rule(q, k, v, g, beta), p("kda_o_norm_gamma"),
             cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_mm(x, p("kda_g_weight"), cast))
    return _mm(o.reshape(b, t, h * dk) * gate, p("kda_o_weight"), cast)


def _rotary(x, theta):
    """Interleaved rotary embedding over all of the last axis; x
    (B, T, heads, n), position along axis 1; dim 2i pairs with 2i + 1."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(n // 2, dtype=jnp.float32) * 2.0 / n)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    pairs = x.reshape(x.shape[:-1] + (n // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _mla(x, p, z, cfg, cast):
    b, t, _ = x.shape
    nope, rope, vdim = z["nope"], z["rope"], z["vdim"]
    h = p("attn_gate_weight").shape[0]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = _mm(x, p("attn_q_weight"), cast).reshape(b, t, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)], -1)
    kva = _mm(x, p("attn_kva_weight"), cast)
    ckv = _rms(kva[..., : z["kv_rank"]], p("attn_kva_norm_gamma"), eps)
    k_pe = _rotary(kva[..., z["kv_rank"]:].reshape(b, t, 1, rope), theta)
    kv = _mm(ckv, p("attn_kvb_weight"), cast).reshape(b, t, h, nope + vdim)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, t, h, rope))], -1)
    v = kv[..., nope:]
    if cast is not None:
        q, k = cast(q), cast(k)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) \
        * (nope + rope) ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if cast is not None:
        pr, v = cast(pr), cast(v)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HI)
    gate = jax.nn.sigmoid(_mm(x, p("attn_gate_weight"), cast))
    return _mm((o * gate[..., None]).reshape(b, t, h * vdim),
               p("attn_o_weight"), cast)


def _ffn(x, w_gate, w_up, w_down, cast):
    return _mm(jax.nn.silu(_mm(x, w_gate, cast)) * _mm(x, w_up, cast),
               w_down, cast)


def _largest(pick, n):
    """(.., n) indices of the n largest along the last axis, one round
    of argmax and mask at a time: the lower index among equals."""
    chosen = []
    for _ in range(n):
        e = jnp.argmax(pick, axis=-1)
        chosen.append(e)
        pick = jnp.where(jnp.arange(pick.shape[-1]) == e[..., None],
                         -jnp.inf, pick)
    return jnp.stack(chosen, axis=-1)


def route(scores, bias, top_k, scaling, n_group=1, topk_group=1):
    """The chosen experts (T, k) and their weights (T, k)."""
    pick = scores + bias
    if n_group > 1:
        tokens, experts = pick.shape
        per = pick.reshape(tokens, n_group, experts // n_group)
        best2 = jnp.take_along_axis(per, _largest(per, 2), axis=-1)
        groups = _largest(jnp.sum(best2, axis=-1), topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(n_group), axis=1)
        pick = jnp.where(jnp.repeat(kept, experts // n_group, axis=1),
                         pick, -jnp.inf)
    idx = _largest(pick, top_k)
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


def _expert_layer(x, p, bias, z, cfg, cast):
    rows = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(_mm(rows, p("moe_router_weight"), cast))
    idx, w = route(scores, bias, z["top_k"], cfg["routed_scaling_factor"],
                   z["n_group"], z["topk_group"])
    y = _ffn(rows, p("moe_shared_gate_weight"), p("moe_shared_up_weight"),
             p("moe_shared_down_weight"), cast)
    y = y + routed_part(rows, idx, w, p("moe_experts_gate_weight"),
                        p("moe_experts_up_weight"),
                        p("moe_experts_down_weight"), z["first"], cast)
    count = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(z["experts"]),
                    axis=0).astype(jnp.float32)
    return y.reshape(x.shape), count


def _block(x, params, bias, pre, kind, dense, z, cfg, cast):
    """One block on (B, T, d); returns (x, the experts' count or None)."""
    p = lambda n: params[pre + n]                           # noqa: E731
    eps = cfg["rms_norm_eps"]
    mixer = _kda if kind == "kda" else _mla
    x = x + mixer(_rms(x, p("norm1_gamma"), eps), p, z, cfg, cast)
    h = _rms(x, p("norm2_gamma"), eps)
    if dense:
        return x + _ffn(h, p("mlp_gate_weight"), p("mlp_up_weight"),
                        p("mlp_down_weight"), cast), None
    y, count = _expert_layer(h, p, bias, z, cfg, cast)
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
    logits = _mm(_rms(x, params["norm_gamma"], cfg["rms_norm_eps"]),
                 params["head_weight"], cast)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, label[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), new_aux


# ----------------------------------------------------------------------
# operations and bytes, from shapes
KDA_CHUNK = 64


def kda_rule_flops(chunk, dk, dv):
    """Operations of the chunked rule's forward for one chunk of one
    head, two a multiply-add, triangles counted as triangles: the two
    decayed products K K^T and Q K^T (chunk^2 dk each), the unit
    triangular system for the corrected values and keys (chunk^2 (dk +
    dv)), the products with the state (2 chunk dk dv each: the corrected
    values, the output, the state's update) and the output's product
    with the corrected values (chunk^2 dv)."""
    return chunk * chunk * (3 * dk + 2 * dv) + 6 * chunk * dk * dv


def costs(cfg, batch):
    """What the algorithm needs for one step of ``batch`` rows, forward
    and backward (three times the forward's products), two operations a
    multiply-add, ``act_bytes`` an element; nothing the chip does not do
    is counted: no absent expert, head or row of the vocabulary.

    ``matmul``: what the trace files under convolution/dot outside the
    attention scope: the mixers' projections, the dense and shared
    feed-forwards, the router, the head.  ``experts``: the three grouped
    products of every expert layer at the expected number of entries,
    rows x k x held / published experts, each expert's weights read once
    a pass.  ``attention``: causal softmax attention of the MLA layers
    at (batch, T, heads, nope + rope) against ``v_head_dim`` wide
    values, the lower triangle only: forward QK^T and PV and their four
    backward products; bytes q, k, v, o and their gradients once.
    ``kda``: the chunked gated delta rule at chunk 64 in every KDA
    layer, whatever implements it: :func:`kda_rule_flops` a chunk and
    head forward and twice that back; bytes q, k, v, g, beta and o once
    a pass and the float32 state once a chunk.  ``model_flops`` is the
    four summed.
    """
    z = _sizes(cfg)
    act = cfg.get("act_bytes", 2)
    t = cfg["input"]["seq_len"]
    rows = batch * t
    d, h, dk = z["d"], z["heads"], z["dk"]
    by_layer, mm_flops, mm_bytes = {}, 0, 0

    def dense(name, fan_in, fan_out):
        nonlocal mm_flops, mm_bytes
        by_layer[name] = 3 * 2 * rows * fan_in * fan_out
        mm_flops += by_layer[name]
        mm_bytes += act * 3 * (rows * fan_in + fan_in * fan_out
                               + rows * fan_out)

    dense("head", d, z["vocab"])
    entries = rows * z["top_k"] * z["held"] / z["experts"]
    ex_flops = ex_bytes = at_flops = at_bytes = kda_flops = kda_bytes = 0
    for i in range(z["layers"]):
        pre = "l%d_" % i
        if z["kinds"][i] == "kda":
            for n in ("q", "k", "v", "f", "g"):
                dense(pre + "kda_" + n, d, h * dk)
            dense(pre + "kda_beta", d, h)
            dense(pre + "kda_o", h * dk, d)
            chunks = batch * h * (t // KDA_CHUNK)
            by_layer[pre + "kda_core"] = 3 * chunks * kda_rule_flops(
                KDA_CHUNK, dk, dk)
            kda_flops += by_layer[pre + "kda_core"]
            kda_bytes += 3 * (act * rows * h * (5 * dk + 1)
                              + 4 * chunks * dk * dk)
        else:
            qk = z["nope"] + z["rope"]
            dense(pre + "attn_q", d, h * qk)
            dense(pre + "attn_kva", d, z["kv_rank"] + z["rope"])
            dense(pre + "attn_kvb", z["kv_rank"], h * (z["nope"] + z["vdim"]))
            dense(pre + "attn_gate", d, h)
            dense(pre + "attn_o", h * z["vdim"], d)
            by_layer[pre + "attn"] = 3 * 2 * batch * h * (t * t // 2) \
                * (qk + z["vdim"])
            at_flops += by_layer[pre + "attn"]
            at_bytes += act * 2 * rows * h * 2 * (qk + z["vdim"])
        if z["is_dense"][i]:
            for n, a, b in (("gate", d, z["dense"]), ("up", d, z["dense"]),
                            ("down", z["dense"], d)):
                dense(pre + "mlp_" + n, a, b)
            continue
        dense(pre + "moe_router", d, z["experts"])
        for n, a, b in (("gate", d, z["moe"]), ("up", d, z["moe"]),
                        ("down", z["moe"], d)):
            dense(pre + "moe_shared_" + n, a, b)
        by_layer[pre + "moe_experts"] = int(3 * 2 * entries * 3 * d
                                            * z["moe"])
        ex_flops += by_layer[pre + "moe_experts"]
        ex_bytes += int(act * 3 * (3 * z["held"] * d * z["moe"]
                                   + entries * (2 * d + 3 * z["moe"])))
    return {"model_flops": mm_flops + ex_flops + at_flops + kda_flops,
            "by_layer": by_layer,
            "matmul": {"flops": mm_flops, "bytes": mm_bytes},
            "experts": {"flops": ex_flops, "bytes": ex_bytes},
            "attention": {"flops": at_flops, "bytes": at_bytes},
            "kda": {"flops": kda_flops, "bytes": kda_bytes}}
