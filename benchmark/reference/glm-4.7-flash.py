"""Plain reference for the ``glm-4.7-flash`` configuration.

GLM-4.7-Flash (``zai-org/GLM-4.7-Flash`` ``config.json``, ``model_type``
``glm4_moe_lite``) as one chip of an 8-chip layer holds it: pre-norm
blocks x + Attn(RMSNorm(x)), x + FFN(RMSNorm(x)); latent attention
(MLA); the first block's feed-forward dense, the others' a shared expert
plus the routed experts this chip holds; a final RMSNorm and an untied
head over the vocabulary share; one multi-token-prediction module
(arXiv:2412.19437 sec. 2.2).  The equations, with d the hidden size and
H the heads:

* RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.
* Attention: c_q = RMSNorm(x W_qa); q = c_q W_qb -> (H, nope + rope).
  [c_kv | k_pe] = x W_kva; c_kv <- RMSNorm(c_kv); c_kv W_kvb ->
  (H, nope + v) = [k_nope | v].  Rotary embedding (theta, rotate-half)
  on q's last ``rope`` dims and on k_pe, one vector a position shared by
  all heads.  k = [k_nope | k_pe]; scale (nope + rope)^-1/2; causal
  softmax; (H x v) W_o.  No bias anywhere.
* F(x) = (silu(x W_gate) * x W_up) W_down.
* Expert layer: s = sigmoid(x W_r) over all the published experts; the
  chosen are the top k of s + b (lower index first among equals); w_e =
  scaling * s_e / sum of s over the chosen; y = F_shared(x) + sum over
  chosen e that this chip holds of w_e F_e(x).  Nothing is dropped and
  nothing stands in for the absent experts.
* Loss: cross-entropy of the next token, plus lambda times the module's:
  h'_i = W_eh [RMSNorm(h_i) | RMSNorm(Emb(t_{i+1}))], h_i the trunk's
  state before its final norm; one more expert block; a norm of its own;
  the same embedding and head; the token after next.  Both are sums over
  positions divided by the number of positions (the paper's 1/T), the
  last position of a row carrying no module term.

Departures from the published model, the configuration's ``assumed``:
lambda; h_i before the final norm; the order of the concatenation; the
rotate-half pairing; b seeded non-zero and held fixed; the initial
weights; momentum SGD.

Straightforward ``jax.numpy`` in float32: attention materializes its
scores, the experts are a loop over the held experts with a mask, every
expert computing every row.  It imports nothing of the program under
test: parameter names are the program symbol's public names.
``jax.checkpoint`` around each block only bounds the memory of the
backward pass.  ``cast`` is the hook of the lower-precision control,
applied to both operands of every matrix product (router, experts and
attention's two included); the reference itself passes ``None``.

The auxiliary state is b, which passes through, and the count of
entries the router sent to each expert, which is of the rows this call
saw (of the last block of rows where the harness calls it in blocks) and
is compared with nothing.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _sizes(cfg):
    return dict(
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        nope=cfg["qk_nope_head_dim"], rope=cfg["qk_rope_head_dim"],
        vdim=cfg["v_head_dim"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], dense=cfg["intermediate_size"],
        moe=cfg["moe_intermediate_size"], held=cfg["n_routed_experts"],
        experts=cfg["published"]["n_routed_experts"],
        first=cfg["deployment"]["first_expert"],
        top_k=cfg["num_experts_per_tok"], vocab=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        mtp=cfg["num_nextn_predict_layers"])


def _block_shapes(z, pre, dense):
    d, h = z["d"], z["heads"]
    p = {pre + "norm1_gamma": (d,), pre + "norm2_gamma": (d,),
         pre + "attn_qa_weight": (z["q_rank"], d),
         pre + "attn_qa_norm_gamma": (z["q_rank"],),
         pre + "attn_qb_weight": (h * (z["nope"] + z["rope"]), z["q_rank"]),
         pre + "attn_kva_weight": (z["kv_rank"] + z["rope"], d),
         pre + "attn_kva_norm_gamma": (z["kv_rank"],),
         pre + "attn_kvb_weight": (h * (z["nope"] + z["vdim"]),
                                   z["kv_rank"]),
         pre + "attn_o_weight": (d, h * z["vdim"])}
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
    if z["mtp"] not in (0, 1):
        raise ValueError("num_nextn_predict_layers is 0 or 1")
    p = {"tok_embed_weight": (z["vocab"], z["d"]), "norm_gamma": (z["d"],),
         "head_weight": (z["vocab"], z["d"])}
    aux = {}
    blocks = [("l%d_" % i, i < z["first_dense"]) for i in range(z["layers"])]
    if z["mtp"]:
        blocks.append(("mtp_", False))
        p.update({"mtp_hnorm_gamma": (z["d"],), "mtp_enorm_gamma": (z["d"],),
                  "mtp_eh_proj_weight": (z["d"], 2 * z["d"]),
                  "mtp_norm_gamma": (z["d"],)})
    for pre, dense in blocks:
        bp, ba = _block_shapes(z, pre, dense)
        p.update(bp)
        aux.update(ba)
    return p, aux


RESIDUAL = ("attn_o_weight", "mlp_down_weight", "moe_shared_down_weight",
            "moe_experts_down_weight")


def init(cfg, key):
    """Seeded float32 weights: normal of deviation ``initializer_range``,
    the projections into the residual stream scaled down by
    sqrt(2 num_hidden_layers) as GPT-2 does; gamma 1.  The selection
    bias b normal of deviation ``router_bias_std`` at values bfloat16
    holds exactly (the program keeps auxiliary state at its compute
    type inside a step, so b stays what it was); the counts 0."""
    std = cfg["initializer_range"]
    pshapes, ashapes = param_shapes(cfg)
    params, aux = {}, {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        if name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
            continue
        s = std / (2.0 * cfg["num_hidden_layers"]) ** 0.5 \
            if name.endswith(RESIDUAL) else std
        params[name] = s * jax.random.normal(
            jax.random.fold_in(key, i), shape, jnp.float32)
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


def _rotary(x, theta):
    """Rotate-half rotary embedding over all of the last axis; x
    (B, T, heads, n), position along axis 1."""
    n = x.shape[-1]
    inv = theta ** (-jnp.arange(n // 2, dtype=jnp.float32) * 2.0 / n)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : n // 2], x[..., n // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, p, z, cfg, cast):
    b, t, _ = x.shape
    h, nope, rope, vdim = z["heads"], z["nope"], z["rope"], z["vdim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    cq = _rms(_mm(x, p("attn_qa_weight"), cast), p("attn_qa_norm_gamma"),
              eps)
    q = _mm(cq, p("attn_qb_weight"), cast).reshape(b, t, h, nope + rope)
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
    return _mm(o.reshape(b, t, h * vdim), p("attn_o_weight"), cast)


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
    row, the mask keeps its own.  The loop is a ``lax.scan`` so that
    its body compiles once, and the body is recomputed on the way back
    so that no expert's activations are kept for it."""
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
    idx, w = route(scores, bias, z["top_k"], cfg["routed_scaling_factor"])
    y = _ffn(rows, p("moe_shared_gate_weight"), p("moe_shared_up_weight"),
             p("moe_shared_down_weight"), cast)
    y = y + routed_part(rows, idx, w, p("moe_experts_gate_weight"),
                        p("moe_experts_up_weight"),
                        p("moe_experts_down_weight"), z["first"], cast)
    count = jnp.sum(idx.reshape(-1)[:, None] == jnp.arange(z["experts"]),
                    axis=0).astype(jnp.float32)
    return y.reshape(x.shape), count


def _block(x, params, bias, pre, dense, z, cfg, cast):
    """One block on (B, T, d); returns (x, the experts' count or None)."""
    p = lambda n: params[pre + n]                           # noqa: E731
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms(x, p("norm1_gamma"), eps), p, z, cfg, cast)
    h = _rms(x, p("norm2_gamma"), eps)
    if dense:
        return x + _ffn(h, p("mlp_gate_weight"), p("mlp_up_weight"),
                        p("mlp_down_weight"), cast), None
    y, count = _expert_layer(h, p, bias, z, cfg, cast)
    return x + y, count


def _nll(x, gamma, params, target, cfg, cast):
    """-log p[target] at every position, (B, T)."""
    logits = _mm(_rms(x, gamma, cfg["rms_norm_eps"]), params["head_weight"],
                 cast)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]


def loss(cfg, params, aux, data, label, cast=None):
    """(main + lambda x module, new auxiliary state).  ``data`` and
    ``label`` (N, T) int32, ``label`` the next tokens."""
    z = _sizes(cfg)
    new_aux = dict(aux)

    def run(x, pre, dense):
        bias = None if dense else aux[pre + "moe_router_bias"]
        x, count = jax.checkpoint(functools.partial(
            _block, pre=pre, dense=dense, z=z, cfg=cfg, cast=cast))(
                x, params, bias)
        if count is not None:
            new_aux[pre + "moe_experts_count"] = count
        return x

    x = params["tok_embed_weight"][data]
    for i in range(z["layers"]):
        x = run(x, "l%d_" % i, i < z["first_dense"])
    positions = data.shape[0] * data.shape[1]
    total = jnp.sum(_nll(x, params["norm_gamma"], params, label, cfg,
                         cast)) / positions
    if z["mtp"]:
        eps = cfg["rms_norm_eps"]
        joined = jnp.concatenate(
            [_rms(x, params["mtp_hnorm_gamma"], eps),
             _rms(params["tok_embed_weight"][label],
                  params["mtp_enorm_gamma"], eps)], axis=-1)
        h = run(_mm(joined, params["mtp_eh_proj_weight"], cast), "mtp_",
                False)
        # position i predicts the label at i + 1; the last has none
        nll = _nll(h[:, :-1], params["mtp_norm_gamma"], params,
                   label[:, 1:], cfg, cast)
        total = total + cfg["mtp_loss_weight"] * jnp.sum(nll) / positions
    return total, new_aux


# ----------------------------------------------------------------------
# operations and bytes, from shapes
def costs(cfg, batch):
    """What the algorithm needs for one step of ``batch`` rows, forward
    and backward (three times the forward's products), two operations a
    multiply-add, ``act_bytes`` an element; nothing the chip does not do
    is counted: no absent expert, no row of the full vocabulary.

    ``matmul``: what the trace files under convolution/dot outside the
    attention scope: the latent attention's five projections, the dense
    and shared feed-forwards, the router, ``eh_proj`` and both passes of
    the head.  The experts' grouped products are NOT in it: the program
    runs them as ``lax.ragged_dot``, which the TPU's compiler lowers to
    kernels of its own (``ragged-dot-none``: a custom call, not a
    convolution, and stripped of the node's scope; first trace, PR 30).
    ``experts``: the three grouped products of every expert layer at the
    expected number of entries, rows x k x held / published experts
    (uniform routing), each expert's weights read once a pass.
    ``attention``: causal attention at (batch, T, heads, nope + rope)
    in every block, the module's included: the lower triangle only,
    forward QK^T and PV and their four backward products; bytes q, k, v,
    o and their gradients once.  ``model_flops`` is the three summed.
    """
    z = _sizes(cfg)
    act = cfg.get("act_bytes", 2)
    t = cfg["input"]["seq_len"]
    rows = batch * t
    d, h = z["d"], z["heads"]
    by_layer, mm_flops, mm_bytes = {}, 0, 0

    def dense(name, fan_in, fan_out):
        nonlocal mm_flops, mm_bytes
        by_layer[name] = 3 * 2 * rows * fan_in * fan_out
        mm_flops += by_layer[name]
        mm_bytes += act * 3 * (rows * fan_in + fan_in * fan_out
                               + rows * fan_out)

    blocks = [("l%d_" % i, i < z["first_dense"]) for i in range(z["layers"])]
    if z["mtp"]:
        blocks.append(("mtp_", False))
        dense("mtp_eh_proj", 2 * d, d)
        dense("mtp_head", d, z["vocab"])
    dense("head", d, z["vocab"])
    entries = rows * z["top_k"] * z["held"] / z["experts"]
    ex_flops = ex_bytes = at_flops = at_bytes = 0
    for pre, is_dense in blocks:
        dense(pre + "attn_qa", d, z["q_rank"])
        dense(pre + "attn_qb", z["q_rank"], h * (z["nope"] + z["rope"]))
        dense(pre + "attn_kva", d, z["kv_rank"] + z["rope"])
        dense(pre + "attn_kvb", z["kv_rank"], h * (z["nope"] + z["vdim"]))
        dense(pre + "attn_o", h * z["vdim"], d)
        hd = z["nope"] + z["rope"]
        by_layer[pre + "attn"] = (2 + 4) * 2 * batch * h * (t * t // 2) * hd
        at_flops += by_layer[pre + "attn"]
        at_bytes += act * 8 * rows * h * hd
        if is_dense:
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
    return {"model_flops": mm_flops + ex_flops + at_flops,
            "by_layer": by_layer,
            "matmul": {"flops": mm_flops, "bytes": mm_bytes},
            "experts": {"flops": ex_flops, "bytes": ex_bytes},
            "attention": {"flops": at_flops, "bytes": at_bytes}}
