"""Plain reference for the ``ouro-2.6b`` configuration.

Ouro-2.6B (``ByteDance/Ouro-2.6B`` ``config.json``, ``model_type``
``ouro``; the looped language model of arXiv:2510.25741): one stack of
N layers applied U = ``total_ut_steps`` times to its own output on one
set of weights; every pass ends in the output head, and a learned gate
at the end of each pass says how much of a token's loss that pass
answers for.  Rows are positions, every projection without bias:

* RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.
* h^0 = E[x].  Block l, the same weights in every pass (a norm before
  and after each half, the paper's "sandwich" normalization):
  a = RMSNorm_1(h); q, k, v = a W_q, a W_k, a W_v as 16 heads of 128;
  rotary embedding (base ``rope_theta``, rotate-half) over all 128 dims
  of q and k; o = softmax_causal(q k^T / sqrt(128)) v;
  h <- h + RMSNorm_2(o W_o);
  m = RMSNorm_3(h); h <- h + RMSNorm_4((silu(m W_g) * m W_u) W_d).
* Pass t = 1..U: h^t = RMSNorm_f(Block_N(... Block_1(h^{t-1}))): the
  final norm is inside the loop, and the normed state is what the next
  pass starts from; z^t = h^t W_head^T; l_t = -log softmax(z^t)[y] a
  position; lambda_t = sigmoid(h^t . w_exit / sqrt(d) + b_exit) a
  position, for t < U (d the hidden size: a parametrization of the
  same linear unit under which a step of SGD on w_exit moves the logit
  by the rate times its gradient and not by d times that).
* Exit distribution a position: S_0 = 1, S_t = prod_{j<=t} (1 -
  lambda_j), p_t = lambda_t S_{t-1} for t < U, p_U = S_{U-1}.
* Loss (the paper's first-stage objective): the mean over positions of
  sum_t p_t l_t - beta H(p), H(p) = -sum_t p_t log p_t.

Departures from the published model, the configuration's ``assumed``:
where the four norms sit; the final norm inside the loop; the gate's
form, scale and bias; beta; the first stage's objective; the rotate-half
pairing; the initial weights; 4,096 positions; momentum SGD.

Straightforward ``jax.numpy`` in float32: the loop is a Python loop
over the same dictionary of weights, attention materializes its scores.
It imports nothing of the program under test: parameter names are the
program symbol's public names.  ``jax.checkpoint`` around each block
and each head pass only bounds the memory of the backward pass.
``cast`` is the hook of the lower-precision control, applied to both
operands of every matrix product (the gate's and attention's two
included); the reference itself passes ``None``.

The auxiliary state is the mean exit distribution of the rows this
call saw, which the program publishes as a gauge; it is compared with
nothing.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.special import xlogy

HI = lax.Precision.HIGHEST

BLOCK = {"norm1_gamma": "d", "attn_q_weight": "qd", "attn_k_weight": "qd",
         "attn_v_weight": "qd", "attn_o_weight": "dq", "norm2_gamma": "d",
         "norm3_gamma": "d", "mlp_gate_weight": "wd", "mlp_up_weight": "wd",
         "mlp_down_weight": "dw", "norm4_gamma": "d"}
RESIDUAL = ("attn_o_weight", "mlp_down_weight")


def _sizes(cfg):
    return dict(d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                hd=cfg["head_dim"], width=cfg["intermediate_size"],
                vocab=cfg["vocab_size"], layers=cfg["num_hidden_layers"],
                steps=cfg["total_ut_steps"])


def param_shapes(cfg):
    """({parameter: shape}, {auxiliary state: shape})."""
    z = _sizes(cfg)
    if z["heads"] != cfg["num_key_value_heads"]:
        raise ValueError("as many key/value heads as query heads")
    dim = {"d": z["d"], "q": z["heads"] * z["hd"], "w": z["width"]}
    p = {"tok_embed_weight": (z["vocab"], z["d"]), "norm_gamma": (z["d"],),
         "head_weight": (z["vocab"], z["d"]), "exit_weight": (1, z["d"]),
         "exit_bias": (1,)}
    for i in range(z["layers"]):
        for name, axes in BLOCK.items():
            p["l%d_%s" % (i, name)] = tuple(dim[a] for a in axes)
    aux = {"exit_loss_dist_pass_share": (z["steps"],)} if z["steps"] > 1 else {}
    return p, aux


def init(cfg, key):
    """Seeded float32 weights: normal of deviation ``initializer_range``,
    the projections into the residual stream scaled down by the root of
    their 2 N U uses a forward pass, as GPT-2 scales over 2 N; gamma 1;
    the gate's weight of deviation ``exit_gate_init_std`` (its logit,
    divided by sqrt(d), starts with that deviation) and its bias 0, so
    that every gate starts near one half."""
    std = cfg["initializer_range"]
    z = _sizes(cfg)
    pshapes, ashapes = param_shapes(cfg)
    params = {}
    for i, (name, shape) in enumerate(sorted(pshapes.items())):
        if name.endswith("_gamma"):
            params[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_bias"):
            params[name] = jnp.zeros(shape, jnp.float32)
        else:
            s = std / (2.0 * z["layers"] * z["steps"]) ** 0.5 \
                if name.endswith(RESIDUAL) else std
            if name == "exit_weight":
                s = cfg["exit_gate_init_std"]
            params[name] = s * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    return params, {n: jnp.zeros(s, jnp.float32) for n, s in ashapes.items()}


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


def _attention(a, p, z, cfg, cast):
    b, t, _ = a.shape
    h, hd, theta = z["heads"], z["hd"], float(cfg["rope_theta"])
    q, k, v = (_mm(a, p("attn_%s_weight" % n), cast).reshape(b, t, h, hd)
               for n in "qkv")
    q, k = _rotary(q, theta), _rotary(k, theta)
    if cast is not None:
        q, k = cast(q), cast(k)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * hd ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if cast is not None:
        pr, v = cast(pr), cast(v)
    o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HI)
    return _mm(o.reshape(b, t, h * hd), p("attn_o_weight"), cast)


def _block(x, params, pre, z, cfg, cast):
    """One sandwich-norm block on (B, T, d)."""
    p = lambda n: params[pre + n]                           # noqa: E731
    eps = cfg["rms_norm_eps"]
    o = _attention(_rms(x, p("norm1_gamma"), eps), p, z, cfg, cast)
    x = x + _rms(o, p("norm2_gamma"), eps)
    m = _rms(x, p("norm3_gamma"), eps)
    f = _mm(jax.nn.silu(_mm(m, p("mlp_gate_weight"), cast))
            * _mm(m, p("mlp_up_weight"), cast), p("mlp_down_weight"), cast)
    return x + _rms(f, p("norm4_gamma"), eps)


def _head(h, w_head, target, cast):
    """-log softmax(h W_head^T)[target] at every position, (B, T)."""
    logp = jax.nn.log_softmax(_mm(h, w_head, cast), axis=-1)
    return -jnp.take_along_axis(logp, target[..., None], axis=-1)[..., 0]


def exit_distribution(lam):
    """lam (.., U - 1) -> p (.., U): p_t = lam_t prod_{j<t} (1 - lam_j),
    and prod_j (1 - lam_j) for the last pass."""
    if not lam.shape[-1]:               # one pass: it answers for all
        return jnp.ones(lam.shape[:-1] + (1,), lam.dtype)
    stay = jnp.cumprod(1.0 - lam, axis=-1)
    before = jnp.concatenate([jnp.ones_like(lam[..., :1]), stay[..., :-1]],
                             axis=-1)
    return jnp.concatenate([lam * before, stay[..., -1:]], axis=-1)


def row_losses(cfg, params, data, label, cast=None):
    """The U per-position losses (U, B, T) and the U - 1 gates
    (B, T, U - 1) of the loop."""
    z = _sizes(cfg)
    eps = cfg["rms_norm_eps"]
    h = params["tok_embed_weight"][data]
    losses, gates = [], []
    for t in range(z["steps"]):
        for i in range(z["layers"]):
            h = jax.checkpoint(functools.partial(
                _block, pre="l%d_" % i, z=z, cfg=cfg, cast=cast))(h, params)
        h = _rms(h, params["norm_gamma"], eps)
        losses.append(jax.checkpoint(functools.partial(_head, cast=cast))(
            h, params["head_weight"], label))
        if t < z["steps"] - 1:
            gates.append(jax.nn.sigmoid(
                _mm(h, params["exit_weight"], cast)[..., 0] * z["d"] ** -0.5
                + params["exit_bias"][0]))
    lam = jnp.stack(gates, axis=-1) if gates \
        else jnp.zeros(data.shape + (0,), jnp.float32)
    return jnp.stack(losses), lam


def loss(cfg, params, aux, data, label, cast=None):
    """(mean over positions of the expected loss under the exit
    distribution less beta times its entropy, new auxiliary state).
    ``data`` and ``label`` (N, T) int32, ``label`` the next tokens."""
    losses, lam = row_losses(cfg, params, data, label, cast)
    p = exit_distribution(lam)                              # (N, T, U)
    expected = jnp.sum(p * jnp.moveaxis(losses, 0, -1), axis=-1)
    entropy = -jnp.sum(xlogy(p, p), axis=-1)
    total = jnp.mean(expected - cfg["exit_beta"] * entropy)
    new_aux = {n: jnp.mean(p.reshape(-1, p.shape[-1]), axis=0) for n in aux}
    return total, new_aux


# ----------------------------------------------------------------------
# operations and bytes, from shapes
def costs(cfg, batch):
    """What the algorithm needs for one step of ``batch`` rows, forward
    and backward (three times the forward's products), two operations a
    multiply-add, ``act_bytes`` an element, every pass of the loop
    counted and **nothing recomputed**: what the program runs again in
    its backward pass is the program's cost, not the model's.

    ``matmul``: the four projections and the three feed-forward
    products of every block in every pass, the head in every pass and
    the gate in every pass but the last.  ``attention``: causal
    attention at (batch, T, heads, head_dim) in every block of every
    pass: the lower triangle only, forward QK^T and PV and their four
    backward products; bytes q, k, v, o and their gradients once.
    ``model_flops`` is the two summed.
    """
    z = _sizes(cfg)
    act = cfg.get("act_bytes", 2)
    t = cfg["input"]["seq_len"]
    rows = batch * t
    d, q, hd = z["d"], z["heads"] * z["hd"], z["hd"]
    by_layer, mm_flops, mm_bytes = {}, 0, 0

    def dense(name, fan_in, fan_out):
        nonlocal mm_flops, mm_bytes
        by_layer[name] = 3 * 2 * rows * fan_in * fan_out
        mm_flops += by_layer[name]
        mm_bytes += act * 3 * (rows * fan_in + fan_in * fan_out
                               + rows * fan_out)

    at_flops = at_bytes = 0
    for u in range(1, z["steps"] + 1):
        for i in range(z["layers"]):
            pre = "u%d_l%d_" % (u, i)
            for n in "qkv":
                dense(pre + "attn_" + n, d, q)
            dense(pre + "attn_o", q, d)
            dense(pre + "mlp_gate", d, z["width"])
            dense(pre + "mlp_up", d, z["width"])
            dense(pre + "mlp_down", z["width"], d)
            by_layer[pre + "attn"] = (2 + 4) * 2 * batch * z["heads"] \
                * (t * t // 2) * hd
            at_flops += by_layer[pre + "attn"]
            at_bytes += act * 8 * rows * q
        dense("u%d_exit_head" % u, d, z["vocab"])
        if u < z["steps"]:
            dense("u%d_exit_gate" % u, d, 1)
    return {"model_flops": mm_flops + at_flops, "by_layer": by_layer,
            "matmul": {"flops": mm_flops, "bytes": mm_bytes},
            "attention": {"flops": at_flops, "bytes": at_bytes}}
