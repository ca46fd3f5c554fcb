"""Ops of a layer that carries a state along the sequence.

``_contrib_GatedDeltaRule`` is the gated delta rule with a decay a
channel (Kimi delta attention, arXiv:2510.26692 sec. 3).  A head keeps a
state S (d_k x d_v, float32, zero at a row's start) and at position t

    S' = Diag(exp(g_t)) S          g_t <= 0, a log decay a key channel
    S  = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S^T (scale q_t)

It runs in chunked form.  With G the running sum of g inside a chunk of
C positions, S0 the state the chunk starts from and

    A[s, r]  = sum_c k_s[c] k_r[c] exp(G_s[c] - G_r[c])     r <  s
    Aq[s, r] = sum_c q_s[c] k_r[c] exp(G_s[c] - G_r[c])     r <= s

the corrected values U = beta (v - S'^T k) of all C positions solve one
unit lower triangular system, (I + Diag(beta) A) U = beta V - beta
(K exp G) S0, and then O = (Q exp G) S0 + Aq U and the chunk ends at
Diag(exp G_C) S0 + (K exp(G_C - G))^T U.  Everything that does not hold
S0 is computed for all chunks at once; what is left for the scan along
the sequence is two small products a chunk.

g may fall to -5 a step (``kda_lower_bound``), 320 over a chunk, and
float32 ends at e^88, so A and Aq are built from 16-position blocks, in
one of two forms.  With no gate bound declared, a block on the diagonal
is computed from the differences themselves, elementwise, a [16, 16, K]
tile a block whose exponents are never positive, and a block below it
as a product of two factors taken against the running sum at the rows'
block start, exp(G_s - G_b) and exp(G_b - G_r), both at most 1.  Where
the node declares ``lower_bound``, every g at least that, and the bound
is -9 or above, both factors are taken against the running sum at the
middle of the rows' block, exp(G_s - G_m) and exp(G_m - G_r): inside
the block each lies within e^(8 x 9) = e^72 of 1, columns before it
get a factor of at most 1, so one product computes the diagonal blocks
with those below and a lower triangular select cuts the rest away.  At
e^72 the low bfloat16 part that a ``HIGHEST`` product splits off a
factor, 2^-16 of it, is still a normal float32 (from e^-87.3); below -9
it would be flushed, and at the block's start, against which a factor
could fall to e^-16|g|, already at -5.

The arithmetic is float32 at the highest product precision whatever the
operands' type (the products are a few GFLOP a layer; the time is in the
passes around them), and the whole rule is one ``jax.checkpoint``: the
reverse mode is autodiff through the scan and recomputes the forward
from the five operands, which are all that lives between the passes.

``_contrib_ShortConv`` is the depthwise causal convolution of a few taps
that feeds the rule its q, k and v.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs as _obs
from ..base import MXNetError
from .registry import Param, register

_BLOCK = 16          # positions whose decays may be divided by one another
_HI = lax.Precision.HIGHEST
# the lowest gate bound under which the diagonal blocks are the product
_PRODUCT_BOUND = -9.0

# nodes traced, those of them on the product form, and the chunk steps
# their scans take (batch x t / chunk)
_NODES = _obs.counter("attention.kda.nodes")
_BOUNDED = _obs.counter("attention.kda.bounded_nodes")
_CHUNKS = _obs.counter("attention.kda.chunks")


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HI)


def _bounded(lower_bound):
    """Whether a declared gate bound lets the diagonal blocks be the
    product (the module's text)."""
    return lower_bound is not None and lower_bound >= _PRODUCT_BOUND


def _decayed_products(rows, k, G, lower_bound=None):
    """[..., C, C]: sum_c rows_s[c] k_r[c] exp(G_s[c] - G_r[c]) for
    r <= s and 0 above the diagonal; ``rows`` is q or k, all [..., C, K].
    With no bound, or one under -9, no exponent taken is positive."""
    lead, (C, K) = G.shape[:-2], G.shape[-2:]
    nb = C // _BLOCK
    blocks = lambda x: x.reshape(lead + (nb, _BLOCK, K))    # noqa: E731
    Gb, rb, kb = blocks(G), blocks(rows), blocks(k)
    if _bounded(lower_bound):
        # both factors against the running sum at the block's middle;
        # the columns up to the end of the rows' own block
        mid = Gb[..., _BLOCK // 2 - 1, :]                     # [.., nb, K]
        rfac = rb * jnp.exp(Gb - mid[..., :, None, :])
        upto = (jnp.arange(C)[None, :]
                < _BLOCK * (jnp.arange(nb)[:, None] + 1))[:, :, None]
        cfac = k[..., None, :, :] * jnp.exp(jnp.where(
            upto, mid[..., :, None, :] - G[..., None, :, :], -jnp.inf))
        full = _mm("...isc,...irc->...isr", rfac, cfac).reshape(lead + (C, C))
        return jnp.where(jnp.tril(jnp.ones((C, C), bool)), full, 0.0)
    # a block on the diagonal, from the differences themselves
    low = jnp.tril(jnp.ones((_BLOCK, _BLOCK), bool))[:, :, None]
    diff = jnp.where(low, Gb[..., :, None, :] - Gb[..., None, :, :],
                     -jnp.inf)
    diag = jnp.sum(rb[..., :, None, :] * kb[..., None, :, :]
                   * jnp.exp(diff), axis=-1)              # [.., nb, B, B]
    out = (diag[..., :, :, None, :]
           * jnp.eye(nb, dtype=G.dtype)[:, None, :, None]
           ).reshape(lead + (C, C))
    if nb == 1:
        return out
    # the blocks below: both factors against the running sum at the
    # rows' block start, which lies between the two positions
    start = jnp.concatenate([jnp.zeros_like(Gb[..., :1, 0, :]),
                             Gb[..., :-1, -1, :]], axis=-2)   # [.., nb, K]
    rfac = rb * jnp.exp(Gb - start[..., :, None, :])
    before = (jnp.arange(C)[None, :]
              < _BLOCK * jnp.arange(nb)[:, None])[:, :, None]  # [nb, C, 1]
    cfac = k[..., None, :, :] * jnp.exp(jnp.where(
        before, start[..., :, None, :] - G[..., None, :, :], -jnp.inf))
    below = _mm("...isc,...irc->...isr", rfac, cfac)          # [.., nb, B, C]
    return out + below.reshape(lead + (C, C))


def _chunks(x, n, C):
    """[b, t, h, ...] -> [n, b, h, C, ...]."""
    b, _, h = x.shape[:3]
    x = x.reshape((b, n, C, h) + x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)


def gated_delta_rule(q, k, v, g, beta, scale, chunk, lower_bound=None):
    """q, k, g [b, t, h, d_k], v [b, t, h, d_v], beta [b, t, h] ->
    o [b, t, h, d_v] in v's type; ``lower_bound``, where given, is at
    most every entry of g.  See the module's text."""
    b, t, h, dk = q.shape
    n, C = t // chunk, chunk
    f32 = lambda x: x.astype(jnp.float32)                   # noqa: E731
    Q, K, V, G = (_chunks(f32(x), n, C) for x in (q, k, v, g))
    Q = Q * scale
    B = _chunks(f32(beta), n, C)[..., None]                  # [n,b,h,C,1]
    G = jnp.cumsum(G, axis=-2)
    decay = jnp.exp(G)
    A = _decayed_products(K, K, G, lower_bound)
    Aq = _decayed_products(Q, K, G, lower_bound)
    eye = jnp.eye(C, dtype=jnp.float32)
    system = eye + B * (A * (1.0 - eye))
    TV, TK = jnp.split(
        jax.scipy.linalg.solve_triangular(
            system, jnp.concatenate([B * V, B * K * decay], axis=-1),
            lower=True, unit_diagonal=True),
        [V.shape[-1]], axis=-1)
    G_end = G[..., -1:, :]
    K_end = K * jnp.exp(G_end - G)
    keep = jnp.exp(G_end)[..., 0, :, None]                  # [n,b,h,K,1]

    def step(S, xs):
        tv, tk, k_end, keep = xs
        U = tv - _mm("...ck,...kv->...cv", tk, S)
        return keep * S + _mm("...ck,...cv->...kv", k_end, U), (S, U)

    S0 = jnp.zeros((b, h, dk, V.shape[-1]), jnp.float32)
    _, (S, U) = lax.scan(step, S0, (TV, TK, K_end, keep))
    O = _mm("...ck,...kv->...cv", Q * decay, S) \
        + _mm("...sr,...rv->...sv", Aq, U)
    O = jnp.moveaxis(jnp.moveaxis(O, 0, 2), 1, 3)          # [b,n,C,h,v]
    return O.reshape(b, t, h, -1).astype(v.dtype)


def _rule_infer_shape(p, in_shapes):
    # a rule of its own, so that no walk of a Symbol traces the body
    if any(s is None or 0 in s for s in in_shapes[:3]):
        return None
    q, k, v = (tuple(s) for s in in_shapes[:3])
    _check_chunks(q[1], p["chunk"])
    return [q, k, v, k, q[:3]], [q[:3] + (v[3],)], []


def _check_chunks(t, chunk):
    if chunk % _BLOCK or t % chunk:
        raise MXNetError(
            "_contrib_GatedDeltaRule: the sequence length (%d) has to be "
            "a multiple of the chunk (%d), and the chunk of %d"
            % (t, chunk, _BLOCK))


@register("_contrib_GatedDeltaRule",
          input_names=("query", "key", "value", "gate", "beta"),
          params_spec=(Param("scale", float, -1.0),
                       Param("chunk", int, 64),
                       Param("lower_bound", float, None)),
          hint="gateddeltarule", infer_shape=_rule_infer_shape)
def _gated_delta_rule(p, c, q, k, v, g, beta):
    """The gated delta rule with a log decay a key channel, chunked:
    query, key, gate [b, t, h, d_k], value [b, t, h, d_v], beta
    [b, t, h] -> [b, t, h, d_v].  ``gate`` is at most 0; ``scale``
    multiplies the query (default d_k^-1/2); ``t`` is a multiple of
    ``chunk``, which is a multiple of 16.  ``lower_bound``, where given,
    says that every entry of ``gate`` is at least that (the default:
    no bound declared); from -9 up the rule computes its diagonal
    16-position blocks as one product with the blocks below them."""
    t, chunk, bound = q.shape[1], p["chunk"], p["lower_bound"]
    _check_chunks(t, chunk)
    scale = p["scale"] if p["scale"] > 0 else q.shape[-1] ** -0.5
    _NODES.inc()
    if _bounded(bound):
        _BOUNDED.inc()
    _CHUNKS.inc(q.shape[0] * t // chunk)
    rule = jax.checkpoint(
        lambda *xs: gated_delta_rule(*xs, scale=scale, chunk=chunk,
                                     lower_bound=bound))
    return rule(q, k, v, g, beta)


def _conv_infer_shape(p, in_shapes):
    d = in_shapes[0]
    if d is None or 0 in d:
        return None
    return [tuple(d), (d[2], p["kernel"])], [tuple(d)], []


@register("_contrib_ShortConv", input_names=("data", "weight"),
          params_spec=(Param("kernel", int, 4),),
          hint="shortconv", infer_shape=_conv_infer_shape)
def _short_conv(p, c, data, weight):
    """Depthwise causal convolution along time, no bias: data [b, t, c],
    weight [c, kernel] -> y[t] = sum_j weight[:, j] * data[t - (kernel
    - 1) + j], zero before a row's start, never across rows."""
    k, t = p["kernel"], data.shape[1]
    x = jnp.pad(data.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = sum(w[:, j] * lax.slice_in_dim(x, j, j + t, axis=1)
            for j in range(k))
    return y.astype(data.dtype)
