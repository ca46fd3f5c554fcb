"""Attention ops.

The reference predates attention entirely (SURVEY.md §5 long-context:
bucketing + truncated BPTT were its only sequence-scaling tools), so these
are greenfield capability ops.  ``_contrib_DotProductAttention`` is exact
multi-head attention over ``[batch, time, heads, dim]`` inputs; on TPU it
runs the Pallas flash kernel (O(T*block) memory, MXU-blocked); elsewhere a
jnp oracle with identical semantics.  Value heads may be narrower or wider
than query/key heads (latent attention at 192 / 128): the kernels take one
head dimension, so the three operands are padded with zero columns to the
next whole lane tile and the result is cut to the value width, which is
exact.  Keys and values may carry fewer heads than queries (grouped
key/value heads): ``h_kv`` dividing the query's ``h``, query head j
reads key/value head j // (h / h_kv).  Neither path takes a key/value
head count, so k and v are repeated along the head axis to ``h`` first;
autodiff sums dk and dv over each group, which is exact.  A ``window``
(causal only) lets query t see the keys t - window < s <= t: the
kernels visit only the tiles such a window reaches, the oracle masks
the same pairs.  Sequence
parallelism over a mesh is ``mx.parallel.ring_attention`` — same math,
K/V rotated over ICI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import obs as _obs
from ..base import MXNetError
from .registry import Param, register


_LANES = 128

# nodes traced with fewer key/value heads than query heads, and the
# bytes of k and v their repetition to the query's heads writes
_GROUPED = _obs.counter("attention.grouped_kv.nodes")
_REPEAT_BYTES = _obs.counter("attention.grouped_kv.repeat_bytes")
# nodes traced under a causal window
_WINDOWED = _obs.counter("attention.window.nodes")


def _kv_groups(q_shape, k_shape, v_shape):
    """How many query heads read one key/value head; an error where key
    and value differ in heads or their heads do not divide the query's."""
    h, h_kv = q_shape[2], k_shape[2]
    if v_shape[2] != h_kv:
        raise MXNetError("_contrib_DotProductAttention: key and value "
                         "carry %d and %d heads" % (h_kv, v_shape[2]))
    if h_kv < 1 or h % h_kv:
        raise MXNetError(
            "_contrib_DotProductAttention: %d key/value heads do not "
            "divide %d query heads" % (h_kv, h))
    return h // h_kv


def _blocks(p):
    """The blocks a node asks of the kernels; none: the kernels' own."""
    return {name: p[name] for name in ("block_q", "block_k") if p[name]}


def _attention_infer_shape(p, in_shapes):
    # the query's shape with the value's last dimension: no walk of a
    # Symbol's shapes has to trace the kernel to learn it
    if any(s is None or 0 in s for s in in_shapes):
        return None
    q, k, v = in_shapes
    _kv_groups(q, k, v)
    if p["window"] > 0 and p["causal"] and p["flash"]:
        # the live share a traced node leaves, left here as well: a
        # program loaded from the program cache is never traced
        from .pallas.flash_attention import note_window
        note_window(q[1], k[1], p["window"], **_blocks(p))
    return ([tuple(s) for s in in_shapes],
            [tuple(q[:-1]) + (v[-1],)], [])


@register("_contrib_DotProductAttention",
          input_names=("query", "key", "value"),
          params_spec=(Param("causal", bool, False),
                       Param("scale", float, -1.0),
                       Param("flash", bool, True),
                       # default 0 = inherit the kernel's tuned blocks
                       # (512x512, measured 2-3x over 128x128 at 8k+)
                       Param("block_q", int, 0),
                       Param("block_k", int, 0),
                       # 0: every earlier key; else the last `window`
                       Param("window", int, 0)),
          hint="dotproductattention",
          infer_shape=_attention_infer_shape)
def _dot_product_attention(p, c, q, k, v):
    """query [b, t, h, d], key [b, t_kv, h_kv, d], value [b, t_kv,
    h_kv, d_v] -> [b, t, h, d_v]; ``h_kv`` divides ``h`` and query head
    j reads key/value head j // (h / h_kv); a ``window`` > 0 (causal
    only) keeps the keys t - window < s <= t of query t."""
    scale = None if p["scale"] <= 0 else p["scale"]
    groups = _kv_groups(q.shape, k.shape, v.shape)
    window = p["window"]
    if window < 0 or (window and not p["causal"]):
        raise MXNetError("_contrib_DotProductAttention: a window (%d) is "
                         "causal and positive" % window)
    if window:
        _WINDOWED.inc()
    if groups > 1:
        _GROUPED.inc()
        _REPEAT_BYTES.inc(groups * (k.size * k.dtype.itemsize
                                    + v.size * v.dtype.itemsize))
        k, v = (jnp.repeat(x, groups, axis=2) for x in (k, v))
    if p["flash"]:
        from .pallas import flash_attention
        plat = c.platform or jax.default_backend()
        interpret = plat != "tpu"
        d_qk, d_v = q.shape[-1], v.shape[-1]
        if d_qk != d_v:
            # the kernels take one head dimension: zero columns up to
            # whole lane tiles (192 / 128 -> 256) add nothing to a score
            # and leave zero columns of the result, which are cut off
            width = -(-max(d_qk, d_v) // _LANES) * _LANES
            q, k, v = (jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))
                       for x in (q, k, v))
            scale = scale or d_qk ** -0.5
        out = flash_attention(q, k, v, causal=p["causal"], scale=scale,
                              interpret=interpret, window=window,
                              **_blocks(p))
        return out if d_qk == d_v else out[..., :d_v]
    from ..parallel.ring_attention import attention_reference
    return attention_reference(q, k, v, causal=p["causal"], scale=scale,
                               window=window)
