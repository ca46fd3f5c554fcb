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
exact.  Sequence parallelism over a mesh is
``mx.parallel.ring_attention`` — same math, K/V rotated over ICI.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import Param, register


_LANES = 128


def _attention_infer_shape(p, in_shapes):
    # the query's shape with the value's last dimension: no walk of a
    # Symbol's shapes has to trace the kernel to learn it
    if any(s is None or 0 in s for s in in_shapes):
        return None
    q, _, v = in_shapes
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
                       Param("block_k", int, 0)),
          hint="dotproductattention",
          infer_shape=_attention_infer_shape)
def _dot_product_attention(p, c, q, k, v):
    scale = None if p["scale"] <= 0 else p["scale"]
    if p["flash"]:
        from .pallas import flash_attention
        plat = c.platform or jax.default_backend()
        interpret = plat != "tpu"
        kw = {}
        if p["block_q"]:
            kw["block_q"] = p["block_q"]
        if p["block_k"]:
            kw["block_k"] = p["block_k"]
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
                              interpret=interpret, **kw)
        return out if d_qk == d_v else out[..., :d_v]
    from ..parallel.ring_attention import attention_reference
    return attention_reference(q, k, v, causal=p["causal"], scale=scale)
