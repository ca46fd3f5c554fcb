"""Expert-layer ops: the router and a chip's share of the experts.

``MoERouter`` scores every token against all ``num_experts`` experts and
chooses ``top_k`` of them; ``MoEExperts`` is told which experts it holds
(``experts_held`` of them from ``first_expert``) and computes their part
of the layer's result for every entry routed to them.  No capacity, no
dropped entry, and no buffer for the worst case either: the sorted
entries are walked in chunks of rows fixed by the shapes, as many trips
as the step's routing fills.  The mathematics is ``parallel/moe.py``'s
(:func:`sigmoid_topk_route`, :func:`moe_apply_held`), beside the
capacity paths that ``parallel/transformer.py`` runs and that do drop.

Both carry an auxiliary state through the step as BatchNorm carries its
moving statistics: the router its selection bias, which nothing here
updates (its rule is a training recipe), and the experts the last
step's count of entries for each of all the experts, which
``obs.snapshot()`` turns into the load gauges and the count of chunks
walked (``moe.row_chunks_per_layer``).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from .registry import Param, register
from . import registry as _reg


@register("MoERouter",
          params_spec=(Param("num_experts", int, required=True),
                       Param("top_k", int, required=True),
                       Param("scale", float, 1.0),
                       Param("n_group", int, 1),
                       Param("topk_group", int, 1)),
          input_names=("data", "weight"), aux_names=("bias",),
          num_outputs=3,
          output_names=lambda p: ["expert", "weight", "score"],
          hint="moerouter")
def _moe_router(p, c, data, weight, bias):
    """data (T, d), weight (num_experts, d) -> chosen experts (T, k)
    int32, their weights (T, k) and all scores (T, num_experts), both
    float32: sigmoid scores from a product accumulated in float32, the
    ``top_k`` by score + bias, weights renormalized over the chosen and
    times ``scale``.  ``n_group`` > 1 limits the choice to the experts
    of the ``topk_group`` groups whose two best score + bias sum
    highest (``num_experts`` / ``n_group`` neighbours a group)."""
    from ..parallel.moe import sigmoid_topk_route
    if p["num_experts"] % p["n_group"] or not \
            1 <= p["topk_group"] <= p["n_group"]:
        raise MXNetError(
            "MoERouter: %d experts in %d groups of which %d are kept"
            % (p["num_experts"], p["n_group"], p["topk_group"]))
    logits = lax.dot_general(data, weight, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    expert, wt, score = sigmoid_topk_route(
        logits, bias, p["top_k"], p["scale"], p["n_group"], p["topk_group"])
    return expert, wt, score, bias


def _router_infer_shape(p, in_shapes):
    dshape = in_shapes[0]
    if dshape is None or 0 in dshape:
        return None
    t, e, k = dshape[0], p["num_experts"], p["top_k"]
    return [tuple(dshape), (e, dshape[1])], [(t, k), (t, k), (t, e)], [(e,)]


def _router_infer_dtype(p, in_dtypes):
    known = [d for d in in_dtypes if d is not None]
    dt = known[0] if known else jnp.dtype(jnp.float32)
    f32 = jnp.dtype(jnp.float32)
    return [dt, dt], [jnp.dtype(jnp.int32), f32, f32], [f32]


@register("MoEExperts",
          params_spec=(Param("num_experts", int, required=True),
                       Param("experts_held", int, required=True),
                       Param("first_expert", int, 0),
                       Param("num_hidden", int, required=True)),
          input_names=("data", "expert", "weight", "gate_weight",
                       "up_weight", "down_weight"),
          aux_names=("count",), hint="moeexperts")
def _moe_experts(p, c, data, expert, weight, w_gate, w_up, w_down, count):
    """data (T, d), the router's expert and weight (T, k) -> the held
    experts' part of the layer (T, d), and as auxiliary state the count
    of entries the router sent to each of the ``num_experts``."""
    from ..parallel.moe import moe_apply_held
    return moe_apply_held(data, expert, weight, w_gate, w_up, w_down,
                          p["first_expert"], p["num_experts"])


def _experts_infer_shape(p, in_shapes):
    dshape, tk = in_shapes[0], in_shapes[1] or in_shapes[2]
    if dshape is None or 0 in dshape or tk is None:
        return None
    g, h, d = p["experts_held"], p["num_hidden"], dshape[1]
    tk = tuple(tk)
    return ([tuple(dshape), tk, tk, (g, h, d), (g, h, d), (g, d, h)],
            [tuple(dshape)], [(p["num_experts"],)])


def _experts_infer_dtype(p, in_dtypes):
    dt = in_dtypes[0] if in_dtypes[0] is not None \
        else jnp.dtype(jnp.float32)
    f32 = jnp.dtype(jnp.float32)
    return ([dt, jnp.dtype(jnp.int32), f32, dt, dt, dt], [dt], [f32])


_reg.get("MoERouter").infer_shape = _router_infer_shape
_reg.get("MoERouter").infer_dtype = _router_infer_dtype
_reg.get("MoEExperts").infer_shape = _experts_infer_shape
_reg.get("MoEExperts").infer_dtype = _experts_infer_dtype
