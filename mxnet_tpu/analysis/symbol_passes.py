"""Symbol-level lint passes: walk the ``_Node`` graph before binding.

These run on the :class:`~.core.GraphView` + :class:`~.core.Annotation`
(whole-graph shape/dtype inference with per-node diagnostics happens in
``core.annotate``; the passes here consume its results).  Rule catalog
in ``docs/how_to/graph_lint.md``.
"""
from __future__ import annotations

from typing import List

import numpy as np

from .core import (ERROR, INFO, WARN, Finding, GraphPass, PassContext,
                   register_pass)

__all__ = ["DeadCodePass", "DuplicateSubgraphPass", "TpuLayoutPass",
           "DtypePromotionPass"]

# ops whose inner loop runs on the MXU: operand feature dims map onto
# the 128-wide lanes, row dims onto the 8-deep (f32) sublanes — see the
# tiling table in the Pallas guide.  Misaligned dims are zero-padded to
# the tile, burning HBM bytes and MXU cycles on padding.
_MATMUL_OPS = {"FullyConnected", "Convolution", "Deconvolution",
               "_contrib_DotProductAttention", "batch_dot", "dot",
               "linalg_gemm", "linalg_gemm2"}


@register_pass
class DeadCodePass(GraphPass):
    """Unused arguments and dead subgraphs.

    A JSON graph can carry nodes no output head reaches (the load path
    silently drops them, hiding dead weight); a live multi-output node
    can have outputs nothing consumes.  Both are wasted compute/bytes
    if they survive to the compiler — and usually a symptom of a wiring
    mistake (the classic forgotten-head MXNet footgun).
    """

    name = "dead-code"
    level = "symbol"

    def run(self, ctx: PassContext):
        view = ctx.view
        out: List[Finding] = []
        for node in view.nodes:
            if node.idx in view.reachable:
                continue
            if node.idx in view.aux_vars:
                # reference-style JSON lists aux states (moving_mean...)
                # as inputs; the graph tracks them implicitly per node,
                # so they are consumed, just not through edges
                continue
            sev = WARN
            kind = "unused argument" if node.is_variable else "dead subgraph"
            out.append(Finding(
                self.name, sev, node.name, node.op_name,
                "%s: node is unreachable from every output head" % kind,
                detail=node.provenance()))
        # unconsumed outputs of reachable multi-output nodes
        consumed = set(view.heads)
        for node in view.nodes:
            if node.idx in view.reachable:
                consumed.update(node.inputs)
        for node in view.nodes:
            if node.idx not in view.reachable or node.is_variable:
                continue
            n_out = node.num_outputs()
            if n_out <= 1:
                continue
            dead = [i for i in range(n_out)
                    if (node.idx, i) not in consumed]
            if dead:
                out.append(Finding(
                    self.name, INFO, node.name, node.op_name,
                    "outputs %s are never consumed (of %d)" % (dead, n_out),
                    detail=node.provenance()))
        return out


@register_pass
class DuplicateSubgraphPass(GraphPass):
    """Structurally identical compute subgraphs (CSE opportunities).

    Two nodes with the same op, same params, and the same input entries
    compute the same value; XLA's CSE usually fuses them, but the graph
    still pays trace/compile time and the duplication is almost always
    an authoring accident (e.g. a layer built twice instead of shared).
    """

    name = "duplicate-subgraph"
    level = "symbol"

    def run(self, ctx: PassContext):
        view = ctx.view
        ids = {}        # structural key -> small int, one per distinct value
        sig = {}        # node idx -> the int of its key
        groups = {}     # key -> [node]
        for node in view.topo():
            if node.is_variable:
                # variables are identity: same name = same value source
                key = ("var", node.name)
            elif node.op is not None and node.op.uses_rng:
                key = ("rng", node.idx)   # stochastic: never CSE
            else:
                # inputs enter by their ints, so a key is as large as the
                # node's fan-in and not as the graph beneath it
                key = (node.op_name,
                       tuple(sorted((k, str(v))
                                    for k, v in node.params.items())),
                       tuple((sig.get(i, ("?", i)), oi)
                             for i, oi in node.inputs))
                groups.setdefault(key, []).append(node)
            sig[node.idx] = ids.setdefault(key, len(ids))
        out = []
        for key, nodes in groups.items():
            if len(nodes) < 2:
                continue
            first = nodes[0]
            out.append(Finding(
                self.name, INFO, first.name, first.op_name,
                "%d structurally identical %s nodes (CSE opportunity): %s"
                % (len(nodes), first.op_name,
                   ", ".join(n.name for n in nodes[:6])),
                detail={"nodes": [n.name for n in nodes]}))
        return out


@register_pass
class TpuLayoutPass(GraphPass):
    """Matmul/conv operand dims off the TPU (sublane, lane) = (8, 128)
    tiles.

    The MXU is a 128x128 systolic array and VREGs are (8, 128) for f32;
    a contracting or feature dim that is not a multiple of 128 (or a row
    dim not a multiple of 8) is padded to the next tile — pure HBM bytes
    and MXU cycles spent on zeros.  Flags the padding fraction per
    offending dim so the finding ranks itself.
    """

    name = "tpu-layout"
    level = "symbol"

    @staticmethod
    def _pad_note(dim, width, what):
        if dim % width == 0:
            return None
        padded = -(-dim // width) * width
        return "%s %d pads to %d (%.0f%% waste)" \
            % (what, dim, padded, 100.0 * (padded - dim) / padded)

    def _conv_hazards(self, node, ann, view, lane):
        """Convolution/Deconvolution: lanes hold the CHANNEL dims (the
        NHWC/HWIO native conv layout maps C onto lanes; spatial dims
        tile freely).  Channels-first additionally forces relayout
        transposes around every conv."""
        hazards = []
        layout = (node.params.get("layout") or "NCHW").upper()
        channels_last = layout[-1] == "C"
        data_shape = ann.shape.get(node.inputs[0]) if node.inputs else None
        if data_shape and len(data_shape) >= 3:
            c_in = data_shape[-1] if channels_last else data_shape[1]
            hazards.append(self._pad_note(
                c_in, lane, "input-channel lane dim"))
        num_filter = node.params.get("num_filter")
        if num_filter:
            hazards.append(self._pad_note(
                int(num_filter), lane, "num_filter lane dim"))
        if not channels_last:
            hazards.append(
                "channels-first layout %s forces relayout transposes "
                "around the conv (lanes = channels is the native TPU "
                "layout)" % layout)
        return [h for h in hazards if h]

    def _matmul_hazards(self, node, ann, view, sublane, lane):
        hazards = []
        for (ci, coi) in node.inputs:
            shape = ann.shape.get((ci, coi))
            if shape is None or len(shape) < 2:
                continue
            cname = view.nodes[ci].name
            for dim, width, kind in ((shape[-1], lane, "lane"),
                                     (shape[-2], sublane, "sublane")):
                note = self._pad_note(
                    dim, width, "%s dim %d of %s:" % (kind, dim, cname))
                if note:
                    hazards.append(note)
        return hazards

    def run(self, ctx: PassContext):
        view, ann = ctx.view, ctx.annotation
        if ann is None:
            return []
        lane = int(ctx.config.get("lane", 128))
        sublane = int(ctx.config.get("sublane", 8))
        out = []
        for node in view.topo():
            if node.op_name not in _MATMUL_OPS:
                continue
            if node.op_name in ("Convolution", "Deconvolution"):
                hazards = self._conv_hazards(node, ann, view, lane)
            else:
                hazards = self._matmul_hazards(node, ann, view, sublane,
                                               lane)
            if hazards:
                d = node.provenance()
                d["operand_shapes"] = [
                    ann.shape.get(e) for e in node.inputs]
                out.append(Finding(
                    self.name, WARN, node.name, node.op_name,
                    "operands off the (%d, %d) tile: %s"
                    % (sublane, lane, "; ".join(hazards)), detail=d))
        return out


@register_pass
class DtypePromotionPass(GraphPass):
    """f64 / weak-type promotion creep through the op registry's dtype
    inference.

    TPUs have no f64 ALU — XLA emulates it at a >10x slowdown, and one
    f64 variable (or a ``dtype=float64`` op param) silently widens every
    downstream node through ``infer_dtype_generic``'s first-known-dtype
    propagation.  Error severity: nothing in this tree wants f64.
    """

    name = "dtype-promotion"
    level = "symbol"

    def run(self, ctx: PassContext):
        view, ann = ctx.view, ctx.annotation
        if ann is None:
            return []
        out = []
        f64 = np.dtype(np.float64)
        for node in view.topo():
            outs = [ann.dtype.get((node.idx, i))
                    for i in range(node.num_outputs())]
            if not any(t is not None and np.dtype(t) == f64 for t in outs):
                continue
            # blame the INTRODUCING node: a variable that DECLARED f64
            # (type_dict / __dtype__ attr) or an op producing f64 from
            # non-f64 inputs; back-inferred variables and pure
            # propagation get info so one leak reads as one error
            in_dts = [ann.dtype.get(e) for e in node.inputs]
            if node.is_variable:
                introduced = node.name in ann.declared_dtype
            else:
                introduced = not any(
                    t is not None and np.dtype(t) == f64 for t in in_dts)
            d = node.provenance()
            d["input_dtypes"] = [str(t) for t in in_dts]
            if introduced:
                src = "declares" if node.is_variable else "produces"
                out.append(Finding(
                    self.name, ERROR, node.name, node.op_name,
                    "%s float64 (TPU emulates f64 at >10x slowdown); "
                    "widens every downstream node" % src, detail=d))
            else:
                out.append(Finding(
                    self.name, INFO, node.name, node.op_name,
                    "carries float64 promoted from an upstream node",
                    detail=d))
        return out
