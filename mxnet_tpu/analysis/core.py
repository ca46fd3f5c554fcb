"""Pass framework for the trace-time graph linter.

The reference MXNet catches graph mistakes only at bind/run time, deep
inside ``InferShape``/``InferType`` with no provenance
(``src/executor/graph_executor.cc:425-426``).  Here both program forms
are statically inspectable before a single step runs:

  * the **symbol graph** (``symbol.py::_Node``) — op identity, params,
    attrs, and whole-graph shape/dtype inference via the op registry's
    abstract evaluation hooks, and
  * the **jitted jaxpr** (``executor.py::_GraphProgram``) — the traced
    program where compiler-level hazards (f64 widening, host callbacks,
    non-donated buffers, unfused gather/scatter) are visible.

A :class:`GraphPass` consumes a :class:`PassContext` and yields
:class:`Finding`s with per-node provenance (op name, symbol attrs,
source layer).  Passes self-register via :func:`register_pass`; the
orchestration lives in ``analysis/lint.py`` and the CLI in
``tools/graph_lint.py``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..base import MXNetError

__all__ = [
    "ERROR", "WARN", "INFO", "SEVERITIES", "Finding", "GraphLintWarning",
    "NodeView", "GraphView", "annotate", "GraphPass", "PassContext",
    "LintReport", "register_pass", "get_pass", "list_passes", "run_passes",
    "render_reports",
]

ERROR = "error"
WARN = "warn"
INFO = "info"
SEVERITIES = (ERROR, WARN, INFO)


class GraphLintWarning(UserWarning):
    """Warn-level lint findings surfaced at bind time (``simple_bind``)."""


@dataclass
class Finding:
    """One lint finding with node provenance.

    ``node`` is the symbol node the finding anchors to (``<graph>`` for
    whole-graph findings); ``layer`` is the source layer a jaxpr-level
    finding was attributed to via the executor's per-node
    ``jax.named_scope`` (the same correlation ``benchmark/lib/tracered.py``
    uses for a traced op's scope).  ``detail`` carries structured
    provenance: op params, symbol attrs, shapes, dims.
    """

    rule: str
    severity: str
    node: str
    op: str
    message: str
    layer: Optional[str] = None
    detail: Dict[str, Any] = field(default_factory=dict)

    def __post_init__(self):
        if self.severity not in SEVERITIES:
            raise MXNetError("finding severity must be one of %s, got %r"
                             % (SEVERITIES, self.severity))

    def format(self) -> str:
        where = self.node
        if self.layer and self.layer != self.node:
            where = "%s@%s" % (self.node, self.layer)
        return "[%s] %-22s %s(%s): %s" % (
            self.severity.upper(), self.rule, where, self.op, self.message)

    def dedupe_key(self) -> str:
        """Stable identity for one finding across runs and sources:
        ``rule|node|op|layer`` — deliberately EXCLUDES the message (its
        wording carries volatile values — ages, counts, thread idents)
        so graph and concurrency findings dedupe on what they flag, not
        on how they phrase it."""
        return "%s|%s|%s|%s" % (self.rule, self.node, self.op,
                                self.layer or "")

    def to_dict(self) -> Dict[str, Any]:
        d = {"rule": self.rule, "severity": self.severity, "node": self.node,
             "op": self.op, "message": self.message}
        if self.layer:
            d["layer"] = self.layer
        if self.detail:
            d["detail"] = {k: str(v) for k, v in self.detail.items()}
        return d


# ----------------------------------------------------------------------
# graph views
class NodeView:
    """Uniform node record for passes: works for live ``_Node`` graphs
    and for raw nnvm JSON (where nodes unreachable from the heads — dead
    subgraphs — still exist and must be visible to dead-code analysis)."""

    __slots__ = ("idx", "name", "op", "op_name", "params", "attrs", "inputs")

    def __init__(self, idx, name, op, op_name, params, attrs, inputs):
        self.idx = idx
        self.name = name
        self.op = op            # registry Op, or None for variables
        self.op_name = op_name  # "null" for variables
        self.params = params
        self.attrs = attrs
        self.inputs = inputs    # list[(node_idx, out_idx)]

    @property
    def is_variable(self):
        return self.op_name == "null"

    def num_outputs(self):
        return 1 if self.op is None else self.op.n_outputs(self.params)

    def provenance(self) -> Dict[str, Any]:
        d = {}
        if self.params:
            d["params"] = {k: str(v) for k, v in self.params.items()
                           if v is not None}
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        return d


class GraphView:
    """The linter's graph: every node (reachable or not), the output
    heads, and the reachable set."""

    def __init__(self, nodes: List[NodeView], heads: List[Tuple[int, int]],
                 symbol=None, aux_vars=None):
        self.nodes = nodes
        self.heads = heads
        self.symbol = symbol     # live Symbol when built from one
        # variable idxs that are aux states in reference-style JSON
        # (their edges are dropped on load, which makes them LOOK
        # unreachable — dead-code must exempt them)
        self.aux_vars = aux_vars or set()
        self.reachable = self._reach()
        self._topo_cache = None

    def _reach(self):
        seen = set()
        stack = [h[0] for h in self.heads]
        while stack:
            i = stack.pop()
            if i in seen:
                continue
            seen.add(i)
            stack.extend(c for c, _ in self.nodes[i].inputs)
        return seen

    def topo(self) -> List[NodeView]:
        """Reachable nodes in dependency (post-)order, cached (the view
        is immutable after construction; annotate + three passes all
        walk it).  Same three-color DFS as ``symbol._topo``: a node
        re-encountered while gray is a cycle."""
        if self._topo_cache is not None:
            return self._topo_cache
        order, seen, gray = [], set(), set()
        stack = [(h[0], False) for h in reversed(self.heads)]
        while stack:
            i, expanded = stack.pop()
            if expanded:
                order.append(self.nodes[i])
                gray.discard(i)
                continue
            if i in seen:
                if i in gray:
                    raise MXNetError(
                        "cycle detected in graph at node %r (op %s); "
                        "on-stack nodes: %s"
                        % (self.nodes[i].name, self.nodes[i].op_name,
                           sorted(self.nodes[j].name for j in gray)[:8]))
                continue
            seen.add(i)
            gray.add(i)
            stack.append((i, True))
            for c, _ in reversed(self.nodes[i].inputs):
                stack.append((c, False))
        self._topo_cache = order
        return order

    # ------------------------------------------------------------------
    @classmethod
    def from_symbol(cls, sym) -> "GraphView":
        from ..symbol import _topo
        raw = _topo([e[0] for e in sym._outputs])
        nid = {id(n): i for i, n in enumerate(raw)}
        nodes = [NodeView(i, n.name, n.op,
                          "null" if n.is_variable else n.op.name,
                          dict(n.params), dict(n.attrs),
                          [(nid[id(c)], oi) for c, oi in n.inputs])
                 for i, n in enumerate(raw)]
        heads = [(nid[id(n)], oi) for n, oi in sym._outputs]
        return cls(nodes, heads, symbol=sym)

    @classmethod
    def from_json(cls, json_str) -> "GraphView":
        """Build from nnvm JSON keeping EVERY node, including ones
        unreachable from the heads (load_json silently drops those; the
        dead-code pass needs to see them).  Unregistered ops become
        op=None nodes that annotation reports instead of raising."""
        from ..op import registry as _reg
        data = json.loads(json_str)
        jnodes = data["nodes"]
        nodes: List[NodeView] = []
        for i, jn in enumerate(jnodes):
            attrs = dict(jn.get("attrs") or jn.get("attr")
                         or jn.get("param") or {})
            if jn["op"] == "null":
                nodes.append(NodeView(i, jn["name"], None, "null", {},
                                      attrs, []))
                continue
            op = _reg.get(jn["op"]) if _reg.exists(jn["op"]) else None
            params, extra = {}, attrs
            if op is not None:
                spec = {p.name for p in op.params_spec}
                raw_params = {k: v for k, v in attrs.items() if k in spec}
                extra = {k: v for k, v in attrs.items() if k not in spec}
                params = op.parse_params(raw_params)
            nodes.append(NodeView(i, jn["name"], op, jn["op"], params,
                                  extra, []))
        aux_vars = set()
        for jn, node in zip(jnodes, nodes):
            inputs = []
            for e in jn["inputs"]:
                if _is_aux_edge(nodes[e[0]], node):
                    aux_vars.add(e[0])
                else:
                    inputs.append((e[0], e[1]))
            node.inputs = inputs
        heads = [(h[0], h[1]) for h in data.get("heads", [])]
        return cls(nodes, heads, aux_vars=aux_vars)


def _is_aux_edge(child: NodeView, parent: NodeView) -> bool:
    """Reference JSON lists aux states (moving_mean...) as inputs; the
    graph here tracks them implicitly per node (symbol.py::_is_aux_input
    drops the same edges on load)."""
    if parent.op is None or not child.is_variable:
        return False
    aux = parent.op.list_aux(parent.params)
    return any(child.name.endswith("_" + a) or child.name == a for a in aux)


# ----------------------------------------------------------------------
# whole-graph annotation (shape + dtype inference with per-node
# conflict diagnostics)
class Annotation:
    """Per-entry inferred shapes/dtypes: ``shape[(node_idx, out_idx)]``
    and ``dtype[(node_idx, out_idx)]`` (None where inference could not
    reach).  ``var_shape``/``var_dtype`` are the variable-name keyed
    views (arguments refined backwards, e.g. FC weight shapes)."""

    def __init__(self):
        self.shape: Dict[Tuple[int, int], tuple] = {}
        self.dtype: Dict[Tuple[int, int], Any] = {}
        self.var_shape: Dict[str, tuple] = {}
        self.var_dtype: Dict[str, Any] = {}
        self.aux_shape: Dict[str, tuple] = {}
        self.aux_dtype: Dict[str, Any] = {}
        # variables whose dtype was DECLARED (caller type_dict or a
        # __dtype__ attr) vs back-inferred — promotion blame anchors here
        self.declared_dtype: set = set()

    def node_outputs(self, node: NodeView):
        """(shape, dtype) per output of one node."""
        return [(self.shape.get((node.idx, i)), self.dtype.get((node.idx, i)))
                for i in range(node.num_outputs())]


def annotate(view: GraphView, shapes: Optional[Dict[str, tuple]] = None,
             dtypes: Optional[Dict[str, Any]] = None):
    """Walk the reachable graph once, inferring shapes AND dtypes per
    node via the registry hooks, catching per-node failures as findings
    with full provenance instead of one opaque deep throw
    (``symbol.py::_infer_graph`` raises from inside ``_infer_shape_impl``
    naming only the first failing node).

    Returns ``(annotation, findings)``.
    """
    import ast
    findings: List[Finding] = []
    ann = Annotation()
    ann.var_shape = {k: tuple(v) for k, v in (shapes or {}).items()
                     if v is not None}
    ann.var_dtype = {k: np.dtype(v) for k, v in (dtypes or {}).items()
                     if v is not None}
    ann.declared_dtype = set(ann.var_dtype)

    for node in view.topo():
        if node.is_variable:
            s = ann.var_shape.get(node.name)
            if s is None and "__shape__" in node.attrs:
                s = tuple(ast.literal_eval(node.attrs["__shape__"]))
                ann.var_shape[node.name] = s
            dt = ann.var_dtype.get(node.name)
            if dt is None and node.attrs.get("__dtype__"):
                dt = np.dtype(node.attrs["__dtype__"])
                ann.var_dtype[node.name] = dt
                ann.declared_dtype.add(node.name)
            ann.shape[(node.idx, 0)] = s
            ann.dtype[(node.idx, 0)] = dt
            continue
        if node.op is None:
            findings.append(Finding(
                "unknown-op", ERROR, node.name, node.op_name,
                "operator %r is not registered; inference cannot "
                "continue through this node" % node.op_name,
                detail=node.provenance()))
            continue
        in_shapes = [ann.shape.get(e) for e in node.inputs]
        in_dtypes = [ann.dtype.get(e) for e in node.inputs]
        n_out = node.num_outputs()
        aux_names = ["%s_%s" % (node.name, a)
                     for a in node.op.list_aux(node.params)]
        # ---- shape
        try:
            in_s, out_s, aux_s = node.op.infer_shape_generic(
                node.params, in_shapes)
            for a, s in zip(aux_names, aux_s):
                ann.aux_shape[a] = tuple(s) if s is not None else None
        except Exception as e:  # noqa: BLE001 — per-node diagnostics
            # unknown input shapes propagating is not a finding (the
            # caller simply didn't seed shapes); a failure with every
            # input KNOWN is a real graph error, with full provenance
            if not any(s is None for s in in_shapes):
                d = node.provenance()
                d["input_shapes"] = in_shapes
                d["inputs"] = [view.nodes[i].name for i, _ in node.inputs]
                findings.append(Finding(
                    "shape-infer", ERROR, node.name, node.op_name,
                    "shape inference failed: %s (input shapes %s from %s)"
                    % (e, in_shapes, d["inputs"]), detail=d))
            in_s, out_s = in_shapes, [None] * n_out
        # write refined input shapes back into variables, diagnosing
        # conflicts with BOTH nodes named
        for (ci, coi), new_s in zip(node.inputs, in_s):
            child = view.nodes[ci]
            if child.is_variable and new_s is not None:
                prev = ann.var_shape.get(child.name)
                if prev is not None and tuple(prev) != tuple(new_s):
                    findings.append(Finding(
                        "shape-conflict", ERROR, child.name, "null",
                        "shape conflict: %s inferred as %s by %s(%s) but "
                        "already %s" % (child.name, tuple(new_s), node.name,
                                        node.op_name, tuple(prev)),
                        detail={"consumer": node.name,
                                "consumer_op": node.op_name}))
                    continue
                ann.var_shape[child.name] = tuple(new_s)
                ann.shape[(ci, coi)] = tuple(new_s)
        for i, s in enumerate(out_s):
            ann.shape[(node.idx, i)] = tuple(s) if s is not None else None
        # ---- dtype
        try:
            in_t, out_t, aux_t = node.op.infer_dtype_generic(
                node.params, in_dtypes)
            for a, t in zip(aux_names, aux_t):
                ann.aux_dtype[a] = t
        except Exception as e:  # noqa: BLE001
            d = node.provenance()
            d["input_dtypes"] = [str(t) for t in in_dtypes]
            findings.append(Finding(
                "dtype-infer", ERROR, node.name, node.op_name,
                "dtype inference failed: %s (input dtypes %s)"
                % (e, [str(t) for t in in_dtypes]), detail=d))
            in_t, out_t = in_dtypes, [None] * n_out
        for (ci, coi), new_t in zip(node.inputs, in_t):
            child = view.nodes[ci]
            if child.is_variable and new_t is not None \
                    and ann.var_dtype.get(child.name) is None:
                ann.var_dtype[child.name] = new_t
                ann.dtype[(ci, coi)] = new_t
        for i, t in enumerate(out_t):
            ann.dtype[(node.idx, i)] = t
    return ann, findings


# ----------------------------------------------------------------------
# pass registry
@dataclass
class PassContext:
    """Everything a pass may consume.  Symbol-level passes read ``view``
    + ``annotation``; jaxpr-level passes read ``jaxpr`` (+ donation
    metadata when the caller is a Trainer).  ``config`` carries
    thresholds (``sublane``, ``lane``, ``donation_min_bytes``...)."""

    view: Optional[GraphView] = None
    annotation: Optional[Annotation] = None
    jaxpr: Any = None                      # ClosedJaxpr
    donated_invars: Optional[tuple] = None
    invar_labels: Optional[List[str]] = None   # pytree path per invar
    invar_shardings: Optional[List[Any]] = None  # device sharding per invar
    platform: Optional[str] = None
    dtype_policy: Optional[str] = None
    is_train: bool = True
    config: Dict[str, Any] = field(default_factory=dict)


class GraphPass:
    """Base class: subclass, set ``name``/``level``/``severity-policy``,
    implement :meth:`run`, and decorate with :func:`register_pass` (see
    ``docs/how_to/graph_lint.md`` for registering a custom pass)."""

    name: str = ""
    level: str = "symbol"       # "symbol" | "jaxpr"
    doc: str = ""

    def run(self, ctx: PassContext) -> Iterable[Finding]:
        raise NotImplementedError


_PASSES: Dict[str, GraphPass] = {}


def register_pass(cls):
    """Class decorator: instantiate and register a :class:`GraphPass`."""
    inst = cls()
    if not inst.name:
        raise MXNetError("GraphPass %r needs a name" % cls.__name__)
    _PASSES[inst.name] = inst
    return cls


def get_pass(name) -> GraphPass:
    if name not in _PASSES:
        raise MXNetError("no graph pass %r (have %s)"
                         % (name, sorted(_PASSES)))
    return _PASSES[name]


def list_passes(level=None) -> List[str]:
    return sorted(n for n, p in _PASSES.items()
                  if level is None or p.level == level)


def run_passes(ctx: PassContext, level, only=None) -> List[Finding]:
    findings: List[Finding] = []
    for name in list_passes(level):
        if only is not None and name not in only:
            continue
        findings.extend(_PASSES[name].run(ctx))
    return findings


# ----------------------------------------------------------------------
class LintReport:
    """Findings + the annotated graph for one linted program."""

    def __init__(self, model: str = "<graph>"):
        self.model = model
        self.findings: List[Finding] = []
        self.annotation: Optional[Annotation] = None
        self.traced = False

    def extend(self, findings: Iterable[Finding]):
        self.findings.extend(findings)
        return self

    def dedupe(self) -> "LintReport":
        """Drop findings whose :meth:`Finding.dedupe_key` repeats,
        keeping the first (stable order) — one report line per distinct
        hazard site regardless of how many passes or replays saw it."""
        seen, kept = set(), []
        for f in self.findings:
            k = f.dedupe_key()
            if k in seen:
                continue
            seen.add(k)
            kept.append(f)
        self.findings = kept
        return self

    def filter_severity(self, min_severity: Optional[str]) -> "LintReport":
        """Keep findings at or above ``min_severity`` (``None`` keeps
        all) — the ``--severity`` CLI filter, shared by graph and
        concurrency reports."""
        if min_severity is None:
            return self
        if min_severity not in SEVERITIES:
            raise MXNetError("severity must be one of %s, got %r"
                             % (SEVERITIES, min_severity))
        order = {s: i for i, s in enumerate(SEVERITIES)}
        cut = order[min_severity]
        self.findings = [f for f in self.findings
                         if order[f.severity] <= cut]
        return self

    def counts(self) -> Dict[str, int]:
        c = {s: 0 for s in SEVERITIES}
        for f in self.findings:
            c[f.severity] += 1
        return c

    def by_rule(self, severity=None) -> Dict[str, int]:
        c: Dict[str, int] = {}
        for f in self.findings:
            if severity is None or f.severity == severity:
                c[f.rule] = c.get(f.rule, 0) + 1
        return dict(sorted(c.items()))

    def errors(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == ERROR]

    def warnings(self) -> List[Finding]:
        return [f for f in self.findings if f.severity == WARN]

    def summary(self, max_findings=50) -> str:
        c = self.counts()
        lines = ["graph-lint[%s]: %d error, %d warn, %d info%s"
                 % (self.model, c[ERROR], c[WARN], c[INFO],
                    "" if self.traced else " (symbol-level only)")]
        order = {ERROR: 0, WARN: 1, INFO: 2}
        shown = sorted(self.findings, key=lambda f: order[f.severity])
        for f in shown[:max_findings]:
            lines.append("  " + f.format())
        if len(shown) > max_findings:
            lines.append("  ... %d more" % (len(shown) - max_findings))
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {"model": self.model, "counts": self.counts(),
                "errors_by_rule": self.by_rule(ERROR),
                "warns_by_rule": self.by_rule(WARN),
                "infos_by_rule": self.by_rule(INFO),
                "findings": [f.to_dict() for f in self.findings]}


def render_reports(reports: Dict[str, "LintReport"],
                   severity: Optional[str] = None, as_json: bool = False,
                   max_findings: int = 25) -> str:
    """The CLIs' shared output block (``tools/graph_lint.py`` and
    ``tools/concurrency_lint.py``): severity-filter DISPLAY COPIES —
    never the reports a baseline gate will judge or record — and render
    them as summaries or one JSON object."""
    import copy
    shown = {n: copy.copy(r).filter_severity(severity)
             for n, r in reports.items()}
    if as_json:
        return json.dumps({n: shown[n].to_dict() for n in sorted(shown)},
                          indent=1)
    return "\n".join(shown[n].summary(max_findings=max_findings)
                     for n in sorted(shown))
