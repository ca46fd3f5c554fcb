"""Static collective-communication analysis over jitted programs.

The reference framework's distributed story IS its comm layer (ps-lite
``KVWorker``/``KVServer`` push/pull); here every push/pull became an XLA
collective scheduled inside the step (``parallel/collectives.py``) — and
until now nothing audited what collectives a compiled program would
actually issue before it ran.  This module extracts an ordered **comm
plan** from a jaxpr — one entry per ``psum`` / ``all_gather`` /
``reduce_scatter`` / ``ppermute`` / ``all_to_all`` with axis, dtype,
element count, predicted wire bytes
(:func:`~..parallel.collectives.collective_wire_bytes`), and
``named_scope`` layer provenance — and runs policy rules over it:

* ``f32-wire`` (error) — a >=1 MB float32 collective on the data axis
  while the active gradient-wire policy is bf16
  (``MXTPU_GRAD_DTYPE=bf16``): the byte diet this policy buys is being
  silently spent.
* ``resharding-thrash`` (error) — under ZeRO-1, an all-gather
  re-materializing a buffer a reduce-scatter just sharded (or a >=1 MB
  all-gather inside the optimizer-update/zero-shard region): the plan
  paid to shard state and then paid again to unshard it.
* ``comm-budget`` (error) — total predicted wire GB/step regressed past
  the checked-in ``COMM_BASELINE.json`` figure (the
  ``analysis.baseline.run_gate`` semantics — tolerance_pct, ratchet
  with ``--write-baseline``).
* ``rank-divergent-collective`` (error, source level) — Python control
  flow conditioned on ``rank``/``process_index`` guarding a
  collective-issuing call: the classic cause of the multi-host wedges
  the elastic guard (PR 7) only catches at runtime.  Suppress a
  deliberate site with ``# comm: ok <why>``.

The plan's **digest** (:func:`plan_digest`) is the cross-rank parity
token: each rank stamps it into the elastic shared dir before the first
step and the collective-entry guard refuses to enter with mismatched
digests (``elastic.ElasticCoordinator.publish_comm_plan``), turning a
would-be silent wedge into a loud ``MXNetError`` naming the diverging
rank and the first differing collective.

CLI: ``tools/comm_lint.py`` (``--check`` gates CI against
``COMM_BASELINE.json``).  Docs: ``docs/how_to/static_analysis.md``
"Communication analysis".
"""
from __future__ import annotations

import ast
import hashlib
import os
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional

import numpy as np

from ..parallel.collectives import collective_wire_bytes
from .core import (ERROR, INFO, Finding, GraphPass, LintReport,
                   PassContext, register_pass, run_passes)
from .jaxpr_passes import iter_eqns_scoped, layer_of_eqn

__all__ = ["CommEntry", "extract_comm_plan", "plan_digest",
           "plan_wire_bytes", "plan_wire_gb", "lint_comm",
           "scan_rank_divergence", "lint_comm_source",
           "COLLECTIVE_PRIMS"]

# the jaxpr primitives that put bytes on the wire (pmean/pmax/pmin are
# psum-shaped reductions; psum_scatter is reduce_scatter's lax name)
COLLECTIVE_PRIMS = ("psum", "pmean", "pmax", "pmin", "all_gather",
                    "all_to_all", "ppermute", "reduce_scatter",
                    "psum_scatter")


@dataclass
class CommEntry:
    """One collective in program order.

    ``elements``/``dtype`` describe the operand a replica feeds in (the
    jaxpr invar aval); ``wire_bytes`` is the predicted per-replica wire
    traffic for ALL executions (``repeat`` folds scan trip counts in);
    ``layer`` is the ``named_scope`` provenance — threaded through
    sub-jaxpr boundaries by ``iter_eqns_scoped``, so a collective
    inside a ``shard_map`` body traced under a scope is attributed.
    ``source`` is ``"jaxpr"`` for an extracted equation or ``"spmd"``
    for an entry the Trainer synthesizes from its own sharding plan
    (GSPMD inserts those collectives at compile time — they never
    appear as jaxpr equations)."""

    index: int
    primitive: str
    axis: str
    dtype: str
    elements: int
    wire_bytes: int
    layer: Optional[str] = None
    bwd: bool = False
    repeat: int = 1
    source: str = "jaxpr"

    def key(self) -> str:
        """Digest identity: what must agree across ranks — primitive,
        axis, dtype, element count, execution count.  Deliberately
        EXCLUDES layer (scope wording may differ across builds of the
        same program) and wire bytes (derived)."""
        return "%s|%s|%s|%d|x%d" % (self.primitive, self.axis,
                                    self.dtype, self.elements,
                                    self.repeat)

    def format(self) -> str:
        where = self.layer or "(unattributed)"
        if self.bwd:
            where += " (bwd)"
        rep = " x%d" % self.repeat if self.repeat != 1 else ""
        return "[%2d] %-14s axis=%-6s %-9s %10d elem%s %10.3f MB  @ %s%s" \
            % (self.index, self.primitive, self.axis, self.dtype,
               self.elements, rep, self.wire_bytes / 1e6, where,
               "" if self.source == "jaxpr" else "  [%s]" % self.source)

    def to_dict(self) -> Dict[str, Any]:
        return {"index": self.index, "primitive": self.primitive,
                "axis": self.axis, "dtype": self.dtype,
                "elements": self.elements, "wire_bytes": self.wire_bytes,
                "layer": self.layer, "bwd": self.bwd,
                "repeat": self.repeat, "source": self.source}

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "CommEntry":
        return cls(int(d["index"]), str(d["primitive"]), str(d["axis"]),
                   str(d["dtype"]), int(d["elements"]),
                   int(d["wire_bytes"]), d.get("layer"),
                   bool(d.get("bwd", False)), int(d.get("repeat", 1)),
                   str(d.get("source", "jaxpr")))


def _axis_names(eqn) -> List[str]:
    names = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if not isinstance(names, (list, tuple)):
        names = (names,)
    return [str(a) for a in names]


def _axis_degree(eqn, names: List[str],
                 axis_sizes: Dict[str, int]) -> int:
    # all_gather carries its own axis_size param — trust the jaxpr first
    n = eqn.params.get("axis_size")
    if n is not None:
        try:
            return max(1, int(n))
        except (TypeError, ValueError):
            pass
    n = 1
    for a in names:
        n *= int(axis_sizes.get(a, 1) or 1)
    return max(1, n)


def extract_comm_plan(jaxpr, axis_sizes: Optional[Dict[str, int]] = None
                      ) -> List[CommEntry]:
    """Walk a (Closed)Jaxpr — recursing through pjit/shard_map/scan
    bodies with scope and trip-count threading — and return the ordered
    comm plan.  ``axis_sizes`` maps mesh axis names to their degree
    (``dict(mesh.shape)``); an axis the caller doesn't name counts as
    size 1, predicting 0 wire bytes (visible in the plan, so a missing
    mapping is loud rather than silently dropped)."""
    axis_sizes = axis_sizes or {}
    plan: List[CommEntry] = []
    for eqn, prefix, repeat in iter_eqns_scoped(jaxpr):
        pname = eqn.primitive.name
        if pname not in COLLECTIVE_PRIMS:
            continue
        names = _axis_names(eqn)
        n = _axis_degree(eqn, names, axis_sizes)
        # price each operand at ITS dtype width (one psum equation may
        # bind a mixed-width pytree); the entry's dtype label takes the
        # first operand's
        elements, dtype, wire = 0, None, 0
        for v in eqn.invars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            try:
                itemsize = np.dtype(aval.dtype).itemsize
            except TypeError:       # extended dtypes (PRNG keys)
                continue
            size = int(np.prod(aval.shape or (1,)))
            elements += size
            wire += collective_wire_bytes(pname, size, itemsize, n)
            if dtype is None:
                dtype = str(np.dtype(aval.dtype))
        if dtype is None:
            continue
        layer, bwd = layer_of_eqn(eqn, prefix)
        plan.append(CommEntry(len(plan), pname, "+".join(names) or "?",
                              dtype, elements, wire * repeat, layer, bwd,
                              repeat))
    return plan


def plan_wire_bytes(plan: Iterable[CommEntry]) -> int:
    return int(sum(e.wire_bytes for e in plan))


def plan_wire_gb(plan: Iterable[CommEntry]) -> float:
    return plan_wire_bytes(plan) / 1e9


def plan_digest(plan: Iterable) -> str:
    """Stable digest of the ordered plan — the cross-rank parity token.
    Two ranks that would issue different collectives (count, order,
    shape, dtype, axis) digest differently; layer wording and predicted
    bytes do not participate (see :meth:`CommEntry.key`).  Accepts
    :class:`CommEntry` objects or their ``key()`` strings — the ONE
    hashing definition ``elastic.publish_comm_plan`` and every analysis
    caller share."""
    h = hashlib.sha1()
    for e in plan:
        h.update((e if isinstance(e, str) else e.key()).encode())
        h.update(b"\n")
    return h.hexdigest()


# ----------------------------------------------------------------------
# comm rules (level "comm": run only on the comm-lint path — the
# graph-lint jaxpr passes keep their own baseline)
@register_pass
class F32WirePass(GraphPass):
    """A large f32 collective on the data axis under a bf16 wire policy.

    ``MXTPU_GRAD_DTYPE=bf16`` promises the cross-chip gradient wire at
    half width; an f32 collective >= 1 MB on the data axis means some
    gradient (or optimizer) traffic fell off the low-precision path —
    exactly the regression ``grad_comm_gb_per_step`` only shows after
    the fact, caught here at trace time."""

    name = "f32-wire"
    level = "comm"

    def run(self, ctx: PassContext):
        if str(ctx.config.get("grad_dtype", "f32")) != "bf16":
            return []
        plan = ctx.config.get("comm_plan") or []
        data_axis = str(ctx.config.get("comm_data_axis", "data"))
        min_bytes = int(ctx.config.get("f32_wire_min_bytes", 1 << 20))
        out = []
        for e in plan:
            if e.dtype != "float32" or e.wire_bytes < min_bytes:
                continue
            if data_axis not in e.axis.split("+"):
                continue
            out.append(Finding(
                self.name, ERROR, e.layer or "(unattributed)",
                e.primitive,
                "%.1f MB float32 %s on the %r axis while the gradient "
                "wire policy is bf16 (plan index %d, %d elements): this "
                "traffic fell off the low-precision path — route it "
                "through collectives.lowp_allreduce or cast before the "
                "wire" % (e.wire_bytes / 1e6, e.primitive, data_axis,
                          e.index, e.elements),
                layer=e.layer, detail={"entry": e.key()}))
        return out


# value-preserving ops the thrash chase looks through when walking an
# all-gather operand back to its producer
_PASSTHROUGH = ("convert_element_type", "reshape", "squeeze",
                "broadcast_in_dim", "transpose", "copy", "mul", "div")
_OPT_SCOPES = ("optimizer_update", "zero_shard", "zero_grad_shard")


@register_pass
class ReshardingThrashPass(GraphPass):
    """Under ZeRO-1, an all-gather undoing a reduce-scatter's work.

    The zero plan's whole point is that the update consumes the OWNED
    shard: a reduce-scatter (or the all_to_all+sum decomposition
    ``lowp_allreduce`` uses) followed by an all-gather of that same
    buffer pays the gather wire AND re-materializes the replicated copy
    the plan promised never to hold.  Also flags a >= 1 MB all-gather
    attributed to the optimizer-update / zero-shard scopes — optimizer
    state the plan should have kept sharded."""

    name = "resharding-thrash"
    level = "comm"

    def run(self, ctx: PassContext):
        if int(ctx.config.get("zero", 0) or 0) != 1:
            return []
        if ctx.jaxpr is None:
            return []
        min_bytes = int(ctx.config.get("thrash_min_bytes", 1 << 20))
        out = []
        self._walk(ctx.jaxpr, "", out, min_bytes)
        return out

    # ----- dataflow chase, one sub-jaxpr body at a time (vars are
    # scoped to their body; cross-body flow is through call boundaries
    # the chase deliberately does not cross)
    def _walk(self, jaxpr, prefix, out, min_bytes):
        from .jaxpr_passes import _eqn_stack, _sub_jaxprs
        jx = getattr(jaxpr, "jaxpr", jaxpr)
        produced = {}
        for eqn in jx.eqns:
            for v in eqn.outvars:
                produced[id(v)] = eqn
        for eqn in jx.eqns:
            if eqn.primitive.name == "all_gather":
                self._check_gather(eqn, produced, prefix, out, min_bytes)
            stack = _eqn_stack(eqn)
            sub_prefix = ("%s/%s" % (prefix, stack) if prefix and stack
                          else (stack or prefix))
            for sub in _sub_jaxprs(eqn):
                self._walk(sub, sub_prefix, out, min_bytes)

    def _chase(self, var, produced, hops=8):
        """Producer of ``var``, looking through value-preserving ops."""
        for _ in range(hops):
            eqn = produced.get(id(var))
            if eqn is None:
                return None
            if eqn.primitive.name in _PASSTHROUGH:
                var = eqn.invars[0]
                continue
            return eqn
        return None

    def _check_gather(self, eqn, produced, prefix, out, min_bytes):
        aval = getattr(eqn.invars[0], "aval", None)
        if aval is None or not hasattr(aval, "dtype"):
            return
        try:
            nbytes = int(np.prod(aval.shape or (1,))
                         * np.dtype(aval.dtype).itemsize)
        except TypeError:
            return
        layer, bwd = layer_of_eqn(eqn, prefix)
        where = layer or "(unattributed)"
        src = self._chase(eqn.invars[0], produced)
        src_name = src.primitive.name if src is not None else None
        if src_name in ("reduce_scatter", "psum_scatter"):
            hit = ("all_gather re-materializes the buffer a %s just "
                   "sharded" % src_name)
        elif src_name == "reduce_sum" and any(
                p is not None and p.primitive.name == "all_to_all"
                for p in (self._chase(v, produced)
                          for v in src.invars)):
            # lowp_allreduce's reduce-scatter spelling: all_to_all
            # chunks summed in f32 — gathering the result undoes it
            hit = ("all_gather re-materializes the shard the "
                   "all_to_all+sum reduce-scatter just produced")
        elif nbytes >= min_bytes and layer in _OPT_SCOPES:
            hit = ("%.1f MB all_gather inside the %r scope" %
                   (nbytes / 1e6, layer))
        else:
            return
        out.append(Finding(
            self.name, ERROR, where, "all_gather",
            "%s under ZeRO-1 (%d bytes): the zero plan should have kept "
            "this sharded — drop the gather and let the update consume "
            "the owned shard (keep_shard), or take the state off the "
            "zero plan deliberately" % (hit, nbytes),
            layer=layer))


@register_pass
class CommBudgetPass(GraphPass):
    """Total predicted wire GB/step vs the checked-in baseline figure.

    The ``analysis.baseline.run_gate`` semantics: regression past
    ``tolerance_pct`` is an ERROR (the CI gate fails on it as a new
    error finding); an improvement past the same tolerance is reported
    INFO so the baseline gets ratcheted down with
    ``--write-baseline``."""

    name = "comm-budget"
    level = "comm"

    def run(self, ctx: PassContext):
        base = ctx.config.get("comm_baseline_gb")
        if base is None:
            return []
        base = float(base)
        tol = float(ctx.config.get("comm_tolerance_pct", 3.0))
        gb = plan_wire_gb(ctx.config.get("comm_plan") or [])
        floor = max(abs(base), 1e-9)
        delta_pct = (gb - base) / floor * 100.0
        if delta_pct > tol:
            return [Finding(
                self.name, ERROR, "<plan>", "<total>",
                "predicted comm %.6f GB/step regressed %.1f%% past the "
                "baseline %.6f GB (tolerance %.1f%%) — shrink the "
                "traffic or ratchet deliberately with --write-baseline"
                % (gb, delta_pct, base, tol),
                detail={"gb": gb, "baseline_gb": base,
                        "delta_pct": round(delta_pct, 2)})]
        if base > 1e-9 and delta_pct < -tol:
            return [Finding(
                self.name, INFO, "<plan>", "<total>",
                "predicted comm %.6f GB/step improved %.1f%% vs the "
                "baseline %.6f GB — ratchet with --write-baseline"
                % (gb, -delta_pct, base))]
        return []


# ----------------------------------------------------------------------
def lint_comm(jaxpr, model: str = "<program>",
              axis_sizes: Optional[Dict[str, int]] = None,
              plan: Optional[List[CommEntry]] = None,
              config: Optional[Dict[str, Any]] = None) -> LintReport:
    """Extract the comm plan of ``jaxpr`` (or take a precomputed
    ``plan`` — e.g. ``Trainer.comm_plan()``, which adds the synthesized
    SPMD entries) and run the comm rules over it.  The plan rides the
    report as ``report.comm_plan`` and its digest as
    ``report.comm_digest``."""
    cfg = dict(config or {})
    if plan is None:
        plan = extract_comm_plan(jaxpr, axis_sizes or
                                 cfg.get("axis_sizes"))
    cfg.setdefault("comm_plan", plan)
    if axis_sizes:
        cfg.setdefault("axis_sizes", dict(axis_sizes))
    report = LintReport(model=model)
    ctx = PassContext(jaxpr=jaxpr, is_train=cfg.get("is_train", True),
                      config=cfg)
    report.extend(run_passes(ctx, "comm"))
    report.traced = jaxpr is not None
    report.comm_plan = plan
    report.comm_digest = plan_digest(plan)
    return report


# ----------------------------------------------------------------------
# source-level rule: rank-divergent collectives
_RANK_NAMES = frozenset(("rank", "process_index", "process_id",
                         "_process_index", "local_rank", "node_rank"))
_COLLECTIVE_CALLS = frozenset((
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "ppermute", "psum_scatter", "reduce_scatter", "lowp_allreduce",
    "global_allreduce", "psum_over_mesh", "barrier",
    "broadcast_from_rank0", "broadcast_one_to_all",
    "sync_global_devices", "process_allgather", "all_reduce"))
_COMM_SUPPRESS = "comm: ok"


def _terminal_name(node) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _mentions_rank(test) -> Optional[str]:
    """The rank-identity name a condition expression references, if
    any.  ``process_count``/``num_workers`` comparisons are NOT rank
    identity — every rank agrees on the world size."""
    for node in ast.walk(test):
        name = None
        if isinstance(node, (ast.Name, ast.Attribute)):
            name = _terminal_name(node)
        elif isinstance(node, ast.Call):
            name = _terminal_name(node.func)
        if name in _RANK_NAMES:
            return name
    return None


def _collective_call(node) -> Optional[str]:
    if isinstance(node, ast.Call):
        name = _terminal_name(node.func)
        if name in _COLLECTIVE_CALLS:
            return name
    return None


def _scan_comm_file(path: str, rel: str) -> List[Finding]:
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        return [Finding("source-parse", ERROR, rel, "<source>",
                        "could not parse: %s" % e)]
    lines = src.splitlines()
    marked = {i + 1 for i, line in enumerate(lines)
              if _COMM_SUPPRESS in line}
    suppressed = marked | {i + 1 for i in marked}
    findings: List[Finding] = []

    def visit(node, guard):
        """``guard`` is the (rank_name, lineno) of the innermost
        enclosing rank-conditioned control flow, or None."""
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Lambda)):
            # a nested def executes later, outside this branch's guard
            for ch in ast.iter_child_nodes(node):
                visit(ch, None)
            return
        here = guard
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            rank = _mentions_rank(node.test)
            if rank is not None and node.lineno not in suppressed:
                here = (rank, node.lineno)
        if guard is not None:
            coll = _collective_call(node)
            if coll is not None and node.lineno not in suppressed:
                findings.append(Finding(
                    "rank-divergent-collective", ERROR,
                    "%s:%d" % (rel, node.lineno), coll,
                    "collective-issuing call %s() guarded by control "
                    "flow conditioned on %r (line %d): ranks taking "
                    "different branches issue different collectives "
                    "and the job wedges inside XLA — hoist the "
                    "collective out of the branch, or mark a deliberate "
                    "site '# %s <why>'"
                    % (coll, guard[0], guard[1], _COMM_SUPPRESS),
                    detail={"guard": guard[0], "guard_line": guard[1]}))
        for ch in ast.iter_child_nodes(node):
            visit(ch, here)

    visit(tree, None)
    return findings


def scan_rank_divergence(root: Optional[str] = None) -> List[Finding]:
    """The ``rank-divergent-collective`` rule over every ``*.py`` under
    ``root`` (default: the installed ``mxnet_tpu`` package)."""
    from .concurrency.static_pass import default_root
    root = root or default_root()
    base = os.path.dirname(os.path.abspath(root.rstrip(os.sep)))
    findings: List[Finding] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            findings.extend(_scan_comm_file(path,
                                            os.path.relpath(path, base)))
    return findings


@register_pass
class RankDivergentCollectivePass(GraphPass):
    """AST rule: rank-conditioned control flow guarding collectives."""

    name = "rank-divergent-collective"
    level = "comm-source"
    doc = "collective-issuing call under rank/process_index-conditioned " \
          "control flow (the classic multi-host wedge)"

    def run(self, ctx: PassContext):
        return scan_rank_divergence(ctx.config.get("source_root"))


def lint_comm_source(root: Optional[str] = None,
                     config: Optional[Dict[str, Any]] = None) -> LintReport:
    """Run the comm source rules (``rank-divergent-collective``) over a
    source tree into one report."""
    cfg = dict(config or {})
    if root is not None:
        cfg["source_root"] = root
    report = LintReport(model="comm-source")
    ctx = PassContext(config=cfg)
    report.extend(run_passes(ctx, "comm-source"))
    report.traced = True
    return report
