"""Static memory analysis: buffer-liveness peak-HBM prediction.

The static-analysis lane audits graphs (``core.py``), concurrency
(``concurrency/``), and communication (``comm_passes.py``) — but the
resource that actually kills TPU jobs is memory, and every memory knob
in the repo (remat, ZeRO-1, grad accumulation, ``donate_batch``,
serving bucket ladders, multi-tenant weight residency) was flying
blind: a config that OOMs was only discovered by running it.  This
module predicts ``peak_bytes_per_chip`` from the SAME lowered programs
the comm analyzer walks — ``Trainer.step_jaxpr`` /
``abstract_step_args`` for training, the ``CompiledForward`` body per
AOT bucket for serving — with a **buffer-liveness timeline**:
topological-order interval analysis over the jaxpr equations
(equations are emitted in dependency order, so program order IS a
topological order):

* each value lives from its defining equation to its LAST use
  (program outputs to the end of the program);
* donated inputs are released at their donation point (the last use —
  the buffer is reused for the aliased output from there on);
* ``scan``/``pjit``/``shard_map`` bodies are recursed with the comm
  analyzer's scope threading (:func:`~.jaxpr_passes.iter_eqns_scoped`
  semantics), so peak contributors carry ``named_scope`` layer
  provenance; a scan body's temporaries count ONCE (XLA reuses the
  iteration buffers), while its stacked outputs/carries are priced at
  the call level; a ``jax.checkpoint`` (``remat2``) body is priced at
  its transient working-set floor (max single-equation operand+result
  bytes) — rematerialized values are recomputable next to their uses,
  which is the memory the knob exists to reclaim;
* bytes are per chip under the sharding plan: invars through their
  committed shardings (``sharding.shard_shape``), ``shard_map`` body
  values at face value (block-local shapes), and batch-leading
  intermediates divided by the data-axis degree (the trainer's
  ``in_specs`` row-shard).

The resulting :class:`MemTimeline` yields ``peak_bytes_per_chip``, the
argmax program point, and a per-layer breakdown of what is live at
the peak.  Rules on top (pass level ``"mem"``):

* ``mem-budget`` (error) — predicted peak regressed past the
  checked-in ``MEM_BASELINE.json`` figure (``COMM_BASELINE.json``'s
  ratchet semantics, via the shared ``analysis.baseline.run_gate``).
* ``mem-capacity`` (error) — predicted peak exceeds ``MXTPU_HBM_BYTES``
  or the detected device memory: the OOM-before-you-run gate.
* ``remat-opportunity`` (warn) — a large activation band live across
  the fwd/bwd boundary while remat is off, naming the layers.
* ``donation-missed`` (warn) — a >=1 MB state leaf whose input buffer
  outlives an output that could alias it (scan-carried state counts
  as donated — the grad-accum path).
* ``pad-waste`` (warn) — predicted bytes burned by serving bucket
  padding at the observed occupancy (``stats()`` pad counters).

CLI: ``tools/mem_lint.py`` (``--check`` gates CI against
``MEM_BASELINE.json``).  Consumers: ``tools/autotune.py`` (memory
feasibility pruning), ``ModelServer.add_model``
(``MXTPU_SERVE_MEM_BUDGET`` admission), ``tests/test_mem_lint.py``
(the prediction against the compiler's ``memory_analysis()``),
``tools/mem_lint.py --live``.  Docs:
``docs/how_to/static_analysis.md`` "Memory analysis".
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from .core import (ERROR, INFO, WARN, Finding, GraphPass, LintReport,
                   PassContext, register_pass, run_passes)
from .jaxpr_passes import _eqn_stack, _sub_jaxprs, layer_of_eqn

__all__ = ["MemTimeline", "extract_liveness", "lint_mem",
           "detect_capacity", "trainer_timeline", "timeline_peak_gb"]

_STATE_LABELS = ("params", "aux", "opt_state")


def _aval_nbytes(aval) -> int:
    """Per-buffer bytes of an abstract value; extended dtypes (PRNG
    keys) numpy cannot size are priced at 4 bytes/elem (they lower to
    uint32 words — tiny either way)."""
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    try:
        itemsize = np.dtype(aval.dtype).itemsize
    except (TypeError, AttributeError):
        itemsize = 4
    return int(np.prod(shape or (1,)) * itemsize)


def _sharded_nbytes(aval, sharding) -> Optional[int]:
    """Per-chip bytes of an invar with a committed sharding, or None
    when the sharding cannot answer (fall back to the heuristic)."""
    if sharding is None:
        return None
    try:
        shard_shape = sharding.shard_shape(tuple(aval.shape))
    except Exception:  # noqa: BLE001 — unknown sharding kinds
        return None
    try:
        itemsize = np.dtype(aval.dtype).itemsize
    except (TypeError, AttributeError):
        itemsize = 4
    return int(np.prod(shard_shape or (1,)) * itemsize)


class MemTimeline:
    """The liveness walk's result: predicted per-chip peak, its argmax
    program point, and what was live there."""

    def __init__(self):
        self.peak_bytes_per_chip = 0
        self.peak_index = -1            # global program-point counter
        self.peak_point = "<empty>"     # "primitive @ layer"
        self.peak_layers: Dict[str, int] = {}   # layer -> live bytes
        self.peak_buffers: List[Dict[str, Any]] = []  # live at the peak
        self.input_bytes: Dict[str, int] = {}   # label head -> bytes
        self.steady_bytes = 0           # resident program inputs
        # fwd activations still live at the first backward equation —
        # the band rematerialization would trade for recompute
        self.residual_bytes = 0
        self.residual_layers: Dict[str, int] = {}
        self.events: List[Tuple[int, str, int]] = []  # new-peak marks
        self.n_points = 0

    def top_contributors(self, k: int = 10) -> List[Dict[str, Any]]:
        return sorted(self.peak_buffers,
                      key=lambda b: -b["bytes"])[:k]

    def format_top(self, k: int = 10) -> str:
        lines = ["peak %.1f MB/chip at [%d] %s (%d live buffers)"
                 % (self.peak_bytes_per_chip / 1e6, self.peak_index,
                    self.peak_point, len(self.peak_buffers))]
        for b in self.top_contributors(k):
            lines.append("  %10.3f MB  %-28s %s"
                         % (b["bytes"] / 1e6, b["layer"] or
                            "(unattributed)", b["desc"]))
        return "\n".join(lines)

    def to_dict(self) -> Dict[str, Any]:
        return {"peak_bytes_per_chip": self.peak_bytes_per_chip,
                "peak_index": self.peak_index,
                "peak_point": self.peak_point,
                "peak_layers": dict(sorted(
                    self.peak_layers.items(), key=lambda kv: -kv[1])),
                "input_bytes": dict(self.input_bytes),
                "steady_bytes": self.steady_bytes,
                "residual_bytes": self.residual_bytes,
                "residual_layers": dict(sorted(
                    self.residual_layers.items(), key=lambda kv: -kv[1])),
                "n_points": self.n_points}


def timeline_peak_gb(timeline: Optional[MemTimeline]) -> float:
    return (timeline.peak_bytes_per_chip if timeline else 0) / 1e9


class _LiveWalker:
    """One liveness walk over a jaxpr tree.  Mutable shared state:
    the live-buffer registry (so a peak inside a sub-body counts the
    enclosing scopes' live bytes too) and the running peak."""

    _MAX_EVENTS = 512

    def __init__(self, data_axis: int, batch_leading):
        self.data_axis = max(1, int(data_axis or 1))
        self.batch_leading = set(int(b) for b in (batch_leading or ())
                                 if int(b) > 0)
        self.live: Dict[int, Dict[str, Any]] = {}   # token -> record
        self._tok = 0
        self.cur = 0
        self.idx = 0
        self.t = MemTimeline()
        self._bwd_seen = False

    # ---------------------------------------------------------- alloc
    def _alloc(self, nbytes, layer, bwd, desc, kind) -> int:
        self._tok += 1
        self.live[self._tok] = {"bytes": int(nbytes), "layer": layer,
                                "bwd": bwd, "desc": desc, "kind": kind}
        self.cur += int(nbytes)
        return self._tok

    def _free(self, token: int) -> None:
        rec = self.live.pop(token, None)
        if rec is not None:
            self.cur -= rec["bytes"]

    def _check_peak(self, point: str) -> None:
        self.t.n_points = self.idx
        if self.cur <= self.t.peak_bytes_per_chip:
            return
        self.t.peak_bytes_per_chip = self.cur
        self.t.peak_index = self.idx
        self.t.peak_point = point
        self.t.peak_buffers = [dict(r) for r in self.live.values()]
        layers: Dict[str, int] = {}
        for r in self.live.values():
            key = (r["layer"] or "(unattributed)") \
                + (" (bwd)" if r["bwd"] else "")
            layers[key] = layers.get(key, 0) + r["bytes"]
        self.t.peak_layers = layers
        if len(self.t.events) < self._MAX_EVENTS:
            self.t.events.append((self.idx, point, self.cur))

    def _value_bytes(self, aval, perchip: bool,
                     sharding=None) -> int:
        """Per-chip bytes of one value.  ``perchip`` means the aval is
        already block-local (inside a shard_map body).  A committed
        invar sharding answers exactly; otherwise batch-leading global
        values divide by the data-axis degree (the row-shard the
        trainer/server commit), everything else counts replicated —
        the conservative side for an OOM gate."""
        n = _aval_nbytes(aval)
        if perchip:
            return n
        exact = _sharded_nbytes(aval, sharding)
        if exact is not None:
            return exact
        shape = getattr(aval, "shape", None)
        if (self.data_axis > 1 and shape
                and int(shape[0]) in self.batch_leading):
            return n // self.data_axis
        return n

    # ----------------------------------------------------------- walk
    def walk_top(self, jx, donated=None, labels=None, shardings=None,
                 prefix: str = "") -> MemTimeline:
        jx = getattr(jx, "jaxpr", jx)
        n = len(jx.invars)
        donated = tuple(donated) if donated is not None \
            and len(donated) == n else (False,) * n
        labels = list(labels) if labels is not None \
            and len(labels) == n else ["<input>"] * n
        shardings = list(shardings) if shardings is not None \
            and len(shardings) == n else [None] * n
        invar_alloc = {}
        for var, don, label, sh in zip(jx.invars, donated, labels,
                                       shardings):
            aval = getattr(var, "aval", None)
            if aval is None:
                continue
            nbytes = self._value_bytes(aval, False, sh)
            head = label.split("[")[0].split(".")[0]
            self.t.input_bytes[head] = \
                self.t.input_bytes.get(head, 0) + nbytes
            tok = self._alloc(nbytes, head, False,
                              "%s %s %s" % (label,
                                            getattr(aval, "dtype", "?"),
                                            tuple(getattr(aval, "shape",
                                                          ()))),
                              "input")
            # a donated input's buffer is reused for its aliased
            # output from the last use on; a non-donated one belongs
            # to the caller for the whole program
            if don:
                invar_alloc[id(var)] = tok
        self.t.steady_bytes = self.cur
        self._check_peak("<inputs resident>")
        self._walk_body(jx, prefix, False, invar_alloc, top=True)
        # residuals never snapshotted (pure-forward program): stay 0
        return self.t

    def _walk_body(self, jx, prefix, perchip, invar_alloc,
                   top=False) -> None:
        jx = getattr(jx, "jaxpr", jx)
        eqns = jx.eqns
        last: Dict[int, int] = {}
        for i, eqn in enumerate(eqns):
            for v in eqn.invars:
                if hasattr(v, "val"):       # Literal
                    continue
                last[id(v)] = i
        for v in jx.outvars:
            if not hasattr(v, "val"):
                last[id(v)] = len(eqns)     # body outputs: never freed here
        owned: Dict[int, int] = dict(invar_alloc)   # id(var) -> token

        for i, eqn in enumerate(eqns):
            layer, bwd = layer_of_eqn(eqn, prefix)
            where = layer or "(unattributed)"
            if bwd and not self._bwd_seen:
                self._bwd_seen = True
                self._snapshot_residuals()
            subs = list(_sub_jaxprs(eqn))
            if subs:
                stack = _eqn_stack(eqn)
                sub_prefix = ("%s/%s" % (prefix, stack)
                              if prefix and stack else (stack or prefix))
                sub_perchip = perchip or \
                    eqn.primitive.name == "shard_map"
                if eqn.primitive.name in ("remat2", "remat", "checkpoint"):
                    # a checkpointed region: the scheduler may
                    # rematerialize body values next to their uses, so
                    # cumulative liveness over-prices it — charge the
                    # transient working-set floor instead
                    for sub in subs:
                        self._walk_remat_transient(sub, sub_prefix,
                                                   sub_perchip)
                else:
                    for sub in subs:
                        # body invars alias the operands already counted
                        # at this level; only body-allocated temporaries
                        # add
                        self._walk_body(sub, sub_prefix, sub_perchip, {})
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is None:
                    continue
                nbytes = self._value_bytes(aval, perchip)
                tok = self._alloc(
                    nbytes, layer, bwd,
                    "%s %s %s" % (eqn.primitive.name,
                                  getattr(aval, "dtype", "?"),
                                  tuple(getattr(aval, "shape", ()))),
                    "tmp")
                owned[id(v)] = tok
            self.idx += 1
            self._check_peak("%s @ %s%s" % (eqn.primitive.name, where,
                                            " (bwd)" if bwd else ""))
            # release: operands at their last use, outvars nobody reads
            for v in list(eqn.invars) + list(eqn.outvars):
                if hasattr(v, "val"):
                    continue
                if last.get(id(v), -1) <= i and id(v) in owned:
                    self._free(owned.pop(id(v)))
        if not top:
            # body temporaries die at the call boundary; the caller
            # prices the call's own outvars (stacked scan outputs,
            # carries) at its level right after this returns
            for tok in owned.values():
                self._free(tok)

    def _walk_remat_transient(self, jx, prefix, perchip) -> None:
        """Price a ``jax.checkpoint`` (``remat2``) body.  Rematerialized
        values are recomputable next to their uses — the whole point of
        checkpointing — so the cumulative-liveness model would charge
        the recompute as if every regenerated residual were resident at
        once and predict remat *increases* the peak.  Instead the region
        is charged its transient working set: the max over body
        equations of one equation's operand + result bytes, the floor
        any recompute schedule must pay.  The region's OUTPUTS (grads,
        policy-saved residuals) still persist — the caller prices the
        call's outvars at its own level."""
        best, best_layer, best_bwd = 0, None, False
        best_where = "(unattributed)"
        pending = [(getattr(jx, "jaxpr", jx), prefix)]
        while pending:
            body, pfx = pending.pop()
            body = getattr(body, "jaxpr", body)
            for eqn in body.eqns:
                subs = list(_sub_jaxprs(eqn))
                if subs:
                    stack = _eqn_stack(eqn)
                    sub_prefix = ("%s/%s" % (pfx, stack)
                                  if pfx and stack else (stack or pfx))
                    for sub in subs:
                        pending.append((sub, sub_prefix))
                local, seen = 0, set()
                for v in list(eqn.invars) + list(eqn.outvars):
                    if hasattr(v, "val") or id(v) in seen:
                        continue
                    seen.add(id(v))
                    aval = getattr(v, "aval", None)
                    if aval is None:
                        continue
                    local += self._value_bytes(aval, perchip)
                if local > best:
                    layer, bwd = layer_of_eqn(eqn, pfx)
                    best, best_layer, best_bwd = local, layer, bwd
                    best_where = "%s @ %s" % (eqn.primitive.name,
                                              layer or "(unattributed)")
        if best:
            tok = self._alloc(best, best_layer, best_bwd,
                              "checkpointed-region working set (%s)"
                              % best_where, "tmp")
            self.idx += 1
            self._check_peak("remat[%s]%s"
                             % (best_where, " (bwd)" if best_bwd else ""))
            self._free(tok)

    def _snapshot_residuals(self) -> None:
        total, layers = 0, {}
        for rec in self.live.values():
            if rec["kind"] != "tmp" or rec["bwd"] or rec["layer"] is None:
                continue
            total += rec["bytes"]
            layers[rec["layer"]] = \
                layers.get(rec["layer"], 0) + rec["bytes"]
        self.t.residual_bytes = total
        self.t.residual_layers = layers


def extract_liveness(jaxpr, axis_sizes: Optional[Dict[str, int]] = None,
                     donated_invars=None, invar_labels=None,
                     invar_shardings=None,
                     config: Optional[Dict[str, Any]] = None
                     ) -> MemTimeline:
    """Walk a (Closed)Jaxpr and return its :class:`MemTimeline`.

    ``axis_sizes`` maps mesh axis names to degree (``dict(mesh.shape)``)
    — the ``data`` entry drives the batch-leading per-chip divisor;
    ``config["batch_leading"]`` lists the global batch row counts the
    heuristic recognizes (the bound batch size, plus the microbatch
    rows under grad accumulation).  ``donated_invars`` /
    ``invar_labels`` / ``invar_shardings`` are the lint_trainer-style
    invar metadata; absent, inputs count replicated and permanently
    resident."""
    cfg = dict(config or {})
    axis_sizes = dict(axis_sizes or cfg.get("axis_sizes") or {})
    data_axis = int(cfg.get("data_axis_size",
                            axis_sizes.get("data", 1)) or 1)
    walker = _LiveWalker(data_axis, cfg.get("batch_leading"))
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    # unwrap a single top-level pjit (Trainer.step_jaxpr's shape) so
    # the donation metadata lines up with the invars actually walked
    if donated_invars is None and len(jx.eqns) == 1 \
            and jx.eqns[0].primitive.name == "jit":
        inner = jx.eqns[0].params.get("jaxpr")
        if inner is not None:
            jx = getattr(inner, "jaxpr", inner)
    return walker.walk_top(jx, donated_invars, invar_labels,
                           invar_shardings)


# ----------------------------------------------------------------------
def detect_capacity(default: Optional[int] = None) -> Optional[int]:
    """Per-chip HBM capacity for the ``mem-capacity`` gate:
    ``MXTPU_HBM_BYTES`` wins; else the backend's reported
    ``bytes_limit`` (TPU/GPU expose it, CPU does not); else
    ``default`` (None disarms the rule)."""
    from .. import envknobs as _envknobs
    raw = _envknobs.get_str("MXTPU_HBM_BYTES", None) \
        if _envknobs.declared("MXTPU_HBM_BYTES") \
        else os.environ.get("MXTPU_HBM_BYTES")
    if raw:
        try:
            return int(float(raw))
        except ValueError:
            from ..base import MXNetError
            raise MXNetError("MXTPU_HBM_BYTES=%r is not a byte count"
                             % raw) from None
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        if limit:
            return int(limit)
    except Exception:  # noqa: BLE001 — backends without memory_stats
        pass
    return default


# ----------------------------------------------------------------------
# mem rules (level "mem": run only on the mem-lint path)
@register_pass
class MemBudgetPass(GraphPass):
    """Predicted peak GB/chip vs the checked-in baseline figure — the
    ``COMM_BASELINE.json`` ratchet semantics (regression past
    ``tolerance_pct`` errors; an improvement past it is INFO so the
    baseline gets ratcheted down with ``--write-baseline``)."""

    name = "mem-budget"
    level = "mem"

    def run(self, ctx: PassContext):
        base = ctx.config.get("mem_baseline_gb")
        t = ctx.config.get("mem_timeline")
        if base is None or t is None:
            return []
        base = float(base)
        tol = float(ctx.config.get("mem_tolerance_pct", 5.0))
        gb = timeline_peak_gb(t)
        floor = max(abs(base), 1e-9)
        delta_pct = (gb - base) / floor * 100.0
        if delta_pct > tol:
            return [Finding(
                self.name, ERROR, "<timeline>", "<peak>",
                "predicted peak %.6f GB/chip regressed %.1f%% past the "
                "baseline %.6f GB (tolerance %.1f%%) — shrink the "
                "footprint or ratchet deliberately with --write-baseline"
                % (gb, delta_pct, base, tol),
                detail={"gb": gb, "baseline_gb": base,
                        "delta_pct": round(delta_pct, 2)})]
        if base > 1e-9 and delta_pct < -tol:
            return [Finding(
                self.name, INFO, "<timeline>", "<peak>",
                "predicted peak %.6f GB/chip improved %.1f%% vs the "
                "baseline %.6f GB — ratchet with --write-baseline"
                % (gb, -delta_pct, base))]
        return []


@register_pass
class MemCapacityPass(GraphPass):
    """Predicted peak vs per-chip HBM capacity — the OOM-before-you-run
    gate.  Capacity comes resolved in ``config["capacity_bytes"]``
    (``MXTPU_HBM_BYTES`` > detected ``bytes_limit``); absent, the rule
    is inert (the CPU tier has no HBM to protect)."""

    name = "mem-capacity"
    level = "mem"

    def run(self, ctx: PassContext):
        cap = ctx.config.get("capacity_bytes")
        t = ctx.config.get("mem_timeline")
        if not cap or t is None:
            return []
        cap = int(cap)
        peak = int(t.peak_bytes_per_chip)
        if peak <= cap:
            return []
        top = t.top_contributors(3)
        return [Finding(
            self.name, ERROR, t.peak_point, "<peak>",
            "predicted peak %.1f MB/chip exceeds the %.1f MB capacity "
            "(%.0f%% over) — this program OOMs before step 1; top "
            "contributors: %s"
            % (peak / 1e6, cap / 1e6, (peak - cap) / cap * 100.0,
               ", ".join("%s (%.1f MB)" % (b["layer"] or b["desc"],
                                           b["bytes"] / 1e6)
                         for b in top)),
            detail={"peak_bytes": peak, "capacity_bytes": cap,
                    "peak_point": t.peak_point})]


@register_pass
class RematOpportunityPass(GraphPass):
    """A large forward-activation band live across the fwd/bwd
    boundary while remat is off: the exact bytes ``remat=dots`` /
    ``convs_dots`` would trade for recompute, named per layer."""

    name = "remat-opportunity"
    level = "mem"

    def run(self, ctx: PassContext):
        t = ctx.config.get("mem_timeline")
        if t is None or not ctx.is_train:
            return []
        remat = str(ctx.config.get("remat", "none") or "none")
        if remat not in ("none", "off", "0", "False"):
            return []
        min_bytes = int(ctx.config.get("remat_min_bytes", 8 << 20))
        if t.residual_bytes < min_bytes:
            return []
        layers = sorted(t.residual_layers.items(), key=lambda kv: -kv[1])
        return [Finding(
            self.name, WARN, layers[0][0] if layers else "<step>",
            "<fwd/bwd>",
            "%.1f MB of forward activations live across the fwd/bwd "
            "boundary with remat off — Trainer(remat=...) would trade "
            "them for recompute; layers: %s"
            % (t.residual_bytes / 1e6,
               ", ".join("%s (%.1f MB)" % (l, b / 1e6)
                         for l, b in layers[:5])),
            detail={"residual_bytes": t.residual_bytes,
                    "layers": [l for l, _ in layers]})]


@register_pass
class DonationMissedPass(GraphPass):
    """A >=1 MB persistent-state leaf whose input buffer outlives an
    output that could alias it: donation (or a scan carry — the
    grad-accum path, which counts as donated) would make the update an
    in-place write instead of doubling the leaf's footprint."""

    name = "donation-missed"
    level = "mem"

    def run(self, ctx: PassContext):
        if ctx.jaxpr is None or ctx.donated_invars is None \
                or ctx.invar_labels is None:
            return []
        from .jaxpr_passes import scan_carried_invars
        min_bytes = int(ctx.config.get("donation_min_bytes", 1 << 20))
        jx = getattr(ctx.jaxpr, "jaxpr", ctx.jaxpr)
        carried = scan_carried_invars(jx)
        out_avals = {}
        for v in jx.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                key = (tuple(aval.shape), str(aval.dtype))
                out_avals[key] = out_avals.get(key, 0) + 1
        offenders = []
        for var, donated, label in zip(jx.invars, ctx.donated_invars,
                                       ctx.invar_labels):
            if donated or id(var) in carried \
                    or not label.startswith(_STATE_LABELS):
                continue
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            nbytes = _aval_nbytes(aval)
            if nbytes < min_bytes:
                continue
            key = (tuple(aval.shape), str(aval.dtype))
            if out_avals.get(key, 0) > 0:
                offenders.append((label, nbytes))
        if not offenders:
            return []
        offenders.sort(key=lambda kv: -kv[1])
        total = sum(b for _, b in offenders)
        return [Finding(
            self.name, WARN, "<step>", "pjit",
            "%d state leaf(s) totalling %.1f MB have a same-shaped "
            "output to alias but are not donated — the input buffer "
            "outlives the update it could have been overwritten by: %s"
            % (len(offenders), total / 1e6,
               ", ".join("%s (%.1f MB)" % (l, b / 1e6)
                         for l, b in offenders[:5])),
            detail={"offenders": [l for l, _ in offenders]})]


@register_pass
class PadWastePass(GraphPass):
    """Predicted bytes burned by serving bucket padding at the
    observed occupancy: each dispatched batch allocates the full
    bucket's activations; the pad rows' share bought nothing.  Needs
    ``config["pad_occupancy"]`` (bucket -> {"rows_real", "rows_padded"}
    — the ``stats()`` counters) and ``config["bucket_peak_bytes"]``
    (bucket -> predicted forward peak)."""

    name = "pad-waste"
    level = "mem"

    def run(self, ctx: PassContext):
        occ = ctx.config.get("pad_occupancy") or {}
        peaks = ctx.config.get("bucket_peak_bytes") or {}
        if not occ or not peaks:
            return []
        min_bytes = int(ctx.config.get("pad_waste_min_bytes", 1 << 20))
        waste, rows_pad, rows_tot, per_bucket = 0.0, 0, 0, []
        for b, o in sorted(occ.items()):
            peak = peaks.get(b) or peaks.get(int(b)) \
                or peaks.get(str(b))
            padded = int(o.get("rows_padded", 0))
            real = int(o.get("rows_real", 0))
            if not peak or padded <= 0:
                continue
            frac = max(0.0, (padded - real) / float(padded))
            w = float(peak) * frac
            waste += w
            rows_pad += padded - real
            rows_tot += padded
            if frac > 0:
                per_bucket.append("b%s %.1f MB (%.0f%% pad)"
                                  % (b, w / 1e6, frac * 100))
        if waste < min_bytes:
            return []
        return [Finding(
            self.name, WARN, "<serving>", "pad",
            "%.1f MB of predicted activation bytes burned by bucket "
            "padding (%d of %d dispatched rows were pad): %s — tighten "
            "the bucket ladder toward the observed batch sizes"
            % (waste / 1e6, rows_pad, rows_tot,
               ", ".join(per_bucket[:5])),
            detail={"waste_bytes": int(waste), "pad_rows": rows_pad,
                    "rows": rows_tot})]


# ----------------------------------------------------------------------
def lint_mem(jaxpr, model: str = "<program>",
             axis_sizes: Optional[Dict[str, int]] = None,
             timeline: Optional[MemTimeline] = None,
             config: Optional[Dict[str, Any]] = None) -> LintReport:
    """Extract (or take) the liveness timeline of ``jaxpr`` and run the
    mem rules over it.  The timeline rides the report as
    ``report.mem_timeline``.  Capacity for ``mem-capacity`` resolves
    ``config["capacity_bytes"]`` > ``MXTPU_HBM_BYTES`` > detected
    device ``bytes_limit`` > inert."""
    cfg = dict(config or {})
    if timeline is None and jaxpr is not None:
        timeline = extract_liveness(
            jaxpr, axis_sizes or cfg.get("axis_sizes"),
            donated_invars=cfg.get("donated_invars"),
            invar_labels=cfg.get("invar_labels"),
            invar_shardings=cfg.get("invar_shardings"), config=cfg)
    cfg.setdefault("mem_timeline", timeline)
    if "capacity_bytes" not in cfg:
        cap = detect_capacity()
        if cap:
            cfg["capacity_bytes"] = cap
    report = LintReport(model=model)
    ctx = PassContext(jaxpr=jaxpr,
                      donated_invars=cfg.get("donated_invars"),
                      invar_labels=cfg.get("invar_labels"),
                      is_train=cfg.get("is_train", True), config=cfg)
    report.extend(run_passes(ctx, "mem"))
    report.traced = jaxpr is not None
    report.mem_timeline = timeline
    return report


# ----------------------------------------------------------------------
def trainer_timeline(trainer, input_dtypes: Optional[Dict] = None
                     ) -> MemTimeline:
    """The fused trainer step's liveness timeline, with the
    lint_trainer-style invar metadata (donation flags, pytree-path
    labels, live committed shardings) so state buffers are priced per
    chip exactly — ZeRO-sharded optimizer state at ~1/n, replicated
    params at full size."""
    from .lint import step_invar_metadata
    closed = trainer.step_jaxpr(input_dtypes)
    args = trainer.abstract_step_args(input_dtypes)
    jaxpr, donated, labels, shardings = \
        step_invar_metadata(trainer, closed, args)
    batch_leading = set()
    for s in trainer._input_shapes.values():
        if s:
            b = int(s[0])
            batch_leading.add(b)
            accum = int(getattr(trainer, "grad_accum", 1) or 1)
            if accum > 1 and b % accum == 0:
                batch_leading.add(b // accum)
    axis_sizes = dict(trainer.mesh.shape) \
        if trainer.mesh is not None else {}
    return extract_liveness(
        jaxpr, axis_sizes, donated_invars=donated, invar_labels=labels,
        invar_shardings=shardings,
        config={"batch_leading": batch_leading,
                "data_axis_size": trainer._data_axis_size()})
