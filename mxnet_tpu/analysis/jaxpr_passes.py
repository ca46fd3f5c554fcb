"""Jaxpr-level lint passes: hazards the symbol graph can't see.

The traced program (``jax.make_jaxpr`` over the ``_GraphProgram`` body,
or over the Trainer's fused step) exposes what autodiff and the op
bodies actually emit: dtype widenings, host callbacks, buffer-donation
gaps, unfused gather/scatter.  Findings are attributed back to symbol
layers through each equation's name stack — the same per-node
``jax.named_scope`` the executor stamps, which the benchmark's trace
reduction (``benchmark/lib/tracered.py``) reads too, so lint provenance
and a traced op's attribution agree.
"""
from __future__ import annotations

import re
from typing import Iterator, List, Optional, Tuple

import numpy as np

from .core import (ERROR, INFO, WARN, Finding, GraphPass, PassContext,
                   register_pass)

__all__ = ["iter_eqns", "iter_eqns_scoped", "layer_of_eqn",
           "scan_carried_invars",
           "F64WideningPass",
           "HostCallbackPass", "DonationPass", "GatherScatterPass",
           "ReplicatedOptStatePass", "ServeShapeBucketPass",
           "DequantUnfusedPass"]

_SCOPE_RE = re.compile(r"^(transpose\()?(?:jvp\()?([A-Za-z0-9_.\-]+?)\)*$")


def layer_of_eqn(eqn, prefix: str = "") -> Tuple[Optional[str], bool]:
    """``(symbol_layer, is_backward)`` from an equation's name stack.

    The executor's per-node ``jax.named_scope`` leaves the symbol node
    name as a stack component — plain (``conv0``), or autodiff-wrapped:
    ``jvp(conv0)`` forward, ``transpose(jvp(conv0))`` backward.  Deepest
    symbol scope wins (``benchmark/lib/tracered.py: scope_of`` parses
    the same stack out of a traced op's XLA metadata).

    ``prefix`` is the accumulated name stack of the ENCLOSING call
    equations (:func:`iter_eqns_scoped`): an equation inside a
    ``shard_map``/``pjit``/``scan`` body only carries the stack relative
    to that body, so a scope applied AROUND the call — the common case
    for the trainer's shard_map'd backward — would otherwise be lost.
    """
    try:
        stack = str(eqn.source_info.name_stack)
    except Exception:  # pragma: no cover - older jax layouts
        stack = ""
    if prefix:
        stack = "%s/%s" % (prefix, stack) if stack else prefix
    layer, bwd = None, False
    for part in stack.split("/"):
        if "(" in part and not part.startswith(("transpose(", "jvp(")):
            continue                       # jit(...)/pjit wrappers
        m = _SCOPE_RE.match(part)
        if m and m.group(2):
            layer = m.group(2)
            bwd = bwd or bool(m.group(1))
    return layer, bwd


def _is_f64(dt) -> bool:
    """True for float64, tolerating extended dtypes (PRNG key avals)
    numpy cannot interpret."""
    try:
        return np.dtype(dt) == np.dtype(np.float64)
    except TypeError:
        return False


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        if hasattr(v, "eqns"):                       # Jaxpr
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # Closed
            yield v.jaxpr
        elif isinstance(v, (list, tuple)):
            for w in v:
                if hasattr(w, "eqns"):
                    yield w
                elif hasattr(w, "jaxpr") and hasattr(w.jaxpr, "eqns"):
                    yield w.jaxpr


def _eqn_stack(eqn) -> str:
    try:
        return str(eqn.source_info.name_stack)
    except Exception:  # pragma: no cover - older jax layouts
        return ""


def _trip_count(eqn) -> int:
    """Static per-call execution count of ``eqn``'s sub-jaxprs: a
    ``scan`` body runs ``length`` times (``fori_loop`` with static
    bounds lowers to scan); everything else — pjit, shard_map, cond
    branches, while bodies (trip count unknowable) — counts once."""
    if eqn.primitive.name == "scan":
        try:
            return max(1, int(eqn.params.get("length", 1)))
        except (TypeError, ValueError):
            return 1
    return 1


def iter_eqns_scoped(jaxpr, prefix: str = "",
                     repeat: int = 1) -> Iterator:
    """``(eqn, prefix, repeat)`` for every equation of a (Closed)Jaxpr,
    recursing through nested call/pjit/shard_map/custom-vjp/scan
    bodies.  ``prefix`` accumulates the name stacks of the ENCLOSING
    call equations so :func:`layer_of_eqn` can attribute an equation
    inside a sub-jaxpr to a scope applied around the call (a sub-jaxpr
    equation's own stack is relative to its body — without the prefix,
    everything inside a ``shard_map`` traced under a ``named_scope``
    reported ``(unattributed)``).  ``repeat`` is the static execution
    multiplier (scan trip counts fold in), which the comm byte model
    needs for collectives living inside a scan body."""
    jx = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jx.eqns:
        yield eqn, prefix, repeat
        subs = list(_sub_jaxprs(eqn))
        if not subs:
            continue
        stack = _eqn_stack(eqn)
        sub_prefix = ("%s/%s" % (prefix, stack) if prefix and stack
                      else (stack or prefix))
        sub_repeat = repeat * _trip_count(eqn)
        for sub in subs:
            for item in iter_eqns_scoped(sub, sub_prefix, sub_repeat):
                yield item


def iter_eqns(jaxpr) -> Iterator:
    """Every equation of a (Closed)Jaxpr, recursing through nested
    call/pjit/custom-vjp/scan bodies (no scope threading — use
    :func:`iter_eqns_scoped` when provenance matters)."""
    for eqn, _, _ in iter_eqns_scoped(jaxpr):
        yield eqn


def _where(eqn, prefix: str = ""):
    layer, bwd = layer_of_eqn(eqn, prefix)
    if layer is None:
        return None, "(unattributed)"
    return layer, layer + (" (bwd)" if bwd else "")


@register_pass
class F64WideningPass(GraphPass):
    """``convert_element_type`` widening to f64 inside the step.

    The symbol-level dtype pass sees declared dtypes; this one sees what
    the trace actually emits — np.float64 scalars leaking in through op
    params, weak-type promotion inside an op body, a stray
    ``astype(float)``.  One finding per (layer, primitive) so a single
    leak doesn't spam per-equation.
    """

    name = "f64-widening"
    level = "jaxpr"

    def run(self, ctx: PassContext):
        if ctx.jaxpr is None:
            return []
        out, seen = [], set()
        f64 = np.dtype(np.float64)
        for eqn, prefix, _ in iter_eqns_scoped(ctx.jaxpr):
            hit = None
            if eqn.primitive.name == "convert_element_type" \
                    and _is_f64(eqn.params.get("new_dtype", np.float32)):
                hit = "convert_element_type widens to float64"
            elif any(_is_f64(getattr(v.aval, "dtype", np.float32))
                     for v in eqn.outvars if hasattr(v.aval, "dtype")) \
                    and not any(
                        _is_f64(getattr(v.aval, "dtype", np.float32))
                        for v in eqn.invars if hasattr(v, "aval")
                        and hasattr(v.aval, "dtype")):
                hit = "%s produces float64 from non-f64 inputs" \
                    % eqn.primitive.name
            if hit is None:
                continue
            layer, where = _where(eqn, prefix)
            key = (where, eqn.primitive.name)
            if key in seen:
                continue
            seen.add(key)
            out.append(Finding(
                self.name, ERROR, where, eqn.primitive.name,
                "%s inside the jitted step (TPU emulates f64 at >10x "
                "slowdown)" % hit, layer=layer,
                detail={"outvars": [str(v.aval) for v in eqn.outvars][:4]}))
        return out


_CALLBACK_PRIMS = {"io_callback", "pure_callback", "debug_callback",
                   "callback", "outside_call", "host_callback_call"}


@register_pass
class HostCallbackPass(GraphPass):
    """Host callbacks / device_put inside the jitted step.

    A callback stalls the step on a host round trip every invocation;
    a ``device_put`` inside the trace forces a placed copy where the
    sharding propagation should have decided placement (the executor's
    ``group2ctx`` path inserts them deliberately, which is why this is
    warn, not error, for device_put).
    """

    name = "host-callback"
    level = "jaxpr"

    def run(self, ctx: PassContext):
        if ctx.jaxpr is None:
            return []
        out, seen = [], set()
        for eqn, prefix, _ in iter_eqns_scoped(ctx.jaxpr):
            pname = eqn.primitive.name
            if pname in _CALLBACK_PRIMS:
                sev, msg = ERROR, ("host callback %r inside the jitted "
                                   "step: one host round trip per step"
                                   % pname)
            elif pname == "device_put":
                sev, msg = WARN, ("device_put inside the jitted step "
                                  "forces placement mid-program")
            else:
                continue
            layer, where = _where(eqn, prefix)
            key = (where, pname)
            if key in seen:
                continue
            seen.add(key)
            out.append(Finding(self.name, sev, where, pname, msg,
                               layer=layer))
        return out


def scan_carried_invars(jx) -> set:
    """``id()``s of top-level invars threaded through a ``lax.scan``
    carry whose updated value is returned (directly or via the scan's
    carry output).  Such a buffer is donated INTO the scan — XLA
    aliases the carry in place across iterations (the grad-accum
    path threads params/opt_state exactly this way), so donation
    analysis must count it as donated even when the pjit-level
    ``donated_invars`` flag is absent."""
    jx = getattr(jx, "jaxpr", jx)
    carried = set()
    for eqn in jx.eqns:
        if eqn.primitive.name != "scan":
            continue
        try:
            nc = int(eqn.params.get("num_consts", 0))
            ncar = int(eqn.params.get("num_carry", 0))
        except (TypeError, ValueError):
            continue
        for v in eqn.invars[nc:nc + ncar]:
            if not hasattr(v, "val"):
                carried.add(id(v))
    return carried


@register_pass
class DonationPass(GraphPass):
    """Large persistent-state buffers not donated to the step.

    The fused trainer step (``parallel/trainer.py``) donates params,
    aux, and optimizer state so updates are in-place HBM writes; a
    non-donated state buffer doubles its HBM footprint and forces a
    copy.  Runs only when the caller supplied donation metadata (the
    pjit ``donated_invars`` plus a pytree-path label per invar); batch
    inputs are exempt — they are fresh every step by design.  A state
    buffer threaded through a ``lax.scan`` carry (the grad-accum
    microbatch loop) is donated into the scan — XLA aliases the carry
    in place — and is exempt too (:func:`scan_carried_invars`).
    """

    name = "donation"
    level = "jaxpr"

    _STATE = ("params", "aux", "opt_state")

    def run(self, ctx: PassContext):
        if ctx.jaxpr is None or ctx.donated_invars is None \
                or ctx.invar_labels is None:
            return []
        min_bytes = int(ctx.config.get("donation_min_bytes", 1 << 20))
        jx = getattr(ctx.jaxpr, "jaxpr", ctx.jaxpr)
        carried = scan_carried_invars(jx)
        out = []
        offenders = []
        total = 0
        for var, donated, label in zip(jx.invars, ctx.donated_invars,
                                       ctx.invar_labels):
            if donated or id(var) in carried \
                    or not label.startswith(self._STATE):
                continue
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            try:
                itemsize = np.dtype(aval.dtype).itemsize
            except TypeError:       # extended dtypes (PRNG keys)
                continue
            nbytes = int(np.prod(aval.shape or (1,)) * itemsize)
            if nbytes >= min_bytes:
                offenders.append((label, nbytes))
                total += nbytes
        if offenders:
            offenders.sort(key=lambda kv: -kv[1])
            out.append(Finding(
                self.name, WARN, "<step>", "pjit",
                "%d state buffer(s) totalling %.1f MB are not donated "
                "(doubled HBM footprint + copy per step): %s"
                % (len(offenders), total / 1e6,
                   ", ".join("%s (%.1f MB)" % (l, b / 1e6)
                             for l, b in offenders[:5])),
                detail={"offenders": [l for l, _ in offenders]}))
        return out


@register_pass
class ReplicatedOptStatePass(GraphPass):
    """Replicated optimizer-state buffers on a data mesh with ZeRO off.

    On a data-parallel mesh every chip holds a FULL copy of momentum /
    variance unless ``Trainer(zero=1)`` shards them along the ``data``
    axis (the reference kvstore's server-side state ownership) — pure
    waste: the update for a slice only ever reads that slice's state.
    Flags every ≥1 MB ``opt_state`` invar whose committed sharding does
    not mention the ``data`` axis when one of size >1 exists and zero is
    off, labelled by the same pytree path the donation pass uses.  Warn:
    a small model (or a deliberate A/B) may not care; the baseline entry
    keeps CI honest about when it appears.  Runs only on the
    ``lint_trainer`` path — it needs live shardings and mesh metadata.
    """

    name = "zero-opt-state"
    level = "jaxpr"

    def run(self, ctx: PassContext):
        if ctx.jaxpr is None or ctx.invar_labels is None \
                or ctx.invar_shardings is None:
            return []
        n = int(ctx.config.get("data_axis_size", 1) or 1)
        if n <= 1 or int(ctx.config.get("zero", 0) or 0):
            return []
        min_bytes = int(ctx.config.get("opt_state_min_bytes", 1 << 20))
        jx = getattr(ctx.jaxpr, "jaxpr", ctx.jaxpr)
        offenders, total = [], 0
        for var, label, sh in zip(jx.invars, ctx.invar_labels,
                                  ctx.invar_shardings):
            if not label.startswith("opt_state"):
                continue
            aval = getattr(var, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            try:
                itemsize = np.dtype(aval.dtype).itemsize
            except TypeError:       # extended dtypes (PRNG keys)
                continue
            nbytes = int(np.prod(aval.shape or (1,)) * itemsize)
            if nbytes < min_bytes:
                continue
            spec = getattr(sh, "spec", None)
            axes = [a for e in (spec or ()) if e is not None
                    for a in (e if isinstance(e, tuple) else (e,))]
            if "data" in axes:
                continue
            offenders.append((label, nbytes))
            total += nbytes
        if not offenders:
            return []
        offenders.sort(key=lambda kv: -kv[1])
        return [Finding(
            self.name, WARN, "<step>", "pjit",
            "%d optimizer-state buffer(s) totalling %.1f MB are "
            "replicated across the %d-way data axis (every chip a full "
            "copy; per-chip HBM could be ~1/%d): %s — enable "
            "Trainer(zero=1) / MXTPU_ZERO=1"
            % (len(offenders), total / 1e6, n, n,
               ", ".join("%s (%.1f MB)" % (l, b / 1e6)
                         for l, b in offenders[:5])),
            detail={"offenders": [l for l, _ in offenders],
                    "data_axis_size": n})]


@register_pass
class GatherScatterPass(GraphPass):
    """Gather/scatter families in the step.

    Max ``Pooling`` differentiates to ``select_and_scatter_add``, XLA's
    dense window op.  A ``sort`` or ``scatter-add`` traced under a
    ``Pooling`` node's scope is that backward rewritten as a scatter at
    saved indices: on the TPU v5e it sorted 51M indices and scattered
    them serially, 573 of ResNet-50's 698 ms a step (PERF.md, PR 28) —
    warn.  Which scopes are ``Pooling`` nodes is read from ``ctx.view``;
    without one nothing can be told apart and nothing warns.  Every
    other gather/scatter is legitimate (embeddings) and is reported as
    info counts per layer so a traced step's per-scope breakdown
    (``benchmark/lib/tracered.py``) has a trace-time cross-check.
    """

    name = "gather-scatter"
    level = "jaxpr"

    def run(self, ctx: PassContext):
        if ctx.jaxpr is None:
            return []
        pools = set() if ctx.view is None else {
            n.name for n in ctx.view.nodes
            if n.op is not None and n.op.name == "Pooling"}
        out = []
        pool_scatters = {}
        counts = {}
        for eqn, prefix, _ in iter_eqns_scoped(ctx.jaxpr):
            pname = eqn.primitive.name
            scatters = pname in ("scatter", "scatter-add", "scatter_add")
            if not scatters and pname not in ("gather", "sort"):
                continue
            layer, where = _where(eqn, prefix)
            if layer in pools and (scatters or pname == "sort"):
                pool_scatters.setdefault(where, set()).add(pname)
            elif pname != "sort":
                counts[where] = counts.get(where, 0) + 1
        if pool_scatters:
            layers = sorted(pool_scatters)
            out.append(Finding(
                self.name, WARN, layers[0],
                sorted(pool_scatters[layers[0]])[0],
                "sort/scatter under a Pooling node (layers %s): a pooling "
                "backward that scatters at saved indices is sorted and "
                "serialized on the TPU; differentiate the plain "
                "reduce_window (select_and_scatter_add) instead"
                % (layers[:4],),
                detail={"layers": {l: sorted(p)
                                   for l, p in pool_scatters.items()}}))
        if counts:
            total = sum(counts.values())
            top = sorted(counts.items(), key=lambda kv: -kv[1])
            out.append(Finding(
                self.name, INFO, top[0][0], "gather/scatter",
                "%d gather/scatter eqns in the step: %s" %
                (total, ", ".join("%s x%d" % kv for kv in top[:5])),
                detail={"counts": counts}))
        return out


@register_pass
class ServeShapeBucketPass(GraphPass):
    """Per-request-shape specialized compilations on the serve path.

    The serving layer (``serving/server.py``) pre-compiles a fixed
    bucket set of batch sizes at server start and pads every dispatched
    batch to the next bucket, so steady state runs with ZERO retraces.
    A forward compiled for a batch size OUTSIDE the bucket set means a
    request slipped past the padding (an oversized request falling back
    to an exact-shape trace, a direct ``CompiledForward.run`` at an ad
    hoc shape) — each such compile stalls the serve loop for a full
    trace+compile, exactly the latency spike continuous batching exists
    to prevent.  Warn per (model, off-bucket size); the count of AOT
    compiles beyond the bucket set is an error (the warmup itself is
    mis-targeted).  Runs only on the ``lint_server`` path — it needs
    the server's observed trace log (``serve_batch_sizes``) and bucket
    set in ``ctx.config``.
    """

    name = "serve-shape-bucket"
    level = "jaxpr"

    def run(self, ctx: PassContext):
        buckets = ctx.config.get("serve_buckets")
        if not buckets:
            return []
        bset = set(int(b) for b in buckets)
        out = []
        for model, sizes in sorted(
                (ctx.config.get("serve_batch_sizes") or {}).items()):
            off = sorted({int(s) for s in sizes if int(s) not in bset})
            if not off:
                continue
            hits = sum(1 for s in sizes if int(s) not in bset)
            out.append(Finding(
                self.name, WARN, model, "jit",
                "%d serve-path compilation(s) at batch size(s) %s, "
                "outside the AOT bucket set %s — each is a trace+compile "
                "stall on the hot path; widen the buckets or cap request "
                "rows" % (hits, off, sorted(bset)),
                detail={"off_bucket_sizes": off, "buckets": sorted(bset)}))
        return out


_DQ_NARROW = ("int8", "uint8")
_DQ_WIDE = ("float32", "bfloat16", "float16")
# elementwise/layout prims a dequant chain may pass through and still
# fuse into its consumer (the scale multiply + broadcast + reshape of
# contrib.quantization's dequant subgraph)
_DQ_CHAIN = ("mul", "broadcast_in_dim", "reshape", "convert_element_type",
             "transpose", "squeeze")
# call-like prims: crossing one forces the operand to materialize as a
# buffer at the call boundary (XLA does not fuse across these)
_DQ_CALLS = ("jit", "xla_call", "closed_call", "core_call", "scan",
             "while", "cond", "shard_map", "custom_jvp_call",
             "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
             "remat2", "checkpoint")


@register_pass
class DequantUnfusedPass(GraphPass):
    """Dequantized weights materialized outside their consumer's fusion.

    The whole premise of int8 serving is that weights live in device
    memory at 1 byte/elem and widen to the compute dtype INSIDE the
    consuming matmul/conv fusion — registers, not HBM.  A dequantized
    f32/bf16 copy that escapes the fusion (returned as a program
    output, or forced through a call boundary like pjit/scan, which XLA
    never fuses across) silently re-materializes the full-width weight
    every step: the HBM traffic AND footprint win are both gone while
    the checkpoint still *looks* quantized.  Error on any int8->float
    ``convert_element_type`` of at least ``dequant_min_bytes`` (default
    1 MiB) whose dequant chain (scale mul / broadcast / reshape, up to
    3 hops) ends anywhere but a fusible consumer.  A dequant feeding
    SEVERAL dot/conv consumers is fine — XLA duplicates the cheap
    widen-multiply into each fusion rather than materializing it.
    """

    name = "dequant-unfused"
    level = "jaxpr"

    def run(self, ctx: PassContext):
        if ctx.jaxpr is None:
            return []
        min_bytes = int(ctx.config.get("dequant_min_bytes", 1 << 20))
        out: List[Finding] = []
        self._scan(getattr(ctx.jaxpr, "jaxpr", ctx.jaxpr), "",
                   min_bytes, out)
        return out

    # each jaxpr scope is scanned independently: vars are scope-local,
    # and escaping a sub-jaxpr's outvars is a materialization at that
    # call boundary just like escaping the top-level program
    def _scan(self, jx, prefix, min_bytes, out):
        jx = getattr(jx, "jaxpr", jx)
        consumers = {}
        for eqn in jx.eqns:
            for v in eqn.invars:
                if not hasattr(v, "val"):       # skip Literals
                    consumers.setdefault(id(v), []).append(eqn)
        outvar_ids = {id(v) for v in jx.outvars}

        for eqn in jx.eqns:
            if eqn.primitive.name == "convert_element_type":
                self._check(eqn, consumers, outvar_ids, prefix,
                            min_bytes, out)
            for sub in _sub_jaxprs(eqn):
                stack = _eqn_stack(eqn)
                sub_prefix = ("%s/%s" % (prefix, stack)
                              if prefix and stack else (stack or prefix))
                self._scan(sub, sub_prefix, min_bytes, out)

    def _check(self, eqn, consumers, outvar_ids, prefix, min_bytes, out):
        src = eqn.invars[0]
        if hasattr(src, "val") or not hasattr(src, "aval"):
            return
        sdt = str(getattr(src.aval, "dtype", ""))
        odt = str(eqn.outvars[0].aval.dtype)
        if sdt not in _DQ_NARROW or odt not in _DQ_WIDE:
            return
        aval = eqn.outvars[0].aval
        nbytes = int(np.prod(aval.shape or (1,))) * aval.dtype.itemsize
        if nbytes < min_bytes:
            return
        reason = self._chase(eqn.outvars[0], consumers, outvar_ids, 3)
        if reason is None:
            return
        layer, where = _where(eqn, prefix)
        out.append(Finding(
            self.name, ERROR, where, "convert_element_type",
            "%.1f MB %s weight dequantized to %s and %s — the widened "
            "copy materializes in HBM instead of fusing into its "
            "consumer, forfeiting the int8 footprint and bandwidth win"
            % (nbytes / 1e6, sdt, odt, reason),
            layer=layer,
            detail={"bytes": nbytes, "shape": tuple(aval.shape),
                    "from": sdt, "to": odt, "reason": reason}))

    def _chase(self, var, consumers, outvar_ids, hops):
        """Follow the dequant chain; return why it materializes, or
        None when every path ends in a fusible consumer."""
        if id(var) in outvar_ids:
            return "returned as a program output"
        for user in consumers.get(id(var), ()):
            pname = user.primitive.name
            if pname in _DQ_CALLS:
                return "passed into %r (a call boundary XLA cannot " \
                       "fuse across)" % pname
            if pname in _DQ_CHAIN:
                if hops <= 0:
                    return "still unconsumed after the dequant chain " \
                           "(%r)" % pname
                reason = self._chase(user.outvars[0], consumers,
                                     outvar_ids, hops - 1)
                if reason is not None:
                    return reason
            # anything else (dot_general, conv, add, ...) fuses the
            # cheap widen in place of a materialized operand
        return None
