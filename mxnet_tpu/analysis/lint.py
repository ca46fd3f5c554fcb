"""Lint orchestration: symbol walk -> symbol passes -> jaxpr trace ->
jaxpr passes.

Entry points:

* :func:`lint_symbol` — lint a live :class:`~..symbol.Symbol`.
* :func:`lint_json` — lint serialized nnvm JSON (keeps dead nodes the
  load path would drop).
* :func:`lint_trainer` — lint a bound :class:`~..parallel.trainer.Trainer`'s
  fused step jaxpr, with buffer-donation metadata.
* :func:`lint_server` — lint a :class:`~..serving.server.ModelServer`'s
  observed serve-path compilations against its AOT bucket set.

Everything is pure trace time: ``jax.eval_shape`` for the symbol walk,
``jax.make_jaxpr`` for the program — no device execution, so the CI
gate (``tools/graph_lint.py --check``) runs in the fast tier.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from ..base import MXNetError
from .core import (ERROR, INFO, Finding, GraphView, LintReport, PassContext,
                   annotate, run_passes)

__all__ = ["lint_symbol", "lint_json", "lint_trainer", "lint_server",
           "step_invar_metadata"]


def lint_symbol(sym, shapes: Optional[Dict[str, tuple]] = None,
                dtypes: Optional[Dict[str, Any]] = None, trace: bool = True,
                is_train: bool = True, platform: Optional[str] = None,
                dtype_policy: Optional[str] = None,
                model: Optional[str] = None,
                config: Optional[Dict[str, Any]] = None,
                only=None) -> LintReport:
    """Run the full pass pipeline over a Symbol.

    ``shapes``/``dtypes`` seed the argument variables (same keys as
    ``infer_shape`` kwargs).  ``trace=False`` skips the jaxpr level
    (used by the cheap ``simple_bind`` hook).  ``only`` restricts to a
    set of pass names.
    """
    view = GraphView.from_symbol(sym)
    return _lint_view(view, shapes, dtypes, trace, is_train, platform,
                      dtype_policy, model or (sym.name or "<graph>"),
                      config, only)


def lint_json(json_str: str, shapes: Optional[Dict[str, tuple]] = None,
              dtypes: Optional[Dict[str, Any]] = None, trace: bool = True,
              is_train: bool = True, platform: Optional[str] = None,
              dtype_policy: Optional[str] = None,
              model: Optional[str] = None,
              config: Optional[Dict[str, Any]] = None,
              only=None) -> LintReport:
    """Lint serialized nnvm JSON.  Unlike ``symbol.load_json`` this
    keeps nodes unreachable from the heads, so dead subgraphs and
    unused arguments are visible to the dead-code pass."""
    view = GraphView.from_json(json_str)
    report = _lint_view(view, shapes, dtypes, False, is_train, platform,
                        dtype_policy, model or "<json>", config, only)
    if trace and not report.errors():
        from ..symbol import load_json
        _trace_into(report, load_json(json_str), view, report.annotation,
                    is_train, platform, dtype_policy, config, only)
    return report


def _lint_view(view, shapes, dtypes, trace, is_train, platform,
               dtype_policy, model, config, only) -> LintReport:
    report = LintReport(model=model)
    try:
        ann, infer_findings = annotate(view, shapes, dtypes)
    except MXNetError as e:
        # topo itself failed (graph cycle): one error finding, no passes
        report.extend([Finding("graph-structure", ERROR, "<graph>",
                               "<graph>", str(e))])
        return report
    report.annotation = ann
    report.extend(infer_findings)
    ctx = PassContext(view=view, annotation=ann, platform=platform,
                      dtype_policy=dtype_policy, is_train=is_train,
                      config=config or {})
    report.extend(run_passes(ctx, "symbol", only))
    if trace and view.symbol is not None and not report.errors():
        _trace_into(report, view.symbol, view, ann, is_train, platform,
                    dtype_policy, config, only)
    return report


# ----------------------------------------------------------------------
def _trace_into(report, sym, view, ann, is_train, platform, dtype_policy,
                config, only):
    """Trace the graph program (fwd, plus vjp when ``is_train``) to a
    jaxpr and run the jaxpr-level passes into ``report``.  ``view`` goes
    along so a pass can tell what kind of node a scope names."""
    import jax
    import jax.numpy as jnp
    from ..executor import _GraphProgram

    prog = _GraphProgram(sym)
    if platform is not None:
        prog.platform = platform
    prog.dtype_policy = dtype_policy

    missing = [n for n in prog.arg_names if ann.var_shape.get(n) is None]
    aux_missing = [n for n in prog.aux_names
                   if ann.aux_shape.get(n) is None]
    if missing or aux_missing:
        report.extend([Finding(
            "trace-skipped", INFO, "<graph>", "<graph>",
            "jaxpr-level passes skipped: unknown shapes for %s"
            % (missing + aux_missing)[:6])])
        return
    args = tuple(jax.ShapeDtypeStruct(tuple(ann.var_shape[n]),
                                      ann.var_dtype.get(n) or np.float32)
                 for n in prog.arg_names)
    aux = tuple(jax.ShapeDtypeStruct(tuple(ann.aux_shape[n]),
                                     ann.aux_dtype.get(n) or np.float32)
                for n in prog.aux_names)

    def fwd_only(a, x):
        return prog._eval(list(a), list(x), jax.random.key(0), is_train)

    def train_step(a, x):
        def fwd(p):
            return prog._eval(list(p), list(x), jax.random.key(0), True)
        (outs, new_aux), vjp = jax.vjp(fwd, a)
        cot = (tuple(jnp.ones(o.shape, o.dtype) for o in outs),
               tuple(jnp.zeros(v.shape, v.dtype) for v in new_aux))
        grads = vjp(cot)
        return outs, new_aux, grads

    try:
        # trace under x64 so an f64 widening ACTUALLY APPEARS in the
        # jaxpr — with x64 off jax silently truncates the cast to f32
        # and the hazard (real on any x64-enabled process) is invisible.
        # Inputs keep their declared dtypes; python-scalar weak types
        # still promote toward the array dtype, so healthy f32 graphs
        # trace identically.
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(train_step if is_train else fwd_only)(
                args, aux)
    except Exception as e:  # noqa: BLE001 — surface, don't crash the lint
        report.extend([Finding(
            "trace-failed", ERROR, "<graph>", "<graph>",
            "tracing the %s program failed: %s"
            % ("train" if is_train else "eval", e))])
        return
    ctx = PassContext(view=view, jaxpr=closed, platform=prog.platform,
                      dtype_policy=dtype_policy, is_train=is_train,
                      config=config or {})
    report.extend(run_passes(ctx, "jaxpr", only))
    report.traced = True


# ----------------------------------------------------------------------
_STEP_ARG_LABELS = ("params", "aux", "opt_state", "batch", "lr", "t", "key")
_STEP_ARG_LABELS_SENTINEL = ("params", "aux", "opt_state", "sentinel",
                             "batch", "lr", "t", "key")


def step_invar_metadata(trainer, closed, args):
    """``(jaxpr, donated_invars, invar_labels, invar_shardings)`` for a
    Trainer's traced fused step: unwrap the single top-level pjit to
    the program whose invars carry donation flags, label every invar
    with its pytree path (``params['fc1_weight']``...), and read the
    LIVE committed sharding of each persistent-state leaf.  Shared by
    :func:`lint_trainer` (donation/zero passes) and the memory
    analyzer (``mem_passes.trainer_timeline`` — per-chip byte
    pricing), so both judge the SAME program.  Any layout surprise
    returns ``(closed, None, None, None)`` — metadata-consuming
    passes deactivate instead of mislabeling."""
    import jax

    sent = getattr(trainer, "_sent", None)
    arg_labels = _STEP_ARG_LABELS if sent is None \
        else _STEP_ARG_LABELS_SENTINEL
    jaxpr, donated, labels, shardings = closed, None, None, None
    eqns = closed.jaxpr.eqns
    if len(eqns) == 1 and eqns[0].primitive.name == "jit":
        jaxpr = eqns[0].params["jaxpr"]
        donated = eqns[0].params.get("donated_invars")
        leaves = jax.tree_util.tree_flatten_with_path(args)[0]
        labels = ["%s%s" % (arg_labels[p[0].idx]
                            if p and p[0].idx < len(arg_labels)
                            else "arg%d" % (p[0].idx if p else 0),
                            jax.tree_util.keystr(p[1:]))
                  for p, _ in leaves]
        # live device shardings for the persistent-state invars (the
        # batch/lr/t/key tail has no committed layout: None) — the
        # zero-opt-state pass reads these to spot replicated state on a
        # data mesh; the mem analyzer to price per-chip bytes exactly
        state_args = (trainer.params, trainer.aux, trainer.opt_state) + \
            (() if sent is None else (sent,))
        state_shards = [getattr(v, "sharding", None)
                        for v in jax.tree_util.tree_leaves(state_args)]
        shardings = state_shards + [None] * (len(labels)
                                             - len(state_shards))
        inner_n = len(getattr(jaxpr, "jaxpr", jaxpr).invars)
        if donated is not None and (len(donated) != inner_n
                                    or len(labels) != inner_n):
            jaxpr = closed
            donated, labels, shardings = None, None, None
    return jaxpr, donated, labels, shardings


def lint_trainer(trainer, config: Optional[Dict[str, Any]] = None,
                 input_dtypes: Optional[Dict[str, Any]] = None,
                 only=None) -> LintReport:
    """Lint a bound+initialized Trainer's fused step: trace
    ``trainer._step_fn`` to its pjit jaxpr, recover ``donated_invars``
    and a pytree-path label per invar, and run the jaxpr passes (the
    donation pass only activates on this path — it needs to know which
    invars are persistent state vs fresh batch inputs).

    ``input_dtypes`` sets the traced batch dtypes (name -> dtype) so
    the lint trace matches the program an int-token or uint8-pipeline
    model actually runs; unlisted inputs trace as float32."""
    if trainer._step_fn is None or trainer.params is None:
        raise MXNetError("lint_trainer needs a bound, initialized Trainer "
                         "(call bind() + init_params() first)")
    args = trainer.abstract_step_args(input_dtypes)
    report = LintReport(model="trainer-step")
    try:
        # x64 trace (Trainer.step_jaxpr): an f64 cast must APPEAR in
        # the jaxpr instead of being silently truncated (both jaxpr
        # entry points must give one verdict for one hazard)
        closed = trainer.step_jaxpr(input_dtypes, x64=True)
    except Exception as e:  # noqa: BLE001
        report.extend([Finding("trace-failed", ERROR, "<step>", "<step>",
                               "tracing the fused step failed: %s" % e)])
        return report
    jaxpr, donated, labels, shardings = \
        step_invar_metadata(trainer, closed, args)
    lint_cfg = dict(config or {})
    lint_cfg.setdefault("data_axis_size", trainer._data_axis_size())
    lint_cfg.setdefault("zero", trainer.zero)
    ctx = PassContext(view=GraphView.from_symbol(trainer.symbol),
                      jaxpr=jaxpr, donated_invars=donated,
                      invar_labels=labels, invar_shardings=shardings,
                      platform=trainer.prog.platform,
                      dtype_policy=trainer.dtype_policy, is_train=True,
                      config=lint_cfg)
    report.extend(run_passes(ctx, "jaxpr", only))
    report.traced = True
    return report


# ----------------------------------------------------------------------
def lint_server(server, config: Optional[Dict[str, Any]] = None,
                only=None) -> LintReport:
    """Lint a :class:`~..serving.server.ModelServer`'s serve path.

    Feeds the server's observed compilation log (every traced batch
    size, per model — recorded by the shared ``CompiledForward``'s
    trace-time counter) plus its AOT bucket set into the jaxpr-level
    passes; the ``serve-shape-bucket`` pass warns on every forward
    compiled for a batch size outside the bucket set (a request that
    slipped past the padding and paid a trace+compile on the hot path).
    No device execution and no re-trace: the log was collected as the
    server ran."""
    lint_cfg = dict(config or {})
    lint_cfg.setdefault("serve_buckets", list(server.buckets))
    # LAZY traces only: an AOT-registered signature (another server's
    # bucket set, a Predictor's construction warmup on the shared
    # compiled forward) is deliberate, not a hot-path stall.  Tenants
    # sharing one compiled forward are reported as one joined entry so
    # a shared stall isn't double-counted.
    lint_cfg.setdefault("serve_batch_sizes", {
        "+".join(names): cf.counts()["lazy_batch_sizes"]
        for cf, names in server._cf_groups()})
    report = LintReport(model="serving")
    ctx = PassContext(jaxpr=None, is_train=False, config=lint_cfg)
    report.extend(run_passes(ctx, "jaxpr", only))
    report.traced = True
    return report
