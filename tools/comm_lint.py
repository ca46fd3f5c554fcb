#!/usr/bin/env python
"""Static collective-communication linter CLI.

Extracts an ordered **comm plan** — (primitive, axis, dtype, element
count, predicted wire bytes, ``named_scope`` layer provenance) per
collective — from the jitted programs this repo actually ships traffic
through, and runs the comm rules over each plan
(``mxnet_tpu/analysis/comm_passes.py``):

  * ``trainer-step`` — the fused trainer step under the ZeRO-1 + bf16
    gradient-wire config on a 2-device data mesh (the shard_map'd
    ``lowp_allreduce`` collectives, extracted with layer provenance).
  * ``serving-forward`` — the serving eval program (no collectives on a
    replicated single-host mesh: the baseline records an EMPTY plan, so
    a collective showing up here is loud).
  * ``ring-attention`` — the sequence-parallel ring (ONE fused K/V
    ``ppermute`` per rotation, n-1 rotations, trip-counted through the
    inner loop).
  * ``pipeline`` — the SPMD pipeline on the interleaved v=2 schedule
    (stage-hop ``ppermute`` inside the tick scan; the output collect is
    a select + the same hop — no closing ``psum``).
  * ``comm-source`` — the ``rank-divergent-collective`` AST rule over
    ``mxnet_tpu/`` (rank-conditioned control flow guarding collective
    calls — the classic multi-host wedge).

Everything is pure trace time (no device execution), so the gate runs
in the fast CI tier.  ``--check`` fails on NEW error findings OR a
predicted-GB regression past tolerance vs the checked-in
``COMM_BASELINE.json`` (the ``LINT_BASELINE.json`` ratchet pattern);
``--write-baseline`` re-records both after an intentional change.
Docs: ``docs/how_to/static_analysis.md`` "Communication analysis".
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

COMM_BASELINE_PATH = os.environ.get(
    "MXTPU_COMM_BASELINE", os.path.join(ROOT, "COMM_BASELINE.json"))


def _mlp_trainer(zero=1, grad_dtype="bf16"):
    """The canonical analyzed trainer: a momentum-SGD MLP with a >1 MB
    weight on a 2-device data mesh under ZeRO-1 + bf16 grad comm — the
    config whose gradient wire is all explicit shard_map collectives
    (``collectives.lowp_allreduce``), so the extracted plan exercises
    provenance, the byte model, and the keep-shard accounting."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=512, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    devices = jax.devices()
    mesh = parallel.make_mesh({"data": min(2, len(devices))}, devices)
    trainer = parallel.Trainer(
        sym, mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9),
        mesh=mesh, zero=zero, grad_dtype=grad_dtype)
    trainer.bind(data_shapes={"data": (8, 600)},
                 label_shapes={"softmax_label": (8,)})
    trainer.init_params(mx.init.Xavier())
    return trainer


def trainer_step_target(inject=None):
    """(plan, jaxpr, config) for the fused-step target.  ``inject``
    deliberately mis-builds the program so the gate's failure path is
    testable end to end: ``f32-wire`` keeps the policy claim at bf16
    while the program ships f32 gradients."""
    from mxnet_tpu.analysis import comm_passes
    grad_dtype = "f32" if inject == "f32-wire" else "bf16"
    trainer = _mlp_trainer(zero=1, grad_dtype=grad_dtype)
    plan = trainer.comm_plan()
    jaxpr = trainer.step_jaxpr()
    cfg = {"axis_sizes": dict(trainer.mesh.shape), "grad_dtype": "bf16",
           "zero": trainer.zero, "comm_plan": plan}
    return plan, jaxpr, cfg, trainer


def serving_forward_target(trainer):
    """The eval/serving forward of the same model: replicated weights,
    row-sharded batch — GSPMD decides placement, the traced program
    carries no explicit collective, and the baseline pins that."""
    import jax
    import numpy as np
    plan_args = (
        {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
         for n, v in trainer.params.items()},
        {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
         for n, v in trainer.aux.items()},
        {n: jax.ShapeDtypeStruct(tuple(s), np.float32)
         for n, s in trainer._input_shapes.items()},
        jax.random.key(0),
    )
    jaxpr = jax.make_jaxpr(trainer._eval_fn)(*plan_args)
    cfg = {"axis_sizes": dict(trainer.mesh.shape), "is_train": False}
    return jaxpr, cfg


def ring_attention_target():
    import jax
    import numpy as np
    from mxnet_tpu.parallel import make_mesh, ring_attention_sharded

    mesh = make_mesh({"seq": min(2, len(jax.devices()))}, jax.devices())

    def prog(q, k, v):
        with jax.named_scope("ring_attn"):
            return ring_attention_sharded(q, k, v, mesh)

    sds = jax.ShapeDtypeStruct((2, 8, 2, 4), np.float32)
    jaxpr = jax.make_jaxpr(prog)(sds, sds, sds)
    return jaxpr, {"axis_sizes": dict(mesh.shape)}


def pipeline_target():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.parallel import make_mesh, pipeline_apply

    mesh = make_mesh({"pipe": min(2, len(jax.devices()))}, jax.devices())
    S = 2 * mesh.shape["pipe"]       # v=2 stages/device: interleaved
    d = 16
    params = {"w": jax.ShapeDtypeStruct((S, d, d), np.float32)}

    def stage(p, x):
        return jnp.tanh(x @ p["w"])

    def prog(params, xs):
        with jax.named_scope("pipe_apply"):
            return pipeline_apply(stage, params, xs, mesh,
                                  schedule="interleaved")

    xs = jax.ShapeDtypeStruct((4, 8, d), np.float32)
    jaxpr = jax.make_jaxpr(prog)(params, xs)
    return jaxpr, {"axis_sizes": dict(mesh.shape)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("targets", nargs="*",
                    help="targets to analyze (default: trainer-step, "
                         "serving-forward, ring-attention, pipeline, "
                         "comm-source)")
    ap.add_argument("--plan", action="store_true",
                    help="print every comm-plan entry (default: first 8 "
                         "per target)")
    ap.add_argument("--digest", action="store_true",
                    help="print each target's plan digest (the "
                         "cross-rank parity token)")
    ap.add_argument("--source-root", default=None,
                    help="source tree for the rank-divergence scan "
                         "(default: the installed mxnet_tpu package)")
    ap.add_argument("--check", action="store_true",
                    help="gate NEW error findings + predicted-GB "
                         "regressions against %s"
                         % os.path.basename(COMM_BASELINE_PATH))
    ap.add_argument("--write-baseline", action="store_true",
                    help="record current findings + comm GB into the "
                         "baseline (ratchet after an intentional change)")
    ap.add_argument("--severity", choices=("error", "warn", "info"),
                    default=None,
                    help="minimum severity to report (display filter; "
                         "the --check gate always judges errors)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full reports as one JSON object")
    ap.add_argument("--max-findings", type=int, default=25,
                    help="findings printed per target (default 25)")
    ap.add_argument("--inject", choices=("f32-wire",), default=None,
                    help=argparse.SUPPRESS)  # gate-failure test hook
    args = ap.parse_args(argv)

    # trace-time only: keep the gate off the chip, on two virtual host
    # devices so the mesh targets get real >1 axes (graph_lint pattern)
    if "MXTPU_LINT_PLATFORM" not in os.environ:
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=2")
        import jax
        jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu import analysis
    from mxnet_tpu.analysis import comm_passes

    all_targets = ["trainer-step", "serving-forward", "ring-attention",
                   "pipeline", "comm-source"]
    names = args.targets or all_targets
    unknown = sorted(set(names) - set(all_targets))
    if unknown:
        raise SystemExit("unknown target(s) %s (have %s)"
                         % (unknown, all_targets))

    baseline = analysis.load_baseline(COMM_BASELINE_PATH) or {}
    tol = float(os.environ.get("MXTPU_COMM_TOLERANCE_PCT", "3"))

    reports, extras = {}, {}
    trainer = None
    for name in names:
        if name == "comm-source":
            reports[name] = analysis.lint_comm_source(
                root=args.source_root).dedupe()
            continue
        if name == "trainer-step":
            plan, jaxpr, cfg, trainer = trainer_step_target(args.inject)
        elif name == "serving-forward":
            if trainer is None:
                trainer = _mlp_trainer()
            jaxpr, cfg = serving_forward_target(trainer)
            plan = None
        elif name == "ring-attention":
            jaxpr, cfg = ring_attention_target()
            plan = None
        else:
            jaxpr, cfg = pipeline_target()
            plan = None
        entry = baseline.get(name) or {}
        # never feed the OLD baseline figure on the write path: a
        # ratchet run while comm has moved would otherwise mint a
        # comm-budget error finding and record errors_by_rule
        # {"comm-budget": 1} into the fresh baseline, permanently
        # disarming the budget gate for this target
        if "comm_gb_per_step" in entry and not args.write_baseline:
            cfg["comm_baseline_gb"] = entry["comm_gb_per_step"]
            cfg["comm_tolerance_pct"] = entry.get("tolerance_pct", tol)
        report = comm_passes.lint_comm(jaxpr, model=name, plan=plan,
                                       config=cfg)
        report.dedupe()
        reports[name] = report
        gb = comm_passes.plan_wire_gb(report.comm_plan)
        # 9 decimals = 1-byte resolution at GB scale: a micro-GB target
        # (ring-attention's KBs of ppermute) must not acquire a
        # phantom delta from the recording itself exceeding the 3%
        # tolerance
        extras[name] = {"comm_gb_per_step": round(gb, 9),
                        "tolerance_pct": tol}
        show = report.comm_plan if args.plan else report.comm_plan[:8]
        print("comm-plan[%s]: %d collective(s), %.6f GB/step predicted, "
              "digest %.12s" % (name, len(report.comm_plan), gb,
                                report.comm_digest))
        for e in show:
            print("  " + e.format())
        if len(report.comm_plan) > len(show):
            print("  ... %d more (--plan shows all)"
                  % (len(report.comm_plan) - len(show)))
        if args.digest:
            print("comm-digest[%s]: %s" % (name, report.comm_digest))

    print(analysis.render_reports(reports, severity=args.severity,
                                  as_json=args.json,
                                  max_findings=args.max_findings))
    return analysis.run_gate(reports, "comm-lint", check=args.check,
                             write=args.write_baseline,
                             path=COMM_BASELINE_PATH, extras=extras)


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
