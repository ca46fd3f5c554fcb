#!/usr/bin/env python
"""Static memory linter CLI: buffer-liveness peak-HBM prediction.

Walks the SAME lowered programs the comm linter walks and predicts
``peak_bytes_per_chip`` from a buffer-liveness timeline
(``mxnet_tpu/analysis/mem_passes.py``), then runs the mem rules over
each program:

  * ``trainer-step`` — the fused trainer step (ZeRO-1 + bf16 gradient
    wire on a 2-device data mesh): donated state released at its
    donation point, ZeRO-sharded optimizer state priced per chip
    through its committed sharding.
  * ``serving-forward`` — the eval/serving forward of the same model
    (replicated weights, row-sharded batch).
  * ``ring-attention`` — the sequence-parallel ring (block-local
    shard_map bodies priced at face value).
  * ``pipeline`` — the SPMD pipeline on the interleaved v=2 schedule
    (stage-hop scan: body temporaries counted once, stacked outputs at
    call level).
  * ``transformer-large`` — the composed workload's full train
    step (pipeline x MoE x grad-accum x ZeRO momentum) at the exact
    ``transformer_large()`` config.
  * ``ringattn-long-context`` — the long-context causal ring-attention
    LM forward at the exact ``ringattn_long_context()`` config.

Rules: ``mem-budget`` (predicted-GB ratchet vs ``MEM_BASELINE.json``),
``mem-capacity`` (peak vs ``MXTPU_HBM_BYTES`` / detected device memory
— the OOM-before-you-run gate), ``remat-opportunity``,
``donation-missed``, ``pad-waste``.

Everything is pure trace time (no device execution), so the gate runs
in the fast CI tier.  ``--check`` fails on NEW error findings OR a
predicted-GB regression past tolerance vs the checked-in
``MEM_BASELINE.json`` (the ``LINT_BASELINE.json`` ratchet pattern);
``--write-baseline`` re-records both after an intentional change.
Docs: ``docs/how_to/static_analysis.md`` "Memory analysis".
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MEM_BASELINE_PATH = os.environ.get(
    "MXTPU_MEM_BASELINE", os.path.join(ROOT, "MEM_BASELINE.json"))


def _mlp_trainer(zero=1, grad_dtype="bf16"):
    """The canonical analyzed trainer (comm_lint's twin): a momentum-SGD
    MLP with a >1 MB weight on a 2-device data mesh under ZeRO-1 + bf16
    grad comm — donation, sharded optimizer state, and the batch
    row-shard all visible to the byte model."""
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


def trainer_step_target():
    """(jaxpr, config, trainer) for the fused-step target, with the
    lint_trainer-style invar metadata so state buffers are priced per
    chip exactly and ``donation-missed`` can see the donation flags."""
    from mxnet_tpu.analysis.lint import step_invar_metadata
    trainer = _mlp_trainer()
    closed = trainer.step_jaxpr()
    abstract = trainer.abstract_step_args()
    jaxpr, donated, labels, shardings = \
        step_invar_metadata(trainer, closed, abstract)
    batch_leading = {int(s[0]) for s in trainer._input_shapes.values()
                     if s}
    cfg = {"axis_sizes": dict(trainer.mesh.shape),
           "donated_invars": donated, "invar_labels": labels,
           "invar_shardings": shardings,
           "batch_leading": batch_leading,
           "data_axis_size": trainer._data_axis_size(),
           "remat": trainer.remat, "is_train": True}
    return jaxpr, cfg, trainer


def serving_forward_target(trainer):
    """The eval/serving forward of the same model: no donation, weights
    replicated and resident for the whole program."""
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
    batch_leading = {int(s[0]) for s in trainer._input_shapes.values()
                     if s}
    cfg = {"axis_sizes": dict(trainer.mesh.shape), "is_train": False,
           "batch_leading": batch_leading,
           "data_axis_size": trainer._data_axis_size()}
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
    return jaxpr, {"axis_sizes": dict(mesh.shape), "is_train": False}


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
    return jaxpr, {"axis_sizes": dict(mesh.shape), "is_train": False}


def _abstract(tree):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def transformer_large_target():
    """The composed transformer-large train step, traced abstractly at
    ``transformer_large()`` config — the peak-HBM ratchet for the
    composed workload (needs the 8-device mesh)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel import transformer as tfm

    cfg = tfm.transformer_large()
    mesh = make_mesh({"pipe": cfg.pipe}, jax.devices())
    params = _abstract(tfm.transformer_init(jax.random.PRNGKey(0), cfg))
    mom = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    step = tfm.make_train_step(cfg, mesh, params_template=params)
    toks = jax.ShapeDtypeStruct(
        (cfg.grad_accum, cfg.n_micro, cfg.microbatch, cfg.seq),
        np.int32)

    def prog(params, mom, toks):
        with jax.named_scope("transformer_large_step"):
            return step(params, mom, toks)

    jaxpr = jax.make_jaxpr(prog)(params, mom, toks)
    return jaxpr, {"axis_sizes": dict(mesh.shape), "is_train": True}


def ringattn_long_context_target():
    """The long-context ring-attention LM forward at the bench config
    (needs the 8-device mesh for the seq axis)."""
    import jax
    import numpy as np
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel import transformer as tfm

    cfg = tfm.ringattn_long_context()
    mesh = make_mesh({"seq": cfg.seq_shards}, jax.devices())
    params = _abstract(tfm.ringattn_init(jax.random.PRNGKey(0), cfg))
    toks = jax.ShapeDtypeStruct((cfg.microbatch, cfg.seq), np.int32)

    def prog(params, toks):
        with jax.named_scope("ringattn_forward"):
            return tfm.ringattn_forward(params, toks, cfg, mesh)

    jaxpr = jax.make_jaxpr(prog)(params, toks)
    return jaxpr, {"axis_sizes": dict(mesh.shape), "is_train": False}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("targets", nargs="*",
                    help="targets to analyze (default: trainer-step, "
                         "serving-forward, ring-attention, pipeline, "
                         "transformer-large, ringattn-long-context)")
    ap.add_argument("--live", action="store_true",
                    help="print the full liveness top-10 per target "
                         "(default: top 3)")
    ap.add_argument("--check", action="store_true",
                    help="gate NEW error findings + predicted-GB "
                         "regressions against %s"
                         % os.path.basename(MEM_BASELINE_PATH))
    ap.add_argument("--write-baseline", action="store_true",
                    help="record current findings + peak GB into the "
                         "baseline (ratchet after an intentional change)")
    ap.add_argument("--severity", choices=("error", "warn", "info"),
                    default=None,
                    help="minimum severity to report (display filter; "
                         "the --check gate always judges errors)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full reports as one JSON object")
    ap.add_argument("--max-findings", type=int, default=25,
                    help="findings printed per target (default 25)")
    ap.add_argument("--inject", choices=("capacity",), default=None,
                    help=argparse.SUPPRESS)  # gate-failure test hook
    args = ap.parse_args(argv)

    # trace-time only: keep the gate off the chip, on EIGHT virtual
    # host devices so the composed bench-config targets trace at their
    # real pipe/seq axis sizes (the 2-axis targets still take
    # min(2, ...) and are unchanged)
    if "MXTPU_LINT_PLATFORM" not in os.environ:
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8")
        import jax
        jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu import analysis
    from mxnet_tpu.analysis import mem_passes

    all_targets = ["trainer-step", "serving-forward", "ring-attention",
                   "pipeline", "transformer-large",
                   "ringattn-long-context"]
    names = args.targets or all_targets
    unknown = sorted(set(names) - set(all_targets))
    if unknown:
        raise SystemExit("unknown target(s) %s (have %s)"
                         % (unknown, all_targets))

    baseline = analysis.load_baseline(MEM_BASELINE_PATH) or {}
    tol = float(os.environ.get("MXTPU_MEM_TOLERANCE_PCT", "5"))

    reports, extras = {}, {}
    trainer = None
    for name in names:
        if name == "trainer-step":
            jaxpr, cfg, trainer = trainer_step_target()
        elif name == "serving-forward":
            if trainer is None:
                trainer = _mlp_trainer()
            jaxpr, cfg = serving_forward_target(trainer)
        elif name == "ring-attention":
            jaxpr, cfg = ring_attention_target()
        elif name == "transformer-large":
            jaxpr, cfg = transformer_large_target()
        elif name == "ringattn-long-context":
            jaxpr, cfg = ringattn_long_context_target()
        else:
            jaxpr, cfg = pipeline_target()
        entry = baseline.get(name) or {}
        # never feed the OLD baseline figure on the write path: a
        # ratchet run while the footprint has moved would otherwise
        # mint a mem-budget error finding and record errors_by_rule
        # {"mem-budget": 1} into the fresh baseline, permanently
        # disarming the budget gate for this target
        if "mem_peak_gb" in entry and not args.write_baseline:
            cfg["mem_baseline_gb"] = entry["mem_peak_gb"]
            cfg["mem_tolerance_pct"] = entry.get("tolerance_pct", tol)
        if args.inject == "capacity":
            cfg["capacity_bytes"] = 1   # everything breaches: gate test
        report = mem_passes.lint_mem(jaxpr, model=name, config=cfg)
        report.dedupe()
        reports[name] = report
        t = report.mem_timeline
        gb = mem_passes.timeline_peak_gb(t)
        # 9 decimals = 1-byte resolution at GB scale (the comm_lint
        # recording rule): a KB-scale target must not acquire a phantom
        # delta from the rounding itself exceeding the tolerance
        extras[name] = {"mem_peak_gb": round(gb, 9),
                        "tolerance_pct": tol}
        print("mem-timeline[%s]: %s"
              % (name, t.format_top(10 if args.live else 3)))

    print(analysis.render_reports(reports, severity=args.severity,
                                  as_json=args.json,
                                  max_findings=args.max_findings))
    return analysis.run_gate(reports, "mem-lint", check=args.check,
                             write=args.write_baseline,
                             path=MEM_BASELINE_PATH, extras=extras)


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
