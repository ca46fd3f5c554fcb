#!/usr/bin/env python
"""Trace-time graph linter CLI.

Runs the ``mxnet_tpu.analysis`` pass pipeline — whole-graph shape/dtype
inference with per-node diagnostics, dead-code / duplicate-subgraph /
TPU-layout / f64-promotion symbol passes, then ``jax.make_jaxpr`` over
the train program for the jaxpr-level hazards (f64 widening, host
callbacks, non-donated buffers, unfused gather/scatter) — on:

  * serialized symbol JSON files passed as arguments, or
  * the bench models (ResNet-50 NHWC at the bench shape + the
    transformer LM) when called with no files.

Everything is pure trace time (no device execution), so the gate runs
in the fast CI tier.  ``--check`` diffs error-severity findings against
the checked-in ``LINT_BASELINE.json`` and exits non-zero on NEW errors
(the ratchet of ``mxnet_tpu/analysis/baseline.py``);
``--write-baseline`` re-records after an intentional change.  Rule
catalog: ``docs/how_to/graph_lint.md``.
"""
import argparse
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bench_targets():
    """The two gated bench graphs at their canonical shapes.  Trace
    cost is shape-independent (abstract evaluation), so the full bench
    shapes are used even on CPU-only hosts."""
    from mxnet_tpu import models
    return {
        "resnet-50": dict(
            sym=models.get_symbol("resnet-50", num_classes=1000,
                                  layout="NHWC"),
            shapes={"data": (256, 224, 224, 3), "softmax_label": (256,)},
            dtypes=None),
        "transformer": dict(
            sym=models.get_symbol("transformer", num_classes=1000,
                                  seq_len=128, num_hidden=256, num_heads=4),
            shapes={"data": (8, 128), "softmax_label": (8, 128)},
            dtypes={"data": np.int32}),
    }


def trainer_step_report():
    """Lint the FUSED TRAINER STEP on a small data mesh — the only path
    where the buffer-level passes (donation, zero-opt-state) have the
    pjit metadata they need.  A momentum-SGD MLP with a >1 MB weight on
    a 2-device data mesh, zero off: the checked-in baseline records the
    expected zero-opt-state warn, so a change that silently loses (or
    multiplies) the finding shows up as baseline drift.  Built on
    virtual CPU devices (main() forces 2); on a 1-device platform the
    mesh degrades to size 1 and the pass self-disables (warn drift is
    informational — errors gate)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import analysis, parallel

    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=512, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    devices = jax.devices()
    mesh = parallel.make_mesh({"data": min(2, len(devices))}, devices)
    trainer = parallel.Trainer(
        sym, mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9),
        mesh=mesh)
    trainer.bind(data_shapes={"data": (8, 600)},
                 label_shapes={"softmax_label": (8,)})
    trainer.init_params(mx.init.Xavier())
    report = analysis.lint_trainer(trainer)
    report.model = "trainer-step"
    return report


def serving_report():
    """Lint the SERVE PATH: a minimal in-process ModelServer (the bench
    MLP, a 2-bucket AOT set) driven through a few mixed-size requests,
    then ``analysis.lint_server`` over its observed compilation log.
    The checked-in baseline records ZERO findings — a warn showing up
    here means a forward compiled for a batch size outside the bucket
    set, i.e. the serve path's padding regressed
    (docs/how_to/serving.md)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import serving

    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    args = {"fc1_weight": mx.nd.array(rng.randn(16, 8).astype("f")),
            "fc1_bias": mx.nd.array(np.zeros(16, "f")),
            "fc2_weight": mx.nd.array(rng.randn(4, 16).astype("f")),
            "fc2_bias": mx.nd.array(np.zeros(4, "f"))}
    srv = serving.ModelServer(buckets=[1, 2], max_wait_us=500)
    srv.add_model("mlp", sym, args, {}, input_shapes={"data": (8,)})
    with srv:
        # exercise the hot path so the lint sees a REAL trace log: one
        # single-example and one padded two-row cycle, both in-bucket
        srv.predict(data=np.zeros((8,), "f"))
        srv.predict(data=np.zeros((2, 8), "f"))
        report = srv.lint()
    report.model = "serving"
    return report


def quantized_mlp_report():
    """Lint a QUANTIZED serving graph: an MLP with a >1 MB weight put
    through ``contrib.quantization.quantize_model`` (weights-only) and
    traced as the eval program.  The dequant-unfused jaxpr pass walks
    the int8->f32 ``convert_element_type`` chains; the checked-in
    baseline records ZERO findings — a finding here means the dequant
    subgraph the rewriter emits stopped fusing into its consumer, i.e.
    the int8 footprint/bandwidth win silently regressed
    (docs/how_to/quantization.md).  Pure trace time, like the bench
    targets."""
    import mxnet_tpu as mx
    from mxnet_tpu import analysis
    from mxnet_tpu.contrib import quantization

    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=512, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=128, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(0)
    # fc1: (512, 1024) -> 512K int8 elems, a 2 MB f32 dequant (over the
    # pass's 1 MiB floor); fc2 stays above min_elems too so BOTH
    # dequant chains are exercised
    args = {"fc1_weight": mx.nd.array(rng.randn(512, 1024).astype("f")),
            "fc1_bias": mx.nd.array(np.zeros(512, "f")),
            "fc2_weight": mx.nd.array(rng.randn(128, 512).astype("f")),
            "fc2_bias": mx.nd.array(np.zeros(128, "f"))}
    qsym, _, _ = quantization.quantize_model(sym, args, {})
    report = analysis.lint_symbol(
        qsym, shapes={"data": (8, 1024), "softmax_label": (8,)},
        is_train=False, model="quantized-mlp")
    return report


def _parse_shapes(specs):
    """--shape name=(1,224,224,3) pairs -> dict."""
    import ast
    out = {}
    for spec in specs or []:
        name, _, val = spec.partition("=")
        if not val:
            raise SystemExit("--shape expects name=(d0,d1,...), got %r"
                             % spec)
        v = ast.literal_eval(val)
        out[name] = tuple(v) if isinstance(v, (tuple, list)) else (int(v),)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("graphs", nargs="*",
                    help="symbol JSON files to lint (default: the bench "
                         "ResNet-50 and transformer graphs)")
    ap.add_argument("--model", action="append", default=None,
                    help="bench model name(s) to lint instead of all "
                         "(resnet-50, transformer)")
    ap.add_argument("--shape", action="append", default=None,
                    metavar="NAME=(D0,D1,...)",
                    help="input shape for JSON graphs (repeatable)")
    ap.add_argument("--no-trace", action="store_true",
                    help="symbol-level passes only (skip jax.make_jaxpr)")
    ap.add_argument("--eval", action="store_true",
                    help="trace the eval program instead of fwd+bwd")
    ap.add_argument("--policy", default=None,
                    help="dtype policy for the trace (bytediet|legacy)")
    ap.add_argument("--check", action="store_true",
                    help="gate NEW error findings against %s"
                         % os.path.basename("LINT_BASELINE.json"))
    ap.add_argument("--write-baseline", action="store_true",
                    help="record current findings into the baseline "
                         "(ratchet after an intentional change)")
    ap.add_argument("--severity", choices=("error", "warn", "info"),
                    default=None,
                    help="minimum severity to report (display filter; "
                         "the --check gate always judges errors)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full reports as one JSON object")
    ap.add_argument("--max-findings", type=int, default=25,
                    help="findings printed per graph (default 25)")
    args = ap.parse_args(argv)

    # trace-time only: keep the gate off the chip unless the caller
    # explicitly wants a platform
    if "MXTPU_LINT_PLATFORM" not in os.environ:
        # two virtual host devices so the trainer-step target gets a real
        # data mesh (must land before the first backend touch)
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=2")
        import jax
        jax.config.update("jax_platforms", "cpu")

    from mxnet_tpu import analysis

    reports = {}
    if args.graphs:
        shapes = _parse_shapes(args.shape)
        for path in args.graphs:
            with open(path) as f:
                txt = f.read()
            name = os.path.basename(path)
            reports[name] = analysis.lint_json(
                txt, shapes=shapes or None, trace=not args.no_trace,
                is_train=not args.eval, dtype_policy=args.policy,
                model=name)
    else:
        targets = bench_targets()
        names = args.model or sorted(targets) + ["trainer-step", "serving",
                                                 "quantized-mlp",
                                                 "program-source"]
        for name in names:
            if name == "trainer-step":
                reports[name] = trainer_step_report()
                continue
            if name == "serving":
                reports[name] = serving_report()
                continue
            if name == "quantized-mlp":
                reports[name] = quantized_mlp_report()
                continue
            if name == "program-source":
                # the program-bypass AST rule over the unified-path
                # layers (trainer / executor / serving / predictor):
                # every compile must flow through
                # mxnet_tpu.program.CompiledProgram — baseline holds
                # ZERO findings (docs/how_to/compiled_programs.md)
                reports[name] = analysis.lint_program_source()
                continue
            if name not in targets:
                raise SystemExit("unknown bench model %r (have %s, "
                                 "trainer-step, serving, quantized-mlp, "
                                 "program-source)"
                                 % (name, sorted(targets)))
            t = targets[name]
            reports[name] = analysis.lint_symbol(
                t["sym"], shapes=t["shapes"], dtypes=t["dtypes"],
                trace=not args.no_trace, is_train=not args.eval,
                dtype_policy=args.policy, model=name)

    # stable-key dedupe + display-severity filter (render_reports is
    # shared with tools/concurrency_lint.py so graph and concurrency
    # findings read as one report format; it filters display copies —
    # the gate below still judges everything)
    for r in reports.values():
        r.dedupe()
    print(analysis.render_reports(reports, severity=args.severity,
                                  as_json=args.json,
                                  max_findings=args.max_findings))

    # shared ratchet block (analysis.run_gate — graph, concurrency, and
    # comm lint all gate through the same baseline logic)
    return analysis.run_gate(reports, "graph-lint", check=args.check,
                             write=args.write_baseline)


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
