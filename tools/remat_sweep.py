#!/usr/bin/env python
"""Sweep rematerialization policies on the bench model and record the
throughput + XLA cost-model accounting for each.

The fused ResNet-50 step was HBM-bandwidth-bound in the older record
from before PR 1 (~37% MFU with the MXU two-thirds idle — ROOFLINE.json):
remat trades free MXU
flops for scarce HBM bytes by saving fewer residuals and recomputing
the rest inside backward.  This tool measures each policy end-to-end on
the real chip and writes ``REMAT_SWEEP.json`` at the repo root — the
artifact behind bench.py's choice of default policy.

Reference contract being beaten: the reference has no remat story at
all (``mirror`` in old mxnet was memonger, docs/how_to/smart_cache.md);
its P100 number (BASELINE.md) is the target.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

POLICIES = ("none", "convs_dots", "dots", "nothing")


def bench_policy(policy, batch=256, image=224, steps=60, warmup=5):
    """Fresh Module on the bench model under one remat policy; returns
    throughput + cost-model accounting."""
    os.environ["MXTPU_MODULE_FUSED"] = "always"
    os.environ["MXTPU_REMAT"] = policy
    import jax  # noqa: F401  (backend init before Module construction)
    import mxnet_tpu as mx
    from mxnet_tpu import io, models

    sym = models.get_symbol("resnet-50", num_classes=1000, layout="NHWC")
    mod = mx.mod.Module(context=mx.tpu(), symbol=sym,
                        compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (batch, image, image, 3))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / batch})
    assert mod._trainer is not None
    assert mod._trainer.remat == policy

    rng = np.random.RandomState(0)
    x = rng.normal(0, 1, (batch, image, image, 3)).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.float32)
    data_batch = io.DataBatch(data=[mx.nd.array(x)],
                              label=[mx.nd.array(y)], pad=0)
    metric = mx.metric.create("acc")

    from tools.stepcost import (compile_step, cost_analysis,
                                timed_module_steps)
    elapsed, compile_s = timed_module_steps(mod, metric, data_batch,
                                            steps, warmup=warmup)
    img_s = batch * steps / elapsed

    row = {"policy": policy,
           "img_per_sec": round(img_s, 1),
           "step_ms": round(1e3 * elapsed / steps, 2),
           "compile_warmup_s": round(compile_s, 1)}
    try:
        comp = compile_step(mod._trainer,
                            {"data": data_batch.data[0].data,
                             "softmax_label": data_batch.label[0].data})
        ca = cost_analysis(comp)
        flops, byts = ca["flops"], ca["bytes"]
        row["cost_model_tflop_per_step"] = round(flops / 1e12, 3)
        row["cost_model_gb_per_step"] = round(byts / 1e9, 2)
        row["achieved_tflops"] = round(flops * img_s / batch / 1e12, 1)
        row["achieved_gbps_cost_model"] = round(byts * img_s / batch / 1e9, 1)
        mem = comp.memory_analysis()
        if mem is not None:
            row["temp_alloc_gb"] = round(
                getattr(mem, "temp_size_in_bytes", 0) / 1e9, 2)
    except Exception as e:                                  # noqa: BLE001
        row["cost_model_error"] = str(e)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--policies", default=",".join(POLICIES))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=256)
    args = ap.parse_args(argv)

    from mxnet_tpu import program
    program.place_compile_cache()
    rows = []
    for pol in args.policies.split(","):
        print("=== policy %s ===" % pol, file=sys.stderr)
        rows.append(bench_policy(pol, batch=args.batch, steps=args.steps))
        print(json.dumps(rows[-1]), file=sys.stderr)

    best = max(rows, key=lambda r: r["img_per_sec"])
    result = {"model": "resnet-50 NHWC bf16 batch %d" % args.batch,
              "note": ("rates here read a few %% below the BENCH "
                       "headline for the same policy because this "
                       "tool times a %d-step window per policy while "
                       "bench.py amortizes fixed overheads over a "
                       "longer one; both share tools/stepcost timing, "
                       "so any delta is window amortization, not a "
                       "measurement disagreement" % args.steps),
              "best_policy": best["policy"],
              "best_img_per_sec": best["img_per_sec"],
              "rows": rows}
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "REMAT_SWEEP.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
