#!/usr/bin/env python
"""Memory-cost planning for a deep net, the TPU way (reference
``example/memcost/inception_memcost.py``).

The reference demonstrated memonger: setting ``mirror`` attributes so
the executor drops and recomputes cheap activations, then comparing the
allocated bytes with/without mirroring.  The TPU-native analog is
rematerialization policies on the fused train step (``jax.checkpoint``
inside the Trainer): XLA reports, per policy, the temp-buffer
allocation (what memonger's "cost" column showed) and the recompute
flops it paid for the saving.

Compile-only — no chip time is needed to *plan* memory, so this runs
anywhere (CPU included) in seconds with a tiny spatial size; the
relative savings track the policy, not the batch.

Run: ``python examples/memcost/inception_memcost.py``
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import mxnet_tpu as mx                                      # noqa: E402
from mxnet_tpu import models                                # noqa: E402
from mxnet_tpu.parallel.trainer import Trainer              # noqa: E402
from mxnet_tpu import optimizer as opt                      # noqa: E402

POLICIES = ("none", "convs_dots", "dots", "nothing")


def plan(policy, batch, image, num_classes=100):
    """Compile the fused inception-bn train step under one remat policy
    and read XLA's memory/cost analysis — no step is executed."""
    import jax.numpy as jnp
    from tools.stepcost import compile_step, cost_analysis

    sym = models.get_symbol("inception-bn", num_classes=num_classes)
    tr = Trainer(sym, opt.SGD(learning_rate=0.1, momentum=0.9),
                 remat=policy)
    tr.bind(data_shapes={"data": (batch, 3, image, image)},
            label_shapes={"softmax_label": (batch,)})
    tr.init_params(initializer=mx.init.Xavier(magnitude=2.0))

    rng = np.random.RandomState(0)
    comp = compile_step(tr, {
        "data": jnp.asarray(rng.normal(0, 1, (batch, 3, image, image))
                            .astype(np.float32)),
        "softmax_label": jnp.asarray(
            rng.randint(0, num_classes, (batch,)).astype(np.float32))})
    ca = cost_analysis(comp)
    row = {"policy": policy,
           "cost_model_gflop_per_step": round(ca["flops"] / 1e9, 2),
           "cost_model_gb_per_step": round(ca["bytes"] / 1e9, 3)}
    mem = comp.memory_analysis()
    temp = getattr(mem, "temp_size_in_bytes", 0) if mem is not None else 0
    if temp:              # the CPU backend reports 0; TPU reports real
        row["temp_alloc_mb"] = round(temp / 1e6, 1)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--image", type=int, default=64)
    args = ap.parse_args(argv)

    rows = [plan(p, args.batch, args.image) for p in POLICIES]
    for r in rows:
        print(json.dumps(r))

    by = {r["policy"]: r for r in rows}
    flop_ratio = (by["nothing"]["cost_model_gflop_per_step"]
                  / max(by["none"]["cost_model_gflop_per_step"], 1e-9))
    if "temp_alloc_mb" in by["none"] and "temp_alloc_mb" in by["nothing"]:
        full, none = by["none"]["temp_alloc_mb"], \
            by["nothing"]["temp_alloc_mb"]
        print("full remat keeps %.1f%% of the no-remat temp allocation "
              "at %.2fx the flops" % (100.0 * none / max(full, 1e-9),
                                      flop_ratio))
        # the planning contract: saving fewer residuals must not RAISE
        # the temp allocation
        assert none <= full * 1.05, (none, full)
    else:
        print("backend reports no temp-allocation stats (CPU); flop "
              "side of the trade: full remat recomputes the forward at "
              "%.2fx the base step flops" % flop_ratio)
    # the flop signal is backend-independent: recomputing the whole
    # forward must cost strictly more flops than saving every residual
    assert flop_ratio > 1.05, flop_ratio
    return 0


if __name__ == "__main__":
    sys.exit(main())
