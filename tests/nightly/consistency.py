#!/usr/bin/env python
"""Cross-backend consistency, registry-wide.

The reference re-runs its op tests on GPU and asserts CPU/GPU executors
match (``tests/python/gpu/test_operator_gpu.py`` + ``check_consistency``,
SURVEY §4).  The TPU analog iterates the SAME case table as the
registry-wide sweep (``tests/test_op_sweep.py`` — every registered op +
alias has a case): each case's symbol is bound with identical inputs on
the host-CPU jax backend and on the TPU backend; outputs (and, for
differentiable cases, input gradients) must match.

Run standalone on the chip (without a TPU backend it fails):

    python tests/nightly/consistency.py            # full registry
    python tests/nightly/consistency.py --sample 6 # every 6th case (CI)
"""
import argparse
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, os.pardir, os.pardir))
sys.path.insert(0, os.path.join(_HERE, os.pardir))

import numpy as np


def _run_case(mx, case, build, ctx, want_grads):
    sym, aux = build(case)
    args = {k: mx.nd.array(v, ctx=ctx) for k, v in case["loc"].items()}
    aux_states = {k: mx.nd.array(v, ctx=ctx) for k, v in (aux or {}).items()}
    grads = None
    if want_grads:
        grads = {k: mx.nd.zeros(v.shape, ctx=ctx)
                 for k, v in case["loc"].items()}
    exe = sym.bind(ctx, args=args, args_grad=grads,
                   aux_states=aux_states or None)
    exe.forward(is_train=want_grads)
    outs = [o.asnumpy() for o in exe.outputs]
    grad_vals = {}
    if want_grads:
        exe.backward([mx.nd.ones(o.shape, ctx=ctx) for o in exe.outputs])
        names = case["grad_nodes"] or list(case["loc"])
        grad_vals = {k: exe.grad_dict[k].asnumpy() for k in names}
    return outs, grad_vals


def run(sample=1):
    """Compare every ``sample``-th case of the sweep between the CPU
    and the TPU backend of this process.  Returns (matched, failed).
    Raises where JAX has no TPU: nothing was compared."""
    import jax
    import mxnet_tpu as mx
    import test_op_sweep as sweep
    from mxnet_tpu.op import registry as _registry

    if jax.devices()[0].platform != "tpu":
        raise mx.MXNetError("cpu-vs-tpu consistency needs the TPU backend; "
                            "JAX reports %r" % jax.devices()[0].platform)
    # f32 convs/matmuls on TPU default to bf16 MXU passes; raise precision
    # so the cross-backend comparison tests math, not rounding mode
    jax.config.update("jax_default_matmul_precision", "highest")

    ran = failures = 0
    for idx, case in enumerate(sweep.CASES):
        if idx % sample:
            continue
        if case["kind"] == "imp":
            continue                     # imperative-only (host-side) op
        op = _registry.get(case["op"])
        if op.uses_rng and case["params"].get("p") != 0.0:
            continue                     # sampler draws are backend-keyed
        want_grads = case["kind"] == "grad"
        try:
            cpu_out, cpu_grad = _run_case(mx, case, sweep._build_symbol,
                                          mx.cpu(), want_grads)
            tpu_out, tpu_grad = _run_case(mx, case, sweep._build_symbol,
                                          mx.tpu(), want_grads)
            for a, b in zip(cpu_out, tpu_out):
                np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)
            for k in cpu_grad:
                np.testing.assert_allclose(cpu_grad[k], tpu_grad[k],
                                           rtol=2e-2, atol=2e-3,
                                           err_msg="grad %s" % k)
            ran += 1
        except Exception as e:                        # noqa: BLE001
            failures += 1
            print("FAIL %-32s %s" % (case["id"], str(e)[:200]))
    print("cpu-vs-tpu consistency: %d cases matched, %d failed "
          "(registry: %d ops + %d aliases)" %
          (ran, failures, len(_registry._REGISTRY),
           len(_registry._ALIASES)))
    return ran, failures


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sample", type=int, default=1,
                    help="run every Nth case (1 = all)")
    opts = ap.parse_args()
    ran, failures = run(opts.sample)
    return 1 if failures or not ran else 0


if __name__ == "__main__":
    sys.exit(main())
