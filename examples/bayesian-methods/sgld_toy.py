#!/usr/bin/env python
"""Stochastic Gradient Langevin Dynamics, toy-sized (reference
``example/bayesian-methods/sgld.ipynb`` + ``bdk.ipynb``): the ``SGLD``
optimizer injects Gaussian noise scaled to the learning rate so the
iterates SAMPLE from the posterior instead of collapsing to the MAP —
the classic 2-parameter Gaussian-mixture posterior demo.  Checks both
that the sampler finds the posterior mode region and that it keeps
exploring (nonzero posterior variance), which plain SGD would not.

This trains through the CLASSIC executor path on purpose: SGLD is the
one shipped optimizer without a fused-step rule (the fused Module path
falls back automatically, tests/test_module.py).

Run: python examples/bayesian-methods/sgld_toy.py
"""
import argparse
import logging
import os
import sys

# tiny-batch toy: latency-bound, not compute-bound — it selects the CPU
# backend unless MXTPU_TOY_BACKEND says otherwise (same preamble as
# examples/warpctc)
if os.environ.get("MXTPU_TOY_BACKEND", "cpu") == "cpu":
    import jax
    jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir))

import numpy as np

import mxnet_tpu as mx

THETA = np.array([0.0, 2.0], "f")      # true generating parameters
SIGMA_X = 1.0                          # observation noise
N = 120


def make_data(rng):
    """Mixture observations: x ~ 0.5 N(t0, 1) + 0.5 N(t0+t1, 1), separated enough that
    the posterior concentrates on the (symmetric) true modes."""
    comp = rng.rand(N) < 0.5
    x = np.where(comp, rng.normal(THETA[0], SIGMA_X, N),
                 rng.normal(THETA[0] + THETA[1], SIGMA_X, N))
    return x.astype("f")


def log_posterior_grad(theta, x):
    """d log p(theta | x) / d theta (standard two-component mixture
    gradient; prior N(0, 10) on both params)."""
    t0, t1 = theta
    d0 = np.exp(-0.5 * ((x - t0) / SIGMA_X) ** 2)
    d1 = np.exp(-0.5 * ((x - t0 - t1) / SIGMA_X) ** 2)
    denom = d0 + d1 + 1e-12
    w1 = d1 / denom
    g_common = (x - t0 - w1 * t1) / SIGMA_X ** 2
    g0 = g_common.sum() - t0 / 10.0
    g1 = (w1 * (x - t0 - t1) / SIGMA_X ** 2).sum() - t1 / 10.0
    return np.array([g0, g1], "f")


def main(steps=4000, lr=0.02):
    logging.basicConfig(level=logging.INFO)
    rng = np.random.RandomState(0)
    x = make_data(rng)
    mx.random.seed(7)

    opt = mx.optimizer.SGLD(learning_rate=lr, rescale_grad=1.0,
                            wd=0.0)
    updater = mx.optimizer.get_updater(opt)
    theta = mx.nd.array(np.asarray([0.5, -0.5], "f"))
    samples = []
    for step in range(steps):
        grad = log_posterior_grad(theta.asnumpy(), x)
        # SGLD minimizes, so feed the NEGATIVE log-posterior gradient
        updater(0, mx.nd.array(-grad), theta)
        if step > steps // 2:                 # burn-in discarded
            samples.append(theta.asnumpy().copy())
    samples = np.asarray(samples)
    mean = samples.mean(0)
    std = samples.std(0)
    logging.info("posterior mean %s std %s", mean, std)
    return mean, std


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=4000)
    args = ap.parse_args()
    mean, std = main(steps=args.steps)
    # the two component means are exchangeable: the posterior has
    # symmetric modes (t0, t1) = (0, 2) and (2, -2); accept either by
    # checking the component-mean SET, and require the chain to KEEP
    # MOVING (sampling, not optimizing): langevin noise ~ sqrt(lr)
    comps = sorted([mean[0], mean[0] + mean[1]])
    assert abs(comps[0] - 0.0) < 0.5 and abs(comps[1] - 2.0) < 0.5, mean
    assert std.min() > 0.02, std
    print("sgld toy OK: mean %s std %s" % (np.round(mean, 3),
                                           np.round(std, 3)))
