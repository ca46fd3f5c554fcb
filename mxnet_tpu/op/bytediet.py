"""Byte-diet backward formulations for the fused train step.

The fused ResNet-50 step is HBM-bandwidth-bound, not MXU-bound
(an HLO walk of the round-5 step: ~112 of 124 roofline-ms on the
byte side), and the round-5 recapture named the residue: three zero-FLOP
1.2-1.6 GB fusions, a 0.92 GB zero-FLOP ``select_and_scatter`` (MaxPool
backward) and a family of 0.82 GB zero-FLOP fusions — all *backward-pass
residual traffic*, not compute.  This module rewrites the backward
formulations of two ops that materialize activation-sized zero-FLOP
tensors, so the cotangent chain reads fewer full-size operands per
layer:

* **ReLU** (`relu_save_output`): jax's ``max(x, 0)`` vjp carries the
  saved *input* to backward and re-derives the mask from it.  The output
  ``y`` is already resident (the next layer consumed it, so it is a
  saved residual anyway) and the mask is recoverable from it —
  ``dx = where(y > 0, dy, 0)``.  Saving ``y`` instead of ``x`` dedupes
  the residual pair down to one tensor per activation.
* **BatchNorm** (`bn_train_normalize`): letting autodiff differentiate
  the normalize expression materializes activation-sized temporaries
  (the ``(x - mean)`` chains of the stat broadcasts) in the backward
  fusions.  The closed-form BN backward needs only per-channel
  reductions of ``dy`` and ``dy·x̂`` plus one fused elementwise pass:
  ``dx = x·A + dy·S + B`` with per-channel f32 scalars A/S/B — every
  activation-sized read fuses into adjacent elementwise work.

MaxPool is not among them.  Its ``select_and_scatter`` was replaced
here by a scatter-add of the cotangent at an int32 index map the forward
saved, to spare the 0.92 GB re-read (1.1 ms at the v5e's 819 GB/s).  The
chip has no fast path for a scatter of 51.4M arbitrary indices: XLA
sorts them and scatters serially, 573 of the step's 698 ms (PERF.md §6,
PR 28).  ``op/nn.py: _pooling`` differentiates the plain
``reduce_window`` again, under either policy.

**Residual/intermediate dtype policy** (``dtype_policy``): the fused
trainer seeds bf16 cotangents (`parallel/trainer.py`) and these
backwards keep elementwise math in the cotangent dtype while running
every *reduction* with f32 accumulation (``jnp.sum(..., dtype=f32)``) —
the split the op-sweep's bf16 backward checks tolerate
(tests/test_op_sweep.py reduced-precision tiers).  Policy values:

* ``"bytediet"`` (default): the formulations above.
* ``"legacy"``: the pre-round-6 plain-jax formulations (set
  ``MXTPU_DTYPE_POLICY=legacy`` to A/B or bisect).

The policy is threaded as a static trace-time flag:
``Trainer(dtype_policy=...)`` / ``Executor`` → ``_GraphProgram`` →
``OpContext.dtype_policy`` → the op bodies in ``op/nn.py`` /
``op/elemwise.py`` branch on it in Python, like ``is_train``.
"""
from __future__ import annotations

import os
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["enabled", "default_policy", "relu_save_output",
           "bn_batch_stats", "bn_train_normalize"]


def default_policy():
    """Process-wide default (env-overridable escape hatch)."""
    return os.environ.get("MXTPU_DTYPE_POLICY", "bytediet")


def enabled(ctx):
    """True when the context (or the process default) selects the
    byte-diet formulations.  Unknown policy values raise: a typo in the
    A/B knob (``MXTPU_DTYPE_POLICY=Legacy``) silently running the NEW
    formulations would poison the bisection it exists for."""
    pol = getattr(ctx, "dtype_policy", None) or default_policy()
    if pol not in ("bytediet", "legacy"):
        raise ValueError("unknown dtype_policy %r (bytediet|legacy)"
                         % (pol,))
    return pol != "legacy"


# ----------------------------------------------------------------------
# ReLU: backward mask from the OUTPUT, not a saved input
@jax.custom_vjp
def relu_save_output(x):
    return jnp.maximum(x, jnp.zeros((), x.dtype))


def _relu_fwd(x):
    y = jnp.maximum(x, jnp.zeros((), x.dtype))
    return y, y            # the output IS the residual


def _relu_bwd(y, g):
    # subgradient 0 at x == 0, matching jax.nn.relu's custom jvp
    return (jnp.where(y > 0, g, jnp.zeros((), g.dtype)),)


relu_save_output.defvjp(_relu_fwd, _relu_bwd)


# ----------------------------------------------------------------------
# BatchNorm: shared single-pass statistics + fused closed-form backward
#
# Cancellation guard (nn.py single-pass variance): the
# shifted-moment form var = E[(x-c)²] - E[x-c]² centered on the running
# mean c cancels catastrophically when the batch mean sits far from c
# (first steps after init, distribution shift).  The guard is one scalar
# comparison: when d1² > (63/64)·d2 for ANY channel — i.e. the fast-path
# variance would be carved out of less than 1/64 of d2, costing ≥6 of
# f32's 24 mantissa bits — fall back to exact two-pass statistics via
# lax.cond (the second pass only executes in that regime; steady state
# keeps the one-read fast path).  The conditional takes the activation in
# its own dtype and only ``two_pass`` widens it: an operand of a
# conditional is a buffer, so a widened operand would be an f32 copy of
# every BN input written each step and read only in the fallback.
_CANCEL_FRAC = 63.0 / 64.0


def bn_batch_stats(data, center32, reduce_axes):
    """Single-pass f32 batch statistics of ``data`` over ``reduce_axes``
    centered on ``center32`` (per-channel f32), with the catastrophic-
    cancellation fallback.  Returns (mean32, var32) per channel.  The
    statistics are one read of ``data`` in its own dtype, widened inside
    the reduction; no widened copy of it crosses the branch boundary."""
    acc = jnp.promote_types(data.dtype, jnp.float32)
    ndim = data.ndim
    ax = [i for i in range(ndim) if i not in reduce_axes]
    assert len(ax) == 1
    bshape = tuple(data.shape[i] if i == ax[0] else 1 for i in range(ndim))
    n_red = float(np.prod([data.shape[i] for i in reduce_axes]))
    xc = data.astype(acc) - center32.reshape(bshape)
    d1 = jnp.sum(xc, axis=tuple(reduce_axes)) / n_red
    d2 = jnp.sum(xc * xc, axis=tuple(reduce_axes)) / n_red
    mean32 = d1 + center32

    def fast(_):
        return jnp.maximum(d2 - d1 * d1, 0.0)

    def two_pass(operand):
        x, m = operand
        xm = x.astype(acc) - m.reshape(bshape)
        return jnp.sum(xm * xm, axis=tuple(reduce_axes)) / n_red

    cancels = jnp.any(d1 * d1 > _CANCEL_FRAC * d2)
    var32 = lax.cond(cancels, two_pass, fast, (data, mean32))
    return mean32, var32


def _bn_norm_impl(cfg, data, gamma, beta, center32):
    reduce_axes, ax, eps = cfg
    bshape = tuple(data.shape[i] if i == ax else 1
                   for i in range(data.ndim))
    mean32, var32 = bn_batch_stats(data, center32, reduce_axes)
    inv32 = lax.rsqrt(var32 + eps)
    scale32 = gamma.astype(jnp.float32) * inv32
    shift32 = beta.astype(jnp.float32) - mean32 * scale32
    out = data * scale32.reshape(bshape).astype(data.dtype) \
        + shift32.reshape(bshape).astype(data.dtype)
    return out, mean32, inv32, scale32


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def bn_train_normalize(cfg, data, gamma, beta, center32):
    """Train-mode BN normalize with batch statistics; ``cfg`` is the
    static ``(reduce_axes, axis, eps)`` triple.  The statistics are
    recomputed by :func:`bn_batch_stats` — callers computing the moving-
    average update from the same helper get the duplicate reductions
    CSE'd by XLA into one pass."""
    out, _, _, _ = _bn_norm_impl(cfg, data, gamma, beta, center32)
    return out


def _bn_fwd(cfg, data, gamma, beta, center32):
    out, mean32, inv32, scale32 = _bn_norm_impl(cfg, data, gamma, beta,
                                                center32)
    # residuals: the input (alive anyway) + per-channel vectors — no
    # activation-sized temporary survives to backward (gamma/beta ride
    # along only to stamp their dtypes onto the returned cotangents)
    return out, (data, gamma, beta, center32, mean32, inv32, scale32)


def _bn_bwd(cfg, res, dy):
    reduce_axes, ax, eps = cfg
    data, gamma, beta, center32, mean32, inv32, scale32 = res
    bshape = tuple(data.shape[i] if i == ax else 1
                   for i in range(data.ndim))
    n_red = float(np.prod([data.shape[i] for i in reduce_axes]))
    # per-channel reductions with f32 ACCUMULATION over the low-precision
    # elementwise products (the dtype policy's reduction half)
    dbeta32 = jnp.sum(dy, axis=tuple(reduce_axes), dtype=jnp.float32)
    xhat = (data - mean32.reshape(bshape).astype(data.dtype)) \
        * inv32.reshape(bshape).astype(data.dtype)
    dgamma32 = jnp.sum(dy * xhat, axis=tuple(reduce_axes),
                       dtype=jnp.float32)
    # dx = (γ·inv)·(dy − Σdy/n − x̂·Σ(dy·x̂)/n), refactored to
    # dx = x·A + dy·S + B so the broadcasts fuse into ONE elementwise
    # pass in the cotangent dtype (per-channel A/S/B stay f32)
    c2 = dgamma32 / n_red * inv32
    A = -scale32 * c2
    B = scale32 * (mean32 * c2 - dbeta32 / n_red)
    dx = data * A.reshape(bshape).astype(data.dtype) \
        + dy * scale32.reshape(bshape).astype(dy.dtype) \
        + B.reshape(bshape).astype(data.dtype)
    return (dx.astype(data.dtype), dgamma32.astype(gamma.dtype),
            dbeta32.astype(beta.dtype), jnp.zeros_like(center32))


bn_train_normalize.defvjp(_bn_fwd, _bn_bwd)
