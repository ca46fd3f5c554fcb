"""Pipeline parallelism over a ``pipe`` mesh axis.

Greenfield relative to the reference (its only model-splitting tool was
per-layer device placement with cross-device activation copies,
``example/model-parallel-lstm``).  The TPU-native design is an SPMD
pipeline written as ordinary traceable ops: every device runs the same
program, holds its stages' parameters (leading stage dim sharded over
``pipe``), and activations hop stage→stage with ``ppermute``.  Because
the schedule is plain jax (a ``lax.scan`` over ticks), **reverse-mode AD
derives the backward pipeline automatically** — no hand-written 1F1B
schedule.

Two schedules share one engine (``MXTPU_PIPE_SCHEDULE`` or the
``schedule=`` arg):

* ``"gpipe"`` — blocked placement: device ``d`` holds stages
  ``[d·v, (d+1)·v)`` and applies them back to back each tick.  With
  ``M`` microbatches the scan runs ``M + n - 1`` ticks; bubble fraction
  ``(n-1)/(M+n-1)``.
* ``"interleaved"`` (default) — circular placement: device ``d`` holds
  stages ``{r·n + d}`` and walks its ``v`` stage slots in rounds, so a
  microbatch laps the ring ``v`` times.  ``v·M + n - 1`` ticks of
  ``1/v`` the per-tick work cut the bubble to ``(n-1)/(v·M+n-1)`` —
  :func:`pipeline_bubble_frac` is the static model.  Needs
  ``n_micro >= n_devices`` (device 0's between-rounds buffer is
  refilled exactly one round before each slot is re-read).

Fill/drain ticks skip ``stage_fn`` entirely with ``lax.cond`` (the old
engine ran it on garbage and masked the result), so ``stage_fn`` must
be collective-free.  The output leaves on device 0 only — the final
hop of the ring delivers it — and the caller slices that shard out of
the stacked shard_map result instead of paying a full ``psum``
broadcast of the whole output tensor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from jax.sharding import PartitionSpec

from .. import envknobs as _envknobs

__all__ = ["pipeline_apply", "pipeline_bubble_frac"]


def pipeline_bubble_frac(n_devices, n_micro, stages_per_device=1,
                         schedule="interleaved"):
    """Idle fraction of the tick grid, from the static schedule model.

    Each of the ``n`` devices idles ``n - 1`` of the total ticks:
    ``(n-1)/(M+n-1)`` for gpipe, ``(n-1)/(v·M+n-1)`` interleaved (same
    fill/drain cost amortized over ``v``× the ticks at ``1/v`` work).
    """
    n, M = int(n_devices), int(n_micro)
    v = int(stages_per_device)
    ticks = (M + n - 1) if (schedule == "gpipe" or v == 1) else (v * M
                                                                + n - 1)
    return (n - 1) / float(ticks)


def _shift_right(x, axis_name, n):
    """Send to the next device; device 0 receives device n-1's output
    (the ring hop that both hands activations forward and delivers
    finished outputs back to device 0)."""
    perm = [(i, (i + 1) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def pipeline_apply(stage_fn, stage_params, inputs, mesh, axis="pipe",
                   schedule=None):
    """Run ``stage_fn`` as an S-stage pipeline.

    Parameters
    ----------
    stage_fn : (params_one_stage, x) -> y
        one stage's computation; activations keep their shape and must
        contain no collectives (fill/drain ticks ``lax.cond``-skip it).
    stage_params : pytree
        every leaf has leading dim ``S`` (one slice per stage); ``S``
        must be a multiple of ``mesh.shape[axis]`` — ``v = S/n`` stages
        live on each device.  Sharded over ``mesh[axis]`` by this
        function.
    inputs : (n_micro, ...) microbatched input (replicated).
    schedule : "interleaved" | "gpipe" | None
        None resolves ``MXTPU_PIPE_SCHEDULE`` (default interleaved;
        the two coincide when ``v == 1``).

    Returns ``(n_micro, ...)`` outputs.  Differentiable: wrap in
    ``jax.grad``/``value_and_grad`` freely.
    """
    n = mesh.shape[axis]
    S = jax.tree.leaves(stage_params)[0].shape[0]
    if S % n:
        raise ValueError("stage dim %d not a multiple of %s=%d"
                         % (S, axis, n))
    v = S // n
    if schedule is None:
        schedule = _envknobs.get_str("MXTPU_PIPE_SCHEDULE", "interleaved")
    if schedule not in ("interleaved", "gpipe"):
        raise ValueError("MXTPU_PIPE_SCHEDULE=%r (want interleaved|gpipe)"
                         % (schedule,))
    M = inputs.shape[0]

    if schedule == "gpipe" or v == 1:
        # blocked placement — the natural contiguous shard slice; one
        # tick applies all v local stages as one super-stage
        params = stage_params
        rounds = 1

        def step(local_params, r, x):
            del r
            for j in range(v):
                p_j = jax.tree.map(lambda p: p[j], local_params)
                x = stage_fn(p_j, x)
            return x
    else:
        if M < n:
            raise ValueError(
                "interleaved schedule needs n_micro >= n_devices "
                "(%d < %d): a round-r input must land in device 0's "
                "buffer before round r reads it" % (M, n))
        # circular placement: device d runs stage r*n+d in round r.
        # Reorder host-side so the contiguous shard slice [d*v,(d+1)*v)
        # holds slot r = global stage r*n + d.
        idx = jnp.arange(S).reshape(v, n).T.reshape(-1)
        params = jax.tree.map(lambda p: jnp.take(p, idx, axis=0),
                              stage_params)
        rounds = v

        def step(local_params, r, x):
            p_r = jax.tree.map(
                lambda p: lax.dynamic_index_in_dim(p, r, 0,
                                                   keepdims=False),
                local_params)
            return stage_fn(p_r, x)

    param_spec = jax.tree.map(lambda _: PartitionSpec(axis), params)

    def per_device(params, xs):
        # params: leading dim v (this device's stage slots); xs: full
        # microbatches.  Schedule index j = t - d: device d computes
        # (round r, microbatch m) = divmod(j, M) at tick t when
        # 0 <= j < rounds*M.
        d_idx = lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        dtype = xs.dtype
        R = rounds
        TT = R * M + n - 1

        incoming0 = jnp.zeros(mb_shape, dtype)
        # device 0's between-rounds buffer (only meaningful when R > 1)
        queue0 = jnp.zeros((M if R > 1 else 1,) + mb_shape, dtype)
        outs0 = jnp.zeros((M,) + mb_shape, dtype)

        def tick(carry, t):
            incoming, queue, outs = carry
            # ---- bookkeeping first.  incoming was computed by device
            # n-1 at tick t-1 with schedule index jj = t - n: a real
            # end-of-round value whenever jj >= 0 (device n-1 skips its
            # own fill/drain, so nothing else ever lands here).  Write
            # before read: with M == n a round's input arrives exactly
            # the tick device 0 consumes it.
            jj = t - n
            r_in = jj // M
            m_in = jnp.clip(jj % M, 0, M - 1)
            is_d0 = d_idx == 0
            if R > 1:
                queue = lax.cond(
                    is_d0 & (jj >= 0) & (r_in < R - 1),
                    lambda q: lax.dynamic_update_index_in_dim(
                        q, incoming, m_in, 0),
                    lambda q: q, queue)
            outs = lax.cond(
                is_d0 & (jj >= 0) & (r_in == R - 1),
                lambda o: lax.dynamic_update_index_in_dim(
                    o, incoming, m_in, 0),
                lambda o: o, outs)
            # ---- compute ----------------------------------------
            j = t - d_idx
            active = (j >= 0) & (j < R * M)
            jc = jnp.clip(j, 0, R * M - 1)
            r = jc // M
            m = jc % M
            feed = lax.dynamic_index_in_dim(xs, m, 0, keepdims=False)
            if R > 1:
                qval = lax.dynamic_index_in_dim(queue, m, 0,
                                                keepdims=False)
                x0 = jnp.where(r == 0, feed, qval)
            else:
                x0 = feed
            x = jnp.where(is_d0, x0, incoming)
            y = lax.cond(
                active,
                lambda x: step(params, r, x).astype(dtype),
                lambda x: jnp.zeros(mb_shape, dtype), x)
            # the collective runs every tick on every device — only
            # the compute is conditional
            incoming = _shift_right(y, axis, n)
            return (incoming, queue, outs), None

        (incoming, _, outs), _ = lax.scan(
            tick, (incoming0, queue0, outs0), jnp.arange(TT))
        # the last microbatch's final output rides the last rotation;
        # with that, device 0 alone holds the full result — the masked
        # one-hop hand-off that replaces the old full-psum broadcast
        outs = jnp.where(d_idx == 0,
                         lax.dynamic_update_index_in_dim(
                             outs, incoming, M - 1, 0),
                         outs)
        return outs[None]

    fn = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(param_spec, PartitionSpec()),
        out_specs=PartitionSpec(axis),
        check_vma=False)
    # (n, M, ...) stacked shards; device 0's shard is the result (the
    # slice is a one-hop gather under jit, not a broadcast)
    return fn(params, inputs)[0]
