"""Collectives over the mesh.

The reference's two comm layers (intra-node ``Comm`` tree
``src/kvstore/comm.h:17-320``; inter-node ps-lite ZPush/ZPull
``kvstore_dist.h:108-241``) both become XLA collectives here: ``psum``
rides ICI within a slice and DCN across slices, scheduled by the compiler
inside the step that produces the operands — which is what lets gradient
allreduce overlap the backward pass (reference hard part; see
``SURVEY.md`` §7).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec


__all__ = ["global_allreduce", "barrier", "psum_over_mesh",
           "broadcast_from_rank0", "lowp_allreduce", "lowp_comm_bytes",
           "collective_wire_bytes"]


def _process_count():
    try:
        return jax.process_count()
    except Exception:
        return 1


def _process_index():
    try:
        return jax.process_index()
    except Exception:      # noqa: BLE001 — backend not yet initialized
        return 0



def broadcast_from_rank0(value):
    """Every process returns process 0's ``value`` (the reference's
    rank-0-only init push + pull, ``kvstore_dist.h:63-80``)."""
    if _process_count() <= 1:
        return value
    from jax.experimental import multihost_utils
    return jnp.asarray(
        multihost_utils.broadcast_one_to_all(np.asarray(value)))


def global_allreduce(value):
    """Sum ``value`` across all participating processes/devices.

    For a multi-host run this is the out-of-step analog of the reference's
    ``KVStoreDist::Push_`` network path; models trained through the fused
    step never call it — their psum is inside the compiled step.
    """
    if _process_count() <= 1:
        return value
    # one device per process: each process contributes exactly one shard
    # regardless of how many local devices it has
    devs, seen = [], set()
    for d in jax.devices():
        if d.process_index not in seen:
            seen.add(d.process_index)
            devs.append(d)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(devs), ("data",))

    def _sum(x):
        return jax.lax.psum(x, axis_name="data")

    f = jax.jit(
        jax.shard_map(_sum, mesh=mesh,
                      in_specs=PartitionSpec(*(["data"] + [None] * (value.ndim - 1))),
                      out_specs=PartitionSpec(*([None] * value.ndim))))
    # value is host-local; make it a global sharded array first
    garr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, PartitionSpec("data")), np.asarray(value))
    out = f(garr)
    # the result is fully replicated: hand back this process's shard as a
    # plain host-local array so callers can mix it with local arrays
    return jnp.asarray(out.addressable_data(0))


def psum_over_mesh(x, axis_name="data"):
    """In-step psum — call inside a shard_map'd/pjit'd computation."""
    return jax.lax.psum(x, axis_name=axis_name)


def lowp_allreduce(x, axis_name, n, comm_dtype, keep_shard=False):
    """Cross-replica gradient sum with a reduced-precision WIRE and an
    f32 ACCUMULATOR — call inside a ``shard_map`` over ``axis_name``.

    A plain ``psum`` on a bf16 operand would also accumulate in bf16
    (XLA all-reduce computes in the operand dtype); here the reduction
    is opened into its two phases so only the wire runs low-precision:

    1. reduce-scatter: round local grads to ``comm_dtype``, ``all_to_all``
       dim-0 chunks so replica *i* holds every replica's chunk *i*, then
       sum the ``n`` contributions in f32 — each replica now owns the
       exactly-f32-accumulated sum of its 1/n slice.
    2. all-gather: round the reduced slice back to ``comm_dtype`` and
       gather — unless ``keep_shard`` (the ZeRO-1 path), where the
       owned f32 slice feeds the sharded optimizer update directly and
       the gather (and its extra rounding) never happens.

    Per-replica wire bytes: ``(n-1)/n * |g|`` at bf16 for the full
    round trip vs ``2*(n-1)/n * |g|`` at f32 for a ring all-reduce —
    exactly half, at any ``n``.  A leaf whose dim 0 does not divide by
    ``n`` (small biases) falls back to all-gather + local f32 sum (same
    result, wire ``(n-1) * |g|/2``; such leaves are KBs).

    Rounding error: each element is rounded to bf16 at most twice
    (before the wire, after the f32 accumulation), so the summed grad
    carries <= 2 half-ulp bf16 roundings ~ 2^-8 relative — the
    documented tolerance in docs/how_to/perf.md ("Optimizer sharding").
    """
    g16 = x.astype(comm_dtype)
    d0 = x.shape[0] if x.ndim else 0
    if x.ndim and d0 >= n and d0 % n == 0:
        chunks = jax.lax.all_to_all(g16, axis_name, split_axis=0,
                                    concat_axis=0, tiled=True)
        summed = chunks.reshape((n, d0 // n) + x.shape[1:]) \
                       .astype(jnp.float32).sum(axis=0)
        if keep_shard:
            return summed
        return jax.lax.all_gather(summed.astype(comm_dtype), axis_name,
                                  axis=0, tiled=True).astype(jnp.float32)
    parts = jax.lax.all_gather(g16, axis_name)
    out = parts.astype(jnp.float32).sum(axis=0)
    if keep_shard:
        return out      # not dim-0-divisible: the "shard" is the whole leaf
    return out


def lowp_comm_bytes(shape, n, comm_itemsize=2, keep_shard=False):
    """Per-replica wire bytes :func:`lowp_allreduce` moves for one leaf
    (the analytic model ``Trainer.grad_comm_bytes_per_step`` sums)."""
    size = int(np.prod(shape or (1,)))
    d0 = shape[0] if shape else 0
    if d0 >= n and d0 % n == 0:
        rs = (n - 1) / n * size * comm_itemsize
        ag = 0 if keep_shard else (n - 1) / n * size * comm_itemsize
        return rs + ag
    return (n - 1) * size * comm_itemsize


def collective_wire_bytes(primitive: str, elements: int, itemsize: int,
                          n: int) -> int:
    """Predicted per-replica wire bytes for ONE invocation of a
    collective primitive, as it appears in a jaxpr — the static byte
    model behind ``mxnet_tpu/analysis/comm_passes.py``'s comm plans
    (and, composed per-leaf, :func:`lowp_comm_bytes`).

    ``elements`` is the element count of the primitive's OPERAND (the
    local shard a replica feeds in — what the jaxpr invar aval shows),
    ``itemsize`` its dtype width, ``n`` the product of the named axis
    sizes the collective runs over.  Ring-algorithm accounting, the
    same model XLA's cost analysis and ``lowp_comm_bytes`` use:

    * ``psum``/``pmean``/``pmax``/``pmin`` (all-reduce): the ring
      all-reduce moves each byte twice, minus the locally-owned chunk —
      ``2*(n-1)/n * |x|``.
    * ``reduce_scatter``: the reduce phase alone — ``(n-1)/n * |x|``.
    * ``all_gather``: the operand is the LOCAL shard; a replica
      receives the other ``n-1`` shards — ``(n-1) * |x|``.
    * ``all_to_all``: every replica keeps 1/n of its buffer and ships
      the rest — ``(n-1)/n * |x|``.
    * ``ppermute``: one neighbor hop of the whole buffer — ``|x|``.

    Unknown primitives predict 0 (and the comm-plan extractor only
    feeds known ones)."""
    if n <= 1:
        return 0
    size = int(elements) * int(itemsize)
    if primitive in ("psum", "pmean", "pmax", "pmin", "psum2",
                     "all_reduce"):
        return int(2 * (n - 1) / n * size)
    if primitive in ("reduce_scatter", "psum_scatter"):
        return int((n - 1) / n * size)
    if primitive == "all_gather":
        return int((n - 1) * size)
    if primitive == "all_to_all":
        return int((n - 1) / n * size)
    if primitive == "ppermute":
        return size
    return 0


def barrier():
    """Cross-process rendezvous (reference ``ps::Postoffice::Barrier``,
    ``kvstore_dist.h:142-145``)."""
    try:
        if _process_count() > 1:
            # a tiny allreduce acts as the barrier on the coordination svc
            jnp.zeros(()).block_until_ready()
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("mxnet_tpu_barrier")
    except Exception:
        pass
