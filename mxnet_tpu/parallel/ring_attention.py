"""Ring attention: sequence-parallel exact attention over an ICI ring.

Long-context support is first-class in this framework (the 2017-era
reference predates attention entirely — ``SURVEY.md`` §5 long-context:
its only tools were bucketing and truncated BPTT).  Ring attention shards
the sequence across the mesh ``seq`` axis; each device holds a Q block and
rotates K/V blocks around the ring with ``lax.ppermute`` while accumulating
the softmax online (flash-attention style running max/denominator), so
peak memory is O(T/N) and the K/V transfer rides one ICI hop per step,
overlapped by XLA with the local block matmul.

Two hot-path optimizations over the textbook loop:

* **fused K/V permute** — K and V travel as ONE stacked ``(2, ...)``
  array, one ``ppermute`` per step instead of two; and the own block is
  consumed before the loop, so a full sweep launches ``n-1`` collectives
  (down from ``2n``).
* **causal block skip** — under ``causal=True`` a rotated block is fully
  masked iff ``blk_idx > my_idx`` (every key position is ahead of every
  query position), which is ~half of all (device, step) pairs.  A fully
  masked block is an exact no-op on the online-softmax state (p=0,
  m_new=m, corr=1), so ``lax.cond``-skipping it is bit-identical while
  dropping the einsum work.  The permute stays OUTSIDE the cond — every
  device runs the same collective sequence.  ``MXTPU_RING_SKIP=0`` (or
  ``skip_masked=False``) keeps the compute for A/B timing.

``ring_attention`` is the per-shard computation (call under ``shard_map``);
``ring_attention_sharded`` wraps a global array end-to-end.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .. import envknobs as _envknobs

__all__ = ["ring_attention", "ring_attention_sharded", "attention_reference"]


def ring_attention(q, k, v, axis_name="seq", causal=False, scale=None,
                   skip_masked=None):
    """Blockwise attention over a ring.

    Args: ``q, k, v`` local shards of shape ``[batch, t_local, heads, dim]``
    inside a ``shard_map`` over ``axis_name``.  Returns the local output
    shard ``[batch, t_local, heads, dim]``.  ``skip_masked``: None
    resolves ``MXTPU_RING_SKIP`` (default on; only relevant under
    ``causal``).
    """
    if skip_masked is None:
        skip_masked = _envknobs.get_bool("MXTPU_RING_SKIP", True)
    n_shards = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, t, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    q32 = q.astype(jnp.float32) * scale

    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    def accumulate(carry, kv_blk, blk_idx):
        # pure online-softmax update for one K/V block — no collectives
        # (it runs inside lax.cond when the causal skip is on)
        o, m, l = carry
        k_blk, v_blk = kv_blk[0], kv_blk[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, k_blk.astype(jnp.float32))
        if causal:
            q_pos = my_idx * t + jnp.arange(t)
            k_pos = blk_idx * t + jnp.arange(t)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # -inf rows (fully masked block) must not poison the state
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = (o * corr[..., None]
                 + jnp.einsum("bhqk,bkhd->bhqd", p,
                              v_blk.astype(jnp.float32)))
        return o_new, m_new, l_new

    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    m0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)

    # K and V ride one stacked carry so each ring step is ONE ppermute
    kv0 = jnp.stack([k, v])                          # (2, b, t, h, d)
    # own block first (never fully masked under causal: the diagonal),
    # so the loop below is pure permute-then-compute — n-1 hops total
    carry0 = accumulate((o0, m0, l0), kv0, my_idx)

    def body(i, state):
        carry, kv_blk = state
        kv_blk = jax.lax.ppermute(kv_blk, axis_name, perm)
        # after i rotations we hold the block originally on (my_idx - i)
        blk_idx = (my_idx - i) % n_shards
        if causal and skip_masked:
            # fully masked iff the whole block is in the future; the
            # update is an exact no-op there, so skip its FLOPs
            carry = jax.lax.cond(
                blk_idx > my_idx,
                lambda c: c,
                lambda c: accumulate(c, kv_blk, blk_idx),
                carry)
        else:
            carry = accumulate(carry, kv_blk, blk_idx)
        return carry, kv_blk

    (o, m, l), _ = jax.lax.fori_loop(1, n_shards, body, (carry0, kv0))
    l = jnp.where(l == 0.0, 1.0, l)
    out = o / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis="seq", causal=False,
                           scale=None, skip_masked=None):
    """Apply ring attention to globally-shaped ``[b, t, h, d]`` arrays
    sharded (or shardable) over ``mesh[axis]`` on the time dimension."""
    spec = PartitionSpec(None, axis, None, None)
    fn = jax.shard_map(
        partial(ring_attention, axis_name=axis, causal=causal, scale=scale,
                skip_masked=skip_masked),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def attention_reference(q, k, v, causal=False, scale=None, window=0):
    """Single-device exact attention (correctness oracle for the ring).
    ``window`` > 0 (causal) keeps the keys t - window < s <= t."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        if window:
            mask = mask & (jnp.arange(t_k)[None, :]
                           > jnp.arange(t_q)[:, None] - window)
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
