"""Flash attention as a Pallas TPU kernel.

Forward is a Pallas kernel: one grid step per (batch*head, q-block); K/V
live in VMEM and the kernel walks K in ``block_k`` tiles keeping the online
softmax state (running max ``m``, denominator ``l``, accumulator ``o``) in
registers/VMEM, so HBM traffic is O(T) per q-block instead of the O(T^2)
score matrix.  The MXU sees two big matmuls per tile (QK^T and PV) in
float32 accumulation.

Backward is a Pallas kernel too, the standard recomputation form (no score
matrix saved, only the per-row logsumexp): one grid step per (group of
heads, k-block, q-block) recomputes the tile's P from (Q, K, lse) and feeds
five MXU products (S, dP, dV, dK, dQ); S, P, dP and dS live in VMEM and
never reach HBM, and tiles wholly above the causal diagonal are skipped.
The tile is held transposed, ``[block_k, block_q]``, so the per-row ``lse``
and ``delta = sum(dO * O)`` are lane-dense rows ``[1, block_q]`` that
broadcast over sublanes: no lane-replicated ``[t, 128]`` copy of either
exists, in the residuals or in the backward.  dK/dV accumulate in float32
scratch across the q-blocks of one k-block, dQ in a float32 scratch that
stays resident for the whole group (``4 * t_q * 128`` bytes at 64-wide
heads) across the k-blocks.  Heads narrower than the 128 lanes go through
the kernel side by side, ``[b*h/g, t, g*d]``: its arrays fill their HBM
tiles, and the residuals live between the passes as the graph has them,
``[b, t, h, d]``, not as ``[b*h, t, 64]`` padded to 128 lanes (1.3 GB less
at GPT-2 medium's 24 layers, for the same kernel time).  On a v5e the
``lax.scan`` of einsums this replaces took 4.37 ms a layer at
(8, 1024, 16, 64) and 10.97 ms at (1, 4096, 20, 256), layout included;
this takes 1.07 and 2.99 ms.  One fused kernel beat a dK/dV kernel plus a
dQ kernel (seven products and every elementwise pass twice), 1.33 against
1.82 ms and 3.13 against 4.46 ms (PERF.md §6, PR 31).

The 2017-era reference has no attention op at all (SURVEY.md §5
long-context); this is greenfield capability required for parity with
modern workloads.  Layout convention matches ``parallel.ring_attention``:
``[batch, time, heads, dim]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention", "flash_attention_reference"]

_NEG_INF = float("-inf")


_LANES = 128  # VPU lane width; per-row softmax state is lane-replicated


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                block_k, causal, scale, t_kv_real, block_q):
    # Grid is (bh, n_qb, n_kb) with the K dimension innermost: K/V stream
    # through VMEM one [block_k, d] tile per step (never the full sequence),
    # while the online-softmax state (acc/m/l) carries in VMEM scratch.
    q_blk_idx = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # with causal masking, tiles entirely above the diagonal contribute
    # nothing — skip their matmuls (the scheduler still runs init/finalize)
    first_q = q_blk_idx * block_q
    live = (kb * block_k <= first_q + block_q - 1) if causal else True

    @pl.when(live)
    def _update():
        # matmul INPUTS stay in the storage dtype (bf16): casting them
        # to f32 first would force multi-pass f32 MXU kernels at a
        # fraction of bf16 rate; preferred_element_type keeps the
        # ACCUMULATION in f32, and the softmax scale is applied to the
        # f32 scores so no precision is lost to bf16 pre-scaling
        qb = q_ref[0]
        kblk = k_ref[0]
        s = jax.lax.dot_general(
            qb, kblk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        q_pos = first_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < t_kv_real
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG_INF)
        m = m_ref[:, 0:1]  # [block_q, 1], lane-replicated
        l = l_ref[:, 0:1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_blk)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        # PV at bf16 MXU rate too: P is in [0,1] post-softmax, so the
        # bf16 cast costs ~2^-9 relative — inside the bf16 pipeline's
        # own noise (the f32 path would be 4x+ slower on the MXU)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == n_kb - 1)
    def _finalize():
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse = jnp.where(jnp.isneginf(m), _NEG_INF, m + jnp.log(l_safe))
        # lse block is the full [n_qb, block_q] plane for this bh (TPU
        # tiling needs trailing block dims to match the array); each
        # (j, last-k) step fills its own row.
        lse_ref[0, q_blk_idx, :] = lse


def _pad_time(x, block):
    t = x.shape[1]
    pad = (-t) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret):
    """q/k/v: [bh, t, d] -> (o [bh, t, d], lse [bh, t_q_pad])."""
    bh, t_q, d = q.shape
    t_kv = k.shape[1]
    qp = _pad_time(q, block_q)
    kp = _pad_time(k, block_k)
    vp = _pad_time(v, block_k)
    t_qp, t_kvp = qp.shape[1], kp.shape[1]
    n_qb = t_qp // block_q
    n_kb = t_kvp // block_k
    grid = (bh, n_qb, n_kb)
    kernel = functools.partial(
        _fwd_kernel, block_k=block_k, causal=causal, scale=scale,
        t_kv_real=t_kv, block_q=block_q)
    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    from jax.experimental.pallas import tpu as pltpu
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j, kb: (i, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, kb: (i, j, 0)),
            pl.BlockSpec((1, n_qb, block_q), lambda i, j, kb: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_qp, d), q.dtype),
            jax.ShapeDtypeStruct((bh, n_qb, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),       # acc
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
        ],
        interpret=interpret,
        **kwargs,
    )(qp, kp, vp)
    return o[:, :t_q], lse.reshape(bh, t_qp)


_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                block_q, block_k, causal, masked, scale, t_kv_real,
                heads, d):
    # Grid is (groups of heads, n_kb, n_qb), queries innermost: one K/V
    # tile stays in VMEM while the Q/dO tiles stream past it, dk/dv
    # accumulate in scratch over the q-blocks, dq in a scratch row per
    # q-block that lives across the k-blocks of this group.
    kb = pl.program_id(1)
    j = pl.program_id(2)
    n_kb = pl.num_programs(1)
    n_qb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(kb == 0)
    def _init_dq():
        dq_acc[j] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    first_q = j * block_q
    first_k = kb * block_k
    live = (first_k <= first_q + block_q - 1) if causal else True

    @pl.when(live)
    def _update():
        # same contract as the forward: MXU operands in the storage
        # dtype, float32 accumulation, the scale, the exponent, delta
        # and the ds combination in float32.  The tile is [bk, bq].
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        if masked:
            k_pos = first_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            mask = k_pos < t_kv_real
            if causal:
                q_pos = first_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                mask = jnp.logical_and(mask, q_pos >= k_pos)

        def only(hd, x):
            # `heads` heads lie side by side along the lanes.  One
            # head's products come from operands with the other heads'
            # lanes zeroed: a contraction over them adds nothing, a
            # product with them lands in this head's lanes of the
            # accumulator, and the MXU does the work of one d-wide
            # product either way (its depth and width are 128).
            if heads == 1:
                return x
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads * d), 1)
            return jnp.where(lane // d == hd, x, jnp.zeros_like(x))

        for hd in range(heads):
            q_h, k_h, do_h = only(hd, q), only(hd, k), only(hd, do)
            s_t = jax.lax.dot_general(
                k, q_h, _NT, preferred_element_type=jnp.float32) * scale
            p_t = jnp.exp(s_t - lse_ref[0, hd:hd + 1, :])
            if masked:
                p_t = jnp.where(mask, p_t, 0.0)
            dv_acc[...] += jax.lax.dot_general(
                p_t.astype(do.dtype), do_h, _NN,
                preferred_element_type=jnp.float32)
            dp_t = jax.lax.dot_general(
                v, do_h, _NT, preferred_element_type=jnp.float32)
            ds_t = (p_t * (dp_t - delta_ref[0, hd:hd + 1, :])).astype(q.dtype)
            dk_acc[...] += jax.lax.dot_general(
                ds_t, q_h, _NN, preferred_element_type=jnp.float32)
            dq_acc[j] += jax.lax.dot_general(
                ds_t, k_h, _TN, preferred_element_type=jnp.float32)

    @pl.when(j == n_qb - 1)
    def _finalize_dkv():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(kb == n_kb - 1)
    def _finalize_dq():
        dq_ref[0] = (dq_acc[j] * scale).astype(dq_ref.dtype)


def _pack(x, g):
    """[b, t, h, d] -> [b*h/g, t, g*d]: ``g`` neighbouring heads side by
    side along the lanes, so that heads narrower than the 128 lanes do
    not leave the rest of every HBM tile and vector register empty."""
    b, t, h, d = x.shape
    return x.reshape(b, t, h // g, g * d).transpose(0, 2, 1, 3).reshape(
        b * h // g, t, g * d)


def _unpack(x, b, g):
    n, t, gd = x.shape
    return x.reshape(b, n // b, t, gd).transpose(0, 2, 1, 3).reshape(
        b, t, n // b * g, gd // g)


def _bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
              interpret):
    """q/k/v/o/do: [b, t, h, d], lse: [b*h, t_q_pad] -> (dq, dk, dv)."""
    b, t_q, h, d = q.shape
    t_kv = k.shape[1]
    # as many neighbouring heads a grid step as fit the lanes
    g = max(n for n in range(1, h + 1)
            if h % n == 0 and (n == 1 or n * d <= _LANES))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    qp = _pad_time(_pack(q, g), block_q)
    dop = _pad_time(_pack(do, g), block_q)   # zero rows: no gradient
    kp = _pad_time(_pack(k, g), block_k)
    vp = _pad_time(_pack(v, g), block_k)
    n, t_qp, gd = qp.shape
    t_kvp = kp.shape[1]
    n_qb = t_qp // block_q
    n_kb = t_kvp // block_k
    # per-row state as lane-dense rows, a head a row; lse comes padded
    # from the forward
    lse = lse.reshape(n, g, t_qp)
    delta = jnp.pad(delta.transpose(0, 2, 1).reshape(n, g, t_q),
                    ((0, 0), (0, 0), (0, t_qp - t_q)))

    def q_blk(kb, j):
        # a dead tile (queries before this k-block) asks for the block the
        # first live one will, so nothing is fetched for it
        if causal:
            j = jnp.minimum(jnp.maximum(j, kb * block_k // block_q),
                            n_qb - 1)
        return j

    q_spec = pl.BlockSpec((1, block_q, gd),
                          lambda i, kb, j: (i, q_blk(kb, j), 0))
    row_spec = pl.BlockSpec((1, g, block_q),
                            lambda i, kb, j: (i, 0, q_blk(kb, j)))
    kv_spec = pl.BlockSpec((1, block_k, gd), lambda i, kb, j: (i, kb, 0))
    # dq's block stays put until the last k-block, so each block goes to
    # HBM once, after its last contribution
    dq_spec = pl.BlockSpec(
        (1, block_q, gd),
        lambda i, kb, j: (i, jnp.where(kb == n_kb - 1, j, 0), 0))
    kernel = functools.partial(
        _bwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        masked=causal or t_kvp != t_kv, scale=scale, t_kv_real=t_kv,
        heads=g, d=d)
    from jax.experimental.pallas import tpu as pltpu
    kwargs = {}
    if not interpret:
        lanes = -(-gd // _LANES) * _LANES
        vmem = (4 * t_qp * lanes                          # dq, resident
                + 2 * 4 * block_k * lanes                 # dk, dv
                + 12 * (block_q + block_k) * lanes * q.dtype.itemsize
                + 6 * 4 * block_q * block_k)              # the tile's values
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=min(max(2 * vmem, 32 << 20), 100 << 20))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(n, n_kb, n_qb),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[dq_spec, kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct(kp.shape, k.dtype),
            jax.ShapeDtypeStruct(vp.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_qb, block_q, gd), jnp.float32),  # dq
            pltpu.VMEM((block_k, gd), jnp.float32),        # dk
            pltpu.VMEM((block_k, gd), jnp.float32),        # dv
        ],
        interpret=interpret,
        name="flash_attention_bwd",
        **kwargs,
    )(qp, kp, vp, dop, lse, delta)
    return (_unpack(dq[:, :t_q], b, g), _unpack(dk[:, :t_kv], b, g),
            _unpack(dv[:, :t_kv], b, g))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                      interpret)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    # the forward kernel takes [b*h, t, d]
    o, lse = _fwd_impl(_pack(q, 1), _pack(k, 1), _pack(v, 1), causal, scale,
                       block_q, block_k, interpret)
    o = _unpack(o, q.shape[0], 1)
    # what lives between the passes is q, k, v and o as the graph has
    # them, [b, t, h, d], and lse [b*h, t] float32: each pass folds them
    # its own way
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                     interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=512, block_k=512, interpret=None):
    """Memory-efficient exact attention.

    Args: ``q`` [b, t_q, h, d], ``k``/``v`` [b, t_kv, h, d] (the
    ``ring_attention`` layout).  Returns [b, t_q, h, d] in ``q.dtype``.

    ``interpret=None`` auto-selects: compiled Pallas on TPU, interpreter
    elsewhere (bit-accurate, used by the CPU test mesh).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t_q, t_kv, d = q.shape[1], k.shape[1], q.shape[3]
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    def clamp(block, t):
        # clamp to the sequence but keep the block LANE-ALIGNED: a raw
        # min(block, t) for 128 < t < block would hand Mosaic a
        # non-tile-multiple block shape (t=300 -> (300, d) blocks);
        # rounding t up to a 128 multiple keeps one aligned block and
        # the _pad_time path pads the array to match
        return min(block, -(-max(t, 1) // _LANES) * _LANES)
    block_q = clamp(block_q, t_q)
    block_k = clamp(block_k, t_kv)

    return _flash(q, k, v, causal, float(scale), block_q, block_k, interpret)


def flash_attention_reference(q, k, v, causal=False, scale=None):
    """O(T^2) jnp oracle (same layout), for tests and tiny shapes."""
    from ...parallel.ring_attention import attention_reference
    return attention_reference(q, k, v, causal=causal, scale=scale)
