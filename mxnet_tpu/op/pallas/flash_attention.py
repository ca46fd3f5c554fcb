"""Flash attention as two Pallas TPU kernels, one form for both passes.

Both kernels hold the score tile transposed, ``[block_k, block_q]``, in
VMEM, so what a query row carries (the forward's running maximum and
denominator, the backward's ``lse`` and ``delta = sum(dO * O)``) is a
lane-dense row ``[1, block_q]`` a head that broadcasts over sublanes: no
lane-replicated ``[t, 128]`` copy of any of it exists, in a kernel or
between the passes.  Heads narrower than the 128 lanes go through side
by side, ``g`` to a grid step (two at 64 wide): one head's products take
operands with the other heads' lanes zeroed, which costs the MXU nothing
at depth and width 128.  MXU operands stay in the storage dtype (bf16)
with float32 accumulation; the scale, the maximum, the exponent and the
denominator are float32; tiles wholly above the causal diagonal are
neither computed nor fetched.

Forward: grid ``(batch, head groups, q-blocks, k-blocks)``, keys
innermost.  K/V stream through VMEM a ``[block_k, g*d]`` tile a step
while the online softmax's state carries in scratch: two rows a head and
an accumulator ``[g*d, block_q]``, transposed like the tile, so that the
correction by the running maximum is a row broadcast too.  It writes O
and ``lse`` as ``[b*h/g, g, t]`` rows, the layout the backward reads.

Backward: the standard recomputation form (no score matrix saved), grid
``(batch, head groups, k-blocks, q-blocks)``, queries innermost.  One
K/V tile stays while the Q/dO tiles stream past it; five MXU products a
tile (S, dP, dV, dK, dQ); dK/dV accumulate in float32 scratch over the
q-blocks of one k-block, dQ in a float32 scratch that stays resident for
the whole group across the k-blocks; ``delta`` is taken in the group's
first pass over the q-blocks, where O is read, once.

Both read the graph's arrays where they lie: q, k, v, o and dO are
``[b, t, h, d]``, which viewed as ``[b, t, h*d]`` (no copy) has a group
of heads as a ``g*d``-wide column block that a ``BlockSpec`` addresses.
Where such a block is not whole lane tiles (``g*d`` not a multiple of
128) or a time length is not whole blocks, the same kernels take a fold
``[b*h/g, t, g*d]`` padded in time.  The shapes decide, and
``attention.flash.in_place`` / ``attention.flash.folded`` in ``mx.obs``
count which a traced node took.

On a v5e, bf16 causal, blocks 512 x 512, the node with whatever layout
passes it needs (my chip run, PR 34; the parent is PR 31's pair of
kernels, the forward on ``[b*h, t, d]`` with lane-replicated state):

====================  ================  ================  ==================
shape (b, t, h, d)    forward           forward+backward  the same, folded
====================  ================  ================  ==================
(8, 1024, 16, 64)     1.553 -> 0.630    2.840 -> 1.538    0.737 / 1.771
(1, 4096, 20, 256)    3.142 -> 1.929    7.229 -> 5.275    2.531 / 6.416
(2, 8192, 8, 64)      5.828 -> 3.052    11.34 -> 7.533    3.114 / 7.678
(4, 2048, 8, 128)     1.169 -> 0.611    2.135 -> 1.477    0.777 / 1.761
====================  ================  ================  ==================

(ms; "folded" is this file's kernels made to take the fold at a shape
they read in place: what the layout passes cost.)  Outputs and
gradients stand as far from the float32 oracle as the parent's did
(relative gradient error 0.0016962 against 0.0016962 at the first
shape).  One fused backward kernel beat a dK/dV kernel plus a dQ kernel (seven
products and every elementwise pass twice), 1.33 against 1.82 ms and
3.13 against 4.46 ms (PERF.md §6, PR 31).

Under a causal window (``window``: query t sees the keys
t - window < s <= t) both grids step over the blocks of the other side
that one block's window reaches, from the first live one, and never the
whole sequence: the forward's k-steps start at a q-block's first live
k-block, the backward's q-steps at a k-block's diagonal, and a q-block's
dq is whole, and written, at its own diagonal k-block.  Tiles outside
the window are neither computed nor fetched.  On a v5e, bf16, 64 heads
of 128 over 8,192 positions in a window of 512, forward and backward:
12.09 ms at blocks 512 x 512, 16.61 at 256 x 256, 29.82 at 128 x 128,
13.99 and 15.44 at 512 x 256 and 256 x 512, against 37.54 for the causal
kernels over the whole sequence; the default blocks stand, though half
of each 512 x 512 tile they visit is dead (``window_live_share``).

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
from jax.experimental.pallas import tpu as pltpu

from ... import obs as _obs

__all__ = ["flash_attention", "flash_attention_reference"]

_NEG_INF = float("-inf")

_LANES = 128  # VPU lane width: a block's last dimension is a multiple

# nodes traced, by what the kernels read: the graph's arrays where they
# lie, or a fold and a pad of them
_IN_PLACE = _obs.counter("attention.flash.in_place")
_FOLDED = _obs.counter("attention.flash.folded")
# of the last node traced, or whose shapes were inferred, under a
# window: its live (query, key) pairs over all the pairs of the tiles
# the forward kernel visits
_LIVE_SHARE = _obs.gauge("attention.window.live_share")

_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _only(hd, x, heads, d):
    # `heads` heads lie side by side along the lanes.  One head's
    # products come from operands with the other heads' lanes zeroed: a
    # contraction over them adds nothing, a product with them lands in
    # this head's lanes of the accumulator, and the MXU does the work of
    # one d-wide product either way (its depth and width are 128).
    if heads == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads * d), 1)
    return jnp.where(lane // d == hd, x, jnp.zeros_like(x))


def _live(j, kb, block_q, block_k, causal):
    """Whether the causal mask leaves the tile of q-block ``j`` and
    k-block ``kb`` a single score.  (Under a window the grid visits no
    k-block before a q-block's first live one, so this bound is the
    only one left to test.)"""
    return (kb * block_k <= j * block_q + block_q - 1) if causal else True


def _tile_mask(j, kb, block_q, block_k, causal, t_kv_real, window=0):
    """[block_k, block_q]: the scores of the tile that live."""
    k_pos = kb * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 0)
    mask = k_pos < t_kv_real
    if causal:
        q_pos = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 1)
        mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
    return mask


# Under a causal window a query at t sees the keys t - window < s <= t.
# The kernels' grids then step over the blocks that one block of the
# other side can reach, from the first live one, and never the whole
# sequence; these give the first and last live block either way.
def _first_kb(j, block_q, block_k, window):
    """The first k-block a window lets q-block ``j`` see."""
    return jnp.maximum(j * block_q - window + 1, 0) // block_k


def _last_kb(j, block_q, block_k, n_kb):
    """The last k-block q-block ``j`` sees (the causal diagonal)."""
    return jnp.minimum(((j + 1) * block_q - 1) // block_k, n_kb - 1)


def _first_qb(kb, block_q, block_k):
    """The first q-block that sees k-block ``kb`` (the causal diagonal)."""
    return kb * block_k // block_q


def _last_qb(kb, block_q, block_k, window, n_qb):
    """The last q-block a window lets see k-block ``kb``."""
    return jnp.minimum(((kb + 1) * block_k + window - 2) // block_q,
                       n_qb - 1)


def _k_blocks_seen(t_q, t_kv, block_q, block_k, window):
    """For each q-block, how many k-blocks its window reaches."""
    return [min((j + 1) * block_q - 1, t_kv - 1) // block_k
            - max(j * block_q - window + 1, 0) // block_k + 1
            for j in range(-(-t_q // block_q))]


def _window_steps(t_q, t_kv, block_q, block_k, window):
    """(forward's k-steps, backward's q-steps): the most blocks of the
    other side one block's window reaches, counted over the blocks,
    within ``ceil((window + block - 1) / other block) + 1``."""
    n_qb, n_kb = -(-t_q // block_q), -(-t_kv // block_k)
    bwd = max(min(((kb + 1) * block_k + window - 2) // block_q, n_qb - 1)
              - kb * block_k // block_q + 1 for kb in range(n_kb))
    return max(_k_blocks_seen(t_q, t_kv, block_q, block_k, window)), bwd


def window_live_share(t, window, block_q, block_k):
    """The live (query, key) pairs of a causal window over ``t``
    positions, over all the pairs of the tiles the forward kernel
    visits: 1.0 where no tile work is wasted."""
    tiles = sum(_k_blocks_seen(t, t, block_q, block_k, window))
    w = min(window, t)
    live = t * w - w * (w - 1) // 2
    return live / float(tiles * block_q * block_k)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, block_q, block_k, causal, masked, scale, t_kv_real,
                heads, d, window=0):
    # Grid is (batch, groups of heads, n_qb, n_kb), keys innermost: K/V
    # stream through VMEM a [block_k, g*d] tile a step while the online
    # softmax's state carries in scratch.  The tile is [bk, bq], as in
    # the backward: the running maximum and denominator are lane-dense
    # rows [1, bq], a head a row, that broadcast over sublanes, and the
    # reductions over keys run down the sublanes.  Under a window the
    # k-steps start at the q-block's first live k-block.
    j, step = pl.program_id(2), pl.program_id(3)
    n_steps = pl.num_programs(3)
    kb = step + _first_kb(j, block_q, block_k, window) if window else step

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_live(j, kb, block_q, block_k, causal))
    def _update():
        # MXU operands in the storage dtype, float32 accumulation; the
        # scale is applied to the float32 scores; maximum, exponent and
        # denominator in float32; P rounded to the storage dtype for
        # the P.V product only (it is in [0, 1]: 2^-9 relative in bf16).
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        if masked:
            mask = _tile_mask(j, kb, block_q, block_k, causal, t_kv_real,
                              window)
        for hd in range(heads):
            row = slice(hd, hd + 1)
            band = slice(hd * d, (hd + 1) * d)
            s_t = jax.lax.dot_general(
                k, _only(hd, q, heads, d), _NT,
                preferred_element_type=jnp.float32) * scale
            if masked:
                s_t = jnp.where(mask, s_t, _NEG_INF)
            m = m_ref[row, :]
            m_new = jnp.maximum(m, jnp.max(s_t, axis=0, keepdims=True))
            # a row with no live key so far keeps -inf; its exponents
            # are taken against 0 and come out 0
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p_t = jnp.exp(s_t - m_safe)
            corr = jnp.exp(m - m_safe)
            l_ref[row, :] = l_ref[row, :] * corr + jnp.sum(
                p_t, axis=0, keepdims=True)
            m_ref[row, :] = m_new
            # V.T @ P.T for all g*d rows; this head's are its band
            pv_t = jax.lax.dot_general(
                v, p_t.astype(v.dtype), _TN,
                preferred_element_type=jnp.float32)
            acc_ref[band, :] = acc_ref[band, :] * corr + pv_t[band, :]

    @pl.when(step == n_steps - 1)
    def _finalize():
        m, l = m_ref[...], l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        for hd in range(heads):
            band = slice(hd * d, (hd + 1) * d)
            acc_ref[band, :] = acc_ref[band, :] / l_safe[hd:hd + 1, :]
        o_ref[0] = acc_ref[...].T.astype(o_ref.dtype)
        lse_ref[0] = jnp.where(jnp.isneginf(m), _NEG_INF,
                               m + jnp.log(l_safe))


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, delta_acc, *,
                block_q, block_k, causal, masked, scale, t_kv_real,
                heads, d, window=0):
    # Grid is (batch, groups of heads, n_kb, q-steps), queries innermost:
    # one K/V tile stays in VMEM while the Q/dO tiles stream past it,
    # dk/dv accumulate in scratch over the q-blocks, dq in a scratch row
    # per q-block that lives across the k-blocks of this group.  With no
    # window the q-steps are the q-blocks; under one they start at the
    # k-block's diagonal and a q-block's dq is whole at its own.
    kb, step = pl.program_id(2), pl.program_id(3)
    n_kb, n_steps = pl.num_programs(2), pl.num_programs(3)
    if window:
        n_qb = dq_acc.shape[0]
        j = _first_qb(kb, block_q, block_k) + step
        live = j <= _last_qb(kb, block_q, block_k, window, n_qb)
        first = jnp.logical_and(live,
                                kb == _first_kb(j, block_q, block_k, window))
        last = jnp.logical_and(live,
                               kb == _last_kb(j, block_q, block_k, n_kb))
    else:
        j = step

    @pl.when(step == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(first if window else kb == 0)
    def _first_pass():
        # the q-block's first pass (with no window every one is live
        # against the first keys): dq starts, and delta = sum(dO * O)
        # over a head's lanes is taken as the row it is used as
        dq_acc[j] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)
        prod_t = (do_ref[0].astype(jnp.float32)
                  * o_ref[0].astype(jnp.float32)).T           # [g*d, bq]
        for hd in range(heads):
            delta_acc[j, hd:hd + 1, :] = jnp.sum(
                prod_t[hd * d:(hd + 1) * d, :], axis=0, keepdims=True)

    @pl.when(live if window else _live(j, kb, block_q, block_k, causal))
    def _update():
        # same contract as the forward: MXU operands in the storage
        # dtype, float32 accumulation, the scale, the exponent, delta
        # and the ds combination in float32.  The tile is [bk, bq].
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        if masked:
            mask = _tile_mask(j, kb, block_q, block_k, causal, t_kv_real,
                              window)
        for hd in range(heads):
            q_h, k_h, do_h = (_only(hd, x, heads, d) for x in (q, k, do))
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
            ds_t = (p_t * (dp_t - delta_acc[j, hd:hd + 1, :])).astype(q.dtype)
            dk_acc[...] += jax.lax.dot_general(
                ds_t, q_h, _NN, preferred_element_type=jnp.float32)
            dq_acc[j] += jax.lax.dot_general(
                ds_t, k_h, _TN, preferred_element_type=jnp.float32)

    @pl.when(step == n_steps - 1)
    def _finalize_dkv():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(last if window else kb == n_kb - 1)
    def _finalize_dq():
        dq_ref[0] = (dq_acc[j] * scale).astype(dq_ref.dtype)


def _lay(x, g, block, in_place):
    """What the kernels take of a ``[b, t, h, d]`` array: ``[b, t, h*d]``,
    the graph's own array (a block is ``g`` heads' lanes of it), or,
    where such a block is not whole lane tiles or ``t`` not whole
    blocks, the fold ``[b*h/g, t, g*d]`` (``g`` neighbouring heads side
    by side along the lanes) padded in time to whole blocks."""
    b, t, h, d = x.shape
    if in_place:
        return x.reshape(b, t, h * d)
    x = x.reshape(b, t, h // g, g * d).transpose(0, 2, 1, 3).reshape(
        b * h // g, t, g * d)
    return jnp.pad(x, ((0, 0), (0, (-t) % block), (0, 0)))


def _unlay(x, shape, g, in_place):
    """``_lay``'s inverse: back to the graph's ``shape``, [b, t, h, d]."""
    b, t, h, d = shape
    if in_place:
        return x.reshape(b, t, h, d)
    return x[:, :t].reshape(b, h // g, t, g * d).transpose(
        0, 2, 1, 3).reshape(b, t, h, d)


def _compiler_params(interpret, semantics, vmem):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=min(max(2 * vmem, 32 << 20), 100 << 20))}


def _fwd_call(q, k, v, causal, scale, block_q, block_k, g, in_place,
              interpret, window=0):
    """q/k/v: [b, t, h, d] -> (o [b, t_q, h, d], lse [b*h/g, g, t_q_pad])."""
    d = q.shape[3]
    t_kv = k.shape[1]
    qp = _lay(q, g, block_q, in_place)
    kp = _lay(k, g, block_k, in_place)
    vp = _lay(v, g, block_k, in_place)
    n, t_qp, width = qp.shape
    t_kvp = kp.shape[1]
    gd = g * d
    n_hg = width // gd          # groups along the lanes: 1 when folded
    n_qb, n_kb = t_qp // block_q, t_kvp // block_k
    n_steps = _window_steps(t_qp, t_kv, block_q, block_k, window)[0] \
        if window else n_kb

    def k_blk(j, kb):
        # a dead tile (keys after this q-block) asks for the block the
        # last live one did, so nothing is fetched for it; under a
        # window the steps start at the first live block
        if window:
            return jnp.minimum(kb + _first_kb(j, block_q, block_k, window),
                               _last_kb(j, block_q, block_k, n_kb))
        if causal:
            kb = jnp.minimum(kb, ((j + 1) * block_q - 1) // block_k)
        return kb

    q_spec = pl.BlockSpec((1, block_q, gd), lambda i, hg, j, kb: (i, j, hg))
    kv_spec = pl.BlockSpec((1, block_k, gd),
                           lambda i, hg, j, kb: (i, k_blk(j, kb), hg))
    row_spec = pl.BlockSpec((1, g, block_q),
                            lambda i, hg, j, kb: (i * n_hg + hg, 0, j))
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        masked=causal or t_kvp != t_kv, scale=scale, t_kv_real=t_kv,
        heads=g, d=d, window=window)
    lanes = -(-gd // _LANES) * _LANES
    vmem = (4 * block_q * lanes                                  # acc
            + 4 * (2 * block_q + 2 * block_k) * lanes * q.dtype.itemsize
            + 6 * 4 * block_q * block_k)              # the tile's values
    o, lse = pl.pallas_call(
        kernel,
        grid=(n, n_hg, n_qb, n_steps),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct((n * n_hg, g, t_qp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((gd, block_q), jnp.float32),   # acc, transposed
            pltpu.VMEM((g, block_q), jnp.float32),    # running max
            pltpu.VMEM((g, block_q), jnp.float32),    # running denom
        ],
        interpret=interpret,
        name="flash_attention_fwd",
        **_compiler_params(
            interpret, ("parallel", "parallel", "parallel", "arbitrary"),
            vmem),
    )(qp, kp, vp)
    return _unlay(o, q.shape, g, in_place), lse


def _bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k, g,
              in_place, interpret, window=0):
    """q/k/v/o/do: [b, t, h, d], lse: [b*h/g, g, t_q_pad] -> (dq, dk, dv)."""
    d = q.shape[3]
    t_kv = k.shape[1]
    qp = _lay(q, g, block_q, in_place)
    op = _lay(o, g, block_q, in_place)
    dop = _lay(do, g, block_q, in_place)     # zero rows: no gradient
    kp = _lay(k, g, block_k, in_place)
    vp = _lay(v, g, block_k, in_place)
    n, t_qp, width = qp.shape
    t_kvp = kp.shape[1]
    gd = g * d
    n_hg = width // gd
    n_qb, n_kb = t_qp // block_q, t_kvp // block_k
    n_steps = _window_steps(t_qp, t_kv, block_q, block_k, window)[1] \
        if window else n_qb

    def q_blk(kb, j):
        # a dead tile (queries before this k-block) asks for the block the
        # first live one will, so nothing is fetched for it; under a
        # window the steps start at the diagonal and a dead one after
        # the last live block asks for that block again
        if window:
            return jnp.minimum(_first_qb(kb, block_q, block_k) + j,
                               _last_qb(kb, block_q, block_k, window, n_qb))
        if causal:
            j = jnp.minimum(jnp.maximum(j, kb * block_k // block_q),
                            n_qb - 1)
        return j

    q_spec = pl.BlockSpec((1, block_q, gd),
                          lambda i, hg, kb, j: (i, q_blk(kb, j), hg))
    # O is read in the first pass alone, where delta is taken
    o_spec = pl.BlockSpec(
        (1, block_q, gd),
        lambda i, hg, kb, j: (i, jnp.where(kb == 0, j, n_qb - 1), hg))
    if window:
        # a q-block's first pass is at the first k-block that reaches
        # it; before it the block the last pass read is asked again
        def o_blk(kb, j):
            return jnp.where(
                kb == 0, q_blk(kb, j),
                jnp.maximum(q_blk(kb, j), _last_qb(kb - 1, block_q, block_k,
                                                   window, n_qb)))
        o_spec = pl.BlockSpec((1, block_q, gd),
                              lambda i, hg, kb, j: (i, o_blk(kb, j), hg))
    row_spec = pl.BlockSpec(
        (1, g, block_q),
        lambda i, hg, kb, j: (i * n_hg + hg, 0, q_blk(kb, j)))
    kv_spec = pl.BlockSpec((1, block_k, gd), lambda i, hg, kb, j: (i, kb, hg))
    # dq's block stays put until the last k-block, so each block goes to
    # HBM once, after its last contribution
    dq_spec = pl.BlockSpec(
        (1, block_q, gd),
        lambda i, hg, kb, j: (i, jnp.where(kb == n_kb - 1, j, 0), hg))
    if window:
        # a q-block's dq is whole at its diagonal k-block: the block
        # asked is the q-block being finished there, or else the last
        # one finished, whose values the buffer still holds
        def dq_blk(kb, j):
            done = jnp.where(kb == n_kb - 1, n_qb - 1,
                             jnp.clip((kb + 1) * block_k // block_q - 1,
                                      0, n_qb - 1))
            return jnp.minimum(q_blk(kb, j), done)
        dq_spec = pl.BlockSpec((1, block_q, gd),
                               lambda i, hg, kb, j: (i, dq_blk(kb, j), hg))
    kernel = functools.partial(
        _bwd_kernel, block_q=block_q, block_k=block_k, causal=causal,
        masked=causal or t_kvp != t_kv, scale=scale, t_kv_real=t_kv,
        heads=g, d=d, window=window)
    lanes = -(-gd // _LANES) * _LANES
    vmem = (4 * t_qp * lanes                          # dq, resident
            + 2 * 4 * block_k * lanes                 # dk, dv
            + 12 * (block_q + block_k) * lanes * q.dtype.itemsize
            + 6 * 4 * block_q * block_k)              # the tile's values
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(n, n_hg, n_kb, n_steps),
        in_specs=[q_spec, kv_spec, kv_spec, o_spec, q_spec, row_spec],
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
            pltpu.VMEM((n_qb, g, block_q), jnp.float32),   # delta
        ],
        interpret=interpret,
        name="flash_attention_bwd",
        **_compiler_params(
            interpret, ("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem),
    )(qp, kp, vp, op, dop, lse)
    return (_unlay(dq, q.shape, g, in_place),
            _unlay(dk, k.shape, g, in_place),
            _unlay(dv, v.shape, g, in_place))


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 11)))
def _flash(q, k, v, causal, scale, block_q, block_k, g, in_place, interpret,
           window):
    return _fwd_call(q, k, v, causal, scale, block_q, block_k, g, in_place,
                     interpret, window)[0]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, g, in_place,
               interpret, window):
    o, lse = _fwd_call(q, k, v, causal, scale, block_q, block_k, g,
                       in_place, interpret, window)
    # what lives between the passes is q, k, v and o as the graph has
    # them, [b, t, h, d], and lse [b*h/g, g, t] float32
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, g, in_place, interpret,
               window, res, do):
    q, k, v, o, lse = res
    return _bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                     g, in_place, interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _clamp(block, t):
    # clamp to the sequence but keep the block LANE-ALIGNED: a raw
    # min(block, t) for 128 < t < block would hand Mosaic a
    # non-tile-multiple block shape (t=300 -> (300, d) blocks);
    # rounding t up to a 128 multiple keeps one aligned block and
    # the pad in time makes the array match
    return min(block, -(-max(t, 1) // _LANES) * _LANES)


def _plan(t_q, t_kv, h, d, block_q, block_k):
    """(block_q, block_k, g, in_place) for these shapes: the blocks
    clamped to the sequences, how many heads go side by side, and
    whether the kernels can read the graph's arrays where they lie."""
    block_q = _clamp(block_q, t_q)
    block_k = _clamp(block_k, t_kv)
    # as many neighbouring heads a grid step as fit the lanes
    g = max(n for n in range(1, h + 1)
            if h % n == 0 and (n == 1 or n * d <= _LANES))
    # in place where a block is whole lane tiles and the sequences are
    # whole blocks; else a fold and a pad feed the same kernels
    in_place = (g * d % _LANES == 0 and t_q % block_q == 0
                and t_kv % block_k == 0)
    return block_q, block_k, g, in_place


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=512, block_k=512, interpret=None, window=0):
    """Memory-efficient exact attention.

    Args: ``q`` [b, t_q, h, d], ``k``/``v`` [b, t_kv, h, d] (the
    ``ring_attention`` layout).  Returns [b, t_q, h, d] in ``q.dtype``.
    ``window`` > 0 (with ``causal``) lets query t see the keys
    t - window < s <= t alone; the grids then visit only the tiles such
    a window reaches.

    ``interpret=None`` auto-selects: compiled Pallas on TPU, interpreter
    elsewhere (bit-accurate, used by the CPU test mesh).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if window < 0 or (window and not causal):
        raise ValueError("flash_attention: a window (%r) is causal and "
                         "positive" % (window,))
    _, t_q, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_q, block_k, g, in_place = _plan(t_q, k.shape[1], h, d,
                                          block_q, block_k)
    (_IN_PLACE if in_place else _FOLDED).inc()
    if window:
        note_window(t_q, k.shape[1], window, block_q, block_k)
    return _flash(q, k, v, causal, float(scale), block_q, block_k, g,
                  in_place, interpret, int(window))


def note_window(t_q, t_kv, window, block_q=512, block_k=512):
    """Leave in the gauge ``attention.window.live_share`` the live share
    of the tiles the kernels visit for a window over ``t_q`` queries at
    these blocks, clamped as the kernels clamp them.  A traced node sets
    it, and so does the op's shape inference: a program loaded from the
    program cache is never traced."""
    _LIVE_SHARE.set(window_live_share(t_q, window, _clamp(block_q, t_q),
                                      _clamp(block_k, t_kv)))


def flash_attention_reference(q, k, v, causal=False, scale=None, window=0):
    """O(T^2) jnp oracle (same layout), for tests and tiny shapes."""
    from ...parallel.ring_attention import attention_reference
    return attention_reference(q, k, v, causal=causal, scale=scale,
                               window=window)
