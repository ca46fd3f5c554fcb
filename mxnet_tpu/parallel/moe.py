"""Expert parallelism: a mixture-of-experts layer over an ``expert``
mesh axis.

Greenfield relative to the reference.  Two dispatch formulations share
one gating front-end and one capacity rule:

* **dense** — the textbook TPU formulation: top-k token-choice gating
  builds a ``(tokens*k, experts, capacity)`` one-hot dispatch tensor;
  dispatch, per-expert FFN and combine are plain einsums.  Simple, but
  the dispatch/combine einsums cost O(T·E·C·d) FLOPs and bytes for
  what is really a gather/scatter.
* **sparse** — sort-based dispatch: stable-argsort the routing entries
  by expert, gather the first ``C`` entries per expert into the static
  ``(E, C, d)`` expert buffer, and combine by gathering each entry's
  slot back and segment-summing the ``k`` slots per token.  O(T·k·d +
  E·C·d) bytes — :func:`moe_dispatch_bytes` is the static model, and
  the two paths agree bitwise because the stable sort reproduces the
  dense cumsum position-within-expert exactly.

``MXTPU_MOE_DISPATCH=dense|sparse`` selects the path (A/B knob; sparse
is the default), or pass ``dispatch=`` explicitly.

**``keep`` mask contract** — ``moe_apply`` returns ``(out, keep)``.
``keep[t]`` (top-1) or ``keep[t, j]`` (top-k) is True iff that routing
entry landed within its expert's capacity ``C = ceil(T·k/E · factor)``;
a False entry contributed exactly 0 to ``out`` (the token was dropped
by that expert, standard capacity-based routing — shapes stay static
for XLA).  Callers that care about routing health should surface the
fraction via :func:`record_dropped_frac`, which backs the
``parallel.moe.dropped_frac`` obs counter; the trainer-side silent
discard of ``keep`` is exactly what that counter exists to catch.

**The no-drop path** — :func:`sigmoid_topk_route` and
:func:`moe_apply_held` are the mathematics of the Symbol ops
``MoERouter`` / ``MoEExperts`` (``op/moe.py``): sigmoid scores, a
chosen set by score plus a correction bias, and a chip that holds
``G`` of the ``E`` experts and computes its own experts' part for
every entry routed to them, whatever the imbalance.  There is no
capacity and no ``keep``, and no buffer sized for the worst case: the
entries are sorted by expert and the held experts' run of them is
walked in chunks of a fixed number of rows, as many trips as the
routing fills (``moe.row_chunks_per_layer``; one at the expected
load), the three products of a trip grouped matrix products over its
rows.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import envknobs as _envknobs
from .. import obs as _obs

__all__ = ["moe_init", "moe_apply", "moe_apply_dense", "moe_apply_sparse",
           "moe_shardings", "moe_load_balance_loss", "moe_capacity",
           "moe_dispatch_bytes", "record_dropped_frac",
           "sigmoid_topk_route", "moe_apply_held", "held_chunk_rows",
           "row_chunks"]

# last observed dropped-token fraction (registry-backed; scraped by
# obs.snapshot() / tools/obs_report.py).  A fraction, set per call —
# see record_dropped_frac.
_DROPPED_FRAC = _obs.counter("parallel.moe.dropped_frac", initial=0.0)


def moe_init(key, d_model, d_hidden, n_experts, dtype=jnp.float32):
    """Parameters: gate (d, E), per-expert 2-layer FFN."""
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = d_model ** -0.5
    s2 = d_hidden ** -0.5
    return {
        "gate": (jax.random.normal(k1, (d_model, n_experts)) * s1
                 ).astype(dtype),
        "w1": (jax.random.normal(k2, (n_experts, d_model, d_hidden)) * s1
               ).astype(dtype),
        "w2": (jax.random.normal(k3, (n_experts, d_hidden, d_model)) * s2
               ).astype(dtype),
    }


def moe_shardings(mesh, axis="expert"):
    """Per-leaf NamedShardings: experts sharded, gate replicated."""
    return {
        "gate": NamedSharding(mesh, PartitionSpec()),
        "w1": NamedSharding(mesh, PartitionSpec(axis, None, None)),
        "w2": NamedSharding(mesh, PartitionSpec(axis, None, None)),
    }


def moe_capacity(n_tokens, n_experts, capacity_factor=1.25, top_k=1):
    """Static per-expert capacity ``C = ceil(T·k/E · factor)``."""
    return max(1, math.ceil((n_tokens * top_k / n_experts)
                            * capacity_factor))


def _gate_topk(params, x, top_k):
    """Shared gating front-end: softmax gate, top-k expert choice.

    Returns ``(gates, expert, gate_val)`` with ``expert``/``gate_val``
    of shape (T, k).  Top-1 keeps the raw softmax probability (the
    Switch convention); k>1 renormalizes the chosen probabilities to
    sum to 1 per token.
    """
    gates = jax.nn.softmax(x @ params["gate"], axis=-1)
    if top_k == 1:
        expert = jnp.argmax(gates, axis=-1)[:, None]
        gate_val = jnp.take_along_axis(gates, expert, 1)
    else:
        gate_val, expert = jax.lax.top_k(gates, top_k)
        gate_val = gate_val / jnp.sum(gate_val, axis=-1, keepdims=True)
    return gates, expert, gate_val


def _expert_ffn(params, ex_in):
    """(E, C, d) -> (E, C, d): each expert's 2-layer relu FFN."""
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", ex_in, params["w1"]))
    return jnp.einsum("ech,ehd->ecd", h, params["w2"])


def _finish(out_flat, keep_flat, T, top_k, d):
    """Fold the k routing slots back per token (the segment-sum: slots
    of one token are adjacent in entry order t·k+j)."""
    if top_k == 1:
        return out_flat, keep_flat
    return (out_flat.reshape(T, top_k, d).sum(axis=1),
            keep_flat.reshape(T, top_k))


def moe_apply_dense(params, x, capacity_factor=1.25, top_k=1):
    """Dense one-hot dispatch/combine (the A/B reference path).

    ``x``: (tokens, d_model) -> ((tokens, d_model), keep).
    """
    T, d = x.shape
    E = params["gate"].shape[1]
    C = moe_capacity(T, E, capacity_factor, top_k)
    _, expert, gate_val = _gate_topk(params, x, top_k)
    ef = expert.reshape(-1)                              # (N,) N = T*k
    gf = gate_val.reshape(-1)

    # position of each routing entry within its expert's queue
    onehot = jax.nn.one_hot(ef, E, dtype=jnp.int32)      # (N, E)
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1        # (N, E)
    pos_in_e = jnp.max(pos, axis=1)                      # (N,)
    keep = pos_in_e < C

    # dispatch (N, E, C) one-hot; dropped entries vanish
    disp = (jax.nn.one_hot(ef, E, dtype=x.dtype)[:, :, None] *
            jax.nn.one_hot(jnp.clip(pos_in_e, 0, C - 1), C,
                           dtype=x.dtype)[:, None, :] *
            keep[:, None, None].astype(x.dtype))
    x_rep = jnp.repeat(x, top_k, axis=0) if top_k > 1 else x
    ex_in = jnp.einsum("tec,td->ecd", disp, x_rep)       # (E, C, d)
    ex_out = _expert_ffn(params, ex_in)                  # (E, C, d)
    out = jnp.einsum("tec,ecd->td", disp, ex_out) * gf[:, None]
    return _finish(out, keep, T, top_k, d)


def moe_apply_sparse(params, x, capacity_factor=1.25, top_k=1):
    """Sort-based dispatch: argsort entries by expert, gather the first
    ``C`` per expert into the (E, C, d) buffer, combine by gathering
    back.  The stable sort keeps entries of one expert in original
    order, so position-within-expert (and therefore which tokens drop)
    matches the dense cumsum bit-for-bit.
    """
    T, d = x.shape
    E = params["gate"].shape[1]
    C = moe_capacity(T, E, capacity_factor, top_k)
    _, expert, gate_val = _gate_topk(params, x, top_k)
    N = T * top_k
    ef = expert.reshape(-1)                              # (N,)
    gf = gate_val.reshape(-1)

    order = jnp.argsort(ef, stable=True)                 # (N,) entry ids
    counts = jnp.bincount(ef, length=E)                  # (E,)
    start = jnp.cumsum(counts) - counts                  # exclusive cumsum
    # in sorted order, expert e's entries sit at start[e]..+counts[e)-1
    pos_sorted = jnp.arange(N) - start[ef[order]]
    pos_in_e = jnp.zeros(N, pos_sorted.dtype).at[order].set(pos_sorted)
    keep = pos_in_e < C

    # dispatch: slot (e, c) takes entry order[start[e]+c] when c < counts[e]
    slot = start[:, None] + jnp.arange(C)[None, :]       # (E, C)
    valid = jnp.arange(C)[None, :] < counts[:, None]     # (E, C)
    src = order[jnp.clip(slot, 0, N - 1)]                # (E, C) entry ids
    tok = src // top_k if top_k > 1 else src             # (E, C) token ids
    ex_in = jnp.where(valid[..., None], x[tok], jnp.zeros((), x.dtype))
    ex_out = _expert_ffn(params, ex_in)                  # (E, C, d)

    # combine: each kept entry reads its slot back; dropped entries are 0
    gath = ex_out[ef, jnp.clip(pos_in_e, 0, C - 1)]      # (N, d)
    out = jnp.where(keep[:, None], gath,
                    jnp.zeros((), gath.dtype)) * gf[:, None]
    return _finish(out, keep, T, top_k, d)


def moe_apply(params, x, capacity_factor=1.25, top_k=1, dispatch=None):
    """Top-k MoE FFN.  ``x``: (tokens, d_model) -> (tokens, d_model).

    ``dispatch``: "dense" | "sparse" | None (None resolves the
    ``MXTPU_MOE_DISPATCH`` knob, default "sparse").  Both paths agree
    on values, grads, and the ``keep`` mask (see module docstring for
    the mask contract); tokens over an expert's capacity are dropped.
    """
    if dispatch is None:
        dispatch = _envknobs.get_str("MXTPU_MOE_DISPATCH", "sparse")
    if dispatch not in ("dense", "sparse"):
        raise ValueError("MXTPU_MOE_DISPATCH=%r (want dense|sparse)"
                         % (dispatch,))
    fn = moe_apply_dense if dispatch == "dense" else moe_apply_sparse
    return fn(params, x, capacity_factor=capacity_factor, top_k=top_k)


def record_dropped_frac(keep):
    """Host-side: record ``1 - mean(keep)`` on the registry-backed
    ``parallel.moe.dropped_frac`` counter and return it.  Call OUTSIDE
    jit with the concrete ``keep`` mask from :func:`moe_apply` — this
    is the observable that makes silent capacity drops visible."""
    frac = float(1.0 - jnp.mean(jnp.asarray(keep, jnp.float32)))
    _DROPPED_FRAC.set(frac)
    return frac


def moe_dispatch_bytes(n_tokens, d_model, n_experts,
                       capacity_factor=1.25, top_k=1, dispatch="sparse",
                       itemsize=4):
    """Static dispatch+combine traffic model (bytes, excluding the
    expert FFN itself, which is identical in both paths).

    dense: the (N, E, C) dispatch tensor is written once and read by
    both einsums, which also stream x/ex_in/ex_out/out.
    sparse: index arrays (int32) plus two gathers — no (N, E, C)
    tensor ever exists.  ``tests/test_parallel_workloads.py`` holds
    sparse <= dense/2 at the transformer-large shape.
    """
    T, d, E = int(n_tokens), int(d_model), int(n_experts)
    C = moe_capacity(T, E, capacity_factor, top_k)
    N = T * top_k
    if dispatch == "dense":
        return itemsize * (3 * N * E * C      # disp: 1 write + 2 reads
                           + 2 * N * d        # x read, out write
                           + 2 * E * C * d)   # ex_in write, ex_out read
    if dispatch == "sparse":
        return (itemsize * (2 * E * C * d     # gather write, ex_out read
                            + 3 * N * d)      # x read, gath, out write
                + 4 * (2 * N + 2 * E + 2 * E * C))  # int32 index arrays
    raise ValueError("dispatch=%r (want dense|sparse)" % (dispatch,))


def moe_load_balance_loss(params, x, gates=None):
    """Auxiliary load-balancing loss (mean gate prob × token fraction per
    expert, scaled by E) — the standard Switch-style regularizer.  Pass
    ``gates`` (the softmax probabilities, e.g. from a shared gating pass)
    to avoid recomputing the gate matmul on the hot path."""
    if gates is None:
        gates = jax.nn.softmax(x @ params["gate"], axis=-1)
    E = gates.shape[1]
    frac_tokens = jnp.mean(
        jax.nn.one_hot(jnp.argmax(gates, -1), E, dtype=gates.dtype), axis=0)
    frac_gates = jnp.mean(gates, axis=0)
    return E * jnp.sum(frac_tokens * frac_gates)


# ----------------------------------------------------------------------
# the no-drop path: sigmoid routing, a chip's share of the experts
def sigmoid_topk_route(logits, bias, top_k, scale=1.0, n_group=1,
                       topk_group=1):
    """Sigmoid scores and the ``top_k`` experts a token by score plus
    ``bias`` (the bias only chooses; equal sums go to the lower index).
    With ``n_group`` > 1 the choice is group-limited: the experts lie in
    ``n_group`` equal runs, a group's score is the sum of its two largest
    score + bias, and only the ``topk_group`` best groups' experts can
    be chosen.

    ``logits`` (T, E) -> ``(expert (T, k) int32, weight (T, k), score
    (T, E))`` in float32; a token's weights are its chosen scores over
    their sum, times ``scale``.
    """
    score = jax.nn.sigmoid(logits.astype(jnp.float32))
    pick = score + bias.astype(jnp.float32)
    if n_group > 1:
        tokens, experts = pick.shape
        best2, _ = jax.lax.top_k(pick.reshape(tokens, n_group, -1), 2)
        _, groups = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
        kept = jnp.any(groups[:, :, None] == jnp.arange(n_group), axis=1)
        pick = jnp.where(jnp.repeat(kept, experts // n_group, axis=1),
                         pick, -jnp.inf)
    _, expert = jax.lax.top_k(pick, top_k)
    # the chosen scores by comparison, not by gather: a gather's
    # reverse mode is a scatter, which the chip runs serially
    chosen = expert[:, :, None] == jnp.arange(score.shape[1])
    weight = jnp.sum(jnp.where(chosen, score[:, None, :], 0.0), axis=-1)
    weight = scale * weight / jnp.sum(weight, axis=-1, keepdims=True)
    return expert.astype(jnp.int32), weight, score


# A chunk of sorted entries meets the tokens' rows through two moves that
# are each other's reverse mode: a gather of the chunk's rows one way, a
# sum of the chunk's rows by token the other.  The scatter autodiff would
# write runs serially on the chip, so the sum is a sort by token and a
# gather for each row a run may hold, or a product with the chunk's
# one-hot (rows x tokens, built by comparison) where that is cheaper.
_SUMS_SORTED = _obs.counter("moe.sum_by_token.sorted")
_SUMS_ONEHOT = _obs.counter("moe.sum_by_token.onehot")


def _sorted_route(rows, tokens):
    """Whether a sum of ``rows`` sorted rows over ``tokens`` tokens takes
    the sorted route.  The product's work grows as rows x tokens, the
    sorted route's as tokens x the run's bound.  As the v5e read them
    (PERF.md, section 6): alone on bf16 rows 8,192 x 8,192 x 2,048 0.96 ms
    against 1.54 by the product, 4,096 x 4,096 x 2,048 0.44 against 0.39,
    1,024 x 4,096 x 2,560 at 8 rows a run 1.10 against 0.22; in the step,
    ``lfm2_24b_train`` (8,192 x 8,192) +1.0% and ``glm47flash_train``
    (4,096 x 4,096) +0.4% with every sum sorted.  So the crossover lies
    at or below 4,096 x 4,096 and above 1,024 x 4,096."""
    return rows * tokens >= 4096 * 4096


def _sum_by_token(rows, tok, live, tokens, most):
    """``rows`` (R, c) -> (tokens, c) float32: row t is the sum of the
    rows s that are live and whose token ``tok[s]`` is t, of which there
    are at most ``most``."""
    if _sorted_route(rows.shape[0], tokens):
        _SUMS_SORTED.inc()
        return _sum_sorted(rows, tok, live, tokens, most)
    _SUMS_ONEHOT.inc()
    onehot = (tok[:, None] == jnp.arange(tokens)) & live
    exact = jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        onehot.astype(rows.dtype), rows, (((0,), (0,)), ((), ())),
        precision=exact, preferred_element_type=jnp.float32)


def _sum_sorted(rows, tok, live, tokens, most):
    """:func:`_sum_by_token` with no (R, tokens) operand: the row indices
    sorted by token (dead rows keyed past the last), so that a token's
    rows are one run of at most ``most`` ending where the sorted keys
    pass it; then ``most`` gathers of one row a token, the run's last,
    the one before it, ..., each added in float32 where it is the
    token's.  The rows are read where they lie: nothing of R rows is
    written in sorted order."""
    n = rows.shape[0]
    key = jnp.where(live.reshape(n), tok, tokens).astype(jnp.int32)
    perm = jnp.argsort(key, stable=True)
    key = key[perm]
    # sorted rows keyed at most t: where token t lands in a stable sort
    # of the keys followed by the tokens, less the t tokens before it (the
    # landing places as the inverse permutation, a second sort: the
    # searchsorted of jax.numpy would scatter them or loop)
    token = jnp.arange(tokens, dtype=jnp.int32)
    ranks = jnp.argsort(jnp.argsort(jnp.concatenate([key, token]),
                                    stable=True))
    end = ranks[n:] - token
    total = jnp.zeros((tokens, rows.shape[1]), jnp.float32)
    for j in range(1, min(most, n) + 1):
        at = jnp.maximum(end - j, 0)
        own = (end - j >= 0) & (key[at] == token)
        total = total + jnp.where(own[:, None],
                                  rows[perm[at]].astype(jnp.float32), 0.0)
    return total


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _take_rows(x, tok, live, tokens, most):
    """(tokens, c) -> (R, c): sorted row s gets the row of its token."""
    return x[tok]


_take_rows.defvjp(
    lambda x, tok, live, tokens, most: (x[tok], (tok, live)),
    lambda tokens, most, res, g: (
        _sum_by_token(g, *res, tokens, most).astype(g.dtype), None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _sum_rows(ys, tok, live, tokens, most):
    """(R, c) -> (tokens, c) float32: a token's live rows summed."""
    return _sum_by_token(ys, tok, live, tokens, most)


# (an empty array carries the rows' type to the reverse mode)
_sum_rows.defvjp(
    lambda ys, tok, live, tokens, most: (
        _sum_by_token(ys, tok, live, tokens, most),
        (tok, jnp.zeros((0,), ys.dtype))),
    lambda tokens, most, res, g: (g.astype(res[1].dtype)[res[0]], None,
                                  None))


def _grouped_matmul(lhs, rhs, sizes, live):
    """Row r of ``lhs`` (R, k) times ``rhs[g]`` (G, n, k) transposed,
    g the group whose run of ``sizes[g]`` sorted rows holds r; rows past
    the last group (``live`` (R, 1) false) are 0.  ``lax.ragged_dot``
    walks the row tiles that are live and costs no product for the rest
    (PERF.md, PR 30: the TPU compiler's own kernels, level with the
    Pallas megablox kernel on the v5e).  Those kernels leave the rows
    they skip UNWRITTEN, in reverse mode too: the select makes them 0
    here and keeps what comes back for them out of the transposes, so
    that no stale NaN meets a product (0 x NaN is NaN)."""
    out = jax.lax.ragged_dot(lhs, rhs.swapaxes(1, 2), sizes)
    return jnp.where(live, out, jnp.zeros((), out.dtype))


def held_chunk_rows(entries, held, experts):
    """The rows one trip of :func:`moe_apply_held` walks, from shapes
    alone: twice the entries expected on the ``held`` of ``experts``
    experts, up to a multiple of 512, at most all of them.  Twice, so
    that an ordinary step's surplus finds room in the one trip: at some
    hundred rows an expert the products are bound by the weights' bytes,
    and a second trip for a handful of rows reads every weight again."""
    expected = -(-entries * held // experts)
    return min(entries, -(-2 * expected // 512) * 512)


def row_chunks(live, rows):
    """The trips :func:`moe_apply_held` makes over ``live`` sorted
    entries in chunks of ``rows``."""
    return (live + rows - 1) // rows


def _held_chunk(x, weight, w_gate, w_up, w_down, ent, lo, ends):
    """Sorted rows ``lo .. lo + R``'s part of the result, (T, d)
    float32; ``ent`` (R,) their entries (entry n is token n // k, choice
    n % k), ``ends`` (G,) where each held expert's run ends."""
    rows, (tokens, k) = ent.shape[0], weight.shape
    # an expert's run may be cut by the chunk's edge
    sizes = jnp.diff(jnp.clip(ends, lo, lo + rows), prepend=lo)
    live = (lo + jnp.arange(rows) < ends[-1])[:, None]
    tok = ent // k
    # the select on xs is for the way back: what the transposes return
    # for rows past the groups must not reach the tokens' gradient
    # a token's choices are distinct experts: at most k of its rows here
    xs = jnp.where(live, _take_rows(x, tok, live, tokens, k),
                   jnp.zeros((), x.dtype))
    h = jax.nn.silu(_grouped_matmul(xs, w_gate, sizes, live)) \
        * _grouped_matmul(xs, w_up, sizes, live)
    ys = _grouped_matmul(h.astype(x.dtype), w_down, sizes, live)
    chosen = (ent % k)[:, None] == jnp.arange(k)
    ws = jnp.sum(jnp.where(chosen, _take_rows(weight, tok, live, tokens, k),
                           0.0), axis=1, keepdims=True)
    return _sum_rows(ys * ws.astype(ys.dtype), tok, live, tokens, k)


def _chunk_of(order, ends, c, rows):
    """Chunk ``c``'s :func:`_held_chunk` as a function of the five
    leaves."""
    ent = jax.lax.dynamic_slice(order, (c * rows,), (rows,))
    return lambda *leaves: _held_chunk(*leaves, ent, c * rows, ends)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_part(rows, x, weight, w_gate, w_up, w_down, order, ends):
    """The sum of :func:`_held_chunk` over the chunks that hold a live
    row.  Chunk 0 is outside the loop, so that the one chunk of a step
    at the expected load meets no accumulator (with no entry held it
    computes exact zeros); the trips after it, as many as the routing
    fills, add their chunks to its result."""
    leaves = (x, weight, w_gate, w_up, w_down)
    y = jax.lax.fori_loop(
        1, row_chunks(ends[-1], rows),
        lambda c, y: y + _chunk_of(order, ends, c, rows)(*leaves),
        _chunk_of(order, ends, 0, rows)(*leaves))
    return y.astype(x.dtype)


def _held_part_bwd(rows, saved, g):
    """A second walk of the same chunks, each computing its forward
    again (chunk 0's is the forward pass's own, which the compiler
    keeps: R rows) and adding its five gradients: the sum of a trip in
    float32, kept in the leaf's type (an accumulator in float32 is a
    fill, an add and a conversion of every expert weight, 1.3 ms a layer
    at GLM's shapes: PERF.md, PR 36).  Autodiff cannot reverse a loop
    whose trip count is traced, and would keep every chunk's rows if it
    could."""
    *leaves, order, ends = saved
    g = g.astype(jnp.float32)

    def grads(c):
        return jax.vjp(_chunk_of(order, ends, c, rows), *leaves)[1](g)

    def trip(c, sums):
        return tuple((s.astype(jnp.float32) + d.astype(jnp.float32))
                     .astype(s.dtype) for s, d in zip(sums, grads(c)))

    return jax.lax.fori_loop(1, row_chunks(ends[-1], rows), trip,
                             grads(0)) + (None, None)


_held_part.defvjp(lambda rows, *args: (_held_part(rows, *args), args),
                  _held_part_bwd)


def moe_apply_held(x, expert, weight, w_gate, w_up, w_down, first_expert,
                   num_experts, chunk_rows=None):
    """The routed part of an expert layer that a chip holding experts
    ``first_expert .. first_expert + G`` of ``num_experts`` computes:

        y[t] = sum over j with e = expert[t, j] held of weight[t, j] * F_e(x[t])
        F_e(v) = (silu(v W_gate[e]^T) * v W_up[e]^T) W_down[e]^T

    ``x`` (T, d); ``expert``/``weight`` (T, k) from
    :func:`sigmoid_topk_route`; ``w_gate``/``w_up`` (G, h, d), ``w_down``
    (G, d, h).  Returns ``(y (T, d), count (num_experts,) float32)``,
    the count of entries routed to each of all the experts.  Nothing is
    dropped and nothing of T*k rows is built but the int32 order: the
    T*k entries are sorted by expert, those of absent experts last, and
    walked in chunks of ``chunk_rows`` (:func:`held_chunk_rows` of the
    shapes unless given) for as many trips as the held experts' entries
    fill, T*k / ``chunk_rows`` when every entry is theirs; a trip
    gathers its rows, runs the three grouped products over them and adds
    each token's rows into ``y`` in float32.
    """
    tokens, k = expert.shape
    N, G = tokens * k, w_gate.shape[0]
    rows = chunk_rows or held_chunk_rows(N, G, num_experts)
    ef = expert.reshape(N)
    local = ef - first_expert
    key = jnp.where((local >= 0) & (local < G), local, G)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    ends = jnp.cumsum(jnp.sum(key[:, None] == jnp.arange(G), axis=0,
                              dtype=jnp.int32))
    # whole chunks: a slice that starts past the end would be moved back
    order = jnp.pad(order, (0, -N % rows))
    y = _held_part(rows, x, weight, w_gate, w_up, w_down, order, ends)
    count = jnp.sum(ef[:, None] == jnp.arange(num_experts), axis=0,
                    dtype=jnp.float32)
    return y, count
