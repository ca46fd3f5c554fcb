"""Silent-data-corruption defense: on-device state checksums, replica
voting, verified rollback (docs/how_to/resilience.md "Silent data
corruption").

The resilience layer catches the failures that announce themselves —
NaN gradients (the step sentinel), torn files (CRC manifests), dead
hosts (heartbeats).  The dominant UNHANDLED failure at fleet scale is
the quiet one: a flaky chip produces finite-but-wrong numbers and every
green light stays green while the run diverges.  Both source systems
treat state consistency as a design axis (the MXNet parameter-server
consistency story; the TensorFlow fault-tolerance story — PAPERS.md);
this module gives the fused trainer the primitive they assume: a cheap,
deterministic way to NOTICE that two copies of the "same" state no
longer hold the same bits.

Fingerprint algorithm (``ALGO`` = ``"xmf1"``):

* every leaf is BITCAST to uint32 words (f32 directly; narrower/wider
  dtypes through a uint8 view) — the checksum is over bits, not values,
  so ``-0.0 != 0.0`` and NaN payloads all count;
* a leaf's fingerprint is ``sum(bits * (i * 2654435761 | 1)) mod 2**32``
  over the flattened word index ``i`` — position-weighted so permuted
  content changes the sum, yet built ONLY from commutative wrap-around
  integer ops, so the result is independent of reduction order,
  sharding, and device layout: the fingerprint of a ZeRO-sharded leaf
  computed across chips equals the fingerprint of the gathered copy
  computed in numpy, bit for bit;
* the global fingerprint folds the per-leaf values with a CRC32 salt of
  each leaf's path, so leaves swapping contents cannot cancel.

Everything here is pure math + small helpers; the trainer wiring
(the fingerprint-fused check-step program, the cross-replica vote via
``shard_map``, the audit replay) lives in ``parallel/trainer.py``, and
the checkpoint-manifest verification in ``resilience.py``.
"""
from __future__ import annotations

import re
import zlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .base import MXNetError

__all__ = ["ALGO", "IntegrityError", "leaf_fingerprint",
           "host_leaf_fingerprint", "fold_fingerprints", "path_salt",
           "named_state_leaves", "host_fingerprint", "manifest_record",
           "verify_manifest_record", "bitflip", "blame_minority",
           "match_leaf"]

ALGO = "xmf1"

# Knuth's golden-ratio multiplicative constant: spreads the position
# index over the 32-bit ring so neighboring words get uncorrelated
# weights; ``| 1`` keeps every weight odd (odd numbers are units mod
# 2**32 — no word is ever multiplied by zero)
_MULT = np.uint32(2654435761)


class IntegrityError(MXNetError):
    """A state-integrity check failed: replicas disagree on bits that
    must be identical, or a deterministic replay produced a different
    fingerprint.  ``record`` carries the evidence::

        {"step": int,          # update counter at the failed check
         "mode": "vote"|"audit",
         "world": int,         # replicas voting (1 for audit)
         "fps": [[...], ...],  # per-replica fingerprint rows (vote)
         "leaves": [...],      # paths of the diverging leaves
         "blamed": [...]|None} # outvoted ranks (None = indeterminate
                               # until the rollback replay resolves it)

    Subclasses MXNetError so generic training-error handling still
    sees it, but callers with a checkpoint line should catch it FIRST
    and run the rollback-to-last-verified protocol instead of dying.
    """

    def __init__(self, message: str, record: Optional[dict] = None):
        super().__init__(message)
        self.record = record or {}
        # registry-backed event count: every constructed IntegrityError
        # IS a detected divergence, whichever layer raised it
        from . import obs as _obs
        _obs.counter("integrity.divergences").inc()


# ----------------------------------------------------------------- jnp
def leaf_fingerprint(x):
    """uint32 fingerprint of one device array (traceable jnp).

    Bitcasts to uint32 words and folds with position weights; pure
    commutative integer arithmetic, so the value is independent of
    sharding and reduction order (a sharded leaf fingerprints to the
    same word as its gathered copy)."""
    import jax.numpy as jnp
    from jax import lax
    if x.ndim == 0:
        x = x.reshape(1)
    itemsize = np.dtype(x.dtype).itemsize
    if x.dtype == jnp.uint32:
        bits = x
    elif itemsize == 4:
        bits = lax.bitcast_convert_type(x, jnp.uint32)
    else:
        # narrower/wider dtypes via a byte view (bitcast to a narrower
        # type appends a trailing byte dim; to uint8 it is exact)
        bits = lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    bits = bits.ravel()
    idx = (jnp.arange(bits.size, dtype=jnp.uint32) * _MULT) | jnp.uint32(1)
    return jnp.sum(bits * idx, dtype=jnp.uint32)


def fold_fingerprints(fps, salts):
    """Fold a vector of per-leaf fingerprints (uint32) with per-leaf
    salts into one global uint32 — commutative, so leaf order never
    matters as long as the salts ride their leaves."""
    import jax.numpy as jnp
    return jnp.sum(jnp.asarray(fps, jnp.uint32)
                   * jnp.asarray(salts, jnp.uint32), dtype=jnp.uint32)


# --------------------------------------------------------------- numpy
def host_leaf_fingerprint(arr) -> int:
    """Numpy mirror of :func:`leaf_fingerprint` — bit-identical by
    construction (same wrap-around uint32 math), used to re-hash LOADED
    checkpoint artifacts against the device-computed manifest value."""
    a = np.ascontiguousarray(np.asarray(arr))
    if a.ndim == 0:
        a = a.reshape(1)
    if a.dtype == np.uint32:
        bits = a.reshape(-1)
    elif a.dtype.itemsize == 4:
        bits = a.reshape(-1).view(np.uint32)
    else:
        bits = a.reshape(-1).view(np.uint8).astype(np.uint32)
    with np.errstate(over="ignore"):
        idx = (np.arange(bits.size, dtype=np.uint32) * _MULT) | np.uint32(1)
        return int(np.sum(bits * idx, dtype=np.uint32))


def path_salt(path: str) -> int:
    """Odd uint32 salt for a leaf path (CRC32 of the path — stable
    across processes, unlike ``hash()``)."""
    return (zlib.crc32(path.encode("utf-8")) | 1) & 0xFFFFFFFF


def named_state_leaves(arg_params: Optional[Dict] = None,
                       aux_params: Optional[Dict] = None,
                       opt_state=None) -> List[Tuple[str, object]]:
    """The canonical ``(path, leaf)`` flattening of a training state —
    ``arg:NAME`` / ``aux:NAME`` / ``opt:NAME<keystr>`` in sorted-name
    order.  The trainer's device-side fingerprint, the checkpoint
    manifest record, and the load-time re-hash all walk THIS list, so
    the three can never disagree on what a path means."""
    import jax
    out = []
    for name in sorted(arg_params or {}):
        out.append(("arg:%s" % name, arg_params[name]))
    for name in sorted(aux_params or {}):
        out.append(("aux:%s" % name, aux_params[name]))
    if opt_state:
        for name in sorted(opt_state):
            leaves = jax.tree_util.tree_flatten_with_path(
                opt_state[name])[0]
            for kp, leaf in leaves:
                out.append(("opt:%s%s" % (name, jax.tree_util.keystr(kp)),
                            leaf))
    return out


def host_fingerprint(named: Sequence[Tuple[str, object]]
                     ) -> Tuple[int, Dict[str, int]]:
    """``(global, {path: fp})`` over ``(path, host-array)`` pairs —
    the numpy side of the device computation."""
    leaves = {}
    total = np.uint32(0)
    with np.errstate(over="ignore"):
        for path, value in named:
            fp = np.uint32(host_leaf_fingerprint(value))
            leaves[path] = int(fp)
            total = np.uint32(total + fp * np.uint32(path_salt(path)))
    return int(total), leaves


# ------------------------------------------------------- manifest glue
def manifest_record(global_fp: int, leaves: Dict[str, int],
                    mode: str = "fp") -> dict:
    """The checkpoint-manifest ``integrity`` entry."""
    return {"algo": ALGO, "mode": mode, "global": int(global_fp),
            "leaves": {k: int(v) for k, v in leaves.items()}}


def verify_manifest_record(record: dict,
                           named: Sequence[Tuple[str, object]],
                           logger=None, what: str = "checkpoint"
                           ) -> bool:
    """Re-hash loaded artifacts against a manifest integrity record.
    Divergence is reported per leaf (the corrupt tensor is named); an
    unknown algo verifies vacuously (a future format must not brick
    every old reader), but a ``refused`` record — the saver itself
    declined to fingerprint a state its replicas disagreed on — never
    verifies, whatever reader asks."""
    from . import obs as _obs
    if not record:
        return True
    if record.get("refused"):
        if logger is not None:
            logger.warning(
                "%s recorded a REFUSED fingerprint (state diverged at "
                "save): %s", what, record["refused"])
        _obs.counter("integrity.verify_refused").inc()
        return False
    if record.get("algo") != ALGO:
        return True
    global_fp, leaves = host_fingerprint(named)
    if global_fp == record.get("global"):
        return True
    if logger is not None:
        want = record.get("leaves", {})
        bad = sorted(p for p, fp in leaves.items()
                     if want.get(p) is not None and want[p] != fp)
        missing = sorted(set(want) - set(leaves))
        logger.warning(
            "%s fails fingerprint verification (global %08x vs manifest "
            "%08x): diverging leaves %s%s — the bytes changed after the "
            "manifest was committed (CRC alone cannot see a re-hashed "
            "patch; the fingerprint is of the VALUES the manifest saw)",
            what, global_fp, record.get("global") or 0,
            bad or "<global-only>",
            (", missing %s" % missing) if missing else "")
    _obs.counter("integrity.verify_failed").inc()
    return False


# ------------------------------------------------------------ bitflip
def bitflip(value, rank: int, bit: int = 12, mesh=None, spec=None,
            axis: str = "data"):
    """XOR-flip one bit of ``value``'s first element ON DEVICE — on the
    copy held by replica ``rank`` of the mesh ``axis`` when a mesh is
    given (the other replicas keep their bits: the array stays CLAIMED
    replicated while physically divergent, which is exactly what a
    corrupt chip produces), or on the whole (single-copy) array
    otherwise.

    f32 leaves only (the fused state is f32 master weights/opt state);
    ``bit`` 0-22 lands in the mantissa — a finite, quiet corruption the
    NaN sentinel can never see."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec

    if value.dtype != jnp.float32:
        raise MXNetError("bitflip targets f32 state leaves, got %s"
                         % (value.dtype,))
    if not 0 <= int(bit) <= 31:
        raise MXNetError("bitflip bit=%r out of range 0-31" % (bit,))
    mask = jnp.uint32(1 << int(bit))

    def _flip(x):
        bits = lax.bitcast_convert_type(x, jnp.uint32)
        flat = bits.ravel()
        flat = flat.at[0].set(flat[0] ^ mask)
        return lax.bitcast_convert_type(flat.reshape(bits.shape),
                                        jnp.float32)

    if mesh is None or int(dict(mesh.shape).get(axis, 1)) <= 1:
        return jax.jit(_flip)(value)

    spec = spec if spec is not None else PartitionSpec()

    def local(x):
        r = lax.axis_index(axis)
        return jnp.where(r == int(rank), _flip(x), x)

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))(value)


def match_leaf(pattern: str, paths: Sequence[str]) -> Optional[str]:
    """First state-leaf path matching a ``leaf=`` glob.

    Only ``*`` and ``?`` are wildcards — ``[``/``]`` are LITERAL, so
    the opt-state path ``opt:fc1_weight[0]`` is addressable (an fnmatch
    character class would eat the ``[0]``).  ``/`` spells the namespace
    colon (``leaf=opt/fc1_weight[0]``) because ``:`` separates
    conditions in the fault grammar and can never reach this glob; the
    bare name after the namespace is also tried, so ``leaf=fc1*`` works
    without spelling the namespace."""
    rx = re.compile("".join(
        ".*" if ch == "*" else "." if ch == "?" else re.escape(ch)
        for ch in pattern.replace("/", ":")))
    for path in paths:
        bare = path.split(":", 1)[-1]
        if rx.fullmatch(path) or rx.fullmatch(bare):
            return path
    return None


# ---------------------------------------------------------------- vote
def blame_minority(matrix: np.ndarray, rep_cols: Sequence[int]
                   ) -> Tuple[bool, Optional[List[int]], List[int]]:
    """Majority vote over per-replica fingerprint rows.

    ``matrix`` is ``(n_replicas, n_leaves)`` uint32; only ``rep_cols``
    (the REPLICATED leaves — ZeRO shards legitimately differ) vote.
    Returns ``(agree, blamed, diverging_cols)``: ``blamed`` is the
    strict-minority replica list when a strict majority of replicas
    agree on every voting column, else ``None`` (a 1-vs-1 split carries
    no internal evidence of which copy is right — the rollback replay
    resolves it, see Trainer)."""
    mat = np.asarray(matrix)
    n = mat.shape[0]
    cols = list(rep_cols)
    sub = mat[:, cols] if cols else mat[:, :0]
    agree = bool((sub == sub[0:1]).all()) if n > 1 else True
    if agree:
        return True, None, []
    diverging = [cols[j] for j in range(sub.shape[1])
                 if not (sub[:, j] == sub[0, j]).all()]
    # group replicas by their full voting row
    groups: Dict[bytes, List[int]] = {}
    for r in range(n):
        groups.setdefault(sub[r].tobytes(), []).append(r)
    best = max(groups.values(), key=len)
    if len(best) * 2 > n:
        blamed = sorted(r for r in range(n) if r not in best)
        return False, blamed, diverging
    return False, None, diverging
