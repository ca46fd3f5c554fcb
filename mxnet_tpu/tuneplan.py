"""Persisted autotune plans: the artifact ``tools/autotune.py`` emits
and ``Trainer`` / ``ModelServer`` load at construction.

A plan is one JSON document (``TUNE_PLAN.json``) holding the winning
knob values of a search over the joint training + serving space,
**keyed to what it was measured on** — symbol digest, mesh shape, jax
version, platform — plus the measured A/B it rests on.  Knob
RESOLUTION order at a consuming constructor:

    explicit constructor argument  >  set MXTPU_* env var  >
    plan entry  >  built-in default

so a plan can never override an operator's deliberate choice, and a
plan keyed for a FOREIGN (symbol, mesh, jax) is a loud **counted**
fallback to defaults (``tune.plan_foreign`` in the metrics registry +
a logged warning naming every mismatched field) — never silent
misconfiguration.  Key fields may be ``null`` in hand-written plans to
mean "matches anything".

Every (config, measured) pair any bench or tune run produces is also
appended to ``TUNE_CORPUS.jsonl`` (:func:`append_corpus`) — the
TpuGraphs-style accumulation that turns future knob PRs into free
training data for a learned cost model.

Schema::

    {"version": 1,
     "key": {"symbol": "<sha1>|null", "mesh": {"axes": {...},
             "devices": N} | null, "jax": "x/y|null",
             "platform": "cpu|tpu|null", "slo": {...}},
     "train": {"dtype_policy": ..., "remat": ..., "zero": ...,
               "grad_accum": ..., "grad_dtype": ...,
               "integrity_period": ..., "donate_batch": ...,
               "batch": ..., "upload_depth": ..., "upload_chunks": ...},
     "serve": {"buckets": [...], "max_wait_us": ..., "cap": ...,
               "queue_cap": ..., "shed_policy": ...},
     "measured": {...}, "meta": {...}}

See docs/how_to/autotune.md.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional

from .base import MXNetError
from . import obs as _obs

__all__ = ["PLAN_VERSION", "TRAIN_KNOBS", "SERVE_KNOBS", "load", "save",
           "validate", "resolve", "current_key", "train_section",
           "serve_section", "check_symbol", "append_corpus",
           "corpus_path"]

PLAN_VERSION = 1

# knob name -> required python type(s).  A typo'd plan entry
# ("grad_acum") is a validation error with a did-you-mean, mirroring
# envknobs/faults — a plan that configures nothing must be loud.
TRAIN_KNOBS: Dict[str, tuple] = {
    # every name here has a consumer (Trainer._knob / Module.fit's
    # upload wrapper) — a knob no code reads must NOT validate, or a
    # plan entry becomes exactly the silent no-op this schema exists
    # to prevent (batch, for instance, is measurement identity and
    # lives in plan meta/measured, never here)
    "dtype_policy": (str,), "remat": (str,), "zero": (int,),
    "grad_accum": (int,), "grad_dtype": (str,),
    "integrity_period": (int,), "donate_batch": (bool,),
    "upload_depth": (int,), "upload_chunks": (int,),
}
SERVE_KNOBS: Dict[str, tuple] = {
    "buckets": (list,), "max_wait_us": (int,), "cap": (int,),
    "queue_cap": (int,), "shed_policy": (str,),
    # consumed by ModelServer's precision-tier admission (server.py):
    # autotune may only emit "int8" here when the tools/quantize.py
    # accuracy gate passed for the plan's symbol (gate artifact digest
    # recorded in plan meta) — docs/how_to/quantization.md
    "precision": (str,),
}

_APPLIED = _obs.counter("tune.plan_applied")
_FOREIGN = _obs.counter("tune.plan_foreign")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _jax_version() -> str:
    import jax
    import jaxlib
    return "%s/%s" % (jax.__version__,
                      getattr(jaxlib, "__version__", "?"))


def _platform() -> str:
    import jax
    return jax.default_backend()


# the CONCRETE "measured without a mesh" descriptor.  Distinct from a
# null key field: null is the hand-written-plan wildcard ("matches any
# mesh"); a tool-emitted plan measured meshless must NOT silently apply
# to an 8-chip mesh, so autotune stamps this and consumers canonicalize
# their own meshless identity to it for the comparison.
MESHLESS: Dict[str, Any] = {"axes": {}, "devices": 1}


def mesh_desc(mesh) -> Optional[Dict[str, Any]]:
    """The plan-key mesh descriptor (same shape the trainer's program
    key records): axis dict + device count, or None meshless."""
    if mesh is None:
        return None
    return {"axes": {str(k): int(v) for k, v in dict(mesh.shape).items()},
            "devices": int(mesh.size)}


def current_key(symbol_digest: Optional[str] = None, mesh=None,
                platform: Optional[str] = None,
                slo: Optional[Dict] = None) -> Dict[str, Any]:
    return {"symbol": symbol_digest,
            "mesh": mesh_desc(mesh),
            "jax": _jax_version(),
            "platform": platform or _platform(),
            "slo": slo or {}}


def _check_section(name: str, section: Dict, known: Dict[str, tuple]):
    import difflib
    if not isinstance(section, dict):
        raise MXNetError("tune plan %r section must be an object, got %s"
                         % (name, type(section).__name__))
    for key, val in section.items():
        if key not in known:
            close = difflib.get_close_matches(key, sorted(known), n=1)
            raise MXNetError(
                "tune plan %r section has unknown knob %r%s — known: %s "
                "(a typo'd entry would otherwise configure nothing)"
                % (name, key,
                   (" (did you mean %r?)" % close[0]) if close else "",
                   "/".join(sorted(known))))
        want = known[key]
        # bool is an int subclass: reject True where an int is wanted
        if isinstance(val, bool) and bool not in want:
            raise MXNetError("tune plan %s.%s=%r: expected %s"
                             % (name, key, val, want[0].__name__))
        if not isinstance(val, want):
            raise MXNetError("tune plan %s.%s=%r: expected %s"
                             % (name, key, val, want[0].__name__))
        if key == "buckets" and (not val or any(
                not isinstance(b, int) or b < 1 for b in val)):
            raise MXNetError("tune plan serve.buckets=%r: need a "
                             "non-empty list of positive ints" % (val,))


def validate(plan: Dict) -> Dict:
    """Schema-check a plan dict; returns it.  Raises
    :class:`MXNetError` naming the offending field on any violation."""
    if not isinstance(plan, dict):
        raise MXNetError("tune plan must be a JSON object, got %s"
                         % type(plan).__name__)
    if plan.get("version") != PLAN_VERSION:
        raise MXNetError("tune plan version %r != supported %d"
                         % (plan.get("version"), PLAN_VERSION))
    key = plan.get("key")
    if not isinstance(key, dict):
        raise MXNetError("tune plan is missing its 'key' object "
                         "(symbol/mesh/jax/platform identity)")
    _check_section("train", plan.get("train", {}), TRAIN_KNOBS)
    _check_section("serve", plan.get("serve", {}), SERVE_KNOBS)
    return plan


def load(path: str) -> Dict:
    """Load + validate a persisted plan.  Unreadable or malformed plans
    raise loudly — a plan the operator pointed at must never be
    silently skipped."""
    try:
        with open(path) as f:
            plan = json.load(f)
    except OSError as e:
        raise MXNetError("cannot read tune plan %s: %s" % (path, e)) \
            from None
    except ValueError as e:
        raise MXNetError("tune plan %s is not valid JSON: %s"
                         % (path, e)) from None
    return validate(plan)


def save(path: str, plan: Dict) -> None:
    """Validate + atomically commit a plan (tmp write, fsync, rename —
    the manifest recipe)."""
    validate(plan)
    parent = os.path.dirname(os.path.abspath(path))
    os.makedirs(parent, exist_ok=True)
    tmp = "%s.%d.tmp" % (path, os.getpid())
    with open(tmp, "w") as f:
        json.dump(plan, f, indent=1, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def resolve(plan) -> Optional[Dict]:
    """Normalize a constructor ``plan=`` argument: a dict is validated,
    a str is loaded as a path, None falls back to ``MXTPU_TUNE_PLAN``
    (when set), else no plan."""
    if plan is None:
        path = os.environ.get("MXTPU_TUNE_PLAN") or None
        return load(path) if path else None
    if isinstance(plan, str):
        return load(plan)
    return validate(dict(plan))


def _mismatches(key: Dict, checks: Dict[str, Any]) -> List[str]:
    """Compare plan-key fields against the consumer's identity; a None
    plan field is a wildcard.  Returns human-readable mismatch items."""
    out = []
    for field, have in checks.items():
        want = key.get(field)
        if want is None:
            continue
        if want != have:
            out.append("%s: plan %r vs this process %r"
                       % (field, want, have))
    return out


def _section(plan: Optional[Dict], name: str, checks: Dict[str, Any],
             where: str) -> Dict:
    """The applied knob dict of one plan section, or {} (counted, loud)
    when the plan is keyed for a foreign identity."""
    if plan is None:
        return {}
    bad = _mismatches(plan.get("key", {}), checks)
    if bad:
        _FOREIGN.inc()
        import logging
        logging.getLogger("mxtpu.tuneplan").warning(
            "tune plan does not apply to this %s — falling back to "
            "defaults (counted: tune.plan_foreign).  Mismatched key "
            "fields: %s", where, "; ".join(bad))
        return {}
    section = dict(plan.get(name, {}))
    if section:
        _APPLIED.inc()
    return section


def train_section(plan: Optional[Dict], symbol_digest: Optional[str],
                  mesh=None, platform: Optional[str] = None) -> Dict:
    """Training knobs this Trainer should default to (after ctor/env)."""
    return _section(plan, "train",
                    {"symbol": symbol_digest,
                     "mesh": mesh_desc(mesh) or MESHLESS,
                     "jax": _jax_version(),
                     "platform": platform or _platform()},
                    "trainer (symbol/mesh/jax/platform)")


def serve_section(plan: Optional[Dict], mesh=None,
                  platform: Optional[str] = None) -> Dict:
    """Serving knobs for a ModelServer.  Symbol identity is checked
    later, per tenant, at ``add_model`` (:func:`check_symbol`) — the
    constructor knows only the mesh."""
    return _section(plan, "serve",
                    {"mesh": mesh_desc(mesh) or MESHLESS,
                     "jax": _jax_version(),
                     "platform": platform or _platform()},
                    "server (mesh/jax/platform)")


def check_symbol(plan: Optional[Dict], symbol_digest: str,
                 where: str) -> bool:
    """Advisory per-tenant symbol check (``add_model`` time: the serve
    knobs were already applied at construction, so a foreign digest is
    counted + logged rather than reverted)."""
    if plan is None:
        return True
    want = plan.get("key", {}).get("symbol")
    if want is None or want == symbol_digest:
        return True
    _FOREIGN.inc()
    import logging
    logging.getLogger("mxtpu.tuneplan").warning(
        "tune plan was measured for symbol %s but %s hosts %s — its "
        "serving knobs may be stale for this tenant (counted: "
        "tune.plan_foreign)", want[:12], where, symbol_digest[:12])
    return False


# ----------------------------------------------------------------------
# the measured-config corpus (TpuGraphs-style accumulation)
def corpus_path(path: Optional[str] = None) -> str:
    return (path or os.environ.get("MXTPU_TUNE_CORPUS")
            or os.path.join(_ROOT, "TUNE_CORPUS.jsonl"))


def append_corpus(row: Dict, path: Optional[str] = None) -> str:
    """Append one (config, measured) record to the corpus log.  Stamps
    ts/jax/platform when absent; one ``write()`` of one line, so
    concurrent appenders interleave records, not bytes.  Best-effort on
    an unwritable path (a read-only checkout must not fail a bench)."""
    row = dict(row)
    row.setdefault("ts", round(time.time(), 3))
    row.setdefault("jax", _jax_version())
    row.setdefault("platform", _platform())
    p = corpus_path(path)
    try:
        parent = os.path.dirname(os.path.abspath(p))
        os.makedirs(parent, exist_ok=True)
        with open(p, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    except OSError:
        import logging
        logging.getLogger("mxtpu.tuneplan").warning(
            "could not append to tune corpus %s", p)
    return p
