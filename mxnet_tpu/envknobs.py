"""One registry for every ``MXTPU_*`` environment knob.

The knob surface has grown past fifty names across eight subsystems,
each parsing ``os.environ`` privately — which means a typo'd knob like
``MXTPU_GRAD_ACUM=4`` configures NOTHING and says nothing (the operator
believes grad accumulation is on; the framework silently runs without
it).  ``faults.py`` already solved this class of bug for fault-spec
condition keys: a parse-time registry of known names with a difflib
did-you-mean.  This module is the same defense for the env surface:

* :data:`KNOBS` declares every knob the framework (or its tools/CI)
  reads — name, type, default, and the subsystem that owns it.  The
  table IS the documentation source of truth beside
  ``docs/how_to/env_var.md``.
* :func:`validate_environ` scans the process environment for
  ``MXTPU_*`` names that no code reads and warns loudly with a
  did-you-mean (``import mxnet_tpu`` runs it once; ``MXTPU_STRICT_KNOBS=1``
  escalates the warning to :class:`~mxnet_tpu.base.MXNetError`).  Set
  knobs whose values don't parse as their declared type are flagged the
  same way, before the consuming site trips over them mid-run.
* typed accessors (:func:`get_int` / :func:`get_float` /
  :func:`get_bool` / :func:`get_str`) give consuming sites one
  error-message shape (``NAME=value is not an integer``) instead of a
  per-site reimplementation.

Knob RESOLUTION order at a consuming site stays what it always was —
constructor argument beats env beats (new) tune-plan entry beats
default; see :mod:`mxnet_tpu.tuneplan` — this module only owns the env
layer of that chain.
"""
from __future__ import annotations

import os
import warnings
from typing import Dict, List, Optional, Tuple

from .base import MXNetError

__all__ = ["KNOBS", "declared", "is_set", "raw", "get_int", "get_float",
           "get_bool", "get_str", "validate_environ", "KnobWarning"]


class KnobWarning(UserWarning):
    """An ``MXTPU_*`` env var that no code reads (probable typo), or a
    set knob whose value cannot parse as its declared type."""


class _Knob:
    __slots__ = ("name", "kind", "default", "owner", "doc")

    def __init__(self, name, kind, default, owner, doc):
        self.name = name
        self.kind = kind          # int | float | bool | str | list
        self.default = default
        self.owner = owner
        self.doc = doc


def _k(name, kind, default, owner, doc):
    return name, _Knob(name, kind, default, owner, doc)


# every knob some site actually reads (grep MXTPU_ to audit).  "bool"
# knobs accept 0/1/true/false/yes/no; "list" is comma-separated ints;
# "str" values are validated by the consuming site (mode words, paths,
# fault specs).
KNOBS: Dict[str, _Knob] = dict((
    # --- execution / trainer ------------------------------------------
    _k("MXTPU_MODULE_FUSED", "str", "auto", "module",
       "auto|always|never: route Module onto the fused Trainer"),
    _k("MXTPU_COMPUTE_DTYPE", "str", None, "module",
       "default compute dtype for modules (e.g. bfloat16)"),
    _k("MXTPU_DTYPE_POLICY", "str", None, "trainer",
       "bytediet|legacy residual-dtype policy of the fused step"),
    _k("MXTPU_REMAT", "str", "none", "trainer",
       "rematerialization policy: none|convs_dots|dots|nothing"),
    _k("MXTPU_ZERO", "int", 0, "trainer",
       "optimizer-state sharding stage (0|1)"),
    _k("MXTPU_GRAD_ACCUM", "int", 1, "trainer",
       "microbatch accumulation count"),
    _k("MXTPU_GRAD_DTYPE", "str", "f32", "trainer",
       "cross-chip gradient wire dtype: f32|bf16"),
    _k("MXTPU_DONATE_BATCH", "bool", False, "trainer",
       "donate the batch argument (frees staging buffers)"),
    _k("MXTPU_SENTINEL", "str", "off", "trainer",
       "step sentinel: off|skip|abort"),
    _k("MXTPU_SENTINEL_MAX_SKIPS", "int", 3, "trainer",
       "consecutive sentinel skips before abort raises"),
    _k("MXTPU_LOSS_SCALE", "str", None, "trainer",
       "off|dynamic|<float> cotangent loss scale"),
    _k("MXTPU_LS_GROWTH_INTERVAL", "int", 200, "trainer",
       "clean steps before the dynamic loss scale doubles"),
    _k("MXTPU_INTEGRITY_MODE", "str", "off", "trainer",
       "state-integrity mode: off|fp|vote|audit"),
    _k("MXTPU_INTEGRITY_PERIOD", "int", 100, "trainer",
       "updates between integrity checks"),
    _k("MXTPU_INTEGRITY_MAX_ROLLBACKS", "int", 3, "module",
       "consecutive integrity rollbacks before fit raises"),
    _k("MXTPU_TUNE_PLAN", "str", None, "tuneplan",
       "path to a persisted TUNE_PLAN.json applied at Trainer/"
       "ModelServer construction (env and ctor args override it)"),
    _k("MXTPU_STRICT_KNOBS", "bool", False, "envknobs",
       "escalate unknown-knob warnings to MXNetError"),
    # --- large-model parallelism ---------------------------------------
    _k("MXTPU_MOE_DISPATCH", "str", "sparse", "parallel",
       "MoE dispatch path: sparse (sort-based) | dense (one-hot "
       "einsum A/B reference)"),
    _k("MXTPU_PIPE_SCHEDULE", "str", "interleaved", "parallel",
       "pipeline schedule: interleaved (circular placement) | gpipe "
       "(blocked fill-drain)"),
    _k("MXTPU_RING_SKIP", "bool", True, "parallel",
       "causal ring attention: lax.cond-skip fully masked K/V blocks"),
    # --- input pipeline ------------------------------------------------
    _k("MXTPU_UPLOAD_OVERLAP", "bool", None, "io",
       "wrap fit() feeding in DeviceUploadIter (default: multi-core)"),
    _k("MXTPU_UPLOAD_DEPTH", "int", 2, "io",
       "device staging buffers ahead of the step"),
    _k("MXTPU_UPLOAD_CHUNKS", "int", 1, "io",
       "chunked async device_puts per host batch"),
    _k("MXTPU_DECODE_START_METHOD", "str", None, "io",
       "multiprocessing start method for decode workers"),
    # --- serving -------------------------------------------------------
    _k("MXTPU_SERVE_BUCKETS", "list", [1, 4, 8, 16, 32], "serving",
       "AOT batch bucket ladder (comma ints)"),
    _k("MXTPU_SERVE_MAX_WAIT_US", "int", 2000, "serving",
       "head-of-queue coalescing wait"),
    _k("MXTPU_SERVE_CAP", "int", None, "serving",
       "dispatch row cap (default: largest bucket)"),
    _k("MXTPU_SERVE_TIMEOUT_MS", "int", 10000, "serving",
       "per-request deadline (0 = off)"),
    _k("MXTPU_SERVE_VALIDATE", "bool", True, "serving",
       "per-request output finiteness check"),
    _k("MXTPU_SERVE_QUEUE_CAP", "int", 4096, "serving",
       "admission-control queue bound in rows (0 = off)"),
    _k("MXTPU_SERVE_SHED_POLICY", "str", "reject", "serving",
       "reject|block past queue_cap"),
    _k("MXTPU_SERVE_BREAKER_K", "int", 5, "serving",
       "consecutive batch failures that open the breaker (0 = off)"),
    _k("MXTPU_SERVE_BREAKER_COOLDOWN_MS", "int", 1000, "serving",
       "breaker cool-down before the half-open probe"),
    _k("MXTPU_SERVE_DRAIN_S", "float", 0.0, "serving",
       "stop() drain budget for queued work"),
    _k("MXTPU_SERVE_SLOW_S", "float", 0.05, "serving",
       "injected slow_request stall"),
    _k("MXTPU_SERVE_PRECISION", "str", "auto", "serving",
       "tenant precision tier: auto|float32|bfloat16|int8 "
       "(int8 requires a quantized symbol; see quantization.md)"),
    _k("MXTPU_SERVE_MEM_BUDGET", "int", 0, "serving",
       "per-chip byte budget for memory-aware tenant admission "
       "(0 = off; predicted weights + worst-bucket peak must fit)"),
    _k("MXTPU_SERVE_PACE_RPS", "float", 0.0, "serving",
       "per-replica service pacing in rows/s (0 = off) — emulates a "
       "fixed per-chip capacity for fleet drills on the CPU tier"),
    # --- fleet serving -------------------------------------------------
    _k("MXTPU_ROUTER_POLICY", "str", "p2c", "fleet",
       "replica placement policy: p2c|least|rr"),
    _k("MXTPU_ROUTER_RETRIES", "int", 2, "fleet",
       "failover retries on a refused submit (next-best replica)"),
    _k("MXTPU_FLEET_REPLICAS", "int", 3, "fleet",
       "fleet size (target replica count; autoheal grows back to it)"),
    _k("MXTPU_FLEET_CHECK_S", "float", 0.2, "fleet",
       "fleet monitor scan period (crash + heartbeat-lapse detection)"),
    _k("MXTPU_FLEET_HB_TIMEOUT_S", "float", 5.0, "fleet",
       "serve-role heartbeat liveness timeout"),
    _k("MXTPU_FLEET_AUTOHEAL", "bool", True, "fleet",
       "respawn dead replicas back to the target count"),
    _k("MXTPU_FLEET_DRAIN_S", "float", 5.0, "fleet",
       "per-replica drain budget on rollout swap / fleet stop"),
    _k("MXTPU_FLEET_CANARY_N", "int", 8, "fleet",
       "canary requests per rollout swap (0 = gate off)"),
    _k("MXTPU_FLEET_MIN_AGREE", "float", 0.9, "fleet",
       "rollout gate: min top-1 agreement of new vs old weights"),
    _k("MXTPU_FLEET_CANARY_LAT_X", "float", 50.0, "fleet",
       "rollout gate: canary p50 ceiling as a multiple of the old "
       "batch EWMA"),
    _k("MXTPU_FLEET_ROLLOUT_POLL_S", "float", 2.0, "fleet",
       "rollout watcher poll period over latest_verified()"),
    # --- quantization --------------------------------------------------
    _k("MXTPU_QUANT_MODE", "str", "minmax", "quant",
       "activation calibration mode: minmax|percentile"),
    _k("MXTPU_QUANT_PERCENTILE", "float", 99.9, "quant",
       "percentile of |x| per calibration batch (percentile mode)"),
    _k("MXTPU_QUANT_MIN_AGREEMENT", "float", 0.99, "quant",
       "accuracy gate: min argmax agreement vs f32 on holdout"),
    _k("MXTPU_QUANT_MAX_TOP1_DELTA", "float", 0.5, "quant",
       "accuracy gate: max top-1 accuracy drop vs f32, in points"),
    # --- compiled programs --------------------------------------------
    _k("MXTPU_PROGRAM_CACHE", "str", None, "program",
       "persisted compiled-program cache dir"),
    # --- resilience / faults / elastic --------------------------------
    _k("MXTPU_FAULTS", "str", None, "faults", "fault-injection spec"),
    _k("MXTPU_HEARTBEAT_DIR", "str", None, "health",
       "shared heartbeat dir"),
    _k("MXTPU_HEARTBEAT_TRANSPORT", "str", "dir", "health",
       "dir|kv heartbeat transport"),
    _k("MXTPU_ELASTIC", "bool", False, "elastic",
       "elastic worker flag (set by tools/launch.py --local-elastic)"),
    _k("MXTPU_ELASTIC_DIR", "str", None, "elastic",
       "shared membership dir"),
    _k("MXTPU_ELASTIC_CHECK_S", "float", None, "elastic",
       "monitor scan period"),
    _k("MXTPU_ELASTIC_HB_TIMEOUT_S", "float", None, "elastic",
       "liveness timeout"),
    _k("MXTPU_ELASTIC_JOIN_GRACE_S", "float", None, "elastic",
       "never-stamped rank grace"),
    _k("MXTPU_ELASTIC_STEP_TIMEOUT_S", "float", None, "elastic",
       "collective-entry guard wait"),
    _k("MXTPU_COMM_PARITY", "bool", True, "elastic",
       "cross-rank comm-plan digest check"),
    _k("MXTPU_COMM_PARITY_TIMEOUT_S", "float", None, "elastic",
       "bounded wait for peer plan stamps"),
    _k("MXTPU_INIT_ATTEMPTS", "int", None, "distributed",
       "jax.distributed.initialize retries"),
    _k("MXTPU_INIT_TIMEOUT_S", "float", None, "distributed",
       "jax.distributed.initialize hard timeout"),
    _k("MXTPU_COORDINATOR", "str", None, "distributed",
       "coordinator address (set by tools/launch.py)"),
    _k("MXTPU_NUM_PROCESSES", "int", None, "distributed",
       "world size (set by tools/launch.py)"),
    _k("MXTPU_PROCESS_ID", "int", None, "distributed",
       "rank (set by tools/launch.py)"),
    # --- observability / sanitizers / lint gates ----------------------
    _k("MXTPU_OBS", "bool", False, "obs", "arm the span recorder"),
    _k("MXTPU_OBS_LOG", "str", None, "obs", "JSONL span/metric log"),
    _k("MXTPU_OBS_FLUSH_S", "float", None, "obs", "exporter period"),
    _k("MXTPU_TSAN", "bool", False, "tsan", "lockset race recorder"),
    _k("MXTPU_TSAN_LOG", "str", None, "tsan", "TSAN event JSONL"),
    _k("MXTPU_TSAN_STACK", "bool", False, "tsan",
       "record acquisition stacks"),
    _k("MXTPU_GRAPH_LINT", "bool", True, "analysis",
       "surface warn findings at simple_bind"),
    _k("MXTPU_LINT_BASELINE", "str", None, "analysis",
       "graph-lint baseline path override"),
    _k("MXTPU_LINT_PLATFORM", "str", None, "analysis",
       "force the lint target platform"),
    _k("MXTPU_RACE_BASELINE", "str", None, "analysis",
       "concurrency-lint baseline path override"),
    _k("MXTPU_COMM_BASELINE", "str", None, "analysis",
       "comm-lint baseline path override"),
    _k("MXTPU_COMM_TOLERANCE_PCT", "float", 3.0, "analysis",
       "comm-budget gate tolerance"),
    _k("MXTPU_MEM_BASELINE", "str", None, "analysis",
       "mem-lint baseline path override"),
    _k("MXTPU_MEM_TOLERANCE_PCT", "float", 5.0, "analysis",
       "mem-budget gate / bench drift tolerance"),
    _k("MXTPU_HBM_BYTES", "str", None, "analysis",
       "per-chip HBM capacity override for the mem-capacity gate"),
    # --- tools / CI ----------------------------------------------------
    _k("MXTPU_TUNE_CORPUS", "str", None, "tuneplan",
       "TUNE_CORPUS.jsonl path override (default: repo root)"),
    _k("MXTPU_CI_FULL", "bool", False, "ci", "nightly CI tier"),
    _k("MXTPU_TOY_BACKEND", "str", "cpu", "examples",
       "toy example backend pin"),
))


def declared(name: str) -> bool:
    return name in KNOBS


def is_set(name: str) -> bool:
    """The env layer of knob resolution: set AND non-empty (an empty
    export is 'unset' everywhere in this codebase)."""
    return bool(os.environ.get(name))


def raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """The raw string value (or ``default`` when unset/empty)."""
    v = os.environ.get(name)
    return v if v else default


def _parse_int(name, v):
    try:
        return int(v)
    except (TypeError, ValueError):
        raise MXNetError("%s=%r is not an integer" % (name, v)) from None


def _parse_float(name, v):
    try:
        return float(v)
    except (TypeError, ValueError):
        raise MXNetError("%s=%r is not a number" % (name, v)) from None


_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


def _parse_bool(name, v):
    low = str(v).strip().lower()
    if low in _TRUE:
        return True
    if low in _FALSE:
        return False
    raise MXNetError("%s=%r is not a boolean (use 0/1)" % (name, v))


def get_int(name: str, default=None):
    v = os.environ.get(name)
    if not v:
        return default
    return _parse_int(name, v)


def get_float(name: str, default=None):
    v = os.environ.get(name)
    if not v:
        return default
    return _parse_float(name, v)


def get_bool(name: str, default=None):
    v = os.environ.get(name)
    if not v:
        return default
    return _parse_bool(name, v)


def get_str(name: str, default=None):
    return raw(name, default)


def _check_value(knob: _Knob, v: str) -> Optional[str]:
    """Type-check a SET value against its declared kind; returns an
    error string or None."""
    try:
        if knob.kind == "int":
            _parse_int(knob.name, v)
        elif knob.kind == "float":
            _parse_float(knob.name, v)
        elif knob.kind == "bool":
            _parse_bool(knob.name, v)
        elif knob.kind == "list":
            try:
                [int(x) for x in v.split(",") if x]
            except ValueError:
                raise MXNetError(
                    "%s=%r is not a comma-separated integer list"
                    % (knob.name, v)) from None
    except MXNetError as e:
        return str(e)
    return None


def validate_environ(environ=None,
                     strict: Optional[bool] = None
                     ) -> List[Tuple[str, str]]:
    """Scan ``environ`` for ``MXTPU_*`` names no code reads and for set
    knobs whose values don't parse as their declared type.  Returns
    ``[(name, message), ...]`` and warns (:class:`KnobWarning`) per
    finding; with ``strict`` (or ``MXTPU_STRICT_KNOBS=1``) raises
    :class:`MXNetError` on the first finding instead — a typo'd knob
    like ``MXTPU_GRAD_ACUM=4`` must never silently configure nothing.
    """
    import difflib
    env = os.environ if environ is None else environ
    if strict is None:
        strict = str(env.get("MXTPU_STRICT_KNOBS", "")).lower() in _TRUE
    findings: List[Tuple[str, str]] = []
    for name in sorted(env):
        if not name.startswith("MXTPU_"):
            continue
        if name not in KNOBS:
            close = difflib.get_close_matches(name, sorted(KNOBS), n=1)
            msg = ("unknown env knob %s — no mxnet_tpu code reads it%s"
                   % (name, (" (did you mean %s?)" % close[0])
                      if close else ""))
            findings.append((name, msg))
            continue
        err = _check_value(KNOBS[name], env[name])
        if err:
            findings.append((name, err))
    for name, msg in findings:
        if strict:
            raise MXNetError(msg + " (MXTPU_STRICT_KNOBS=1)")
        warnings.warn(msg, KnobWarning, stacklevel=2)
    return findings
