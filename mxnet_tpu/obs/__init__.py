"""Unified runtime telemetry: metrics registry + cross-layer spans +
one exporter (``docs/how_to/observability.md``).

Five subsystems used to invent their own timing and counters —
``ModelServer.stats()``, ``DeviceUploadIter.stats()``, the Chrome-trace
``profiler.py``, TSAN's event log, bench-only figures — with no way to
ask "where did this one slow request/step spend its time" or to scrape
one machine-readable snapshot per process.  This package is the one
place all of it lands (the MXNet engine-profiler / TensorFlow
built-in-monitoring design, PAPERS.md):

* :mod:`~mxnet_tpu.obs.registry` — process-wide named counters /
  gauges / fixed-bucket histograms with atomic updates and a single
  ``snapshot()`` dict.  **Always on** (the migrated ``stats()``
  surfaces read through it).
* :mod:`~mxnet_tpu.obs.spans` — structured spans with parent/child
  links and correlation IDs, threaded through the serving request
  lifecycle, the training step, and the input pipeline.  **Off by
  default**: every site is an inert note (``MXTPU_OBS=1`` arms it, or
  :func:`enable` / :func:`scoped` at runtime), and the off path hands
  back one shared no-op singleton — no allocation, no lock, no event.
* :mod:`~mxnet_tpu.obs.export` — spans + metric deltas stream to a
  ``MXTPU_OBS_LOG`` JSONL ring (periodic ``mxtpu-obs-flush`` thread at
  ``MXTPU_OBS_FLUSH_S``, size-triggered, and atexit — per-recorder
  paths, the ``_tsan.py`` discipline) and render to Chrome tracing
  JSON, standalone or merged into the legacy
  ``profiler.dump_profile()`` timeline.

``tools/obs_report.py`` turns a log into per-request / per-step latency
breakdowns (p50/p99 per segment) and gates span-site closure.
:func:`phase` sites (the training step's and the compile layer's) are
also profiler annotations, armed or not, so a ``jax.profiler`` capture
names its host time in the program's own words.
"""
from __future__ import annotations

import atexit
import os
import threading
from typing import Dict, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .registry import (REGISTRY, Counter, CounterDict,       # noqa: F401
                       DEFAULT_MS_BUCKETS, Gauge, Histogram, Registry)
from .spans import AUTO_PARENT, NULL_SPAN, Span, SpanRecorder  # noqa: F401
from . import export                                          # noqa: F401
from .export import chrome_trace, dump_chrome, parse_log      # noqa: F401

__all__ = [
    "OBS", "enabled", "enable", "disable", "scoped", "recorder",
    "span", "phase", "current_span", "flush", "dump",
    "counter", "gauge", "histogram", "snapshot",
    "REGISTRY", "Registry", "Counter", "Gauge", "Histogram",
    "CounterDict", "DEFAULT_MS_BUCKETS",
    "Span", "SpanRecorder", "NULL_SPAN", "AUTO_PARENT",
    "chrome_trace", "dump_chrome", "parse_log", "export",
]

# the inert fast-path flag: hot sites guard with `if _obs.OBS:` (one
# module-attribute load when off), and `span()` itself checks it — the
# off contract is "no span objects, no recorder traffic"
OBS = os.environ.get("MXTPU_OBS", "") == "1"


def _default_log_path() -> Optional[str]:
    """``MXTPU_OBS_LOG``, suffixed per rank under a multi-process
    launch: every worker inherits the same env verbatim
    (tools/launch.py), and two recorders appending to ONE file would
    interleave span ids and corrupt the ``--check`` closure gate.
    ``obs_report`` accepts the resulting file set as multiple logs."""
    path = os.environ.get("MXTPU_OBS_LOG") or None
    if path and os.environ.get("MXTPU_PROCESS_ID"):
        path = "%s.r%s" % (path, os.environ["MXTPU_PROCESS_ID"])
    return path


_REC = SpanRecorder(_default_log_path(), start_flusher=OBS)
_SWAP_MU = threading.Lock()


def recorder() -> SpanRecorder:
    return _REC


def enabled() -> bool:
    return OBS


def enable() -> None:
    """Turn span recording on (``MXTPU_OBS=1`` does this at import).
    If ``MXTPU_OBS_LOG`` named a log path, a runtime enable also arms
    the exporter thread and the atexit tail flush the import-time path
    would have set up."""
    global OBS, _ATEXIT_ARMED
    OBS = True
    if _REC.log_path is not None:
        _REC.ensure_flusher()
        if not _ATEXIT_ARMED:
            _ATEXIT_ARMED = True
            atexit.register(_REC.close)


def disable() -> None:
    global OBS
    OBS = False


class scoped:
    """Context manager: fresh recorder + forced-on recording for the
    scope, both restored on exit.  The scoped recorder has ITS OWN log
    path (default none), so a test's spans never reach the log a live
    ``MXTPU_OBS_LOG`` sweep is collecting — and its exporter thread (if
    a path is given) is stopped at scope exit, keeping the conftest
    thread-leak check green."""

    def __init__(self, log_path: Optional[str] = None,
                 flush_s: Optional[float] = None,
                 registry=None):
        self._log_path = log_path
        self._flush_s = flush_s
        self._registry = registry

    def __enter__(self) -> SpanRecorder:
        global _REC, OBS
        with _SWAP_MU:
            self._prev_rec, self._prev_on = _REC, OBS
            _REC = SpanRecorder(self._log_path, flush_s=self._flush_s,
                                registry=self._registry)
            OBS = True
        return _REC

    def __exit__(self, *exc):
        global _REC, OBS
        with _SWAP_MU:
            rec, _REC = _REC, self._prev_rec
            OBS = self._prev_on
        rec.close()
        return False


# ----------------------------------------------------------------------
# spans
def span(name: str, corr: Optional[str] = None,
         attrs: Optional[Dict] = None, parent=AUTO_PARENT):
    """Start a span (already started when this returns — enter it as a
    context manager for same-thread nesting, or keep the object and
    ``finish()`` it from wherever the work completes).  When recording
    is off this is an inert site: the shared :data:`NULL_SPAN`
    singleton comes back and nothing is recorded."""
    if not OBS:
        return NULL_SPAN
    return _REC.start(name, corr=corr, attrs=attrs, parent=parent)


class _Phase:
    """An armed :func:`phase`: the span inside a profiler annotation of
    its name."""

    __slots__ = ("_span", "_ann")

    def __init__(self, sp: Span, name: str):
        self._span = sp
        self._ann = _TraceAnnotation(name)

    def __enter__(self) -> Span:
        self._ann.__enter__()
        return self._span.__enter__()

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        return False


def phase(name: str, corr: Optional[str] = None,
          attrs: Optional[Dict] = None, parent=AUTO_PARENT):
    """A same-thread :func:`span` that is also a
    ``jax.profiler.TraceAnnotation`` of the same name, armed or not: a
    profiler capture shows it on the host's line, on the device
    operations' clock.  Enter it as a context manager.  When recording
    is off no span is made and no recorder is touched; the annotation
    costs the profiler's own check, which does nothing unless a trace is
    being collected."""
    if not OBS:
        return _TraceAnnotation(name)
    return _Phase(_REC.start(name, corr=corr, attrs=attrs, parent=parent),
                  name)


def current_span() -> Optional[Span]:
    return _REC.current() if OBS else None


def flush() -> None:
    _REC.flush()


def dump(path: Optional[str] = None) -> Optional[str]:
    """Flush the current recorder's buffered events (``path`` overrides
    its log destination first)."""
    if path is not None:
        _REC.log_path = path
    _REC.flush()
    return _REC.log_path


# ----------------------------------------------------------------------
# registry shortcuts (always on)
def counter(name: str, initial=0) -> Counter:
    return REGISTRY.counter(name, initial=initial)


def gauge(name: str, initial=0) -> Gauge:
    return REGISTRY.gauge(name, initial=initial)


def histogram(name: str, buckets=DEFAULT_MS_BUCKETS) -> Histogram:
    return REGISTRY.histogram(name, buckets=buckets)


def snapshot() -> Dict:
    """The process-wide metrics snapshot."""
    return REGISTRY.snapshot()


_ATEXIT_ARMED = False
if OBS and _REC.log_path is not None:
    _ATEXIT_ARMED = True
    atexit.register(_REC.close)
