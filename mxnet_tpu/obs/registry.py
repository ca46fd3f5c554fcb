"""Process-wide metrics registry: named counters, gauges, and
fixed-bucket histograms.

Every subsystem that used to invent its own counters (the serving
scheduler's ``_stats`` dict, ``DeviceUploadIter``'s stage-wall floats,
the elastic/integrity event tallies) registers here instead, so one
``snapshot()`` call yields the whole process's state in a single
machine-readable dict — the surface the fleet router's per-replica
load-balancing (ROADMAP item 4) scrapes, and what the JSONL exporter
(``spans.py``) streams as periodic metric deltas.

Design rules:

* **always on** — unlike spans, the registry does not gate on
  ``MXTPU_OBS``: the migrated ``stats()`` surfaces must keep returning
  live numbers either way, and a counter bump is one lock + one add.
* **atomic updates** — every metric mutation and every ``snapshot()``
  runs under the registry mutex (a ``_tsan``-named lock, so the
  concurrency sanitizer sees the discipline).  Multi-metric *group*
  atomicity (pairing ``upload_s`` with ``batches_staged``) stays the
  caller's job — the owning subsystem keeps its own outer lock, and the
  registry lock always nests INSIDE it (one direction, never a cycle).
* **fixed buckets** — histograms never allocate per observation; the
  percentile estimate interpolates inside the bucket that crosses the
  requested rank (the Prometheus scheme), so p50/p95/p99 cost one pass
  over ~20 ints.
* **instance scoping** — process-wide names with per-instance
  uniqueness via :meth:`Registry.scope` (``serving.server0``,
  ``io.upload1``, ...): two servers in one process never collide, and
  a snapshot still attributes every number.

``Registry.merge`` folds two snapshots (counters and histogram buckets
sum, gauges last-wins) — the multi-log aggregation ``tools/
obs_report.py`` uses when a run produced one log per process.
"""
from __future__ import annotations

import weakref
from bisect import bisect_left
from collections.abc import MutableMapping
from typing import Dict, Optional, Sequence, Tuple

from .. import _tsan

__all__ = ["Counter", "Gauge", "Histogram", "Registry", "CounterDict",
           "REGISTRY", "DEFAULT_MS_BUCKETS"]

# latency buckets in milliseconds: sub-100us dispatches through
# 10-second stragglers, roughly x2.5 per step (fixed at metric
# creation; a custom ladder rides the histogram() call)
DEFAULT_MS_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
                      50.0, 100.0, 250.0, 500.0, 1000.0, 2500.0,
                      5000.0, 10000.0)


class Counter:
    """A named cumulative value (int or float).  ``set`` exists for the
    dict-shaped views (``CounterDict``) whose ``d[k] += 1`` pattern
    reads then stores; direct users call ``inc``."""

    kind = "counter"
    __slots__ = ("name", "_mu", "_v")

    def __init__(self, name: str, mu, initial=0):
        self.name = name
        self._mu = mu
        self._v = initial

    def inc(self, n=1) -> None:
        with self._mu:
            self._v += n

    def set(self, v) -> None:
        with self._mu:
            self._v = v

    @property
    def value(self):
        with self._mu:
            return self._v


class Gauge:
    """A named point-in-time value (queue depth, sentinel skips)."""

    kind = "gauge"
    __slots__ = ("name", "_mu", "_v")

    def __init__(self, name: str, mu, initial=0):
        self.name = name
        self._mu = mu
        self._v = initial

    def set(self, v) -> None:
        with self._mu:
            self._v = v

    def inc(self, n=1) -> None:
        with self._mu:
            self._v += n

    @property
    def value(self):
        with self._mu:
            return self._v


class Histogram:
    """Fixed-bucket histogram: ``buckets`` are ascending upper bounds,
    observations past the last bound land in the overflow slot.
    Percentiles interpolate linearly inside the crossing bucket, so the
    estimate's resolution is the bucket width — the price of never
    allocating on the hot path."""

    kind = "histogram"
    __slots__ = ("name", "_mu", "buckets", "_counts", "_count", "_sum",
                 "_min", "_max")

    def __init__(self, name: str, mu,
                 buckets: Sequence[float] = DEFAULT_MS_BUCKETS):
        self.name = name
        self._mu = mu
        self.buckets = tuple(float(b) for b in buckets)
        if not self.buckets or \
                list(self.buckets) != sorted(set(self.buckets)):
            raise ValueError("histogram buckets must be ascending "
                             "unique upper bounds, got %r" % (buckets,))
        self._counts = [0] * (len(self.buckets) + 1)
        self._count = 0
        self._sum = 0.0
        self._min = None
        self._max = None

    def observe(self, v: float) -> None:
        v = float(v)
        i = bisect_left(self.buckets, v)
        with self._mu:
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if self._min is None or v < self._min:
                self._min = v
            if self._max is None or v > self._max:
                self._max = v

    def percentile(self, q: float) -> Optional[float]:
        """Estimate the ``q``-th percentile (0-100) from the buckets."""
        with self._mu:
            counts = list(self._counts)
            total = self._count
            lo_seen, hi_seen = self._min, self._max
        if not total:
            return None
        rank = q / 100.0 * total
        cum = 0.0
        for i, c in enumerate(counts):
            if not c:
                continue
            if cum + c >= rank:
                lo = self.buckets[i - 1] if i > 0 else \
                    min(lo_seen, self.buckets[0]) if lo_seen is not None \
                    else 0.0
                hi = self.buckets[i] if i < len(self.buckets) else hi_seen
                if hi is None or hi <= lo:
                    return round(lo, 6)
                frac = (rank - cum) / c
                return round(lo + frac * (hi - lo), 6)
            cum += c
        return round(hi_seen, 6) if hi_seen is not None else None

    def percentiles(self, qs: Tuple[float, ...] = (50, 95, 99)) -> Dict:
        out = {"p%g" % q: self.percentile(q) for q in qs}
        with self._mu:
            out["count"] = self._count
        return out

    def snapshot(self) -> Dict:
        with self._mu:
            return {"buckets": list(self.buckets),
                    "counts": list(self._counts),
                    "count": self._count,
                    "sum": round(self._sum, 6),
                    "min": self._min, "max": self._max}


class Registry:
    """Name → metric, with get-or-create semantics (a name re-requested
    with a different kind is a loud error, not a silent shadow)."""

    def __init__(self):
        self._mu = _tsan.lock("obs.Registry._mu")
        self._metrics: Dict[str, object] = {}
        self._scopes: Dict[str, int] = {}
        self._pulls: list = []          # weak refresh methods, see pull

    # ------------------------------------------------------------- get
    def _get(self, name: str, cls, **kw):
        with self._mu:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, self._mu, **kw)
                self._metrics[name] = m
                return m
        if not isinstance(m, cls):
            from ..base import MXNetError
            raise MXNetError(
                "metric %r already registered as %s, requested as %s"
                % (name, m.kind, cls.kind))
        return m

    def counter(self, name: str, initial=0) -> Counter:
        return self._get(name, Counter, initial=initial)

    def gauge(self, name: str, initial=0) -> Gauge:
        return self._get(name, Gauge, initial=initial)

    def histogram(self, name: str,
                  buckets: Sequence[float] = DEFAULT_MS_BUCKETS
                  ) -> Histogram:
        h = self._get(name, Histogram, buckets=buckets)
        if tuple(float(b) for b in buckets) != h.buckets:
            # a silently-ignored ladder would put observations in the
            # wrong buckets now and fail Registry.merge much later
            from ..base import MXNetError
            raise MXNetError(
                "histogram %r already registered with buckets %s; "
                "re-requested with %s" % (name, h.buckets,
                                          tuple(buckets)))
        return h

    def scope(self, prefix: str) -> str:
        """A process-unique instance namespace: ``scope("io.upload")``
        returns ``io.upload0``, then ``io.upload1``, ..."""
        with self._mu:
            n = self._scopes.get(prefix, 0)
            self._scopes[prefix] = n + 1
            return "%s%d" % (prefix, n)

    # -------------------------------------------------------- snapshot
    def pull(self, refresh) -> None:
        """Have every :meth:`snapshot` call ``refresh`` (a bound method,
        held weakly) before it reads: for gauges whose source lives on
        the device and costs a transfer to read, so that nothing on the
        hot path keeps them current.  An owner that has gone away drops
        out; what it last set stays."""
        with self._mu:
            self._pulls.append(weakref.WeakMethod(refresh))

    def snapshot(self) -> Dict:
        """One machine-readable dict of everything:
        ``{"counters": {...}, "gauges": {...}, "histograms": {...}}``."""
        with self._mu:
            pulls = [r() for r in self._pulls]
            self._pulls = [r for r, f in zip(self._pulls, pulls)
                           if f is not None]
        for refresh in pulls:
            if refresh is not None:
                refresh()
        with self._mu:
            metrics = list(self._metrics.values())
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for m in metrics:
            if m.kind == "counter":
                out["counters"][m.name] = m.value
            elif m.kind == "gauge":
                out["gauges"][m.name] = m.value
            else:
                out["histograms"][m.name] = m.snapshot()
        return out

    @staticmethod
    def merge(a: Dict, b: Dict) -> Dict:
        """Fold snapshot ``b`` into snapshot ``a`` (pure; returns a new
        dict).  Counters and histogram bucket counts SUM (two processes'
        work adds); gauges are point-in-time so ``b`` wins."""
        out = {"counters": dict(a.get("counters") or {}),
               "gauges": dict(a.get("gauges") or {}),
               "histograms": {k: dict(v) for k, v in
                              (a.get("histograms") or {}).items()}}
        for k, v in (b.get("counters") or {}).items():
            out["counters"][k] = out["counters"].get(k, 0) + v
        out["gauges"].update(b.get("gauges") or {})
        for k, h in (b.get("histograms") or {}).items():
            base = out["histograms"].get(k)
            if base is None or list(base["buckets"]) != list(h["buckets"]):
                if base is not None:
                    raise ValueError(
                        "histogram %r bucket ladders differ between "
                        "snapshots — cannot merge" % k)
                out["histograms"][k] = dict(h)
                continue
            merged = dict(base)
            merged["counts"] = [x + y for x, y in zip(base["counts"],
                                                      h["counts"])]
            merged["count"] = base["count"] + h["count"]
            merged["sum"] = round(base["sum"] + h["sum"], 6)
            mins = [m for m in (base.get("min"), h.get("min"))
                    if m is not None]
            maxs = [m for m in (base.get("max"), h.get("max"))
                    if m is not None]
            merged["min"] = min(mins) if mins else None
            merged["max"] = max(maxs) if maxs else None
            out["histograms"][k] = merged
        return out


class CounterDict(MutableMapping):
    """A dict-shaped view over registry counters — the migration shim
    that lets ``ModelServer._stats["requests"] += 1`` keep its exact
    spelling (and ``dict(self._stats)`` its exact shape) while the
    values live in the registry.  ``+=`` desugars to ``__getitem__``
    then ``__setitem__``; both route to the named counter."""

    def __init__(self, scope: str, initial: Dict, registry=None):
        self._registry = registry if registry is not None else REGISTRY
        self._scope = scope
        self._c = {k: self._registry.counter("%s.%s" % (scope, k),
                                             initial=v)
                   for k, v in initial.items()}

    def __getitem__(self, k):
        return self._c[k].value

    def __setitem__(self, k, v):
        c = self._c.get(k)
        if c is None:
            c = self._registry.counter("%s.%s" % (self._scope, k),
                                       initial=0)
            self._c[k] = c
        c.set(v)

    def __delitem__(self, k):
        raise TypeError("CounterDict keys are registry-backed and "
                        "cannot be deleted")

    def __iter__(self):
        return iter(self._c)

    def __len__(self):
        return len(self._c)


REGISTRY = Registry()
