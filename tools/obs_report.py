#!/usr/bin/env python
"""Turn an ``MXTPU_OBS_LOG`` JSONL log into latency breakdowns.

The obs layer (``mxnet_tpu/obs/``, ``docs/how_to/observability.md``)
streams one line per span open (``"k": "o"``), one per close
(``"k": "s"``), and periodic metric deltas (``"k": "m"``).  This tool
reconstructs:

* **per-request serving breakdowns** — each ``serve.request`` root is
  joined with its ``serve.queue`` child and the ``serve.batch`` tree
  that dispatched it (the batch lists its member correlation IDs), so
  every request gets ``queue / pad / dispatch / execute / slice``
  segment durations whose sum tiles the measured end-to-end latency
  (``--tol`` gates the residual; default 5%).
* **per-step training breakdowns** — spans sharing one ``s<n>``
  correlation ID (``fit.fetch``, ``elastic.guard``, ``train.h2d``,
  ``train.dispatch``, ``train.integrity``, ``io.wait``) fold into one
  row per update.  They are the host's side of a step: a step returns
  once its program is dispatched, and the device's time is read from a
  profiler capture, where the same sites are annotations of the same
  names inside a ``train`` step marker.

Aggregates are p50/p99 per segment.  ``--chrome OUT`` additionally
renders the spans to Chrome tracing JSON (open in Perfetto).
``--check`` is the CI gate: every opened span must have closed (an
unclosed span is a leaked lifecycle — a future that never settled, a
batch tree torn by an unsupervised exception) and, when requests are
present, their segment sums must be inside the tolerance.

Multiple logs (one per process) may be given; spans keep their source
index so correlation IDs cannot collide across processes.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu.obs import export as _export                 # noqa: E402

SERVE_SEGMENTS = ("queue", "pad", "dispatch", "execute", "slice")
STEP_SEGMENTS = ("fit.fetch", "elastic.guard", "train.h2d",
                 "train.dispatch", "train.integrity")


def _pcts(vals):
    if not vals:
        return None
    a = np.asarray(sorted(vals), dtype=np.float64) * 1e3
    return {"p50_ms": round(float(np.percentile(a, 50)), 4),
            "p99_ms": round(float(np.percentile(a, 99)), 4),
            "mean_ms": round(float(a.mean()), 4),
            "count": int(a.size)}


def unclosed_spans(events):
    """``(sid, name)`` of every span opened but never closed."""
    opened = {}
    for e in events:
        if e.get("k") == "o":
            opened[e["sid"]] = e.get("n", "?")
        elif e.get("k") == "s":
            opened.pop(e.get("sid"), None)
    return sorted(opened.items())


def serving_breakdown(spans, tol_pct=5.0):
    """Per-request segment durations + aggregate percentiles."""
    reqs = {s["c"]: s for s in spans if s["n"] == "serve.request"
            and s.get("c")}
    queues = {s["c"]: s for s in spans if s["n"] == "serve.queue"}
    batches = [s for s in spans if s["n"] == "serve.batch"]
    kids = {}
    for s in spans:
        if s.get("p") is not None:
            kids.setdefault(s["p"], []).append(s)
    batch_of = {}
    for b in batches:
        for rc in (b.get("a") or {}).get("requests") or []:
            batch_of[rc] = b

    rows, seg_vals, e2e, residuals = [], {}, [], []
    for corr, req in sorted(reqs.items()):
        row = {"request": corr,
               "model": (req.get("a") or {}).get("model"),
               "rows": (req.get("a") or {}).get("rows"),
               "error": (req.get("a") or {}).get("error"),
               "e2e_ms": round((req["t1"] - req["t0"]) * 1e3, 4)}
        segs = {}
        q = queues.get(corr)
        if q is not None:
            segs["queue"] = q["t1"] - q["t0"]
        b = batch_of.get(corr)
        if b is not None:
            for s in kids.get(b["sid"], []):
                name = s["n"].split(".", 1)[1]
                end = s["t1"]
                if name == "slice":
                    # the slice span settles the WHOLE batch; this
                    # request only waited until ITS future was set —
                    # clip the shared span at the request's completion
                    # so early members aren't billed for later ones
                    end = min(end, req["t1"])
                segs[name] = segs.get(name, 0.0) \
                    + max(0.0, end - s["t0"])
        row["segments_ms"] = {k: round(v * 1e3, 4)
                              for k, v in segs.items()}
        complete = b is not None and row["error"] is None
        if complete:
            total = sum(segs.values())
            e2e_s = req["t1"] - req["t0"]
            resid = abs(total - e2e_s) / e2e_s if e2e_s > 0 else 0.0
            row["segment_sum_ms"] = round(total * 1e3, 4)
            row["residual_pct"] = round(resid * 100.0, 2)
            residuals.append(resid * 100.0)
            e2e.append(e2e_s)
            for k, v in segs.items():
                seg_vals.setdefault(k, []).append(v)
        rows.append(row)

    agg = {k: _pcts(v) for k, v in sorted(seg_vals.items())}
    mean_resid = round(float(np.mean(residuals)), 2) if residuals \
        else None
    med_resid = round(float(np.median(residuals)), 2) if residuals \
        else None
    return {
        "requests": len(rows),
        "complete": len(e2e),
        "e2e": _pcts(e2e),
        "segments": agg,
        "mean_residual_pct": mean_resid,
        "median_residual_pct": med_resid,
        "tolerance_pct": tol_pct,
        # the acceptance gate: the per-segment accounting explains the
        # measured end-to-end latency.  Judged on the MEDIAN residual —
        # on a loaded host a single request can be descheduled between
        # two timestamps, and one such outlier must not fail a run
        # whose accounting is otherwise tight
        "sum_within_tol": bool(residuals) and med_resid <= tol_pct,
        "per_request": rows,
    }


def training_breakdown(spans):
    """One row per ``s<n>`` correlation, segments folded by name."""
    steps = {}
    for s in spans:
        c = s.get("c") or ""
        base = c.rsplit("/", 1)[-1]
        if not (base.startswith("s") and base[1:].isdigit()):
            continue
        steps.setdefault(c, {})[s["n"]] = \
            steps.setdefault(c, {}).get(s["n"], 0.0) + (s["t1"] - s["t0"])
    rows, seg_vals, totals = [], {}, []
    for c in sorted(steps, key=lambda x: (x.rsplit("/", 1)[0]
                                          if "/" in x else "",
                                          int(x.rsplit("/", 1)[-1][1:]))):
        segs = steps[c]
        root = segs.pop("train.step", None)
        row = {"step": int(c.rsplit("/", 1)[-1][1:]),
               "step_ms": round(root * 1e3, 4) if root else None,
               "segments_ms": {k: round(v * 1e3, 4)
                               for k, v in sorted(segs.items())}}
        rows.append(row)
        if root:
            totals.append(root)
        for k, v in segs.items():
            seg_vals.setdefault(k, []).append(v)
    return {"steps": len(rows),
            "step": _pcts(totals),
            "segments": {k: _pcts(v)
                         for k, v in sorted(seg_vals.items())},
            "per_step": rows}


def compile_breakdown(spans):
    """Where startup time went (docs/how_to/compiled_programs.md): the
    ``compile.trace`` / ``compile.compile`` / ``compile.load`` spans
    the unified CompiledProgram path emits, folded per phase and per
    artifact kind.  A warm restart shows ``compile.load`` rows only —
    a ``compile.compile`` row on a supposedly-warm start IS the
    regression."""
    phases, kinds = {}, {}
    total = 0.0
    for s in spans:
        n = s["n"]
        if not n.startswith("compile."):
            continue
        dt = s["t1"] - s["t0"]
        total += dt
        phases.setdefault(n, []).append(dt)
        kind = (s.get("a") or {}).get("kind", "?")
        k = kinds.setdefault("%s:%s" % (kind, n.split(".", 1)[1]),
                             [0, 0.0])
        k[0] += 1
        k[1] += dt
    return {
        "total_ms": round(total * 1e3, 3),
        "phases": {k: _pcts(v) for k, v in sorted(phases.items())},
        "by_kind": {k: {"count": c, "total_ms": round(t * 1e3, 3)}
                    for k, (c, t) in sorted(kinds.items())},
    }


def metrics_summary(events):
    """Fold the periodic metric-delta lines: summed counter deltas,
    last gauge values, last histogram snapshots.  Replica-scoped
    serving counters (``serving.server<N>.*`` — each
    :class:`~mxnet_tpu.serving.ModelServer` of a fleet counts under its
    own registry scope) are additionally merged into a ``fleet``
    rollup, the cross-replica sum a capacity dashboard wants next to
    the per-replica lines."""
    counters, gauges, hists = {}, {}, {}
    for e in _export.metric_events(events):
        for k, v in (e.get("c") or {}).items():
            counters[k] = round(counters.get(k, 0) + v, 6)
        gauges.update(e.get("g") or {})
        hists.update(e.get("h") or {})
    fleet, replicas = {}, set()
    for k, v in counters.items():
        m = re.match(r"serving\.server(\d+)\.([^.]+)$", k)
        if m:
            replicas.add(int(m.group(1)))
            fleet[m.group(2)] = round(fleet.get(m.group(2), 0) + v, 6)
    out = {"counter_deltas": dict(sorted(counters.items())),
           "gauges": dict(sorted(gauges.items())),
           "histograms": {k: {kk: vv for kk, vv in h.items()
                              if kk != "counts"}
                          for k, h in sorted(hists.items())}}
    if len(replicas) > 1:
        out["fleet"] = {"replicas": len(replicas),
                        "counter_deltas": dict(sorted(fleet.items()))}
    return out


def report(paths, tol_pct=5.0):
    events, spans, unclosed = [], [], []
    for i, p in enumerate(paths):
        evs = _export.parse_log(p)
        events.extend(evs)
        # unclosed is judged PER LOG (span ids are per recorder and
        # would collide across processes)
        unclosed.extend({"log": p, "sid": sid, "name": n}
                        for sid, n in unclosed_spans(evs))
        for s in _export.span_events(evs):
            if len(paths) > 1:
                # prefix correlation IDs (and the batch→request links
                # that carry them) with the log index so two processes'
                # "r1" stay distinct
                s = dict(s)
                if s.get("c"):
                    s["c"] = "%d/%s" % (i, s["c"])
                reqs = (s.get("a") or {}).get("requests")
                if reqs:
                    s["a"] = dict(s["a"],
                                  requests=["%d/%s" % (i, r)
                                            for r in reqs])
            spans.append(s)
    return {
        "logs": list(paths),
        "events": len(events),
        "spans": len(spans),
        "unclosed": unclosed,
        "serving": serving_breakdown(spans, tol_pct=tol_pct),
        "training": training_breakdown(spans),
        "compile": compile_breakdown(spans),
        "metrics": metrics_summary(events),
    }, spans


def _fmt_segments(title, agg):
    lines = ["  %s:" % title]
    for k, p in (agg or {}).items():
        if p is None:
            continue
        lines.append("    %-18s p50 %8.3f ms   p99 %8.3f ms   (n=%d)"
                     % (k, p["p50_ms"], p["p99_ms"], p["count"]))
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("logs", nargs="+", help="MXTPU_OBS_LOG JSONL file(s)")
    ap.add_argument("--json", action="store_true",
                    help="full JSON report (per-request/per-step rows)")
    ap.add_argument("--chrome", default=None,
                    help="also render the spans to Chrome tracing JSON "
                         "(open in Perfetto)")
    ap.add_argument("--check", action="store_true",
                    help="gate: every opened span closed, and request "
                         "segment sums within --tol of end-to-end")
    ap.add_argument("--tol", type=float, default=5.0,
                    help="segment-sum residual tolerance in percent "
                         "(default 5)")
    args = ap.parse_args(argv)

    rep, spans = report(args.logs, tol_pct=args.tol)
    if args.chrome:
        _export.dump_chrome(spans, args.chrome)
        print("chrome trace -> %s" % args.chrome, file=sys.stderr)

    if args.json:
        print(json.dumps(rep, indent=1))
    else:
        srv, trn = rep["serving"], rep["training"]
        print("%d events, %d spans, %d unclosed"
              % (rep["events"], rep["spans"], len(rep["unclosed"])))
        if srv["requests"]:
            print("serving: %d requests (%d complete), e2e p50 %.3f / "
                  "p99 %.3f ms, mean residual %.2f%%"
                  % (srv["requests"], srv["complete"],
                     srv["e2e"]["p50_ms"], srv["e2e"]["p99_ms"],
                     srv["mean_residual_pct"] or 0.0))
            print("\n".join(_fmt_segments("segments", srv["segments"])))
        if trn["steps"]:
            p = trn["step"]
            print("training: %d steps%s"
                  % (trn["steps"],
                     ", step p50 %.3f / p99 %.3f ms"
                     % (p["p50_ms"], p["p99_ms"]) if p else ""))
            print("\n".join(_fmt_segments("segments", trn["segments"])))
        cmp_ = rep["compile"]
        if cmp_["by_kind"]:
            print("compile/startup: %.1f ms total" % cmp_["total_ms"])
            for k, row in cmp_["by_kind"].items():
                print("    %-28s x%-3d %10.2f ms"
                      % (k, row["count"], row["total_ms"]))

    if args.check:
        failures = []
        if rep["unclosed"]:
            failures.append("%d span(s) opened but never closed: %s"
                            % (len(rep["unclosed"]),
                               rep["unclosed"][:8]))
        srv = rep["serving"]
        if srv["complete"] and not srv["sum_within_tol"]:
            failures.append(
                "request segment sums off by %.2f%% (median; mean "
                "%.2f%%, tolerance %.1f%%)"
                % (srv["median_residual_pct"],
                   srv["mean_residual_pct"], args.tol))
        if failures:
            for f in failures:
                print("obs-report CHECK FAILED: %s" % f,
                      file=sys.stderr)
            return 1
        print("obs-report check OK (%d spans, all closed%s)"
              % (rep["spans"],
                 ", serving residual %.2f%%"
                 % srv["median_residual_pct"]
                 if srv["complete"] else ""), file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
