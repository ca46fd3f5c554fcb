#!/usr/bin/env python
"""Concurrency sanitizer CLI: static thread-safety lint + lockset
replay, gated on ``RACE_BASELINE.json``.

Two finding sources, one report format (the graph linter's
``Finding``/``LintReport``):

* **static scan** (default) — the AST rules over ``mxnet_tpu/``:
  ``unnamed-thread`` / ``undeclared-daemon`` (error),
  ``unlocked-thread-mutation`` / ``blocking-call-under-lock`` (warn).
  Pure parse time; runs in the fast CI tier.
* **runtime replay** (``--replay <log>``) — lockset violations
  (``lockset-race``) and acquisition-graph cycles
  (``lock-order-inversion``) over a ``MXTPU_TSAN_LOG`` JSONL event log
  recorded by an instrumented run (the CI sweep runs the serving,
  stream-pipeline, and elastic suites under ``MXTPU_TSAN=1`` and
  replays their combined log here).

``--check`` fails on NEW error findings vs the checked-in
``RACE_BASELINE.json`` (the ``LINT_BASELINE.json`` ratchet pattern);
``--write-baseline`` re-records after an intentional change.  Rule
catalogue + fix recipes: ``docs/how_to/static_analysis.md``.
"""
import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RACE_BASELINE_PATH = os.environ.get(
    "MXTPU_RACE_BASELINE", os.path.join(ROOT, "RACE_BASELINE.json"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replay", action="append", default=[],
                    metavar="LOG",
                    help="MXTPU_TSAN_LOG JSONL event log(s) to replay "
                         "through the lockset/lock-order analysis "
                         "(repeatable; merged into one runtime report)")
    ap.add_argument("--no-static", action="store_true",
                    help="skip the static AST scan (replay-only gate)")
    ap.add_argument("--root", default=None,
                    help="source tree for the static scan (default: the "
                         "installed mxnet_tpu package)")
    ap.add_argument("--severity", choices=("error", "warn", "info"),
                    default=None,
                    help="minimum severity to report (the gate always "
                         "judges errors)")
    ap.add_argument("--check", action="store_true",
                    help="gate NEW error findings against %s"
                         % os.path.basename(RACE_BASELINE_PATH))
    ap.add_argument("--write-baseline", action="store_true",
                    help="record current findings into the baseline "
                         "(ratchet after an intentional change)")
    ap.add_argument("--json", action="store_true",
                    help="emit the full reports as one JSON object")
    ap.add_argument("--max-findings", type=int, default=25,
                    help="findings printed per report (default 25)")
    args = ap.parse_args(argv)

    # the scan and the replay are both host-side only — never touch a
    # device backend for a lint
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from mxnet_tpu import analysis

    reports = {}
    if not args.no_static:
        reports["concurrency-static"] = analysis.lint_source(
            root=args.root).dedupe()
    if args.replay:
        from mxnet_tpu import _tsan
        events = []
        for path in args.replay:
            events.extend(_tsan.parse_log(path))
        reports["concurrency-runtime"] = analysis.lint_events(
            events).dedupe()
    if not reports:
        raise SystemExit("nothing to do: --no-static with no --replay")

    # the severity filter trims what is PRINTED, never what the ratchet
    # below judges (or what --write-baseline records) — render_reports
    # filters display copies
    print(analysis.render_reports(reports, severity=args.severity,
                                  as_json=args.json,
                                  max_findings=args.max_findings))

    # NOTE: filter_severity only trims what is SHOWN above; the shared
    # ratchet (analysis.run_gate) always judges error-severity
    # findings, which a severity filter at or above "error" cannot hide
    return analysis.run_gate(reports, "concurrency-lint",
                             check=args.check, write=args.write_baseline,
                             path=RACE_BASELINE_PATH)


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
