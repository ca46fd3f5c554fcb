"""Lint baseline: the ratchet that gates CI on NEW error findings.

The pattern ``COMM_BASELINE.json`` and ``MEM_BASELINE.json`` share:
a checked-in ``LINT_BASELINE.json`` records, per linted model, the
finding counts at the last intentional ratchet.  ``--check`` fails when
any rule produces MORE error-severity findings than the baseline allows
(new hazards); warn/info drift is reported but does not gate.
``--write-baseline`` re-records after an intentional change.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

from .core import LintReport

__all__ = ["BASELINE_PATH", "baseline_entry", "load_baseline",
           "check_baseline", "write_baseline", "run_gate"]

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASELINE_PATH = os.environ.get(
    "MXTPU_LINT_BASELINE", os.path.join(_ROOT, "LINT_BASELINE.json"))


def baseline_entry(report: LintReport) -> Dict:
    c = report.counts()
    return {"error": c["error"], "warn": c["warn"], "info": c["info"],
            "errors_by_rule": report.by_rule("error"),
            "warns_by_rule": report.by_rule("warn")}


def load_baseline(path=None):
    path = path or BASELINE_PATH
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def check_baseline(reports: Dict[str, LintReport],
                   baseline=None, path=None) -> Tuple[bool, list]:
    """Gate ``reports`` against the baseline.  Returns ``(ok, messages)``;
    ok is False on any NEW error-severity finding (per model, per rule)
    or a missing baseline entry."""
    baseline = baseline if baseline is not None else load_baseline(path)
    msgs, ok = [], True
    if baseline is None:
        return False, ["no %s — record one with --write-baseline"
                       % os.path.basename(path or BASELINE_PATH)]
    for model, report in reports.items():
        entry = baseline.get(model)
        if entry is None:
            ok = False
            msgs.append("%s: no baseline entry — run --write-baseline"
                        % model)
            continue
        allowed = entry.get("errors_by_rule", {})
        measured = report.by_rule("error")
        for rule, n in sorted(measured.items()):
            base_n = int(allowed.get(rule, 0))
            if n > base_n:
                ok = False
                msgs.append("%s: NEW error findings: rule %s has %d "
                            "(baseline %d)" % (model, rule, n, base_n))
        for rule, base_n in sorted(allowed.items()):
            if measured.get(rule, 0) < base_n:
                msgs.append("%s: rule %s improved to %d errors (baseline "
                            "%d) — ratchet with --write-baseline"
                            % (model, rule, measured.get(rule, 0), base_n))
        warn_n, base_warn = report.counts()["warn"], int(entry.get("warn", 0))
        if warn_n != base_warn:
            msgs.append("%s: warn findings %d vs baseline %d "
                        "(informational; errors gate)"
                        % (model, warn_n, base_warn))
    return ok, msgs


def write_baseline(reports: Dict[str, LintReport], path=None,
                   extras: Dict[str, Dict] = None) -> str:
    """Record ``reports`` into the baseline file.  ``extras`` merges
    additional per-model fields into each entry (the comm linter
    records ``comm_gb_per_step`` beside the finding counts, the
    figure its ``comm-budget`` rule ratchets)."""
    path = path or BASELINE_PATH
    baseline = load_baseline(path) or {}
    for model, report in reports.items():
        entry = baseline_entry(report)
        if extras and model in extras:
            entry.update(extras[model])
        baseline[model] = entry
    with open(path, "w") as f:
        json.dump(baseline, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def run_gate(reports: Dict[str, LintReport], label: str,
             check: bool = False, write: bool = False, path=None,
             extras: Dict[str, Dict] = None) -> int:
    """The CLIs' shared ratchet block (``tools/graph_lint.py``,
    ``tools/concurrency_lint.py``, ``tools/comm_lint.py``): on
    ``write``, record the baseline and say where; on ``check``, gate
    NEW error findings against it and print the verdict.  Returns the
    process exit code."""
    if write:
        out = write_baseline(reports, path=path, extras=extras)
        print("%s: baseline written -> %s" % (label, out))
        return 0
    if check:
        ok, msgs = check_baseline(reports, path=path)
        for m in msgs:
            print("%s: %s" % (label, m))
        print("%s: baseline gate %s" % (label, "OK" if ok else "FAILED"))
        return 0 if ok else 1
    return 0
