"""Perl binding smoke test: build the XS module against the C ABI and
train the pure-Perl linear-regression example (the reference's
perl-package analog, one more generated binding over the choke point).
Also checks the generated per-op layer is fresh against the registry,
like the cpp-package freshness test."""
import os
import shutil
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_ROOT, "perl-package")


def _have_perl_xs():
    if shutil.which("perl") is None:
        return False
    try:
        core = subprocess.run(
            ["perl", "-MConfig", "-e", "print $Config{archlibexp}"],
            capture_output=True, text=True, timeout=30).stdout.strip()
        return os.path.exists(os.path.join(core, "CORE", "perl.h"))
    except Exception:
        return False


@pytest.mark.skipif(not _have_perl_xs(),
                    reason="perl or its CORE headers unavailable")
def test_perl_binding_trains():
    res = subprocess.run(["make", "-s", "check"], cwd=_PKG,
                         capture_output=True, text=True, timeout=240)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    assert "PERL BINDING OK" in res.stdout


def test_perl_ops_layer_fresh():
    sys.path.insert(0, os.path.join(_ROOT, "tools"))
    try:
        import gen_perl_ops
        generated = gen_perl_ops.generate()
    finally:
        sys.path.pop(0)
    committed = open(os.path.join(_PKG, "lib", "MXTPU", "Ops.pm")).read()
    assert generated == committed, \
        "perl-package/lib/MXTPU/Ops.pm is stale: rerun tools/gen_perl_ops.py"
