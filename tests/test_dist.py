"""Multi-process distributed tests, run through the local launcher the
way the reference runs its nightly dist tests on one box
(``tools/launch.py -n N --launcher local``, dmlc local-tracker analog)."""
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _launch(script, n=2, timeout=240):
    env = dict(os.environ)
    env.pop("MXTPU_COORDINATOR", None)   # never nest coordination scopes
    return subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "launch.py"),
         "-n", str(n), "--launcher", "local", "--",
         sys.executable, os.path.join(_ROOT, "tests", "nightly", script)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_ROOT)


def test_dist_sync_kvstore_two_workers():
    res = _launch("dist_sync_kvstore.py")
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("exact-sum OK") == 2, res.stdout + res.stderr


def test_dist_mlp_two_workers():
    res = _launch("dist_mlp.py")
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("params identical") == 2, \
        res.stdout + res.stderr


def test_cpu_tpu_consistency():
    """Cross-backend consistency suite (the reference's GPU re-run trick,
    SURVEY §4) — runs standalone so it sees both backends.  Where JAX
    finds no TPU it must refuse, not report: nothing was compared."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)       # let the default backend load
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tests", "nightly",
                                      "consistency.py"), "--sample", "6"],
        capture_output=True, text=True, timeout=240, env=env, cwd=_ROOT)
    import re
    m = re.search(r"consistency: (\d+) cases matched, (\d+) failed",
                  res.stdout)
    if "needs the TPU backend" in res.stderr:
        assert res.returncode != 0 and m is None, res.stdout + res.stderr
    else:
        assert res.returncode == 0, res.stdout + res.stderr
        assert m and int(m.group(1)) > 30 and m.group(2) == "0", res.stdout


def test_failure_detection_and_restart(tmp_path):
    """Kill 1 of 2 workers mid-training: the survivor must attribute the
    failure via num_dead_node, the launcher must restart, and the job
    must resume from the checkpoint and converge (SURVEY §5
    failure-recovery contract)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", "--auto-restart", "1",
         "--detect-grace", "6", "--",
         sys.executable,
         os.path.join(_ROOT, "tests", "nightly", "dist_resume.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=240, env=env, cwd=_ROOT)
    out = res.stdout + res.stderr
    assert res.returncode == 0, out
    assert "simulating crash" in out, out
    assert "detected 1 dead rank(s) via num_dead_node" in out, out
    assert "restart 1/1" in out, out
    assert "auto-resume from epoch" in out, out
    assert out.count("recovery train done") == 2, out
