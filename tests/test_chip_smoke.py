"""The chip entry point refuses to report from the CPU.

``chip_smoke.py`` measures the chip.  Run where JAX has no accelerator
it must exit non-zero and print no result: a number from a CPU run is
never written under the name of a device metric.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script,result", [
    ("chip_smoke.py", '"ok": true'),
])
def test_refuses_to_report_from_the_cpu(script, result):
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, script)], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert res.returncode != 0, res.stdout + res.stderr
    assert result not in res.stdout
    assert "found no" in res.stderr or "found none" in res.stderr


# the sizes of a rehearsal: wrong paths, arguments and control flow show
# at any size, and the chip is where the real one runs
TINY = {
    "on_chip": False,
    "net": "resnet-18", "batch": 8, "image": 32, "classes": 10,
    "train_steps": 4, "timed_steps": 2,
    "serve_requests": 12, "serve_threads": 3,
    "lm": {"num_hidden": 64, "num_heads": 2, "seq_len": 128,
           "vocab_size": 101, "num_layers": 1, "batch": 2},
    "attn_shapes": [(1, 128, 2, 64), (1, 100, 2, 64)],
    "parity_steps": 3,
}


@pytest.mark.parametrize("chips", [1, 8])
def test_phases_rehearse_on_the_cpu(monkeypatch, tmp_path, capsys, chips):
    """Every phase of ``chip_smoke.py`` but ``ops`` (which needs both
    backends) runs here at a tiny size, the one-chip phases and the
    data-parallel parity over the test mesh: the script the driver runs
    on the chip after every PR cannot rot unseen."""
    import json
    sys.path.insert(0, ROOT)
    import chip_smoke
    # caches under tmp_path: JAX's placed from outside (so the code sets
    # none), the program cache armed from outside
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("MXTPU_PROGRAM_CACHE", str(tmp_path / "programs"))
    monkeypatch.setattr(chip_smoke, "phase_ops", lambda sizes: None)
    failed = chip_smoke.run_phases(TINY, 0, chips)
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    assert not failed, lines
    phases = {ln["phase"]: ln for ln in lines}
    want = ["parity"] if chips > 1 else ["train", "serve", "kernel"]
    assert all(phases[p]["ok"] for p in want), phases
    assert not any("device" in ln and "ok" in ln for ln in lines), \
        "only main() may print the result line"
