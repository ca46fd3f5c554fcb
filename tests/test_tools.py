"""Tooling tests: im2rec list/pack round trip, parse_log, launcher env
contract, op-doc generation (reference ``tools/``)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=240, **kw):
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, cwd=_ROOT, timeout=timeout, **kw)


def test_im2rec_roundtrip(tmp_path):
    from PIL import Image
    rng = np.random.RandomState(0)
    for cls in ("a", "b"):
        d = tmp_path / cls
        d.mkdir()
        for i in range(3):
            Image.fromarray(rng.randint(0, 255, (32, 40, 3),
                                        dtype=np.uint8)).save(
                str(d / ("%s%d.jpg" % (cls, i))))
    prefix = str(tmp_path / "data")
    r = _run(["tools/im2rec.py", prefix, str(tmp_path), "--list",
              "--recursive"])
    assert r.returncode == 0, r.stderr
    assert os.path.exists(prefix + ".lst")
    r = _run(["tools/im2rec.py", prefix, str(tmp_path), "--resize", "24"])
    assert r.returncode == 0, r.stderr
    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(3, 20, 20), batch_size=3)
    batch = next(iter(it))
    labels = sorted(batch.label[0].asnumpy().tolist())
    assert set(labels) <= {0.0, 1.0}
    assert batch.data[0].shape == (3, 3, 20, 20)


def test_parse_log():
    log = ("Epoch[0] Batch [20]\tSpeed: 111.5 samples/sec\t"
           "accuracy=0.5\n"
           "Epoch[0] Train-accuracy=0.91\n"
           "Epoch[0] Time cost=4.2\n"
           "Epoch[0] Validation-accuracy=0.88\n")
    r = _run(["tools/parse_log.py", "--format", "none"], input=log)
    assert r.returncode == 0, r.stderr
    line = r.stdout.strip().splitlines()[-1]
    cells = line.split("\t")
    assert cells[0] == "0"
    assert float(cells[1]) == 0.91
    assert float(cells[2]) == 0.88
    assert abs(float(cells[3]) - 111.5) < 1e-6


def test_launch_local_env_contract(tmp_path):
    out = str(tmp_path / "w")
    r = _run(["tools/launch.py", "-n", "2", "--launcher", "local", "--",
              sys.executable, "-c",
              "import os; open(%r + os.environ['MXTPU_PROCESS_ID'], 'w')"
              ".write(os.environ['MXTPU_NUM_PROCESSES'])" % out])
    assert r.returncode == 0, r.stderr
    assert open(out + "0").read() == "2"
    assert open(out + "1").read() == "2"


def test_launch_local_fails_fast():
    r = _run(["tools/launch.py", "-n", "2", "--launcher", "local", "--",
              sys.executable, "-c",
              "import os, sys, time\n"
              "rank = int(os.environ['MXTPU_PROCESS_ID'])\n"
              "sys.exit(3) if rank == 1 else time.sleep(120)"])
    # a crashing worker must tear down the sleeper well before 120s
    # (the 240s _run timeout would otherwise trip)
    assert r.returncode != 0


def test_gen_op_docs(tmp_path):
    path = str(tmp_path / "ops.md")
    r = _run(["tools/gen_op_docs.py", path])
    assert r.returncode == 0, r.stderr
    text = open(path).read()
    assert "## FullyConnected" in text
    assert "**required**" in text


def test_step_breakdown_budget_and_layers(tmp_path):
    """tools/step_breakdown.py round-6 surface, sans the ResNet compile:
    symbol-layer attribution parses named-scope ``op_name`` metadata out
    of real compiled HLO, and the byte-budget emit → parse → gate cycle
    round-trips (the machinery behind the nightly ``--check`` gate and
    bench.py's ``byte_budget_*`` fields)."""
    import json
    import jax
    import jax.numpy as jnp
    from tools import step_breakdown as sb

    # op_name grammar: jvp-wrapped forward, transpose(jvp()) backward,
    # scope-less wrapper-only paths
    assert sb.layer_from_op_name("jit(step)/jvp(conv0)/max") == \
        ("conv0", False)
    assert sb.layer_from_op_name(
        "jit(step)/transpose(jvp(stage1_relu))/mul") == ("stage1_relu", True)
    assert sb.layer_from_op_name("jit(f)/add")[0] is None

    # attribution over REAL compiled HLO (executor.py stamps the same
    # per-symbol-node scopes the fused step carries)
    def f(x):
        with jax.named_scope("conv0"):
            y = jnp.maximum(x, 0.0)
        with jax.named_scope("fc1"):
            return (y * 2.0).sum()

    comp = jax.jit(jax.grad(f)).lower(jnp.ones((256, 256))).compile()
    rows = sb.analyze(comp.as_text(), hbm_gbps=600.0, mxu_tflops=180.0)
    layers = sb.layer_table(rows)
    assert any(k.split(" ")[0] in ("conv0", "fc1") for k in layers), layers
    assert sum(e["n_instructions"] for e in layers.values()) == len(rows)

    # budget: emit -> parse -> gate (ok inside tolerance, fail outside)
    entry = sb.byte_budget_entry(
        {"model": "toy", "cost_model_gb_per_step": 10.0})
    path = str(tmp_path / "budget.json")
    json.dump({"tolerance_pct": 3.0, "cpu": entry}, open(path, "w"))
    budget = sb.load_budget(path)
    ok, delta = sb.check_byte_budget(10.1, budget["cpu"],
                                     budget["tolerance_pct"])
    assert ok and abs(delta - 1.0) < 0.2
    ok, delta = sb.check_byte_budget(10.4, budget["cpu"],
                                     budget["tolerance_pct"])
    assert not ok and delta > 3.0

    # the checked-in budget file parses and carries the gate's fields
    budget = sb.load_budget()
    assert budget and "tolerance_pct" in budget
    for plat in ("tpu", "cpu"):
        assert "cost_model_gb_per_step" in budget[plat]
        # run_check refuses to gate against a wrong-shape entry (a
        # full-shape capture recorded into the small-shape CPU slot
        # would leave the gate ~95% slack): every entry must carry the
        # model string the guard compares
        assert "model" in budget[plat]


def test_attn_bench_smoke(tmp_path):
    """tools/attn_bench.py runs end-to-end at toy size (flash in
    interpret mode on CPU) and writes a well-formed artifact."""
    import json
    out = str(tmp_path / "attn.json")
    res = _run([os.path.join(_ROOT, "tools", "attn_bench.py"),
                "--seqs", "128", "--batch", "1", "--heads", "2",
                "--dim", "64", "--steps", "2", "--out", out],
               timeout=280, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert res.returncode == 0, res.stderr[-2000:]
    art = json.load(open(out))
    row = art["rows"][0]
    assert row["seq"] == 128
    assert "flash_fwd_ms" in row and "naive_fwd_ms" in row
    assert "flash_fwdbwd_ms" in row


def test_time_limit_fails_a_test_that_outlasts_it(monkeypatch):
    """The suite's own tool: the limit every test has (tests/conftest.py)
    fails what outlasts it, by name, and leaves the test's own timer and
    handler as it found them."""
    import signal
    import time

    import conftest

    handler = signal.getsignal(signal.SIGALRM)
    monkeypatch.setattr(conftest, "TEST_TIME_LIMIT_S", 0.2)
    with pytest.raises(pytest.fail.Exception) as err:
        with conftest.time_limit("tests/test_x.py::test_waits_for_ever"):
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                time.sleep(0.05)
    assert "tests/test_x.py::test_waits_for_ever" in str(err.value)
    assert "0.2 s" in str(err.value)
    assert signal.getsignal(signal.SIGALRM) is handler
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 200 < left <= 300     # this test's own limit is armed again

    with conftest.time_limit("tests/test_x.py::test_returns_in_time"):
        pass
    time.sleep(0.3)              # no stale timer fires after a test
