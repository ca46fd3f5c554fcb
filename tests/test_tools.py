"""Tooling tests: im2rec list/pack round trip, parse_log, launcher env
contract, op-doc generation (reference ``tools/``); the documents
name only files that exist."""
import glob
import os
import re
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


# ---------------------------------------------------- documents and files
_CODE_SPAN = re.compile(r"```.*?```|`[^`\n]+`", re.S)
# a path of this repository as a document writes it: under one of four
# directories, a `<name>.py`, or one of the root's upper-case records
_REPO_PATH = re.compile(
    r"(?<![\w/.*\-])((?:tools|mxnet_tpu|ci|benchmark)/[\w./*\-]+"
    r"|[\w\-]+\.py|[A-Z][A-Z0-9_]*\.jsonl?)(?![\w/\-])")
# not this repository's: the reader's own scripts, two files of the
# reference, and what `tools/quantize.py` writes beside a checkpoint
_NOT_OURS = {"train.py", "serve.py", "kill-mxnet.py",
             "executor_manager.py", "QUANT_GATE.json"}


def _exists(path):
    if "/" not in path:
        # `<name>.py` at the root, or a module named by its file alone
        import conftest
        return any(os.path.basename(f) == path
                   for f in conftest.repo_files())
    if glob.glob(os.path.join(_ROOT, path)):
        return True
    # `tools/stepcost.cost_model`: a module's attribute
    return os.path.exists(
        os.path.join(_ROOT, path.rsplit(".", 1)[0] + ".py"))


@pytest.mark.parametrize("doc", ["README.md"] + sorted(
    os.path.relpath(p, _ROOT)
    for p in glob.glob(os.path.join(_ROOT, "docs", "how_to", "*.md"))))
def test_document_names_only_files_that_exist(doc):
    """Every path of the repository a document back-quotes is in the
    tree: a deleted tool leaves the documents with it."""
    with open(os.path.join(_ROOT, doc)) as f:
        text = f.read()
    named = {m.group(1).rstrip(".") for span in _CODE_SPAN.findall(text)
             for m in _REPO_PATH.finditer(span)} - _NOT_OURS
    assert sorted(p for p in named if not _exists(p)) == []


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
