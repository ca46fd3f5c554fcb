"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY §4): sharding/collective
tests run on ``xla_force_host_platform_device_count=8`` CPU devices (the
local-launcher trick for testing multi-node on one box); the same code
runs unmodified on a real TPU mesh.
"""
import contextlib
import functools
import os
import signal
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# tests run on the virtual CPU mesh whatever the machine holds: this is
# the outright choice of the CPU under which a ``tpu`` context resolves
# to a host device (mxnet_tpu/base.py)
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@functools.lru_cache(maxsize=None)
def repo_files():
    """Every file of the checkout, as paths from its root, less what
    ``.gitignore`` keeps out by directory (copies of other commits, chip
    outputs, caches): what the tests of the tree's own consistency read."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, ".gitignore")) as f:
        skip = {ln.strip().rstrip("/") for ln in f
                if ln.strip().endswith("/")}
    skip.add(".git")
    found = []
    for d, subdirs, files in os.walk(root):
        subdirs[:] = [s for s in subdirs if s not in skip]
        found += [os.path.relpath(os.path.join(d, name), root)
                  for name in files]
    return tuple(found)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: the call takes more than 90 s alone on the CPU (measured); "
        "tier-1 and ci/run_tests.sh's fast gate deselect it (-m 'not "
        "slow'), MXTPU_CI_FULL=1 runs it")


# every test's own time limit, a fifth of tier-1's clock: a test that
# waits for ever fails by name instead of cutting the whole run
TEST_TIME_LIMIT_S = 300.0


@contextlib.contextmanager
def time_limit(nodeid):
    """Fail the test under way once it outlasts ``TEST_TIME_LIMIT_S``.  An
    interval timer of the main thread, where pytest and every xdist worker
    run tests; the handler raises there as soon as the interpreter next
    runs bytecode (a wait inside a C call that never returns is not
    reached: such a wait belongs in a subprocess with a timeout)."""
    limit = TEST_TIME_LIMIT_S

    def _expired(signum, frame):
        pytest.fail("%s outlasted the time limit of %g s every test has "
                    "(tests/conftest.py)" % (nodeid, limit), pytrace=False)

    handler = signal.signal(signal.SIGALRM, _expired)
    outer_left, _ = signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, outer_left)
        signal.signal(signal.SIGALRM, handler)


@pytest.fixture(autouse=True)
def _time_limit(request):
    with time_limit(request.node.nodeid):
        yield


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    # fresh auto-naming counters per test: node names like "plus1" must not
    # depend on how many symbols earlier tests created (process-global state)
    mx.name.NameManager._current.value = mx.name.NameManager()
    yield


@pytest.fixture(autouse=True)
def _mxtpu_thread_leak_check():
    """No ``mxtpu-*`` thread a test spawns may survive it.

    Every framework thread is named (``mxtpu-serve-sched``,
    ``mxtpu-upload``, ``mxtpu-hb-<rank>``, ``mxtpu-decode``, ...: the
    ``unnamed-thread`` lint rule enforces the naming), so a leak is
    attributable on sight.  A thread parked in a bounded-wait loop
    (upload staging, decode producer) ends at teardown/GC — the check
    runs ``gc.collect()`` and grants a short grace before failing, so
    only a genuinely unowned thread (an un-stopped server, an
    un-closed iterator, a heartbeat nobody stopped) trips it."""
    import gc
    import threading
    import time

    before = {t for t in threading.enumerate()
              if t.name.startswith("mxtpu-")}
    yield
    leaked = [t for t in threading.enumerate()
              if t.name.startswith("mxtpu-") and t.is_alive()
              and t not in before]
    if leaked:
        # drop test-local owners (iterators/servers whose __del__ stops
        # their worker), then give daemon loops one poll interval to
        # notice the stop flag
        gc.collect()
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline \
                and any(t.is_alive() for t in leaked):
            time.sleep(0.05)
        leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        "mxtpu-* threads leaked by this test: %s — stop()/close() the "
        "owning server/iterator/heartbeat (docs/how_to/"
        "static_analysis.md)" % sorted(t.name for t in leaked))
