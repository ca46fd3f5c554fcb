"""Training resilience layer: step sentinel, fault injection,
crash-consistent checkpoints, auto-resume (docs/how_to/resilience.md).

Every recovery path is driven by the deterministic fault registry
(``mxnet_tpu.faults``) instead of trusted on faith; the kill-and-resume
e2e uses a real subprocess so ``crash@ckpt_write``'s ``os._exit(137)``
is SIGKILL-faithful (no atexit, no buffered-IO flush).  All CPU-fast.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, io, resilience
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.trainer import Trainer


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _mlp_symbol():
    data = mx.sym.Variable("data")
    fc1 = mx.symbol.FullyConnected(data, name="fc1", num_hidden=16)
    act = mx.symbol.Activation(fc1, name="relu1", act_type="relu")
    fc2 = mx.symbol.FullyConnected(act, name="fc2", num_hidden=4)
    return mx.symbol.SoftmaxOutput(fc2, name="softmax")


def _fixed_params(seed=0):
    rng = np.random.RandomState(seed)
    return {"fc1_weight": rng.randn(16, 32).astype("f") * 0.1,
            "fc1_bias": np.zeros(16, "f"),
            "fc2_weight": rng.randn(4, 16).astype("f") * 0.1,
            "fc2_bias": np.zeros(4, "f")}


def _trainer(**kw):
    t = Trainer(_mlp_symbol(),
                mx.optimizer.SGD(learning_rate=0.1, momentum=0.9,
                                 rescale_grad=1.0 / 8),
                **kw)
    t.bind(data_shapes={"data": (8, 32)},
           label_shapes={"softmax_label": (8,)})
    t.init_params(arg_params={k: mx.nd.array(v)
                              for k, v in _fixed_params().items()})
    return t


def _batches(n=10, seed=1):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, 32).astype("f"),
             rng.randint(0, 4, 8).astype("f")) for _ in range(n)]


def _feed(t, x, y):
    return t.step({"data": mx.nd.array(x), "softmax_label": mx.nd.array(y)})


# ======================================================================
# fault DSL
def test_fault_dsl_parse_and_fire():
    faults.configure("nan_grad@step=3;io_error@batch=5:count=2;"
                     "crash@ckpt_write")
    assert faults.active("nan_grad") and faults.active("crash")
    # below threshold: no fire
    assert not faults.hit("nan_grad", step=2)
    # at threshold: fires once, then spent
    assert faults.hit("nan_grad", step=3)
    assert not faults.hit("nan_grad", step=4)
    assert faults.fired("nan_grad") == 1
    # count=2: two fires from the armed point
    assert faults.hit("io_error", site="iter_next", batch=5)
    assert faults.hit("io_error", site="iter_next", batch=5)
    assert not faults.hit("io_error", site="iter_next", batch=6)
    # site match is exact
    assert not faults.hit("crash", site="manifest_write")
    assert faults.hit("crash", site="ckpt_write")


def test_fault_dsl_rejects_garbage():
    with pytest.raises(MXNetError):
        faults.configure("nan_grad")          # no @
    with pytest.raises(MXNetError):
        faults.configure("io_error@batch=soon")   # non-integer


def test_injected_context_manager_restores():
    faults.configure("nan_grad@step=1")
    with faults.injected("io_error@batch=0"):
        assert faults.active("io_error")
        assert not faults.active("nan_grad")
    assert faults.active("nan_grad")
    assert not faults.active("io_error")


# ======================================================================
# step sentinel
def test_sentinel_skip_counts_and_batch_drop_parity():
    """The acceptance contract: nan_grad@step=3 over a 10-step run ⇒
    exactly one recorded skip, and final params BIT-IDENTICAL to the
    same run with batch 3 dropped (skip semantics: old params, old opt
    state, update counter held)."""
    batches = _batches(10)
    faults.configure("nan_grad@step=3")
    ta = _trainer(sentinel="skip")
    for x, y in batches:
        _feed(ta, x, y)
    assert ta.sentinel_skips == 1
    assert faults.fired("nan_grad") == 1
    faults.clear()

    tb = _trainer(sentinel="skip")
    for i, (x, y) in enumerate(batches):
        if i == 2:                 # drop what run A skipped
            continue
        _feed(tb, x, y)
    pa, _ = ta.get_params()
    pb, _ = tb.get_params()
    for n in pa:
        assert np.array_equal(pa[n].asnumpy(), pb[n].asnumpy()), n


def test_sentinel_off_trains_identically():
    """off-mode must stay byte-for-byte the pre-sentinel program; and a
    skip-mode run with NO faults must match it exactly."""
    batches = _batches(6)
    t_off = _trainer(sentinel="off")
    t_skip = _trainer(sentinel="skip")
    for x, y in batches:
        _feed(t_off, x, y)
        _feed(t_skip, x, y)
    assert t_skip.sentinel_skips == 0
    p0, _ = t_off.get_params()
    p1, _ = t_skip.get_params()
    for n in p0:
        assert np.array_equal(p0[n].asnumpy(), p1[n].asnumpy()), n


def test_sentinel_abort_raises_after_k_consecutive():
    faults.configure("nan_grad@step=2:count=10")   # every step from 2 on
    t = _trainer(sentinel="abort", sentinel_max_skips=3)
    with pytest.raises(MXNetError, match="consecutive non-finite"):
        for x, y in _batches(10):
            _feed(t, x, y)
    assert t.sentinel_skips == 3


def test_sentinel_env_default(monkeypatch):
    monkeypatch.setenv("MXTPU_SENTINEL", "skip")
    t = _trainer()
    assert t.sentinel == "skip" and t._sent is not None
    monkeypatch.setenv("MXTPU_SENTINEL", "bogus")
    with pytest.raises(MXNetError, match="sentinel mode"):
        _trainer()


def test_sentinel_state_rides_opt_states():
    faults.configure("nan_grad@step=1")
    ta = _trainer(sentinel="skip")
    for x, y in _batches(3):
        _feed(ta, x, y)
    assert ta.sentinel_skips == 1
    blob = ta.get_opt_states()
    tb = _trainer(sentinel="skip")
    tb.set_opt_states(blob)
    assert tb.sentinel_skips == 1
    assert tb.num_update == 3
    assert int(np.asarray(tb._sent["t"])) == 2   # one step was skipped


def test_sentinel_state_survives_fit_epoch_boundaries():
    """Module.fit's epoch-end set_params refresh routes through
    Trainer.init_params(force_init=True): the sentinel state must
    survive it — recreating it would zero the skip counters and desync
    the effective update cursor at EVERY epoch end."""
    faults.configure("nan_grad@step=3")
    os.environ["MXTPU_SENTINEL"] = "skip"
    try:
        mod = _fit_module(_train_iter(), num_epoch=2)
    finally:
        os.environ.pop("MXTPU_SENTINEL", None)
    assert mod.sentinel_skips == 1
    # 10 updates, one skipped: the device-side cursor sits at 9
    assert int(np.asarray(mod._trainer._sent["t"])) == 9


def test_opt_states_pre_sentinel_blob_loads():
    ta = _trainer(sentinel="off")
    for x, y in _batches(2):
        _feed(ta, x, y)
    blob = ta.get_opt_states()              # 2-tuple, no sentinel entry
    tb = _trainer(sentinel="skip")
    tb.set_opt_states(blob)
    assert tb.num_update == 2
    assert int(np.asarray(tb._sent["t"])) == 2


# ----------------------------------------------------------------------
# dynamic loss scale
def test_dynamic_loss_scale_backoff_and_growth():
    # a plain linear head: no fixed-loss output op, so the seed-side
    # scale genuinely reaches the backward
    data = mx.sym.Variable("data")
    fc = mx.symbol.FullyConnected(data, name="fc", num_hidden=4)
    t = Trainer(fc, mx.optimizer.SGD(learning_rate=0.01,
                                     rescale_grad=1.0 / 8),
                label_names=(), sentinel="skip", loss_scale="dynamic",
                ls_growth_interval=3)
    t.bind(data_shapes={"data": (8, 8)})
    t.init_params(mx.init.Xavier())
    assert t._ls_applies
    rng = np.random.RandomState(2)
    b = {"data": mx.nd.array(rng.randn(8, 8).astype("f"))}
    s0 = t.loss_scale_value
    faults.configure("nan_grad@step=2")
    t.step(b)
    t.step(b)
    faults.clear()
    assert t.loss_scale_value == s0 / 2          # backoff on skip
    for _ in range(3):
        t.step(b)
    assert t.loss_scale_value == s0              # growth on clean streak
    assert t.sentinel_skips == 1


def test_loss_scale_inert_on_fixed_loss_graph():
    """SoftmaxOutput's vjp injects its loss grad (discards upstream
    cotangents): the trainer must detect that, warn, and run with the
    scale INERT instead of silently dividing real grads by it."""
    t = _trainer(sentinel="skip", loss_scale=1024.0)
    assert not t._ls_applies
    batches = _batches(4)
    t_ref = _trainer(sentinel="skip")
    for x, y in batches:
        _feed(t, x, y)
        _feed(t_ref, x, y)
    p0, _ = t.get_params()
    p1, _ = t_ref.get_params()
    for n in p0:
        assert np.array_equal(p0[n].asnumpy(), p1[n].asnumpy()), n


# ======================================================================
# iterator retry
def _fit_module(train, num_epoch, prefix=None, resume=False):
    """fit on the FUSED path (MXTPU_MODULE_FUSED=always): the sentinel
    and the trainer-side resume state live there; the classic executor
    path shares the same fit/checkpoint wiring."""
    mx.random.seed(0)
    old = os.environ.get("MXTPU_MODULE_FUSED")
    os.environ["MXTPU_MODULE_FUSED"] = "always"
    try:
        mod = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        mod.fit(train, num_epoch=num_epoch,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                                  "rescale_grad": 1.0 / 32},
                initializer=mx.init.Xavier(), checkpoint=prefix,
                resume=resume)
    finally:
        if old is None:
            os.environ.pop("MXTPU_MODULE_FUSED", None)
        else:
            os.environ["MXTPU_MODULE_FUSED"] = old
    return mod


def _train_iter(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(160, 32).astype("f")
    y = rng.randint(0, 4, 160).astype("f")
    return io.NDArrayIter(x, y, batch_size=32, shuffle=False)


def test_transient_io_error_is_retried():
    faults.configure("io_error@batch=2:count=2")
    _fit_module(_train_iter(), num_epoch=1)
    assert faults.fired("io_error") == 2         # failed twice, recovered


def test_persistent_io_error_propagates():
    faults.configure("io_error@batch=1:count=50")
    with pytest.raises(OSError, match="injected io_error"):
        _fit_module(_train_iter(), num_epoch=1)


def test_retry_io_backoff_bounds():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert resilience.retry_io(flaky, attempts=3, delay=0.001) == "ok"
    assert len(calls) == 3
    with pytest.raises(OSError):
        resilience.retry_io(lambda: (_ for _ in ()).throw(OSError("x")),
                            attempts=2, delay=0.001)


def test_retry_io_decorrelated_jitter(monkeypatch):
    """The backoff sequence carries DECORRELATED jitter: each sleep is
    the previous actual sleep times backoff, perturbed ±jitter — pinned
    here with a seeded RNG; and two 'ranks' with different seeds
    desynchronize instead of retrying in lockstep."""
    import random
    sleeps = []
    monkeypatch.setattr(resilience.time, "sleep", sleeps.append)

    def fail():
        raise OSError("transient")

    with pytest.raises(OSError):
        resilience.retry_io(fail, attempts=4, delay=0.05, backoff=2.0,
                            jitter=0.1, rng=random.Random(7))
    # replicate the exact decorrelated recurrence with the same seed
    ref_rng, expect, wait = random.Random(7), [], None
    for _ in range(3):
        wait = 0.05 if wait is None else wait * 2.0
        wait *= 1.0 + 0.1 * (2.0 * ref_rng.random() - 1.0)
        expect.append(wait)
    assert sleeps == pytest.approx(expect)
    # perturbations COMPOUND (sleep k feeds sleep k+1): strictly
    # exponential envelope, never the bare lockstep sequence
    assert all(abs(s - b) > 1e-9
               for s, b in zip(sleeps, (0.05, 0.1, 0.2)))

    # a second rank, different seed: every sleep differs — no lockstep
    sleeps2 = []
    monkeypatch.setattr(resilience.time, "sleep", sleeps2.append)
    with pytest.raises(OSError):
        resilience.retry_io(fail, attempts=4, delay=0.05, backoff=2.0,
                            jitter=0.1, rng=random.Random(11))
    assert all(abs(a - b) > 1e-9 for a, b in zip(sleeps, sleeps2))

    # jitter=0 restores the exact deterministic ladder
    sleeps3 = []
    monkeypatch.setattr(resilience.time, "sleep", sleeps3.append)
    with pytest.raises(OSError):
        resilience.retry_io(fail, attempts=4, delay=0.05, backoff=2.0,
                            jitter=0)
    assert sleeps3 == pytest.approx([0.05, 0.1, 0.2])


# ======================================================================
# checkpoint manager
def test_checkpoint_manager_latest_skips_corrupt(tmp_path):
    prefix = str(tmp_path / "ck")
    mod = _fit_module(_train_iter(), num_epoch=3, prefix=prefix)
    mgr = resilience.CheckpointManager(prefix)
    ck = mgr.latest()
    assert ck is not None and ck.epoch == 3
    assert ck.step == 15                     # 5 batches x 3 epochs
    # truncate the newest params file: scan must fall back to epoch 2
    with open(ck.params_path, "r+b") as f:
        f.truncate(64)
    ck2 = mgr.latest()
    assert ck2 is not None and ck2.epoch == 2
    # manifest gone entirely: epoch ignored even with intact params
    os.remove(mgr._manifest_path(2))
    ck3 = mgr.latest()
    assert ck3 is not None and ck3.epoch == 1
    del mod


def test_checkpoint_retention(tmp_path):
    prefix = str(tmp_path / "keep")
    mgr = resilience.CheckpointManager(prefix, keep=2)
    mod = _fit_module(_train_iter(), num_epoch=1, prefix=None)
    for epoch in (1, 2, 3, 4):
        mgr.save(mod, epoch)
    names = sorted(os.listdir(tmp_path))
    assert not any("-0001." in n or "-0002." in n for n in names), names
    assert any("-0003.params" in n for n in names)
    assert any("-0004.params" in n for n in names)


def test_soft_crash_between_write_and_rename(tmp_path):
    """crash@ckpt_write:soft raises InjectedCrash after the tmp write:
    the params file is NOT committed, the tmp leaks, and the resume scan
    sweeps it while settling on the previous intact checkpoint."""
    prefix = str(tmp_path / "soft")
    mod = _fit_module(_train_iter(), num_epoch=1, prefix=prefix)
    mgr = resilience.CheckpointManager(prefix)
    faults.configure("crash@ckpt_write:save=2:soft")
    with pytest.raises(faults.InjectedCrash):
        mgr.save(mod, 2)
    faults.clear()
    assert not os.path.exists(prefix + "-0002.params")
    assert os.path.exists(prefix + "-0002.params.tmp")
    ck = mgr.latest()
    assert ck is not None and ck.epoch == 1
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


def test_load_checkpoint_names_corrupt_file(tmp_path):
    prefix = str(tmp_path / "bad")
    mod = _fit_module(_train_iter(), num_epoch=1, prefix=prefix)
    del mod
    path = prefix + "-0001.params"
    with open(path, "r+b") as f:
        f.truncate(40)
    with pytest.raises(MXNetError) as err:
        mx.model.load_checkpoint(prefix, 1)
    assert path in str(err.value)
    # garbage magic is also named
    with open(path, "wb") as f:
        f.write(b"\x00" * 64)
    with pytest.raises(MXNetError) as err:
        mx.model.load_checkpoint(prefix, 1)
    assert path in str(err.value)


# ======================================================================
# resume
def test_fit_resume_matches_uninterrupted(tmp_path):
    train = _train_iter()
    modA = _fit_module(train, num_epoch=4, prefix=str(tmp_path / "A"))
    argA, _ = modA.get_params()

    prefix = str(tmp_path / "B")
    _fit_module(_train_iter(), num_epoch=2, prefix=prefix)
    modB = _fit_module(_train_iter(), num_epoch=4, prefix=prefix,
                       resume=True)
    argB, _ = modB.get_params()
    for n in argA:
        assert np.array_equal(argA[n].asnumpy(), argB[n].asnumpy()), n


def test_fit_resume_without_checkpoints_starts_fresh(tmp_path):
    mod = _fit_module(_train_iter(), num_epoch=1,
                      prefix=str(tmp_path / "fresh"), resume=True)
    assert mod.binded and mod.params_initialized


# ----------------------------------------------------------------------
# the kill-and-resume e2e: train in a SUBPROCESS with crash@ckpt_write
# armed; the injected os._exit(137) between tmp-write and rename is the
# SIGKILL-faithful mid-save death.  Resume and assert parity with the
# uninterrupted run.
_E2E_SCRIPT = r"""
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import io

prefix, num_epoch, resume = sys.argv[1], int(sys.argv[2]), sys.argv[3] == "1"
mx.random.seed(0)
rng = np.random.RandomState(0)
x = rng.randn(160, 32).astype("f")
y = rng.randint(0, 4, 160).astype("f")
train = io.NDArrayIter(x, y, batch_size=32, shuffle=False)
data = mx.sym.Variable("data")
fc1 = mx.symbol.FullyConnected(data, name="fc1", num_hidden=16)
act = mx.symbol.Activation(fc1, name="relu1", act_type="relu")
fc2 = mx.symbol.FullyConnected(act, name="fc2", num_hidden=4)
net = mx.symbol.SoftmaxOutput(fc2, name="softmax")
mod = mx.mod.Module(net, context=mx.cpu())
mod.fit(train, num_epoch=num_epoch,
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                          "rescale_grad": 1.0 / 32},
        initializer=mx.init.Xavier(), checkpoint=prefix, resume=resume)
arg, _ = mod.get_params()
np.savez(prefix + "-final.npz", **{k: v.asnumpy() for k, v in arg.items()})
print("COMPLETED")
"""


def _run_e2e(tmp_path, prefix, num_epoch, resume, fault=None):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_MODULE_FUSED"] = "always"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("MXTPU_FAULTS", None)
    if fault:
        env["MXTPU_FAULTS"] = fault
    script = tmp_path / "e2e_train.py"
    script.write_text(_E2E_SCRIPT)
    return subprocess.run(
        [sys.executable, str(script), prefix, str(num_epoch),
         "1" if resume else "0"],
        env=env, cwd=repo, capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("crashed_save", [3])
def test_kill_and_resume_e2e(tmp_path, crashed_save):
    # uninterrupted reference run
    res = _run_e2e(tmp_path, str(tmp_path / "ref"), 4, resume=False)
    assert res.returncode == 0, res.stderr
    ref = np.load(str(tmp_path / "ref") + "-final.npz")

    # killed run: dies inside the save at the end of epoch `crashed_save`
    prefix = str(tmp_path / "killed")
    res = _run_e2e(tmp_path, prefix, 4, resume=False,
                   fault="crash@ckpt_write:save=%d" % crashed_save)
    assert res.returncode == 137, (res.returncode, res.stderr)
    assert "COMPLETED" not in res.stdout
    leftovers = [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]
    assert leftovers, "mid-save kill should leak the tmp file"

    # resume: continues from the newest INTACT checkpoint and finishes
    res = _run_e2e(tmp_path, prefix, 4, resume=True)
    assert res.returncode == 0, res.stderr
    got = np.load(prefix + "-final.npz")
    for n in ref.files:
        assert np.array_equal(ref[n], got[n]), n
    # the torn save's leftovers were swept by the resume scan
    assert not [n for n in os.listdir(tmp_path)
                if n.endswith(".tmp") and "killed" in n]


# ======================================================================
# prefetcher producer-exception propagation
class _BoomIter(io.DataIter):
    def __init__(self, blow_at=3):
        super().__init__(8)
        self.n = 0
        self.blow_at = blow_at
        self.provide_data = [io.DataDesc("data", (8, 4))]
        self.provide_label = [io.DataDesc("softmax_label", (8,))]

    def next(self):
        self.n += 1
        if self.n == self.blow_at:
            raise ValueError("producer blew up on batch %d" % self.n)
        if self.n > 6:
            raise StopIteration
        return io.DataBatch([mx.nd.array(np.zeros((8, 4), "f"))],
                            [mx.nd.array(np.zeros(8, "f"))], pad=0)

    def reset(self):
        self.n = 0


def test_prefetching_iter_propagates_producer_error():
    pf = io.PrefetchingIter(_BoomIter(blow_at=3))
    good = 0
    with pytest.raises(ValueError, match="producer blew up") as err:
        while True:
            pf.next()
            good += 1
    assert good == 2
    # the original producer traceback is on the exception
    import traceback
    tb = "".join(traceback.format_tb(err.value.__traceback__))
    assert "next" in tb
    # reset clears the error latch and the stream recovers
    pf.reset()
    assert pf.next() is not None


class _TransientSource(io.DataIter):
    """Fails ONE production (before consuming the batch), then streams
    clean — the transient-NFS shape the fit retry loop exists for."""

    def __init__(self, total=6, fail_before=3):
        super().__init__(8)
        self.n = 0
        self.total = total
        self.fail_before = fail_before
        self.errored = False
        self.provide_data = [io.DataDesc("data", (8, 4))]
        self.provide_label = [io.DataDesc("softmax_label", (8,))]

    def next(self):
        if self.n + 1 == self.fail_before and not self.errored:
            self.errored = True
            raise OSError("transient read failure")
        if self.n >= self.total:
            raise StopIteration
        self.n += 1
        return io.DataBatch([mx.nd.array(np.full((8, 4), self.n, "f"))],
                            [mx.nd.array(np.zeros(8, "f"))], pad=0)

    def reset(self):
        self.n = 0
        self.errored = False


def test_prefetching_iter_rearms_after_transient_error():
    """The raise re-arms the errored slot: a consumer that treats the
    error as transient (fit's retry_io) continues the stream and sees
    EVERY batch — not a silently truncated epoch."""
    pf = io.PrefetchingIter(_TransientSource(total=6, fail_before=3))
    seen = []
    while True:
        try:
            b = resilience.retry_io(pf.next, attempts=3, delay=0.001)
        except StopIteration:
            break
        seen.append(int(b.data[0].asnumpy()[0, 0]))
    assert seen == [1, 2, 3, 4, 5, 6]


def test_latest_rejects_torn_symbol_json(tmp_path):
    """prefix-symbol.json is shared by every epoch, so it is in every
    manifest: tearing it invalidates ALL checkpoints under the prefix
    (nothing could load anyway) instead of verifying and then dying
    inside sym.load."""
    prefix = str(tmp_path / "sym")
    _fit_module(_train_iter(), num_epoch=2, prefix=prefix)
    mgr = resilience.CheckpointManager(prefix)
    assert mgr.latest().epoch == 2
    with open(prefix + "-symbol.json", "r+") as f:
        f.truncate(10)
    assert mgr.latest() is None


def test_device_upload_iter_surfaces_worker_error():
    up = io.DeviceUploadIter(_BoomIter(blow_at=2))
    assert up.next() is not None
    with pytest.raises(ValueError, match="producer blew up"):
        while True:
            up.next()


# ======================================================================
# latest_verified() verification cache (the rollout watcher polls every
# few seconds; a poll between publishes must not re-hash checkpoint
# bytes — and a byte-patched artifact must STILL be refused after a hit)
def test_latest_verified_memoizes_on_disk_identity(tmp_path, monkeypatch):
    prefix = str(tmp_path / "vc")
    mod = _fit_module(_train_iter(), num_epoch=2, prefix=prefix)
    mgr = resilience.CheckpointManager(prefix)
    ck = mgr.latest_verified()
    assert ck is not None and ck.epoch == 2

    calls = []
    real = resilience._crc32_file

    def counting_crc(path, *a, **kw):
        calls.append(path)
        return real(path, *a, **kw)

    monkeypatch.setattr(resilience, "_crc32_file", counting_crc)
    ck2 = mgr.latest_verified()
    assert ck2 is not None and ck2.epoch == 2
    assert calls == []            # verdict reused: zero bytes re-hashed
    del mod


def test_latest_verified_refuses_bytepatch_after_cache_hit(tmp_path):
    prefix = str(tmp_path / "bp")
    mod = _fit_module(_train_iter(), num_epoch=2, prefix=prefix)
    mgr = resilience.CheckpointManager(prefix)
    ck = mgr.latest_verified()
    assert ck is not None and ck.epoch == 2
    assert mgr.latest_verified().epoch == 2          # warm the cache
    # same-size byte patch: the on-disk identity (mtime_ns) changes, so
    # the cached PASS is dropped and the full verification re-runs
    with open(ck.params_path, "r+b") as f:
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    ck2 = mgr.latest_verified()
    assert ck2 is not None and ck2.epoch == 1        # patched epoch refused
    # the refusal itself is memoized: a repeat poll is stat()-only
    import mxnet_tpu.resilience as _r
    counted = []
    real = _r._crc32_file
    try:
        _r._crc32_file = lambda p, *a, **kw: (counted.append(p),
                                              real(p, *a, **kw))[1]
        assert mgr.latest_verified().epoch == 1
        assert counted == []
    finally:
        _r._crc32_file = real
    del mod
