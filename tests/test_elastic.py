"""Elastic multi-host membership: dead-host detection, shrink, resume
(docs/how_to/multi_host.md "Elastic training").

Unit tier: membership-epoch transitions driven in-process with crafted
heartbeat state — publish-once-per-epoch, late-rejoiner revocation, the
collective-entry barrier, the hb_stall split brain, the host_dead fault
grammar.  E2E tier (``slow``: launcher-spawned subprocesses, runs as its
own hard-timeout CI stage): kill 1 of 2 workers mid-run, survivors
shrink n->n-1, relaunch auto-resumes from the newest manifest, and the
final params are bit-identical to a fresh 1-process run resumed from the
same checkpoint.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401 — conftest seeds/namespaces
from mxnet_tpu import elastic, faults, health
from mxnet_tpu.base import MXNetError

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    faults.clear()
    health._reset_seq_cache()
    monkeypatch.delenv("MXTPU_ELASTIC_DIR", raising=False)
    monkeypatch.delenv("MXTPU_HEARTBEAT_DIR", raising=False)
    yield
    faults.clear()


def _coord(tmp_path, rank, n=2, **kw):
    kw.setdefault("hb_timeout", 0.3)
    kw.setdefault("step_timeout", 0.6)
    kw.setdefault("check_interval", 0.0)
    kw.setdefault("join_grace", 0.0)
    kw.setdefault("barrier_attempts", 2)
    return elastic.ElasticCoordinator(rank=rank, num_workers=n,
                                      directory=str(tmp_path), **kw)


# ======================================================================
# membership epochs
def test_monitor_shrinks_once_per_epoch(tmp_path):
    """A lapsed rank is removed exactly once: the publish moves the
    epoch, and a later scan (same stale stamp) finds the rank no longer
    in the world — no double shrink on slow rejoin."""
    c0 = _coord(tmp_path, 0)          # rank 1 never stamps; join_grace=0
    with pytest.raises(elastic.ElasticShrink) as err:
        c0.guard(1)
    assert not isinstance(err.value, elastic.ElasticRevoked)
    mem = elastic.read_membership(str(tmp_path), 2)
    assert mem.epoch == 2 and mem.world == [0] and mem.dead == [1]
    assert mem.wallclock is not None
    c0.close()

    # a fresh coordinator joining at epoch 2 sees a settled world: the
    # still-missing rank 1 must NOT trigger epoch 3
    c0b = _coord(tmp_path, 0)
    c0b.guard(2)
    assert elastic.read_membership(str(tmp_path), 2).epoch == 2
    c0b.close()


def test_late_rejoiner_observes_revocation(tmp_path):
    """A rank the world shrank away rejoins late: it must observe the
    new epoch, raise ElasticRevoked, and write NOTHING — not the
    membership record, not the checkpoint line."""
    c0 = _coord(tmp_path, 0)
    with pytest.raises(elastic.ElasticShrink):
        c0.guard(1)
    c0.close()
    before = elastic.read_membership(str(tmp_path), 2).to_dict()

    c1 = _coord(tmp_path, 1)          # the shrunk-out rank comes back
    with pytest.raises(elastic.ElasticRevoked):
        c1.guard(1)
    assert elastic.read_membership(str(tmp_path), 2).to_dict() == before
    c1.close()


def test_survivor_adopts_peer_published_epoch(tmp_path):
    """A survivor that did not publish (not the lowest rank) still
    exits on the epoch it observes."""
    c1 = _coord(tmp_path, 1, n=3)
    # rank 0 published a shrink removing rank 2
    elastic._write_membership(str(tmp_path), elastic.Membership(
        2, [0, 1], 3, wallclock=time.time(), dead=[2]))
    with pytest.raises(elastic.ElasticShrink) as err:
        c1.guard(5)
    assert err.value.membership.epoch == 2
    assert err.value.membership.world == [0, 1]
    c1.close()


def test_join_grace_protects_slow_starters(tmp_path):
    """A rank that has NOT yet stamped is not dead inside the join
    grace (ranks compile at different speeds); one that HAS stamped and
    lapsed is dead regardless."""
    c0 = _coord(tmp_path, 0, join_grace=60.0, step_timeout=0.3,
                barrier_attempts=1)
    # rank 1 never stamped: barrier times out but no shrink — wedged
    # (MXNetError), never a false positive
    with pytest.raises(MXNetError, match="wedged"):
        c0.guard(1)
    # now rank 1 stamps once and goes stale: dead on hb_timeout alone
    h1 = health.Heartbeat(1, directory=str(tmp_path), interval=999)
    h1.stop()
    time.sleep(0.4)
    with pytest.raises(elastic.ElasticShrink):
        c0.guard(2)
    c0.close()


def test_nonpublisher_waits_for_published_epoch(tmp_path):
    """A survivor that is NOT the lowest rank must keep its heartbeat
    visible and adopt the epoch the publisher eventually writes — not
    exit on its own unpublished computation (a busy publisher would
    then find IT lapsed too and over-shrink the healthy world)."""
    h0 = health.Heartbeat(0, directory=str(tmp_path), interval=0.05)
    h2 = health.Heartbeat(2, directory=str(tmp_path), interval=999)
    h2.stop()
    time.sleep(0.4)                            # rank 2 lapses
    c1 = _coord(tmp_path, 1, n=3, step_timeout=5.0)
    published = elastic.Membership(2, [0, 1], 3, wallclock=time.time(),
                                   dead=[2])
    timer = threading.Timer(
        0.5, lambda: elastic._write_membership(str(tmp_path), published))
    timer.start()
    t0 = time.monotonic()
    with pytest.raises(elastic.ElasticShrink) as err:
        c1.guard(1)
    assert not isinstance(err.value, elastic.ElasticRevoked)
    assert err.value.membership.epoch == 2
    assert err.value.membership.world == [0, 1]
    assert 0.3 < time.monotonic() - t0 < 5.0   # waited for the publish
    timer.join(timeout=30)
    assert not timer.is_alive()
    h0.stop()
    c1.close()


def test_new_incarnation_adopts_stale_shared_dir(tmp_path):
    """A supervisor that relaunches the shrunk world into the SAME
    shared dir (no launcher wipe): the stale membership record (old
    world size, old rank ids) must not revoke renumbered ranks, and
    stale heartbeat stamps predating this incarnation must not bypass
    the join grace."""
    # leftovers of a 4-rank incarnation that shrank to 3 and exited
    # (mtimes aged too: these files really are a minute old)
    elastic._write_membership(str(tmp_path), elastic.Membership(
        2, [0, 2, 3], 4, wallclock=time.time() - 60, dead=[1]))
    old = time.time() - 60
    for rank in range(4):
        hb = tmp_path / ("hb-%d" % rank)
        hb.write_text("%f 9" % old)
        os.utime(hb, (old, old))
        (tmp_path / ("step-%d" % rank)).write_text("2 40\n")
    # the relaunched world: 3 workers, new contiguous ranks
    c1 = _coord(tmp_path, 1, n=3, join_grace=60.0, step_timeout=0.4,
                barrier_attempts=1)
    mem = c1.membership()
    assert mem.epoch == 3 and mem.world == [0, 1, 2]   # founding epoch
    # rank 0 persists the founding record on construction
    c0 = _coord(tmp_path, 0, n=3, join_grace=60.0, step_timeout=0.4,
                barrier_attempts=1)
    on_disk = elastic.read_membership(str(tmp_path), 3)
    assert on_disk.epoch == 3 and on_disk.num_workers == 3
    # rank 2 has not stamped THIS incarnation (only the stale file):
    # join grace protects it — the barrier wedges (their old epoch-2
    # step stamps cannot satisfy the epoch-3 barrier) instead of a
    # spurious shrink
    with pytest.raises(MXNetError, match="wedged"):
        c0.guard(1)
    c0.close()
    c1.close()


# ======================================================================
# collective-entry barrier
def test_barrier_synchronizes_live_ranks(tmp_path):
    """Two live coordinators guard the same steps concurrently: both
    pass — the barrier is a rendezvous, not a detector, when everyone
    is healthy."""
    c0 = _coord(tmp_path, 0, step_timeout=5.0, join_grace=60.0,
                hb_timeout=5.0)
    c1 = _coord(tmp_path, 1, step_timeout=5.0, join_grace=60.0,
                hb_timeout=5.0)
    errs = []

    def run(c):
        try:
            for step in (1, 2, 3):
                c.guard(step)
        except Exception as e:                  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=run, args=(c,)) for c in (c0, c1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs, errs
    c0.close()
    c1.close()


def test_barrier_detects_death_during_wait(tmp_path):
    """A peer that commits to steps and then dies is detected FROM
    INSIDE the barrier wait in ~hb_timeout, not step_timeout: the
    waiting survivor's throttled scan sees the lapsed stamp and raises
    before the bounded wait even expires."""
    h1 = health.Heartbeat(1, directory=str(tmp_path), interval=0.05)
    c0 = _coord(tmp_path, 0, hb_timeout=0.3, step_timeout=30.0,
                check_interval=0.05, join_grace=60.0)
    # rank 1 committed to an earlier step, then died
    path = os.path.join(str(tmp_path), "step-1")
    with open(path, "w") as f:
        f.write("0\n")
    h1.stop()
    t0 = time.monotonic()
    with pytest.raises(elastic.ElasticShrink):
        c0.guard(1)
    assert time.monotonic() - t0 < 10.0        # far below step_timeout
    c0.close()


# ======================================================================
# split brain: heartbeat stalls, process lives
def test_hb_stall_split_brain(tmp_path):
    """``hb_stall`` freezes rank 1's stamper without killing it: the
    monitor (correctly, per the liveness contract) declares it dead and
    shrinks; the stalled-but-alive rank observes its own revocation and
    exits cleanly."""
    faults.configure("hb_stall@beat=2:rank=1")
    h1 = health.Heartbeat(1, directory=str(tmp_path), interval=0.02)
    deadline = time.time() + 5.0
    while not h1.stalled and time.time() < deadline:
        time.sleep(0.02)
    assert h1.stalled and h1.active            # thread alive, stamps frozen
    time.sleep(0.4)

    c0 = _coord(tmp_path, 0)
    with pytest.raises(elastic.ElasticShrink) as err:
        c0.guard(1)
    assert err.value.dead == [1]
    c0.close()

    c1 = elastic.ElasticCoordinator(rank=1, num_workers=2,
                                    directory=str(tmp_path), heartbeat=h1,
                                    hb_timeout=0.3, check_interval=0.0)
    with pytest.raises(elastic.ElasticRevoked):
        c1.guard(1)
    h1.stop()


# ======================================================================
# fault grammar
def test_host_dead_rank_matches_exactly():
    """``rank=R`` is an identity, not a threshold: killing rank 1 must
    not also kill rank 2."""
    faults.configure("host_dead@step=3:rank=1")
    assert not faults.hit("host_dead", step=3, rank=0)
    assert not faults.hit("host_dead", step=3, rank=2)
    assert not faults.hit("host_dead", step=2, rank=1)   # below threshold
    assert faults.hit("host_dead", step=3, rank=1)
    assert not faults.hit("host_dead", step=4, rank=1)   # spent
    assert faults.fired("host_dead") == 1


# ======================================================================
# dist-store optimizer states (kvstore satellite)
def test_dist_kvstore_optimizer_state_roundtrip(tmp_path):
    """The dist store no longer refuses save/load_optimizer_states: a
    single-process dist store (rank 0 / size 1 — the local-launcher
    degradation) writes atomically and restores."""
    kv = mx.kv.create("dist_sync_tpu")
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    w = mx.nd.array(np.ones((4, 4), "f"))
    g = mx.nd.array(np.full((4, 4), 0.5, "f"))
    kv.init(3, w)
    kv.push(3, g)                               # momentum state appears
    path = str(tmp_path / "dist.states")
    kv.save_optimizer_states(path)
    assert os.path.exists(path)
    kv2 = mx.kv.create("dist_sync_tpu")
    kv2.set_optimizer(mx.optimizer.SGD(learning_rate=0.1, momentum=0.9))
    kv2.load_optimizer_states(path)
    saved, restored = kv._updater.states, kv2._updater.states
    assert sorted(saved) == sorted(restored)
    for k in saved:
        if saved[k] is None:
            assert restored[k] is None
        else:
            np.testing.assert_array_equal(saved[k].asnumpy(),
                                          restored[k].asnumpy())


def test_kvstore_without_optimizer_still_refuses(tmp_path):
    kv = mx.kv.create("dist_sync_tpu")
    with pytest.raises(MXNetError, match="set_optimizer"):
        kv.save_optimizer_states(str(tmp_path / "x.states"))
    with pytest.raises(MXNetError, match="set_optimizer"):
        kv.load_optimizer_states(str(tmp_path / "x.states"))


# ======================================================================
# the launcher-driven e2e: n=2 -> host_dead -> shrink to n=1 ->
# auto-resume -> bit-identical to a fresh 1-process replay from the
# same checkpoint.  19 s alone, so not slow by the marker's measure; it
# keeps the mark it had because its last assertion fails on this
# installation (fc1_weight differs in the last bit between the relaunched
# survivor and the replay), at the commit before PR 27 as after it, and
# tier-1 should not gain a known failure by a change of markers.
# ci/run_tests.sh runs the whole file, unfiltered, in a stage of its own.
@pytest.mark.slow
def test_elastic_shrink_resume_e2e(tmp_path):
    workdir = str(tmp_path / "work")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_FAULTS"] = "host_dead@step=11:rank=1"
    env.pop("MXTPU_COORDINATOR", None)
    env.pop("MXTPU_ELASTIC_DIR", None)
    env.pop("MXTPU_HEARTBEAT_DIR", None)
    res = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "tools", "launch.py"),
         "--local-elastic", "2", "--",
         sys.executable,
         os.path.join(_ROOT, "tests", "nightly", "elastic_train.py"),
         workdir],
        capture_output=True, text=True, timeout=240, env=env, cwd=_ROOT)
    out = res.stdout + res.stderr
    assert res.returncode == 0, out
    # round 1: the shrink was detected and published
    assert "published membership epoch 2" in out or \
        "membership epoch 2" in out, out
    assert "shrinking 2 -> 1" in out, out
    # round 2: the survivor auto-resumed from the manifest line
    assert "auto-resume from checkpoint epoch" in out, out
    assert "elastic train done" in out, out
    assert "ELASTIC_RECOVERY_S=" in out, out

    with open(os.path.join(workdir, "resume-info.json")) as f:
        info = json.load(f)
    assert info["world"] == 1
    resumed_epoch = info["resumed_epoch"]
    assert resumed_epoch >= 1

    # parity reference: fresh 1-process run resumed from the SAME
    # checkpoint epoch must match the elastic run's final params
    # bit-for-bit
    env.pop("MXTPU_FAULTS")
    res = subprocess.run(
        [sys.executable,
         os.path.join(_ROOT, "tests", "nightly", "elastic_train.py"),
         workdir, "--replay", str(resumed_epoch)],
        capture_output=True, text=True, timeout=240, env=env, cwd=_ROOT)
    assert res.returncode == 0, res.stdout + res.stderr
    got = np.load(os.path.join(workdir, "final.npz"))
    ref = np.load(os.path.join(workdir, "replay-final.npz"))
    assert sorted(got.files) == sorted(ref.files)
    for n in ref.files:
        assert np.array_equal(ref[n], got[n]), n
