"""Heartbeat liveness surface (reference get_num_dead_node,
``include/mxnet/kvstore.h:235-244``)."""
import time

import mxnet_tpu as mx
from mxnet_tpu import health


def test_heartbeat_detection(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_HEARTBEAT_DIR", str(tmp_path))
    h0 = health.Heartbeat(0, interval=0.05)
    h1 = health.Heartbeat(1, interval=0.05)
    assert h0.active and h1.active
    time.sleep(0.15)
    assert health.dead_nodes(2, timeout=1.0) == []
    h1.stop()                         # rank 1 "dies"
    # poll: the sequence-progress scan deliberately treats the FIRST
    # observation of a newly-advanced stamp as fresh, so a beat that
    # lands between the scan above and stop() buys rank 1 one more
    # scan period of apparent liveness — a single fixed sleep is
    # timing-fragile under load
    deadline = time.time() + 10.0
    while time.time() < deadline \
            and health.dead_nodes(2, timeout=0.3) != [1]:
        time.sleep(0.1)
    assert health.dead_nodes(2, timeout=0.3) == [1]
    # a never-started rank counts as dead too
    assert health.dead_nodes(3, timeout=0.3) == [1, 2]
    h0.stop()


def test_heartbeat_noop_without_dir(monkeypatch):
    monkeypatch.delenv("MXTPU_HEARTBEAT_DIR", raising=False)
    h = health.Heartbeat(0)
    assert not h.active
    assert health.dead_nodes(4, timeout=0.1) == []
    h.stop()


def test_kvstore_num_dead_node_local():
    kv = mx.kv.create("local")
    assert kv.num_dead_node() == 0


def test_dead_nodes_tolerates_torn_and_unreadable_stamps(tmp_path,
                                                         monkeypatch):
    """A stamp caught mid-write (garbage/empty content) or unreadable as
    a file still proves liveness through its mtime — the scanner must
    never declare a rank dead because IT hit a torn read."""
    import os
    monkeypatch.setenv("MXTPU_HEARTBEAT_DIR", str(tmp_path))
    # rank 0: partially-written garbage, fresh mtime
    (tmp_path / "hb-0").write_text("1723")  # truncated float is fine too
    (tmp_path / "hb-0").write_text("garbage\x00")
    # rank 1: empty file (open succeeds, parse fails)
    (tmp_path / "hb-1").write_text("")
    # rank 2: a directory where the stamp should be (open() fails,
    # getmtime works)
    os.makedirs(tmp_path / "hb-2")
    assert health.dead_nodes(3, timeout=30.0) == []
    # and a genuinely absent rank is still reported dead
    assert health.dead_nodes(4, timeout=30.0) == [3]


def test_heartbeat_stamp_fault_injection(tmp_path, monkeypatch):
    """An injected stamp-write failure must neither kill construction
    nor (transient) flip the rank dead: the beat thread keeps trying."""
    from mxnet_tpu import faults
    monkeypatch.setenv("MXTPU_HEARTBEAT_DIR", str(tmp_path))
    faults.configure("io_error@hb_stamp:beat=1:count=1")
    try:
        h = health.Heartbeat(7, interval=0.02)   # first beat injected
        assert h.active
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if health.dead_nodes(8, timeout=30.0) == list(range(7)):
                break
            time.sleep(0.02)
        # rank 7 recovered on a later beat despite the injected failure
        assert 7 not in health.dead_nodes(8, timeout=30.0)
        assert faults.fired("io_error") == 1
        h.stop()
    finally:
        faults.clear()


def test_seq_progress_overrides_skewed_ahead_clock(tmp_path, monkeypatch):
    """A rank whose wall clock runs far AHEAD cannot stamp itself alive
    into the future: once its sequence number has been observed and
    stops advancing, sequence-progress age (the scanner's own monotonic
    clock) rules the verdict even while the stamp's wall time — and a
    freshly rewritten mtime — still claim alive."""
    monkeypatch.setenv("MXTPU_HEARTBEAT_DIR", str(tmp_path))
    health._reset_seq_cache()
    stamp = "%f 5" % (time.time() + 1e6)       # far-future wall clock
    (tmp_path / "hb-0").write_text(stamp)
    # first observation: only wall evidence exists — alive
    assert health.dead_nodes(1, timeout=0.2) == []
    time.sleep(0.35)
    # same seq rewritten (fresh mtime, future wall): both wall signals
    # say alive, sequence progress says 0.35s of silence — dead
    (tmp_path / "hb-0").write_text(stamp)
    assert health.dead_nodes(1, timeout=0.2) == [0]


def test_seq_progress_saves_skewed_behind_clock(tmp_path, monkeypatch):
    """A rank whose wall clock runs far BEHIND (ancient stamp content
    and mtime) is NOT declared dead while its sequence number keeps
    advancing between scans."""
    import os
    monkeypatch.setenv("MXTPU_HEARTBEAT_DIR", str(tmp_path))
    health._reset_seq_cache()
    path = tmp_path / "hb-0"

    def stamp(seq):
        path.write_text("1.0 %d" % seq)        # wall clock stuck in 1970
        os.utime(path, (1.0, 1.0))             # mtime equally ancient
    stamp(5)
    # first observation: wall evidence only — (correctly) stale
    assert health.dead_nodes(1, timeout=30.0) == [0]
    stamp(6)
    # the sequence advanced between scans: progress is fresh evidence
    # on the scanner's clock, wall age notwithstanding
    assert health.dead_nodes(1, timeout=30.0) == []
    time.sleep(0.3)
    # and once it stops advancing, staleness returns on seq age
    assert health.dead_nodes(1, timeout=0.2) == [0]


def test_seq_advance_since_an_old_scan_is_not_fresh(tmp_path, monkeypatch):
    """A scanner that looked once at the start of a job and again after a
    collective failed: the peer beat a few more times in between and
    died.  Its sequence number differs from the one last seen, but the
    stamp is as old as its wall age says (bounded by the time since that
    first look), not fresh (``tests/nightly/dist_resume.py``: one scan
    with ``timeout=2`` after the crash has to count the dead rank)."""
    import os
    monkeypatch.setenv("MXTPU_HEARTBEAT_DIR", str(tmp_path))
    health._reset_seq_cache()
    path = tmp_path / "hb-0"

    def stamp(seq, age):
        wall = time.time() - age
        path.write_text("%f %d" % (wall, seq))
        os.utime(path, (wall, wall))
    stamp(5, 0.0)
    assert health.dead_nodes(1, timeout=2.0) == []
    time.sleep(0.4)
    stamp(8, 3.0)                       # the last beat, 3 s ago by its clock
    # no older than our own clock says since the first look: 0.4 s
    assert health.dead_nodes(1, timeout=2.0) == []
    assert health.dead_nodes(1, timeout=0.3) == [0]  # was: fresh, alive


def test_heartbeat_registered_for_atexit_stop(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_HEARTBEAT_DIR", str(tmp_path))
    h = health.Heartbeat(0, interval=0.05)
    assert h in health._live_beats
    assert h._thread.daemon                  # can never wedge exit
    health._stop_all_at_exit()
    assert not h.active


# ======================================================================
# role-prefixed stamps: a serving fleet and a co-resident training job
# share one coordination dir without cross-blaming (both directions)
def test_role_prefixed_stamps_both_directions(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_HEARTBEAT_DIR", str(tmp_path))
    health._reset_seq_cache()
    train = [health.Heartbeat(r, interval=0.05) for r in range(2)]
    serve = [health.Heartbeat(r, interval=0.05, role="serve")
             for r in range(3)]
    time.sleep(0.15)
    # distinct stamp files: hb-<rank> vs hb-serve-<rank>
    names = sorted(p.name for p in tmp_path.iterdir())
    assert "hb-0" in names and "hb-serve-0" in names
    # both populations read healthy through their own scans
    assert health.dead_nodes(2, timeout=1.0) == []
    assert health.dead_nodes(3, timeout=1.0, role="serve") == []
    # direction 1: serve replica 2 is alive, but it is NOT a training
    # rank — a training scan of world 3 must still blame rank 2
    # (absence of a TRAIN stamp), not count the serve stamp as alive
    assert health.dead_nodes(3, timeout=1.0) == [2]
    # direction 2: serve replica 1 dies; the serve scan blames it, the
    # training scan stays clean
    serve[1].stop()
    deadline = time.time() + 10.0
    while time.time() < deadline \
            and health.dead_nodes(3, timeout=0.3, role="serve") != [1]:
        time.sleep(0.1)
    assert health.dead_nodes(3, timeout=0.3, role="serve") == [1]
    assert health.dead_nodes(2, timeout=0.3) == []
    # and a training death never shows up in the serve scan
    train[0].stop()
    deadline = time.time() + 10.0
    while time.time() < deadline \
            and 0 not in health.dead_nodes(2, timeout=0.3):
        time.sleep(0.1)
    assert 0 in health.dead_nodes(2, timeout=0.3)
    assert health.dead_nodes(3, timeout=0.3, role="serve") == [1]
    for hb in train + serve:
        hb.stop()
