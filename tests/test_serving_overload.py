"""Overload protection / graceful degradation for the serving layer
(``mxnet_tpu/serving/``): bounded-queue admission control (reject vs
block backpressure), deadline-aware shedding before AND after dispatch,
request cancellation, the per-model circuit breaker, scheduler
supervision (crash fails-all, never hangs), ``stop(drain_s)``, and
round-robin fairness across tenants — docs/how_to/serving.md
"Overload & degradation"."""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving.server import (ServeCancelled, ServeError,
                                      ServeOverload, ServeTimeout,
                                      ServeUnavailable)


@pytest.fixture(autouse=True)
def _fresh_cache():
    """The compiled-forward cache is process-wide and keyed on the
    symbol digest; fresh per test so retrace/latency accounting (and
    the EWMA this suite seeds by hand) never leaks across tests."""
    serving.clear_cache()
    yield
    serving.clear_cache()


def _mlp(din=8, hidden=16, nclass=4, seed=0):
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=hidden, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=nclass, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    rng = np.random.RandomState(seed)
    args = {"fc1_weight": mx.nd.array(rng.randn(hidden, din).astype("f")),
            "fc1_bias": mx.nd.array(rng.randn(hidden).astype("f")),
            "fc2_weight": mx.nd.array(rng.randn(nclass, hidden).astype("f")),
            "fc2_bias": mx.nd.array(rng.randn(nclass).astype("f"))}
    return sym, args, (din,)


def _server(sym, args, example, name="m", **kw):
    kw.setdefault("buckets", [1, 2, 4, 8])
    kw.setdefault("max_wait_us", 1000)
    srv = serving.ModelServer(**kw)
    srv.add_model(name, sym, args, {}, input_shapes={"data": example})
    return srv


def _x(example, n=1, seed=0):
    return np.random.RandomState(seed).randn(n, *example).astype("f")


# ----------------------------------------------------------------------
# admission control
def test_queue_cap_reject_fails_fast():
    """Past queue_cap rows, reject policy sheds at submit() — in
    microseconds, with ServeOverload, leaving the queued work alone."""
    sym, args, example = _mlp()
    # a coalescing window far in the future: nothing dispatches, so the
    # queue provably fills
    with _server(sym, args, example, max_wait_us=10_000_000, cap=64,
                 queue_cap=4, shed_policy="reject") as srv:
        futs = [srv.submit(data=_x(example, seed=i)) for i in range(4)]
        t0 = time.perf_counter()
        with pytest.raises(ServeOverload, match="4/4 rows"):
            srv.submit(data=_x(example))
        assert time.perf_counter() - t0 < 0.05     # fail FAST
        st = srv.stats()
        assert st["rejected_overload"] == 1
        assert st["per_model"]["m"]["queue_depth_rows"] == 4
        assert st["requests"] == 4                 # sheds never admitted
        # a multi-row request is judged by its row count, not 1
        with pytest.raises(ServeOverload):
            srv.submit(data=_x(example, n=3))
        for f in futs:
            assert not f.done()                    # queued work untouched


def test_queue_cap_block_backpressure_then_serves():
    """block policy: submit() waits for queue space instead of
    shedding — the caller is the buffer — and proceeds once the
    scheduler drains."""
    sym, args, example = _mlp()
    with _server(sym, args, example, max_wait_us=150_000, cap=64,
                 queue_cap=2, shed_policy="block",
                 timeout_ms=10_000) as srv:
        t0 = time.perf_counter()
        f1 = srv.submit(data=_x(example, seed=1))
        f2 = srv.submit(data=_x(example, seed=2))
        f3 = srv.submit(data=_x(example, seed=3))   # blocks ~150 ms
        blocked_s = time.perf_counter() - t0
        assert blocked_s >= 0.1     # it really waited out the window
        for f in (f1, f2, f3):
            assert len(f.result(20)) == 1
        st = srv.stats()
        assert st["requests"] == 3 and st["rejected_overload"] == 0


def test_queue_cap_block_sheds_at_deadline(monkeypatch):
    """block policy gives up at the request deadline: with the
    scheduler pinned inside a slow batch, the backpressure wait cannot
    be released and must end in ServeOverload, not a hang."""
    monkeypatch.setenv("MXTPU_SERVE_SLOW_S", "0.5")
    sym, args, example = _mlp()
    with _server(sym, args, example, max_wait_us=1000, cap=1,
                 queue_cap=1, shed_policy="block",
                 timeout_ms=100) as srv:
        with faults.injected("slow_request@request=1"):
            fa = srv.submit(data=_x(example, seed=1))  # dispatched, slow
            time.sleep(0.02)                # let the scheduler take it
            fb = srv.submit(data=_x(example, seed=2))  # queued: cap full
            t0 = time.perf_counter()
            with pytest.raises(ServeOverload, match="blocking"):
                srv.submit(data=_x(example, seed=3))
            waited = time.perf_counter() - t0
        assert 0.08 <= waited < 0.45        # deadline, not the slow batch
        assert srv.stats()["rejected_overload"] == 1
        # the slow batch outlived fa's own deadline: expired in flight
        assert isinstance(fa.exception(20), ServeTimeout)
        assert fb.exception(20) is not None  # fb outlived its deadline


def test_request_larger_than_queue_cap_rejected_up_front():
    """A request that can NEVER fit (rows > queue_cap) is rejected
    immediately under either policy — block must not wait for space
    that cannot exist (with timeout off it would wait forever)."""
    sym, args, example = _mlp()
    with _server(sym, args, example, queue_cap=2, shed_policy="block",
                 timeout_ms=0) as srv:
        t0 = time.perf_counter()
        with pytest.raises(ServeOverload, match="never be admitted"):
            srv.submit(data=_x(example, n=4))
        assert time.perf_counter() - t0 < 0.05


def test_fault_model_key_is_string_identity():
    """model= values are string identities even when they LOOK like
    integers — a tenant literally named '2' must be targetable without
    crashing every other tenant's match."""
    with faults.injected("batch_error@model=2"):
        assert not faults.hit("batch_error", model="m")
        assert faults.hit("batch_error", model="2")
    with pytest.raises(MXNetError, match="integers"):
        faults.configure("batch_error@count=soon")
    faults.clear()


# ----------------------------------------------------------------------
# deadline-aware scheduling
def test_deadline_shed_before_dispatch():
    """A queued request whose remaining deadline cannot cover the EWMA
    batch latency is shed at _take_batch time — no compute burned on a
    result that would arrive dead."""
    sym, args, example = _mlp()
    with _server(sym, args, example, timeout_ms=300) as srv:
        srv.predict(data=_x(example))              # a real baseline batch
        before = srv.stats()["batches"]
        # pretend batches take 5 s: every 300 ms deadline is hopeless
        srv._models["m"].cf.record_latency(1, 5.0)
        exc = srv.submit(data=_x(example)).exception(timeout=20)
        assert isinstance(exc, ServeTimeout) and "shed" in str(exc)
        st = srv.stats()
        assert st["shed_deadline"] == 1
        assert st["batches"] == before             # never dispatched
        assert st["per_model"]["m"]["ewma_batch_ms"] > 1000


def test_ewma_shed_probe_escape():
    """An anomalous batch that inflates the EWMA past every deadline
    must not LATCH the model into 100% shedding: every
    _SHED_PROBE_EVERY consecutive sheds one request dispatches as a
    latency probe, and its real latency decays the estimate."""
    from mxnet_tpu.serving.server import ModelServer
    k = ModelServer._SHED_PROBE_EVERY
    sym, args, example = _mlp()
    with _server(sym, args, example, timeout_ms=300) as srv:
        srv.predict(data=_x(example))             # healthy baseline batch
        srv._models["m"].cf.record_latency(1, 5.0)   # anomaly: 5 s EWMA
        outcomes = []
        for i in range(k + 1):
            try:
                srv.submit(data=_x(example, seed=i)).result(20)
                outcomes.append("ok")
            except ServeTimeout:
                outcomes.append("shed")
        assert outcomes == ["shed"] * k + ["ok"]  # the probe got through
        st = srv.stats()
        assert st["shed_deadline"] == k
        assert st["per_model"]["m"]["ewma_batch_ms"] < 5000   # decayed


def test_expired_after_dispatch_counted(monkeypatch):
    """A request that expires while its batch computes fails its future
    honestly (expired_after_dispatch) instead of delivering late."""
    monkeypatch.setenv("MXTPU_SERVE_SLOW_S", "0.15")
    sym, args, example = _mlp()
    with _server(sym, args, example, timeout_ms=50) as srv:
        with faults.injected("slow_request@request=1"):
            fut = srv.submit(data=_x(example))
            exc = fut.exception(timeout=20)
        assert isinstance(exc, ServeTimeout)
        assert "expired in flight" in str(exc)
        st = srv.stats()
        assert st["expired_after_dispatch"] == 1
        assert st["batches"] == 1                  # it DID dispatch
        assert st["completed"] == 0


def test_cancel_frees_queued_rows():
    """ServeFuture.cancel() removes a still-queued request and frees
    its rows from the model's pending budget; result(timeout) that
    times out gets the same reclamation for free."""
    sym, args, example = _mlp()
    with _server(sym, args, example, max_wait_us=10_000_000,
                 cap=64) as srv:
        f1 = srv.submit(data=_x(example, n=2, seed=1))
        f2 = srv.submit(data=_x(example, n=3, seed=2))
        assert srv.stats()["per_model"]["m"]["queue_depth_rows"] == 5
        assert f1.cancel() is True
        with pytest.raises(ServeCancelled):
            f1.result(timeout=20)
        assert f1.cancel() is False                # already done
        st = srv.stats()
        assert st["cancelled"] == 1
        assert st["per_model"]["m"]["queue_depth_rows"] == 3
        # the abandoned-wait path: a timed-out result() cancels too
        with pytest.raises(ServeTimeout):
            f2.result(timeout=0.05)
        st = srv.stats()
        assert st["cancelled"] == 2
        assert st["per_model"]["m"]["queue_depth_rows"] == 0
        assert isinstance(f2.exception(), ServeCancelled)


# ----------------------------------------------------------------------
# circuit breaker
def test_breaker_open_half_open_close():
    """K consecutive batch failures open the breaker (immediate
    ServeUnavailable), the cool-down admits one half-open probe, and a
    served probe closes it again."""
    sym, args, example = _mlp()
    with _server(sym, args, example, breaker_k=2,
                 breaker_cooldown_ms=150) as srv:
        with faults.injected("batch_error@model=m:count=2"):
            for i in range(2):
                exc = srv.submit(data=_x(example, seed=i)) \
                    .exception(timeout=20)
                assert isinstance(exc, ServeError)
                assert "injected batch_error" in str(exc)
        st = srv.stats()
        assert st["batch_failures"] == 2
        assert st["per_model"]["m"]["breaker_state"] == "open"
        t0 = time.perf_counter()
        with pytest.raises(ServeUnavailable, match="circuit breaker"):
            srv.submit(data=_x(example))
        assert time.perf_counter() - t0 < 0.05     # open = fail fast
        assert srv.stats()["rejected_breaker"] == 1
        time.sleep(0.2)                            # cool-down elapses
        out = srv.predict(data=_x(example, seed=9))  # half-open probe
        assert np.all(np.isfinite(out[0]))
        assert srv.stats()["per_model"]["m"]["breaker_state"] == "closed"
        srv.submit(data=_x(example)).result(20)    # back to normal


def test_breaker_reopens_on_failed_probe_and_flushes_queue():
    sym, args, example = _mlp()
    with _server(sym, args, example, breaker_k=1,
                 breaker_cooldown_ms=100) as srv:
        with faults.injected("batch_error@model=m:count=2"):
            exc = srv.submit(data=_x(example)).exception(timeout=20)
            assert isinstance(exc, ServeError)     # failure #1 -> open
            assert srv.stats()["per_model"]["m"]["breaker_state"] \
                == "open"
            time.sleep(0.15)
            # the admitted probe fails too -> straight back to open
            exc = srv.submit(data=_x(example)).exception(timeout=20)
            assert isinstance(exc, ServeError)
        st = srv.stats()
        assert st["per_model"]["m"]["breaker_state"] == "open"
        assert st["batch_failures"] == 2


def test_breaker_isolated_per_tenant():
    """One tenant's open breaker must not touch the other."""
    sym_a, args_a, ex_a = _mlp(seed=0)
    sym_b, args_b, ex_b = _mlp(din=5, hidden=12, nclass=3, seed=1)
    srv = serving.ModelServer(buckets=[1, 2, 4], max_wait_us=1000,
                              breaker_k=1, breaker_cooldown_ms=60_000)
    srv.add_model("a", sym_a, args_a, {}, input_shapes={"data": ex_a})
    srv.add_model("b", sym_b, args_b, {}, input_shapes={"data": ex_b})
    with srv:
        with faults.injected("batch_error@model=a"):
            exc = srv.submit(data=_x(ex_a), model="a") \
                .exception(timeout=20)
            assert isinstance(exc, ServeError)
        st = srv.stats()
        assert st["per_model"]["a"]["breaker_state"] == "open"
        assert st["per_model"]["b"]["breaker_state"] == "closed"
        with pytest.raises(ServeUnavailable):
            srv.submit(data=_x(ex_a), model="a")
        # tenant b serves straight through
        out = srv.submit(data=_x(ex_b, seed=3), model="b").result(20)
        assert out[0].shape == (1, 3)


# ----------------------------------------------------------------------
# scheduler supervision / drain
def test_scheduler_crash_fails_all_pending():
    """An uncaught scheduler exception fails EVERY pending future and
    flips the server to rejecting — zero futures left unresolved, no
    silent hang."""
    sym, args, example = _mlp()
    srv = _server(sym, args, example, max_wait_us=10_000_000, cap=64)
    with srv:
        f1 = srv.submit(data=_x(example, seed=1))
        f2 = srv.submit(data=_x(example, n=2, seed=2))
        with faults.injected("batch_error@sched"):
            # the notify from this submit wakes the loop into the
            # injected crash; worst case it is refused by the flag —
            # either way nothing hangs
            try:
                f3 = srv.submit(data=_x(example, seed=3))
            except ServeUnavailable:
                f3 = None
            for f in (f1, f2, f3):
                if f is None:
                    continue
                exc = f.exception(timeout=20)
                assert isinstance(exc, ServeUnavailable)
                assert "scheduler crashed" in str(exc)
        st = srv.stats()
        assert st["scheduler_crashed"] is True
        assert st["queue_depth"] == 0              # zero unresolved
        assert st["per_model"]["m"]["queue_depth_rows"] == 0
        with pytest.raises(ServeUnavailable, match="scheduler crashed"):
            srv.submit(data=_x(example))
    # stop() after a crash stays clean (no second drain, no hang)
    assert srv.stats()["scheduler_crashed"] is True
    # ...and a restart gets a FRESH scheduler, not the stale crash flag
    # (submits are admitted again; this server's 10 s coalescing window
    # means we assert admission, not completion)
    srv.start()
    try:
        fut = srv.submit(data=_x(example))
        assert srv.stats()["scheduler_crashed"] is False
        assert fut.cancel() is True
    finally:
        srv.stop()


def test_stop_drain_serves_queued_then_fails_tail(monkeypatch):
    """stop(drain_s): already-queued work is served (coalescing windows
    bypassed) up to the drain deadline; the un-drainable tail fails."""
    sym, args, example = _mlp()
    # positive half: a queued request with a wide-open window is served
    # by the drain instead of waiting out 10 s
    srv = _server(sym, args, example, max_wait_us=10_000_000, cap=64)
    srv.start()
    fut = srv.submit(data=_x(example))
    t0 = time.perf_counter()
    srv.stop(drain_s=5)
    assert time.perf_counter() - t0 < 2
    assert len(fut.result(0)) == 1                 # already completed
    with pytest.raises(MXNetError, match="not started"):
        srv.submit(data=_x(example))

    # negative half: scheduler pinned in a slow batch, drain window too
    # short — the queued tail fails with ServeError, never hangs
    monkeypatch.setenv("MXTPU_SERVE_SLOW_S", "0.4")
    serving.clear_cache()
    srv = _server(sym, args, example, max_wait_us=1000, cap=1)
    srv.start()
    with faults.injected("slow_request@request=1"):
        fa = srv.submit(data=_x(example, seed=1))  # dispatched, slow
        time.sleep(0.05)
        fb = srv.submit(data=_x(example, seed=2))  # queued behind it
        srv.stop(drain_s=0.05)
    assert len(fa.result(20)) == 1                 # in-flight delivered
    assert isinstance(fb.exception(20), ServeError)
    assert fb.done()


def test_round_robin_no_tenant_starvation(monkeypatch):
    """Under saturation from a hot tenant, dispatch rotates across
    models: the light tenant's work completes long before the hot
    tenant's backlog drains."""
    monkeypatch.setenv("MXTPU_SERVE_SLOW_S", "0.2")
    sym_a, args_a, example = _mlp(seed=0)
    _, args_b, _ = _mlp(seed=5)
    srv = serving.ModelServer(buckets=[1, 2], max_wait_us=0, cap=2,
                              queue_cap=0)
    srv.add_model("hot", sym_a, args_a, {},
                  input_shapes={"data": example})
    srv.add_model("light", sym_a, args_b, {},
                  input_shapes={"data": example})
    with srv:
        # the first hot batch is slow: the scheduler is pinned inside
        # it while BOTH backlogs build, so the drain that follows has
        # to interleave the two queues (rotation), not race submission
        with faults.injected("slow_request@request=1"):
            hot = [srv.submit(data=_x(example, seed=i), model="hot")
                   for i in range(30)]
            light = [srv.submit(data=_x(example, seed=i), model="light")
                     for i in range(4)]
            for f in hot + light:
                f.result(30)
        st = srv.stats()
        assert st["completed"] == 34 and st["failed"] == 0
        assert st["per_model"]["hot"]["batches"] >= 1
        assert st["per_model"]["light"]["batches"] >= 1
        # the light tenant finished while the hot backlog still ran
        assert max(f.t_done for f in light) \
            < max(f.t_done for f in hot)
        srv.assert_no_retrace()


# ----------------------------------------------------------------------
# observability
def test_stats_overload_fields():
    sym, args, example = _mlp()
    with _server(sym, args, example, max_wait_us=10_000_000,
                 cap=64) as srv:
        st = srv.stats()
        assert st["policy"]["shed_policy"] == "reject"
        assert st["policy"]["queue_cap"] == 4096   # the env default
        pm = st["per_model"]["m"]
        assert pm["queue_depth_rows"] == 0
        assert pm["oldest_wait_ms"] == 0.0
        assert pm["breaker_state"] == "closed"
        assert pm["ewma_batch_ms"] is None         # nothing ran yet
        assert pm["latency_ms_by_bucket"] == {}
        srv.submit(data=_x(example))
        time.sleep(0.05)
        pm = srv.stats()["per_model"]["m"]
        assert pm["queue_depth_rows"] == 1
        assert pm["oldest_wait_ms"] > 0


def test_overload_probe_quick_degrades_gracefully():
    """The bench's own invariant, at test scale: goodput at the
    highest offered load stays >= 0.9x the 1x goodput, sheds fail fast,
    zero retraces (the INFER_BENCH `overload` section contract)."""
    from tools.serve_bench import overload_probe
    out = overload_probe(quick=True, load_factors=(1.0, 4.0),
                         buckets=[1, 4, 8, 16])
    assert out["degradation_ok"], out
    assert out["retraces"] == 0
    for run in out["loads"]:
        assert run["reject_max_ms"] < 50           # shed = fail fast
        assert run["accepted"] == run["completed_in_deadline"] \
            + run["completed_late"] + run["failed"]
