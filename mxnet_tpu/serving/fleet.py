"""Fleet serving: a stats-routed router over N ModelServer replicas
(ROADMAP item 4 — "millions of users" means replicas, not one server).

:class:`FleetRouter` composes the pieces the repo already has into a
replicated serving tier:

* **routing** — every ``submit`` is placed by a cheap per-replica load
  score (queue depth in rows x the batch-latency EWMA, breaker-state
  penalized) read off :meth:`ModelServer.load_report` — the lock-free
  polling surface built for exactly this call pattern.  Policies
  (``MXTPU_ROUTER_POLICY``): ``p2c`` (power-of-two-choices, default —
  two random replicas polled, the less loaded wins; near-optimal load
  spread at O(2) polls per submit), ``least`` (poll everyone), ``rr``
  (round-robin, load-blind — the baseline the fleet bench beats).
* **failover** — a submit refused by one replica (breaker open,
  draining, crashed, queue full) is retried on the next-best replica,
  up to ``MXTPU_ROUTER_RETRIES`` failovers, inside the same request
  deadline (the deadline budget starts at each server's admission, and
  a refused submit returns in microseconds).
* **elastic membership** — a replica whose scheduler crashed (or whose
  ``role="serve"`` heartbeat lapsed, when a coordination directory is
  configured) is an elastic SHRINK: the fleet epoch bumps, its
  in-flight futures were already failed fast by the server's own crash
  sweep, traffic re-spreads on the next submit, and — with autoheal on
  — a replacement replica is spun up warm from the persisted program
  cache (``spinup`` compile counts land in :meth:`stats`; against a
  warm ``MXTPU_PROGRAM_CACHE`` the fleet bench asserts compiles == 0).
  Membership epochs are published to ``membership-serve.json`` via the
  same atomic-rename record the training world uses (role-prefixed, so
  a co-resident training job never sees serve epochs and vice versa).
* **zero-downtime rollout** — :meth:`roll_weights` deploys a new set of
  weights one replica at a time: take the replica out of rotation,
  build its successor (warm-start — same symbol, program cache),
  canary-gate the successor (output agreement + latency against the
  old weights), swap it in, then drain the old server
  (``stop(drain_s=)``) so every queued request completes.  A failed
  canary rolls the whole fleet back to the old weights.  No request is
  dropped at any point: the router never routes to an out-of-rotation
  replica, and a submit that races a swap is refused synchronously and
  failed over.  :meth:`watch_checkpoints` runs this continuously off
  ``CheckpointManager.latest_verified()`` — training publishes a
  checkpoint, the fleet converges on it, and the two-tier verification
  (CRC + value fingerprint, memoized per on-disk identity) is
  re-checked before each replica re-admits traffic.

Bench: ``tools/serve_bench.py fleet_probe`` (INFER_BENCH.json
``fleet`` section, a CPU run).  Docs:
``docs/how_to/serving.md`` "Fleet serving".
"""
from __future__ import annotations

import os
import random
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from .. import _tsan
from .. import elastic as _elastic
from .. import envknobs as _envknobs
from .. import health as _health
from .. import obs as _obs
from .. import program as _program
from ..base import MXNetError
from .server import (ModelServer, ServeOverload, ServeUnavailable)

__all__ = ["FleetRouter", "ReplicaSpec"]

_POLICIES = ("p2c", "least", "rr")


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        raise MXNetError("%s=%r is not a number"
                         % (name, os.environ[name])) from None


class ReplicaSpec:
    """Everything needed to (re)build one replica's server: the symbol,
    the current weights, the tenant's input declaration, and the
    ``ModelServer`` constructor knobs.  The router uses it for initial
    spin-up, autoheal replacements, and rollout successors — every
    replica of a fleet is a rebuild from this spec plus whatever
    weights are current."""

    def __init__(self, symbol, arg_params: Dict, aux_params: Dict,
                 input_shapes: Dict[str, Sequence[int]],
                 input_dtypes: Optional[Dict] = None,
                 model: str = "model",
                 server_kw: Optional[Dict] = None):
        self.symbol = symbol
        self.arg_params = arg_params
        self.aux_params = aux_params or {}
        self.input_shapes = {k: tuple(v) for k, v in input_shapes.items()}
        self.input_dtypes = input_dtypes
        self.model = model
        self.server_kw = dict(server_kw or {})

    def build(self, arg_params: Optional[Dict] = None,
              aux_params: Optional[Dict] = None,
              server_kw: Optional[Dict] = None) -> ModelServer:
        """A fresh (unstarted) server over ``arg_params``/``aux_params``
        (default: the spec's own weights)."""
        kw = dict(self.server_kw)
        kw.update(server_kw or {})
        srv = ModelServer(**kw)
        srv.add_model(self.model, self.symbol,
                      self.arg_params if arg_params is None else arg_params,
                      self.aux_params if aux_params is None else aux_params,
                      input_shapes=self.input_shapes,
                      input_dtypes=self.input_dtypes)
        return srv


class _Replica:
    __slots__ = ("idx", "server", "state", "version", "heartbeat",
                 "spinup")

    def __init__(self, idx: int, server: ModelServer, version,
                 heartbeat=None, spinup=None):
        self.idx = idx
        self.server = server
        self.state = "live"          # live | draining | dead | removed
        self.version = version
        self.heartbeat = heartbeat
        self.spinup = spinup or {}


class FleetRouter:
    """N replicas, one ``submit`` surface.  See the module docstring
    for the full contract; constructor args default from the
    ``MXTPU_ROUTER_*`` / ``MXTPU_FLEET_*`` knobs (envknobs.py)."""

    def __init__(self, spec: Optional[ReplicaSpec] = None,
                 n: Optional[int] = None,
                 policy: Optional[str] = None,
                 retries: Optional[int] = None,
                 directory: Optional[str] = None,
                 hb_timeout_s: Optional[float] = None,
                 check_interval_s: Optional[float] = None,
                 autoheal: Optional[bool] = None,
                 drain_s: Optional[float] = None,
                 canary_n: Optional[int] = None,
                 canary_min_agree: Optional[float] = None,
                 canary_latency_x: Optional[float] = None,
                 spawn: Optional[Callable] = None,
                 seed: Optional[int] = None):
        if spec is None and spawn is None:
            raise MXNetError("FleetRouter needs a ReplicaSpec or a "
                             "spawn(idx, arg_params, aux_params) hook")
        self.spec = spec
        self._spawn_fn = spawn
        self.n = int(n) if n is not None \
            else _envknobs.get_int("MXTPU_FLEET_REPLICAS", 3)
        if self.n < 1:
            raise MXNetError("a fleet needs at least one replica")
        self.policy = policy if policy is not None \
            else _envknobs.get_str("MXTPU_ROUTER_POLICY", "p2c")
        if self.policy not in _POLICIES:
            raise MXNetError("MXTPU_ROUTER_POLICY %r is not one of %s"
                             % (self.policy, "|".join(_POLICIES)))
        self.retries = int(retries) if retries is not None \
            else _envknobs.get_int("MXTPU_ROUTER_RETRIES", 2)
        self.directory = directory
        self.hb_timeout_s = float(hb_timeout_s) if hb_timeout_s is not None \
            else _env_f("MXTPU_FLEET_HB_TIMEOUT_S", 5.0)
        self.check_interval_s = float(check_interval_s) \
            if check_interval_s is not None \
            else _env_f("MXTPU_FLEET_CHECK_S", 0.2)
        self.autoheal = bool(autoheal) if autoheal is not None \
            else _envknobs.get_bool("MXTPU_FLEET_AUTOHEAL", True)
        self.drain_s = float(drain_s) if drain_s is not None \
            else _env_f("MXTPU_FLEET_DRAIN_S", 5.0)
        self.canary_n = int(canary_n) if canary_n is not None \
            else _envknobs.get_int("MXTPU_FLEET_CANARY_N", 8)
        self.canary_min_agree = float(canary_min_agree) \
            if canary_min_agree is not None \
            else _env_f("MXTPU_FLEET_MIN_AGREE", 0.9)
        self.canary_latency_x = float(canary_latency_x) \
            if canary_latency_x is not None \
            else _env_f("MXTPU_FLEET_CANARY_LAT_X", 50.0)
        self._rng = random.Random(seed)
        # _mu guards the replica table, the epoch, and the round-robin
        # cursor.  Server calls (submit, stop, _on_crash) happen OUTSIDE
        # it: the edge fleet._mu -> server._cond must never form, so the
        # two layers' locks cannot deadlock against each other.
        self._mu = _tsan.lock("serving.fleet.FleetRouter._mu")
        self._replicas: Dict[int, _Replica] = {}
        self._next_idx = 0
        self._epoch = 1
        self._rr = 0
        self._started = False
        self._weights = (spec.arg_params, spec.aux_params) \
            if spec is not None else (None, None)
        self._version = None
        self._roll_mu = _tsan.lock("serving.fleet.FleetRouter._roll_mu")
        self._monitor = None
        self._mon_stop = threading.Event()
        self._watcher = None
        self._watch_stop = threading.Event()
        self._obs_scope = _obs.REGISTRY.scope("serving.fleet")
        self._stats = _obs.CounterDict(self._obs_scope, {
            "routed": 0,         # submits placed on a replica
            "retries": 0,        # failed attempts that were retried
            "failovers": 0,      # submits that succeeded on a retry
            "unroutable": 0,     # submits no replica would take
            "shrinks": 0,        # replicas declared dead (epoch bumps)
            "spinups": 0,        # replicas added (heal or scale-up)
            "rollouts": 0,       # completed weight rollouts
            "rollout_swaps": 0,  # per-replica successful swaps
            "rollbacks": 0,      # canary-gate rollbacks
            "rollout_errors": 0})  # watcher poll/roll failures

    # ------------------------------------------------------------ spawn
    def _spawn(self, idx: int, arg_params, aux_params) -> ModelServer:
        if self._spawn_fn is not None:
            srv = self._spawn_fn(idx, arg_params, aux_params)
        else:
            srv = self.spec.build(arg_params, aux_params)
        if not srv._started:
            srv.start()
        return srv

    def _new_replica(self, arg_params, aux_params, version) -> _Replica:
        """Build + start one replica, spin-up compile accounting
        included (``spinup["compiles"] == 0`` against a warm program
        cache is the cheap-scale-up claim, asserted by the bench)."""
        idx = None
        with self._mu:
            idx = self._next_idx
            self._next_idx += 1
        with _program.stats_delta() as d:
            srv = self._spawn(idx, arg_params, aux_params)
        hb = None
        if self.directory:
            hb = _health.Heartbeat(idx, directory=self.directory,
                                   interval=min(1.0,
                                                self.hb_timeout_s / 4),
                                   role="serve")
        return _Replica(idx, srv, version, heartbeat=hb, spinup=dict(d))

    # ------------------------------------------------------- lifecycle
    def start(self) -> "FleetRouter":
        if self._started:
            return self
        arg, aux = self._weights
        for _ in range(self.n):
            rep = self._new_replica(arg, aux, self._version)
            with self._mu:
                self._replicas[rep.idx] = rep
        self._started = True
        self._publish_membership()
        self._mon_stop.clear()
        self._monitor = threading.Thread(target=self._monitor_loop,
                                         name="mxtpu-fleet-monitor",
                                         daemon=True)
        self._monitor.start()
        return self

    def stop(self, drain_s: Optional[float] = None) -> None:
        self.unwatch()
        self._mon_stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=10)
            self._monitor = None
        with self._mu:
            reps = list(self._replicas.values())
            self._started = False
        for rep in reps:
            if rep.heartbeat is not None:
                rep.heartbeat.stop()
            if rep.state in ("live", "draining"):
                rep.server.stop(drain_s=self.drain_s if drain_s is None
                                else drain_s)
                rep.state = "removed"

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -------------------------------------------------------- routing
    def _candidates(self) -> List[_Replica]:
        """Snapshot of routable replicas.  The (replica, server) pair
        is captured under ``_mu`` so a concurrent rollout swap cannot
        hand a submit half of one replica and half of its successor."""
        with self._mu:
            return [r for r in self._replicas.values()
                    if r.state == "live"]

    @staticmethod
    def _score(server: ModelServer, model: Optional[str]):
        """Load score: estimated queue cost = (queued rows + 1) x the
        per-row batch EWMA, with a breaker-open replica effectively
        last-resort and a half-open one deprioritized (its probe slot
        is one batch wide — piling traffic on it defeats the probe)."""
        lr = server.load_report()
        if not lr["available"]:
            return None
        pm = lr["per_model"].get(model) if model is not None else None
        if pm is None:
            if len(lr["per_model"]) != 1:
                return None
            pm = next(iter(lr["per_model"].values()))
        s = (pm["queue_depth_rows"] + 1.0) * (pm["ewma_batch_ms"] or 1.0)
        if pm["breaker_state"] == "open":
            s += 1e9
        elif pm["breaker_state"] == "half_open":
            s *= 8.0
        return s

    def _pick(self, model: Optional[str],
              exclude: Sequence[int]) -> Optional[_Replica]:
        cands = [r for r in self._candidates() if r.idx not in exclude]
        if not cands:
            return None
        if len(cands) == 1:
            return cands[0]
        if self.policy == "rr" and not exclude:
            with self._mu:
                self._rr += 1
                k = self._rr
            return cands[k % len(cands)]
        if self.policy == "p2c" and not exclude:
            a, b = self._rng.sample(cands, 2)
            sa, sb = self._score(a.server, model), self._score(b.server,
                                                               model)
            if sa is None and sb is None:
                return a
            if sa is None:
                return b
            if sb is None:
                return a
            return a if sa <= sb else b
        # least-loaded full scan — also the retry path for every
        # policy: "next-best" means best of the untried, whatever
        # placed the first attempt
        scored = [(self._score(r.server, model), r) for r in cands]
        scored = [(s, r) for s, r in scored if s is not None]
        if not scored:
            return cands[0]
        return min(scored, key=lambda t: t[0])[1]

    def submit(self, inputs: Optional[Dict] = None,
               model: Optional[str] = None, **kw):
        """Route one request; returns the placing replica's
        ``ServeFuture``.  A refusal (breaker open, draining, crashed,
        queue full, stopped mid-swap) fails over to the next-best
        replica, up to ``retries`` times — refusals are synchronous and
        return in microseconds, so failover spends effectively none of
        the request's deadline budget (which starts at the admitting
        server, not here)."""
        tried: List[int] = []
        last = None
        for _ in range(self.retries + 1):
            rep = self._pick(model, exclude=tried)
            if rep is None:
                break
            try:
                fut = rep.server.submit(inputs, model=model, **kw)
                with self._mu:
                    self._stats["routed"] += 1
                    if tried:
                        self._stats["failovers"] += 1
                return fut
            except (ServeUnavailable, ServeOverload) as e:
                last = e
            except MXNetError as e:
                # "server not started" is a replica mid-swap/stop — a
                # routing race, retryable; anything else (bad input,
                # unknown model) is the CALLER's error and must not
                # burn retries masquerading as load
                if "server not started" not in str(e):
                    raise
                last = e
            tried.append(rep.idx)
            with self._mu:
                self._stats["retries"] += 1
            lr = rep.server.load_report()
            if lr["crashed"]:
                self._note_dead(rep, "scheduler crashed (seen at submit)")
        with self._mu:
            self._stats["unroutable"] += 1
        if last is None:
            raise ServeUnavailable(
                "no live replica available (fleet epoch %d)"
                % self.epoch)
        raise last

    def predict(self, inputs: Optional[Dict] = None,
                model: Optional[str] = None, **kw) -> List[np.ndarray]:
        return self.submit(inputs, model=model, **kw).result()

    # ------------------------------------------------ membership/heal
    @property
    def epoch(self) -> int:
        with self._mu:
            return self._epoch

    def live_replicas(self) -> List[int]:
        return sorted(r.idx for r in self._candidates())

    def _publish_membership(self) -> None:
        """Serve-role membership record (atomic rename, role-suffixed
        file): co-resident training jobs and external orchestrators can
        watch fleet epochs without the router exposing an RPC."""
        if not self.directory:
            return
        with self._mu:
            mem = _elastic.Membership(
                self._epoch,
                [r.idx for r in self._replicas.values()
                 if r.state in ("live", "draining")],
                self._next_idx, wallclock=time.time())
        try:
            _elastic._write_membership(self.directory, mem, role="serve")
        except OSError:
            pass                    # membership is advisory on this tier

    def _note_dead(self, rep: _Replica, reason: str) -> None:
        """Elastic shrink: epoch bump, fast-fail whatever the dead
        replica still held, re-spread traffic (the next ``_pick`` simply
        no longer sees it).  Idempotent — the monitor, the submit path
        and a drill can all notice the same death."""
        with self._mu:
            if rep.state in ("dead", "removed"):
                return
            rep.state = "dead"
            self._epoch += 1
            self._stats["shrinks"] += 1
        if rep.heartbeat is not None:
            rep.heartbeat.stop()
        if rep.server._crashed is None:
            # declared dead without a crash (heartbeat lapse): fail its
            # in-flight futures fast — callers retry elsewhere NOW
            # rather than discovering the lapse at their deadline
            rep.server._on_crash(ServeUnavailable(
                "replica %d declared dead: %s" % (rep.idx, reason)))
        # reap the scheduler thread — _on_crash only flips the server to
        # rejecting; the loop itself exits on the stop flag
        rep.server.stop(drain_s=0)
        self._publish_membership()

    def kill_replica(self, idx: int) -> None:
        """Drill: crash replica ``idx``'s scheduler (in-flight futures
        failed fast) and process the death immediately — the
        kill-one-mid-window move of the fleet bench and the failover
        tests."""
        with self._mu:
            rep = self._replicas.get(idx)
        if rep is None:
            raise MXNetError("no replica %d" % idx)
        rep.server._on_crash(ServeUnavailable(
            "replica %d killed (drill)" % idx))
        self._note_dead(rep, "killed (drill)")

    def add_replica(self) -> int:
        """Elastic scale-up (also the autoheal step): one more replica
        on the CURRENT weights, warm-started from the persisted program
        cache.  Grow is an epoch bump too — membership changed."""
        arg, aux = self._weights
        rep = self._new_replica(arg, aux, self._version)
        with self._mu:
            self._replicas[rep.idx] = rep
            self._epoch += 1
            self._stats["spinups"] += 1
        self._publish_membership()
        return rep.idx

    def _monitor_loop(self) -> None:
        while not self._mon_stop.wait(self.check_interval_s):
            try:
                self._monitor_once()
            except Exception:       # noqa: BLE001 — the monitor must
                pass                # outlive any one scan hiccup

    def _monitor_once(self) -> None:
        with self._mu:
            reps = list(self._replicas.values())
        lapsed = set()
        if self.directory:
            lapsed = set(_health.dead_nodes(
                self._next_idx, timeout=self.hb_timeout_s,
                directory=self.directory, role="serve"))
        for rep in reps:
            if rep.state != "live":
                continue
            if rep.server.load_report()["crashed"]:
                self._note_dead(rep, "scheduler crashed")
            elif rep.idx in lapsed:
                self._note_dead(rep, "heartbeat lapsed (> %.1fs)"
                                % self.hb_timeout_s)
        if self.autoheal and self._started:
            while len(self._candidates()) < self.n:
                self.add_replica()

    # -------------------------------------------------------- rollout
    def _canary_payloads(self) -> List[Dict]:
        if self.spec is None or not self.canary_n:
            return []
        rng = np.random.default_rng(0)
        return [{name: rng.standard_normal((1,) + shape)
                 for name, shape in self.spec.input_shapes.items()}
                for _ in range(self.canary_n)]

    def _canary_gate(self, new_srv: ModelServer, payloads: List[Dict],
                     refs: List, ewma_ms: Optional[float]):
        """Admit the successor only if it agrees with the old weights
        on the canary set (top-1 agreement >= ``canary_min_agree``;
        garbage or non-finite weights fail here) and serves it within
        ``canary_latency_x`` times the old batch EWMA (a successor that
        compiles per request, or whose weights landed on a degraded
        path, fails here).  Returns ``(ok, reason)``."""
        if not payloads:
            return True, None
        agree, lats = 0, []
        for payload, ref in zip(payloads, refs):
            t0 = time.perf_counter()
            try:
                out = new_srv.predict(dict(payload))
            except Exception as e:          # noqa: BLE001
                return False, "canary request failed: %s" % e
            lats.append((time.perf_counter() - t0) * 1e3)
            a, b = np.asarray(out[0]), np.asarray(ref[0])
            if a.shape != b.shape:
                return False, ("canary output shape changed: %s vs %s"
                               % (a.shape, b.shape))
            if a.ndim >= 2:
                ok = np.argmax(a, axis=-1) == np.argmax(b, axis=-1)
                agree += float(np.mean(ok))
            else:
                agree += float(np.allclose(a, b, rtol=0.2, atol=0.1))
        frac = agree / len(payloads)
        if frac < self.canary_min_agree:
            return False, ("canary agreement %.3f < %.3f"
                           % (frac, self.canary_min_agree))
        if ewma_ms and lats:
            p50 = float(np.percentile(lats, 50))
            if p50 > self.canary_latency_x * ewma_ms:
                return False, ("canary p50 %.1f ms > %.0fx the old "
                               "EWMA %.1f ms"
                               % (p50, self.canary_latency_x, ewma_ms))
        return True, None

    def _swap(self, rep: _Replica, new_srv: ModelServer, version,
              drain_s: float) -> ModelServer:
        """Successor in, predecessor drained: the router stops handing
        the old server new work (state flip), the old queue is served
        to completion (``stop(drain_s)``), and a submit racing the flip
        is refused synchronously and failed over — zero drops."""
        old = rep.server
        with self._mu:
            rep.server = new_srv
            rep.version = version
            rep.state = "live"
        old.stop(drain_s=drain_s)
        return old

    def roll_weights(self, arg_params: Dict, aux_params: Optional[Dict],
                     version=None, drain_s: Optional[float] = None,
                     manager=None, manager_epoch: Optional[int] = None
                     ) -> Dict:
        """Zero-downtime rollout of new weights, one replica at a time
        (see module docstring).  With ``manager``/``manager_epoch``,
        the checkpoint's two-tier verification is re-checked before
        EACH replica re-admits traffic on the new weights (memoized —
        a handful of stat() calls unless the bytes changed).  On a
        failed canary the already-swapped replicas are rolled BACK to
        the old weights; the fleet never serves a mix for longer than
        the rollback takes."""
        drain_s = self.drain_s if drain_s is None else float(drain_s)
        aux_params = aux_params or {}
        with self._roll_mu:
            old_arg, old_aux = self._weights
            old_version = self._version
            payloads = self._canary_payloads()
            refs = []
            cands = self._candidates()
            if not cands:
                raise ServeUnavailable("rollout with no live replica")
            ref_rep = cands[0]
            lr = ref_rep.server.load_report()
            pm = next(iter(lr["per_model"].values()), {})
            ewma_ms = pm.get("ewma_batch_ms")
            for payload in payloads:
                refs.append(ref_rep.server.predict(dict(payload)))
            swapped: List[_Replica] = []
            spinup_compiles = 0
            for rep in self._candidates():
                if manager is not None and manager_epoch is not None \
                        and manager.verified(manager_epoch) is None:
                    self._rollback(swapped, old_arg, old_aux,
                                   old_version, drain_s)
                    self._stats["rollbacks"] += 1
                    return {"rolled_back": True, "version": old_version,
                            "swapped": 0,
                            "reason": "checkpoint %04d no longer "
                                      "verifies" % manager_epoch}
                with self._mu:
                    if rep.state != "live":
                        continue
                    rep.state = "draining"
                try:
                    with _program.stats_delta() as d:
                        new_srv = self._spawn(rep.idx, arg_params,
                                              aux_params)
                except Exception as e:      # noqa: BLE001
                    with self._mu:
                        rep.state = "live"
                    self._rollback(swapped, old_arg, old_aux,
                                   old_version, drain_s)
                    self._stats["rollbacks"] += 1
                    return {"rolled_back": True, "version": old_version,
                            "swapped": 0,
                            "reason": "successor build failed: %s" % e}
                spinup_compiles += d.get("compiles", 0)
                ok, why = self._canary_gate(new_srv, payloads, refs,
                                            ewma_ms)
                if not ok:
                    new_srv.stop()
                    with self._mu:
                        rep.state = "live"
                    self._rollback(swapped, old_arg, old_aux,
                                   old_version, drain_s)
                    self._stats["rollbacks"] += 1
                    return {"rolled_back": True, "version": old_version,
                            "swapped": 0, "reason": why}
                self._swap(rep, new_srv, version, drain_s)
                swapped.append(rep)
                self._stats["rollout_swaps"] += 1
            self._weights = (arg_params, aux_params)
            self._version = version
            self._stats["rollouts"] += 1
            return {"rolled_back": False, "version": version,
                    "swapped": len(swapped),
                    "spinup_compiles": spinup_compiles}

    def _rollback(self, swapped: List[_Replica], old_arg, old_aux,
                  old_version, drain_s: float) -> None:
        """Undo a partial rollout: every already-swapped replica gets a
        fresh server on the OLD weights (same warm-build path — the old
        programs are still cached).  The canary is skipped: the old
        weights were serving a moment ago and are the known-good
        reference."""
        for rep in swapped:
            with self._mu:
                if rep.state != "live":
                    continue
                rep.state = "draining"
            try:
                new_srv = self._spawn(rep.idx, old_arg, old_aux)
            except Exception:               # noqa: BLE001
                with self._mu:
                    rep.state = "live"      # keep serving the new
                continue                    # weights rather than die
            self._swap(rep, new_srv, old_version, drain_s)

    # -------------------------------------------------------- watcher
    def watch_checkpoints(self, manager, poll_s: Optional[float] = None
                          ) -> None:
        """Continuous deployment: poll
        ``CheckpointManager.latest_verified()`` (cheap — the
        verification verdict is memoized per on-disk identity) and roll
        the fleet onto every new verified checkpoint."""
        if self._watcher is not None:
            raise MXNetError("already watching a checkpoint line")
        poll_s = float(poll_s) if poll_s is not None \
            else _env_f("MXTPU_FLEET_ROLLOUT_POLL_S", 2.0)
        self._watch_stop.clear()

        def loop():
            while not self._watch_stop.wait(poll_s):
                try:
                    ck = manager.latest_verified()
                    if ck is None or ck.epoch == self._version:
                        continue
                    _, arg, aux = ck.load_params()
                    self.roll_weights(arg, aux, version=ck.epoch,
                                      manager=manager,
                                      manager_epoch=ck.epoch)
                except Exception:           # noqa: BLE001
                    self._stats["rollout_errors"] += 1

        self._watcher = threading.Thread(target=loop, daemon=True,
                                         name="mxtpu-fleet-rollout")
        self._watcher.start()

    def unwatch(self) -> None:
        self._watch_stop.set()
        if self._watcher is not None:
            self._watcher.join(timeout=10)
            self._watcher = None

    # ---------------------------------------------------------- stats
    def stats(self) -> Dict:
        """Fleet-level counters + per-replica summaries + the MERGED
        view of every replica's registry scope (each ``ModelServer``
        counts under its own ``serving.serverN`` namespace; the fleet
        sum is what capacity dashboards want)."""
        with self._mu:
            reps = {r.idx: r for r in self._replicas.values()}
            epoch = self._epoch
        per_replica, scopes = {}, []
        for idx in sorted(reps):
            rep = reps[idx]
            scope = rep.server._obs_scope
            if rep.state in ("live", "draining"):
                scopes.append(scope)
            per_replica[str(idx)] = {
                "state": rep.state, "version": rep.version,
                "obs_scope": scope,
                "spinup_compiles": rep.spinup.get("compiles", 0),
                "spinup_loads": rep.spinup.get("loads", 0)}
        snap = _obs.REGISTRY.snapshot()["counters"]
        merged: Dict[str, float] = {}
        for scope in scopes:
            prefix = scope + "."
            for name, v in snap.items():
                if name.startswith(prefix):
                    k = name[len(prefix):]
                    merged[k] = merged.get(k, 0) + v
        return {"epoch": epoch, "policy": self.policy,
                "target_n": self.n, "live": self.live_replicas(),
                "version": self._version,
                "router": dict(self._stats),
                "replicas": per_replica,
                "merged": merged,
                "obs_scope": self._obs_scope}

    def assert_no_retrace(self) -> None:
        for rep in self._candidates():
            rep.server.assert_no_retrace()
