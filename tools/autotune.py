#!/usr/bin/env python
"""Search-based autotuning over the joint training + serving knob space.

Every knob this framework grew — training ``dtype_policy`` / ``zero`` /
``grad_accum`` / ``grad_dtype`` / ``remat`` / ``integrity_period`` /
batch + upload shape, serving bucket ladder / ``max_wait_us`` / ``cap``
/ queue depth / shed policy — was hand-picked when its PR landed.  TVM
and TpuGraphs (PAPERS.md) both showed config search beats hand tuning;
this driver makes that search cheap by leaning on two existing layers:

* **cheap surrogates prune the space.**  The training side scores every
  candidate with the XLA byte cost model
  (:func:`tools.stepcost.cost_model` — compile, never execute;
  GB/step + gradient wire GB).  The serving side scores candidates with
  the serving latency model: per-bucket execute-latency EWMAs
  (:meth:`CompiledForward.record_latency`) calibrated once, then an
  analytic coalescing model (expected dispatch rows at the offered
  rate → padded bucket → EWMA service time) predicts latency/capacity
  per (ladder, wait, cap) without running a single load sweep.
* **real timed windows only for the surrogate top-K** — and every
  window runs against a warm ``MXTPU_PROGRAM_CACHE``
  (docs/how_to/compiled_programs.md), so a repeated trial at a
  previously-seen (symbol, shapes, policy) point **compiles zero
  programs** (asserted per run via :func:`mxnet_tpu.program.stats_delta`
  and recorded in the plan).  Two configs are always compared against
  the *identical* seeded arrival sequence
  (:func:`tools.serve_bench.arrival_schedule`), never two random draws.

The output is a persisted, validated ``TUNE_PLAN.json``
(:mod:`mxnet_tpu.tuneplan`) that ``Trainer`` and ``ModelServer`` load
at construction (``plan=`` or ``MXTPU_TUNE_PLAN``; ctor/env knobs
override plan entries; a foreign-keyed plan is a loud counted fallback
to defaults).  Every timed window also appends one full
(config, measured) row to ``TUNE_CORPUS.jsonl`` — the TpuGraphs-style
accumulation that makes every future knob PR free training data for a
learned cost model.  ``--ratchet`` merges the winning A/B into
INFER_BENCH.json the way serve_bench already merges its sections.

Modes::

    python tools/autotune.py                     # full search, plan at
                                                 # repo-root TUNE_PLAN.json
    python tools/autotune.py --micro             # CI fast tier: 2-3 knobs,
                                                 # surrogate + 1 timed trial
                                                 # per side of the A/B
    python tools/autotune.py --verify PLAN       # load the plan through a
                                                 # real Trainer + ModelServer
                                                 # and assert it applied

See docs/how_to/autotune.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DEFAULT_PLAN_PATH = os.path.join(ROOT, "TUNE_PLAN.json")

# the serving defaults the A/B is measured against (the ModelServer's
# own built-ins; see serving/server.py's knob table)
SERVE_DEFAULTS = {"buckets": [1, 4, 8, 16, 32], "max_wait_us": 2000,
                  "queue_cap": 4096, "shed_policy": "reject"}
# the training defaults (bytediet policy is dtype_policy=None)
TRAIN_DEFAULTS = {"remat": "none", "zero": 0, "grad_accum": 1,
                  "grad_dtype": "f32"}


# ----------------------------------------------------------------------
# search space
def serve_space(micro=False):
    """Serving-side candidate grid.  Micro keeps 2 knobs (coalescing
    wait x queue bound) on the default ladder — the CI-sized cut."""
    if micro:
        ladders = [[1, 4, 8, 16, 32]]
        waits = [300, 2000]
        qcaps = [64]
    else:
        ladders = [[1, 4, 8, 16, 32], [1, 2, 4, 8, 16, 32], [1, 8, 32]]
        waits = [200, 500, 1000, 2000, 5000]
        qcaps = [64, 256, 4096]
    out = []
    for lad in ladders:
        for w in waits:
            for q in qcaps:
                out.append({"buckets": list(lad), "max_wait_us": w,
                            "queue_cap": q, "shed_policy": "reject"})
    return out


def train_space(micro=False, devices=1):
    """Training-side candidate grid (knob dicts over the trainer's
    config surface).  Surrogate-scored by the byte cost model; corners
    that need a >=2-way data mesh are emitted only when one exists."""
    if micro:
        return [dict(TRAIN_DEFAULTS),
                dict(TRAIN_DEFAULTS, dtype_policy="legacy")]
    out = []
    for policy in (None, "legacy"):
        for remat in ("none", "convs_dots"):
            for accum in (1, 2):
                cfg = dict(TRAIN_DEFAULTS, remat=remat,
                           grad_accum=accum)
                if policy is not None:
                    cfg["dtype_policy"] = policy
                out.append(cfg)
                if devices > 1:
                    # mesh corners carry their data-axis degree so the
                    # surrogate and the timed trial actually BUILD the
                    # mesh — zero/bf16 are silent no-ops on a meshless
                    # trainer and would score byte-identical to base
                    out.append(dict(cfg, zero=1, devices=devices))
                    out.append(dict(cfg, grad_dtype="bf16",
                                    devices=devices))
                    out.append(dict(cfg, zero=1, grad_dtype="bf16",
                                    devices=devices))
    # dedupe (dict equality over sorted items)
    seen, uniq = set(), []
    for cfg in out:
        key = tuple(sorted(cfg.items()))
        if key not in seen:
            seen.add(key)
            uniq.append(cfg)
    return uniq


# ----------------------------------------------------------------------
# surrogates
def train_surrogate(configs, batch=64, model="mlp", capacity=None):
    """Score each training config with the byte cost model (compile
    only): total predicted GB moved per step = on-chip step bytes +
    cross-chip gradient wire bytes.  Returns rows sorted best-first.

    Memory feasibility rides the same surrogate pass: each row carries
    the static liveness ``predicted_peak_bytes`` (tools/mem_lint.py's
    model), and a config whose peak exceeds ``capacity`` (default: the
    detected per-chip HBM / ``MXTPU_HBM_BYTES``) is marked
    ``mem_feasible: False`` and sorted LAST — it is never adopted and
    never gets a timed window: a config that OOMs cannot win a
    wall-clock race it cannot finish."""
    from tools.stepcost import cost_model
    if capacity is None:
        from mxnet_tpu.analysis import detect_capacity
        capacity = detect_capacity()
    rows = []
    for cfg in configs:
        cm = cost_model({"model": model, "batch": batch,
                         "devices": cfg.get("devices") or 1,
                         "dtype_policy": cfg.get("dtype_policy"),
                         "remat": cfg.get("remat"),
                         "zero": cfg.get("zero"),
                         "grad_accum": cfg.get("grad_accum"),
                         "grad_dtype": cfg.get("grad_dtype")})
        score = cm["gb_per_step"] + cm["grad_comm_gb_per_step"]
        peak = int(cm.get("predicted_peak_bytes") or 0)
        feasible = not (capacity and peak and peak > int(capacity))
        rows.append({"config": dict(cfg), "surrogate_gb": round(score, 6),
                     "gb_per_step": cm["gb_per_step"],
                     "grad_comm_gb_per_step": cm["grad_comm_gb_per_step"],
                     "opt_state_bytes_per_chip":
                         cm["opt_state_bytes_per_chip"],
                     "predicted_peak_bytes": peak,
                     "mem_feasible": feasible})
    rows.sort(key=lambda r: (0 if r["mem_feasible"] else 1,
                             r["surrogate_gb"]))
    return rows


def calibrate_service_times(sym, wargs, waux, example, ladders,
                            samples=5):
    """Per-bucket execute-latency EWMAs over the UNION of every
    candidate ladder — one server start, a few barriered executes per
    bucket, each folded through ``CompiledForward.record_latency`` (the
    same EWMA the deadline shedder trusts).  Returns
    ``{bucket: seconds}``."""
    from mxnet_tpu import serving
    buckets = sorted({int(b) for lad in ladders for b in lad})
    serving.clear_cache()
    server = serving.ModelServer(buckets=buckets,
                                 **{k: v for k, v in
                                    SERVE_DEFAULTS.items()
                                    if k != "buckets"})
    server.add_model("m", sym, wargs, waux,
                     input_shapes={"data": example})
    svc = {}
    with server:
        m = server._models["m"]
        for b in buckets:
            shapes = server._bucket_shapes(m, b)
            feed = {n: np.zeros(s, m.input_dtypes[n])
                    for n, s in shapes.items()}
            np.asarray(m.cf.run(m.params, m.aux, feed)[0][:1])  # warm
            for _ in range(samples):
                t0 = time.perf_counter()
                outs = m.cf.run(m.params, m.aux, feed)
                np.asarray(outs[0][:1])        # completion barrier
                m.cf.record_latency(b, time.perf_counter() - t0)
        ewma = m.cf.latency_ms_by_bucket()
    for b in buckets:
        svc[b] = ewma[str(b)] / 1e3
    return svc


def serve_surrogate(configs, svc_s, rate_rps, mean_rows, deadline_s):
    """The analytic pruning model over the calibrated EWMAs: expected
    coalesced rows at the offered rate -> padded bucket -> EWMA service
    time.  Predicted latency ~ half the coalescing wait + service;
    capacity = bucket rows / service.  Infeasible configs (capacity
    below the offered row rate, or predicted latency past the
    deadline) sort last.  A heuristic — the timed top-K is what the
    plan rests on."""
    rows = []
    offered_rows = rate_rps * mean_rows
    for cfg in configs:
        w = cfg["max_wait_us"] / 1e6
        cap_rows = min(cfg.get("cap") or max(cfg["buckets"]),
                       cfg["queue_cap"] or 10 ** 9)
        exp_rows = min(cap_rows, offered_rows * w + mean_rows)
        bucket = next((b for b in sorted(cfg["buckets"])
                       if b >= exp_rows), max(cfg["buckets"]))
        s = svc_s[bucket]
        capacity = bucket / s
        pred_p50 = w / 2.0 + s
        pred_p99 = w + 3.0 * s
        feasible = capacity >= offered_rows and pred_p99 < deadline_s
        score = pred_p99 if feasible \
            else 1e3 + offered_rows / max(capacity, 1e-9)
        rows.append({"config": dict(cfg),
                     "surrogate_p99_ms": round(pred_p99 * 1e3, 3),
                     "surrogate_p50_ms": round(pred_p50 * 1e3, 3),
                     "predicted_bucket": bucket,
                     "capacity_rows_per_s": round(capacity, 1),
                     "feasible": feasible,
                     "_score": score})
    rows.sort(key=lambda r: r["_score"])
    for r in rows:
        r.pop("_score")
    return rows


def _trial_env_names():
    """Ambient env that would leak into a trial's "default" side: an
    exported MXTPU_TUNE_PLAN (the documented production setup when
    re-tuning) or a process-wide trainer/serving knob would silently
    reconfigure every unpinned ctor argument via the ctor > env > plan
    chain — the A/B would compare legacy-vs-legacy while labeled
    default.  Derived from the envknobs registry's owner field so a
    future knob can never be forgotten here."""
    from mxnet_tpu import envknobs
    return sorted(name for name, k in envknobs.KNOBS.items()
                  if k.owner in ("trainer", "serving")
                  or name == "MXTPU_TUNE_PLAN")


class _pinned_env:
    """Scrub the ambient tuning env for the duration of a tune/A-B
    block; restores every popped value on exit."""

    def __enter__(self):
        self._saved = {}
        for name in _trial_env_names():
            if name in os.environ:
                self._saved[name] = os.environ.pop(name)
        return self

    def __exit__(self, *exc):
        os.environ.update(self._saved)
        return False


# ----------------------------------------------------------------------
# timed windows (the measurements the plan actually rests on)
def timed_serve_trial(sym, wargs, waux, example, cfg, payloads,
                      arrivals, rate_rps, deadline_ms, corpus=None,
                      label="serve", windows=2):
    """Real open-loop windows for one serving config — fresh server,
    identical payloads + arrival schedule across configs, warm program
    cache (``program.stats_delta`` records whether any compile
    happened).  ``windows`` back-to-back repeats of the SAME schedule
    with min-of-windows latency (max goodput) is the shared-CI-host
    anti-noise shape the integrity/obs probes established — a single
    p99 is one order statistic of one window.  One corpus row is
    appended PER timed window."""
    from mxnet_tpu import obs as _obs
    from mxnet_tpu import program, serving, tuneplan
    from tools.serve_bench import overload_run

    serving.clear_cache()          # trial isolation: fresh forward
    runs = []
    with _obs.span("tune.trial", attrs={"kind": "serve",
                                        "label": label}):
        with program.stats_delta() as delta:
            server = serving.ModelServer(
                buckets=cfg["buckets"], max_wait_us=cfg["max_wait_us"],
                queue_cap=cfg["queue_cap"],
                shed_policy=cfg["shed_policy"], cap=cfg.get("cap"),
                timeout_ms=deadline_ms)
            server.add_model("m", sym, wargs, waux,
                             input_shapes={"data": example})
            # static worst-bucket footprint (the admission ledger's
            # figure) — recorded into every corpus row so the corpus
            # can answer "what would this config cost in HBM" offline
            peak_bytes = server._models["m"].predicted_peak_bytes
            with server:
                for _ in range(windows):
                    run = overload_run(server, payloads, rate_rps,
                                       deadline_s=deadline_ms / 1e3,
                                       arrivals=arrivals)
                    server.assert_no_retrace()
                    runs.append(run)
    # the trial's measured point is ONE coherent window — the best-p99
    # one — not a min-latency/max-goodput collage: a low-p99 window
    # that got there by shedding must not borrow another window's
    # goodput to pass the adoption gate (the plan would then rest on a
    # (latency, goodput) point never actually observed together)
    with_lat = [r for r in runs if "p99_ms" in r]
    best = min(with_lat, key=lambda r: r["p99_ms"]) if with_lat \
        else runs[0]
    measured = {"requests": best.get("requests"),
                "windows": len(runs),
                "goodput_rps": best.get("goodput_rps", 0),
                "shed_rate": best.get("shed_rate", 0),
                "program_compiles": delta["compiles"],
                "program_loads": delta["loads"],
                "predicted_peak_bytes": peak_bytes}
    for k in ("p50_ms", "p99_ms"):
        if k in best:
            measured[k] = best[k]
    for i, run in enumerate(runs):
        row = {k: run.get(k) for k in
               ("p50_ms", "p99_ms", "goodput_rps", "shed_rate",
                "completed_in_deadline", "requests")}
        row["predicted_peak_bytes"] = peak_bytes
        if i == 0:
            # the delta spans server construction + every window; all
            # compiles/loads happen before window 0 runs, so only its
            # row carries them — later windows ran fully warm and must
            # not be labeled with compile work they didn't do
            row.update({"program_compiles": delta["compiles"],
                        "program_loads": delta["loads"]})
        tuneplan.append_corpus(
            {"kind": "serve", "tool": "autotune",
             "label": "%s#w%d" % (label, i), "config": dict(cfg),
             "offered_rps": round(rate_rps, 1),
             "deadline_ms": deadline_ms, "measured": row},
            path=corpus)
    return measured


def timed_train_trial(sym, cfg, batch=64, steps=40, corpus=None,
                      label="train", seed=5):
    """One real timed training window for one config: fresh Trainer on
    the tune symbol, fixed batch, ``steps`` fused steps between
    barriers.  Warm-cache repeats load their step executable instead of
    compiling (``program_compiles`` says which happened)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import obs as _obs
    from mxnet_tpu import program, tuneplan
    from mxnet_tpu.parallel.trainer import Trainer

    mesh = None
    n_dev = int(cfg.get("devices") or 1)
    if n_dev > 1:
        from mxnet_tpu import parallel
        mesh = parallel.make_mesh({"data": n_dev},
                                  jax.devices()[:n_dev])
    with _obs.span("tune.trial", attrs={"kind": "train",
                                        "label": label}):
        with program.stats_delta() as delta:
            t = Trainer(sym, mx.optimizer.create(
                "sgd", learning_rate=0.1, momentum=0.9,
                rescale_grad=1.0 / batch),
                mesh=mesh,
                dtype_policy=cfg.get("dtype_policy"),
                remat=cfg.get("remat"), zero=cfg.get("zero"),
                grad_accum=cfg.get("grad_accum"),
                grad_dtype=cfg.get("grad_dtype"))
            t.bind(data_shapes={"data": (batch, 64)},
                   label_shapes={"softmax_label": (batch,)})
            mx.random.seed(7)
            t.init_params(mx.init.Xavier())
            rng = np.random.RandomState(seed)
            feed = {"data": mx.nd.array(
                rng.randn(batch, 64).astype("f")),
                "softmax_label": mx.nd.array(
                    rng.randint(0, 16, batch).astype("f"))}
            t.step(feed)                       # compile-or-load + warm
            jax.block_until_ready((t.params, t.opt_state))
            t0 = time.perf_counter()
            for _ in range(steps):
                t.step(feed)
            jax.block_until_ready((t.params, t.opt_state))
            elapsed = time.perf_counter() - t0
    try:
        peak_bytes = t.predicted_peak_bytes()
    except Exception:  # noqa: BLE001 — analysis gap must not void the
        peak_bytes = 0  # timing that already ran
    measured = {"img_per_sec": round(batch * steps / elapsed, 1),
                "step_ms": round(elapsed / steps * 1e3, 3),
                "program_compiles": delta["compiles"],
                "program_loads": delta["loads"],
                "predicted_peak_bytes": peak_bytes}
    tuneplan.append_corpus(
        {"kind": "train", "tool": "autotune", "label": label,
         "config": dict(cfg), "batch": batch, "steps": steps,
         "measured": measured},
        path=corpus)
    return measured


# ----------------------------------------------------------------------
def read_quant_gate(path, symbol_digest):
    """Load a tools/quantize.py gate artifact and decide whether the
    plan may carry ``precision: int8``: the gate must have PASSED and
    must have been measured on THIS plan's float symbol — a gate from
    another model must never license a different tenant's tier.
    Returns the gate record or None."""
    if not path or not os.path.exists(path):
        return None
    with open(path) as f:
        gate = json.load(f)
    if not gate.get("passed"):
        return None
    if gate.get("float_symbol_digest") != symbol_digest:
        return None
    return gate


def run_tune(network="mlp", micro=False, top_k=2, seed=0, out=None,
             corpus=None, requests=None, deadline_ms=250,
             assert_no_worse=False, ratchet=None, quant_gate=None):
    """The search driver.  Returns (plan, summary); writes the plan to
    ``out`` and one corpus row per timed window."""
    import jax
    import mxnet_tpu  # noqa: F401 — registers knobs, validates env
    from mxnet_tpu import program, tuneplan
    from tools.serve_bench import (_mixed_payloads, arrival_schedule,
                                   build_model, single_request_baseline)

    own_cache = None
    if not os.environ.get("MXTPU_PROGRAM_CACHE"):
        # every timed window runs against a persisted program cache so
        # re-evaluating a config is compile-free; honor the operator's
        # dir when exported, else a run-local one
        own_cache = tempfile.mkdtemp(prefix="mxtpu-tune-cache-")
        os.environ["MXTPU_PROGRAM_CACHE"] = own_cache
    pinned = _pinned_env()
    pinned.__enter__()
    try:
        sym, wargs, waux, example = build_model(network, seed)
        digest = program.symbol_digest(sym)
        n_req = requests or (400 if micro else 800)
        rows_mix = (1, 2, 4)

        # --- training side: surrogate over the byte cost model.  The
        # train workload IS the mlp tune symbol (cost_model and the
        # timed trial both drive it); for any other --network the
        # search would score/bind the wrong model, so those runs keep
        # the default train knobs and tune only the serving side.
        t_rows, t_default, t_best = [], None, None
        train_timed = {}
        mem_skipped = 0
        adopted_train = dict(TRAIN_DEFAULTS)
        if network == "mlp":
            tspace = train_space(micro=micro,
                                 devices=len(jax.devices()))
            t_rows = train_surrogate(tspace)
            # memory-infeasible configs (static peak past the per-chip
            # capacity) sorted last by the surrogate: counted here,
            # never timed, never adopted
            mem_skipped = sum(1 for r in t_rows
                              if not r.get("mem_feasible", True))
            t_default = next(r for r in t_rows
                             if r["config"] == TRAIN_DEFAULTS)
            t_best = t_rows[0]
            # a predicted-bytes winner enters the plan ONLY with a
            # timed confirmation (fewer bytes can still be slower
            # wall-clock, as every remat policy did on the v5e)
            # AND only when measured meshless: the plan's one key is
            # the meshless serve identity, so a zero=1/bf16 corner
            # measured on a real mesh stays in measured/corpus (the
            # insight survives) but must not ship mis-keyed.  Micro
            # mode times no train windows, so it can never adopt a
            # non-default config.
            if not micro:
                train_timed["default"] = timed_train_trial(
                    sym, TRAIN_DEFAULTS, corpus=corpus,
                    label="train:default")
                if t_best["config"] != TRAIN_DEFAULTS \
                        and t_best.get("mem_feasible", True):
                    train_timed["winner"] = timed_train_trial(
                        sym, t_best["config"], corpus=corpus,
                        label="train:winner")
                    if not t_best["config"].get("devices") and \
                            train_timed["winner"]["img_per_sec"] >= \
                            0.95 * train_timed["default"]["img_per_sec"]:
                        adopted_train = dict(t_best["config"])

        # --- serving side: EWMA surrogate -> top-K timed trials
        base = single_request_baseline(sym, wargs, waux, example,
                                       n=(80 if micro else 200),
                                       seed=seed + 1)
        cap = base["rps"]
        rate = max(1.0, 1.0 * cap)
        candidates = serve_space(micro=micro)
        svc = calibrate_service_times(
            sym, wargs, waux, example,
            [c["buckets"] for c in candidates] +
            [SERVE_DEFAULTS["buckets"]])
        mean_rows = float(np.mean(rows_mix))
        s_rows = serve_surrogate(candidates, svc, rate, mean_rows,
                                 deadline_ms / 1e3)

        payloads = _mixed_payloads(example, rows_mix, n_req, seed + 2)
        arrivals = arrival_schedule(n_req, rate, seed + 3)
        trial = lambda cfg, label: timed_serve_trial(  # noqa: E731
            sym, wargs, waux, example, cfg, payloads, arrivals, rate,
            deadline_ms, corpus=corpus, label=label, windows=3)

        baseline = trial(SERVE_DEFAULTS, "serve:default")
        timed = []
        k = 1 if micro else top_k
        for i, r in enumerate(s_rows[:k]):
            m = trial(r["config"], "serve:cand%d" % i)
            timed.append({"config": r["config"],
                          "surrogate_p99_ms": r["surrogate_p99_ms"],
                          "measured": m})

        # winner: lowest measured p99 that BEATS the default window,
        # with goodput holding (>= 0.95x the default's) AND p50 not
        # regressing past the no-worse gate's own tolerance — a latency
        # win bought with dropped work is not a win, a p99 win that
        # trades away the median is not either (observed on a slow
        # host: tiny coalescing waits make 1-2-row batches whose
        # per-dispatch overhead blows up p50 while p99 noise still
        # "wins"), and a candidate that merely beats the other
        # candidates falls back to the defaults
        def _ok(m):
            return (m.get("goodput_rps", 0)
                    >= 0.95 * baseline.get("goodput_rps", 0)
                    and "p99_ms" in m
                    and m["p99_ms"] <= baseline.get("p99_ms", 0)
                    and "p50_ms" in m and "p50_ms" in baseline
                    and m["p50_ms"] <= baseline["p50_ms"] * 1.15)

        viable = [t for t in timed if _ok(t["measured"])]
        viable.sort(key=lambda t: t["measured"]["p99_ms"])
        winner = viable[0] if viable else None
        serve_cfg = winner["config"] if winner else dict(SERVE_DEFAULTS)

        # --- gated precision knob: only a PASSED accuracy gate for
        # THIS symbol licenses an int8 serve tier in the plan
        # (tools/quantize.py writes the artifact; ModelServer enforces
        # the tier at add_model)
        gate = read_quant_gate(
            quant_gate or os.environ.get("MXTPU_QUANT_GATE"), digest)
        if gate is not None:
            serve_cfg = dict(serve_cfg)
            serve_cfg["precision"] = "int8"

        # --- the acceptance re-run: the winning timed trial repeated
        # against the now-warm program cache must compile ZERO programs
        recheck = trial(serve_cfg, "serve:warm-recheck")
        if recheck["program_compiles"] != 0:
            raise RuntimeError(
                "warm-cache recheck compiled %d programs — a repeated "
                "trial at a previously-seen config must be compile-free "
                "(MXTPU_PROGRAM_CACHE=%s)"
                % (recheck["program_compiles"],
                   os.environ.get("MXTPU_PROGRAM_CACHE")))

        # --- plan assembly ("devices" is measurement identity, not a
        # trainer knob — the plan's mesh applicability lives in its
        # key, and zero/bf16 are safe no-ops on a smaller mesh)
        train_knobs = {k: v for k, v in adopted_train.items()
                       if v is not None and k != "devices"}
        key = tuneplan.current_key(symbol_digest=digest,
                                   slo={"deadline_ms": deadline_ms})
        # measured identity, not a wildcard: the trials ran meshless,
        # so the plan must NOT silently apply to a real mesh (null is
        # reserved for hand-written matches-anything plans)
        key["mesh"] = dict(tuneplan.MESHLESS)
        plan = {
            "version": tuneplan.PLAN_VERSION,
            "key": key,
            "train": train_knobs,
            "serve": dict(serve_cfg),
            "measured": {
                "objective": "serve_p99_ms",
                "single_request_rps": cap,
                "offered_rps": round(rate, 1),
                "serve_default": baseline,
                "serve_winner": winner["measured"] if winner
                else recheck,
                "train_surrogate_default_gb":
                    t_default["surrogate_gb"] if t_default else None,
                "train_surrogate_winner_gb":
                    t_best["surrogate_gb"] if t_best else None,
                "train_surrogate_winner_config":
                    t_best["config"] if t_best else None,
                "train_adopted_default": adopted_train
                == dict(TRAIN_DEFAULTS),
                "train_timed": train_timed,
                "train_mem_infeasible_skipped": mem_skipped,
                "warm_recheck_compiles": recheck["program_compiles"],
                "warm_recheck_loads": recheck["program_loads"],
            },
            "meta": {"tool": "tools/autotune.py", "network": network,
                     "micro": bool(micro), "seed": seed,
                     "quant_gate": None if gate is None else {
                         "calibration_digest":
                             gate.get("calibration_digest"),
                         "argmax_agreement":
                             gate.get("argmax_agreement"),
                         "top1_delta_pt": gate.get("top1_delta_pt")},
                     "requests_per_window": n_req,
                     "rows_mix": list(rows_mix),
                     "surrogate_candidates": len(candidates),
                     "timed_trials": len(timed) + 2,
                     "service_time_ewma_ms": {
                         str(b): round(s * 1e3, 3)
                         for b, s in sorted(svc.items())}},
        }
        out_path = out or DEFAULT_PLAN_PATH
        tuneplan.save(out_path, plan)

        p99_base = baseline.get("p99_ms")
        p99_win = plan["measured"]["serve_winner"].get("p99_ms")
        p50_base = baseline.get("p50_ms")
        p50_win = plan["measured"]["serve_winner"].get("p50_ms")
        improvement = None
        if p99_base and p99_win:
            improvement = round((1.0 - p99_win / p99_base) * 100.0, 2)
        g_base = baseline.get("goodput_rps") or 0
        g_win = plan["measured"]["serve_winner"].get("goodput_rps") or 0
        summary = {
            "plan": out_path,
            "corpus": tuneplan.corpus_path(corpus),
            # strict: a candidate measurably beat the defaults
            "winner_beats_default": winner is not None
            and p99_win is not None and p99_base is not None
            and p99_win <= p99_base,
            # gated (CI): the EMITTED plan — which falls back to the
            # defaults when no candidate won — is no worse than the
            # default window.  Judged on p50 + goodput, not p99: p50 is
            # structural (coalescing wait + service), while p99 of two
            # back-to-back DEFAULT windows measured >10% apart on a
            # loaded CI host — a gate on it would flake on noise, not
            # catch regressions
            # tolerances are NOISE-sized, not regression-sized:
            # min-of-windows DEFAULT p50s still spread ~1.2x run-to-run
            # on this host class, while a truly bad plan (wrong ladder,
            # starved queue) regresses >2x — the gate catches
            # regressions, the stricter _ok above decides ADOPTION
            "plan_no_worse": p50_win is not None and p50_base is not None
            and p50_win <= p50_base * 1.30 and g_win >= 0.85 * g_base,
            "serve_p99_default_ms": p99_base,
            "serve_p99_winner_ms": p99_win,
            "serve_p50_default_ms": p50_base,
            "serve_p50_winner_ms": p50_win,
            "serve_p99_improvement_pct": improvement,
            "goodput_default_rps": g_base,
            "goodput_winner_rps": g_win,
            "warm_recheck_compiles": recheck["program_compiles"],
            "train_mem_infeasible_skipped": mem_skipped,
        }
        if ratchet:
            _ratchet_infer_bench(ratchet, plan, summary)
        if assert_no_worse and not summary["plan_no_worse"]:
            raise SystemExit(
                "autotune --assert-no-worse: the emitted plan is worse "
                "than the default config on the measured window "
                "(default p50 %.3f ms goodput %.1f vs plan p50 %.3f ms "
                "goodput %.1f)" % (p50_base or -1, g_base,
                                   p50_win or -1, g_win))
        return plan, summary
    finally:
        pinned.__exit__()
        if own_cache is not None:
            import shutil
            os.environ.pop("MXTPU_PROGRAM_CACHE", None)
            shutil.rmtree(own_cache, ignore_errors=True)


def _ratchet_infer_bench(path, plan, summary):
    """Merge the tune A/B into INFER_BENCH.json (the serve_bench --out
    merge pattern): the measured winner rows become the checked-in
    figure the next run is read against."""
    artifact = {}
    if os.path.exists(path):
        with open(path) as f:
            artifact = json.load(f)
    artifact["tune"] = {
        "plan_key": plan["key"],
        "serve": plan["serve"],
        "train": plan["train"],
        "measured": plan["measured"],
        "summary": {k: v for k, v in summary.items()
                    if k not in ("plan", "corpus")},
    }
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")


# ----------------------------------------------------------------------
def verify_plan(plan_path):
    """Load ``plan_path`` through a REAL Trainer and ModelServer and
    assert its sections applied (the CI loadability gate).  Exits
    non-zero with the reason on any failure."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving, tuneplan
    from mxnet_tpu.parallel.trainer import Trainer
    from tools.serve_bench import build_model

    plan = tuneplan.load(plan_path)
    network = plan.get("meta", {}).get("network", "mlp")
    sym, _, _, _ = build_model(network, 0)

    t = Trainer(sym, mx.optimizer.create("sgd", learning_rate=0.1),
                plan=plan_path)
    if plan.get("train") and t.plan_knobs != plan["train"]:
        raise SystemExit("plan train section did not apply to the "
                         "Trainer: applied %r vs plan %r"
                         % (t.plan_knobs, plan["train"]))
    for knob, attr in (("zero", "zero"), ("grad_accum", "grad_accum"),
                       ("grad_dtype", "grad_dtype"),
                       ("remat", "remat")):
        if knob in plan.get("train", {}):
            got = getattr(t, attr)
            if got != plan["train"][knob]:
                raise SystemExit("Trainer.%s=%r != plan %r"
                                 % (attr, got, plan["train"][knob]))

    s = serving.ModelServer(plan=plan_path)
    srv = plan.get("serve", {})
    checks = (("buckets", s.buckets),
              ("max_wait_us", int(round(s.max_wait_s * 1e6))),
              ("queue_cap", s.queue_cap),
              ("shed_policy", s.shed_policy))
    for knob, got in checks:
        if knob in srv and got != srv[knob]:
            raise SystemExit("ModelServer %s=%r != plan %r"
                             % (knob, got, srv[knob]))
    print("plan %s verified: train%s serve%s applied through "
          "Trainer+ModelServer" % (plan_path,
                                   sorted(plan.get("train", {})),
                                   sorted(srv)))
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--network", default="mlp",
                    help="tune target (mlp is the CPU-tier workload)")
    ap.add_argument("--micro", action="store_true",
                    help="CI fast tier: 2-3 knobs, surrogate pruning + "
                         "one timed trial per A/B side")
    ap.add_argument("--top-k", type=int, default=2,
                    help="surrogate survivors that get timed windows")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per timed serving window")
    ap.add_argument("--deadline-ms", type=int, default=250)
    ap.add_argument("--out", default=None,
                    help="plan path (default %s)"
                         % os.path.relpath(DEFAULT_PLAN_PATH))
    ap.add_argument("--corpus", default=None,
                    help="TUNE_CORPUS.jsonl path (default: repo root / "
                         "MXTPU_TUNE_CORPUS)")
    ap.add_argument("--assert-no-worse", action="store_true",
                    help="exit non-zero unless the plan beats the "
                         "default config on the measured window")
    ap.add_argument("--ratchet", default=None, metavar="INFER_BENCH",
                    help="merge the winning A/B into this "
                         "INFER_BENCH.json artifact")
    ap.add_argument("--verify", default=None, metavar="PLAN",
                    help="load PLAN through Trainer+ModelServer and "
                         "assert it applied, then exit")
    ap.add_argument("--quant-gate", default=None, metavar="GATE_JSON",
                    help="tools/quantize.py gate artifact; a PASSED "
                         "gate matching the tuned symbol lets the plan "
                         "carry serve precision=int8 (default: "
                         "MXTPU_QUANT_GATE)")
    args = ap.parse_args(argv)

    if args.verify:
        return verify_plan(args.verify)

    plan, summary = run_tune(
        network=args.network, micro=args.micro, top_k=args.top_k,
        seed=args.seed, out=args.out, corpus=args.corpus,
        requests=args.requests, deadline_ms=args.deadline_ms,
        assert_no_worse=args.assert_no_worse, ratchet=args.ratchet,
        quant_gate=args.quant_gate)
    print(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
