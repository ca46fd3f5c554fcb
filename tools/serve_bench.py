#!/usr/bin/env python
"""Serving benchmark: Poisson load over the continuous-batching
ModelServer vs the single-request Predictor loop.

Two load modes over the same model:

* **single-request baseline** — the pre-serving deploy path: one
  ``Predictor``, requests served strictly one at a time.  Its
  sustained rate is the *capacity* the load sweep is scaled against.
* **open-loop Poisson sweep** — arrivals drawn from an exponential
  inter-arrival distribution at several offered loads (fractions and
  multiples of the baseline capacity), submitted to a
  :class:`~mxnet_tpu.serving.ModelServer`; per-request latency is
  measured submit→future-complete, i.e. queueing + batching + compute.
  Open loop means arrivals do NOT slow down when the server falls
  behind — the honest way to show saturation (a closed loop would
  self-throttle and flatter the p99).

Request row counts are drawn from a mixed set (default 1/2/4), so the
sweep also exercises the bucket padding: the run asserts **zero
steady-state retraces** across the mixed shapes and reports the
batch-occupancy histogram.

A fault-injection pass (``MXTPU_FAULTS`` DSL, ``faults.py``) rides at
the end: one poisoned and a few slow requests inside a burst, showing
graceful degradation — the poisoned future fails alone, the slow
requests stretch only their own cycles.

**Overload sweep** (:func:`overload_probe`): offered load from 1x to 8x
the single-request capacity against a server with admission control ON
(bounded queue, ``reject`` shedding, per-request deadline), reporting
per load factor

* ``goodput_rps`` — completions *within their deadline* per second
  (a late answer is not goodput; the client already gave up),
* ``shed_rate`` — the fraction the server said *no* to (fast
  ``ServeOverload`` rejects + deadline sheds + in-flight expiries),
* ``p99_ms`` over the ACCEPTED completions.

The degradation invariant — goodput at the highest overload >= 0.9x
goodput at 1x — is what "graceful" means quantitatively: past
saturation the server sheds the excess deliberately and keeps serving
at capacity instead of letting queues and p99 grow without bound.
``tests/test_serving_overload.py`` holds it at test scale.

**Fleet sweep** (:func:`fleet_probe`, ``--fleet``): the replicated tier
(``FleetRouter``) under three windows — scaling (same offered load and
arrival schedule against 1 vs N paced replicas), churn (kill one
replica mid-window, autoheal), and a zero-downtime weight rollout
mid-window.  Gates: ``fleet_goodput_rps >= 2.2x`` the single replica,
last-third goodput ``>= 0.9x`` first-third after the kill, zero dropped
requests and zero spin-up compiles across the rollout.

``--out INFER_BENCH.json`` merges ``serving`` and ``overload`` (and
``quant`` / ``fleet`` when requested) sections into the artifact (field
definitions: docs/how_to/perf.md "Serving"); ``--quick`` runs the
bounded sweeps of :func:`serving_probe` / :func:`overload_probe` /
:func:`fleet_probe`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# ----------------------------------------------------------------------
def build_model(network="mlp", seed=0):
    """(symbol, arg_params, aux_params, per-example input shape)."""
    import mxnet_tpu as mx
    rng = np.random.RandomState(seed)
    if network == "mlp":
        # serving-shaped MLP: big enough that batching matters, small
        # enough that the CPU tier sweeps in seconds
        data = mx.sym.Variable("data")
        net = mx.symbol.FullyConnected(data, num_hidden=256, name="fc1")
        net = mx.symbol.Activation(net, act_type="relu", name="relu1")
        net = mx.symbol.FullyConnected(net, num_hidden=256, name="fc2")
        net = mx.symbol.Activation(net, act_type="relu", name="relu2")
        net = mx.symbol.FullyConnected(net, num_hidden=16, name="fc3")
        sym = mx.symbol.SoftmaxOutput(net, name="softmax")
        example = (64,)
        args = {
            "fc1_weight": mx.nd.array(
                (rng.randn(256, 64) / 8).astype("f")),
            "fc1_bias": mx.nd.array(np.zeros(256, "f")),
            "fc2_weight": mx.nd.array(
                (rng.randn(256, 256) / 16).astype("f")),
            "fc2_bias": mx.nd.array(np.zeros(256, "f")),
            "fc3_weight": mx.nd.array(
                (rng.randn(16, 256) / 16).astype("f")),
            "fc3_bias": mx.nd.array(np.zeros(16, "f")),
        }
        return sym, args, {}, example
    if network == "mlp-wide":
        # the obs-overhead probe's workload: same shape as "mlp" but
        # wide enough that a batch's execute time is serving-realistic
        # (hundreds of us on the CPU tier) — a model whose whole batch
        # costs less than a Python function call would measure the
        # interpreter, not the telemetry
        data = mx.sym.Variable("data")
        net = mx.symbol.FullyConnected(data, num_hidden=512, name="fc1")
        net = mx.symbol.Activation(net, act_type="relu", name="relu1")
        net = mx.symbol.FullyConnected(net, num_hidden=512, name="fc2")
        net = mx.symbol.Activation(net, act_type="relu", name="relu2")
        net = mx.symbol.FullyConnected(net, num_hidden=16, name="fc3")
        sym = mx.symbol.SoftmaxOutput(net, name="softmax")
        example = (64,)
        args = {
            "fc1_weight": mx.nd.array(
                (rng.randn(512, 64) / 8).astype("f")),
            "fc1_bias": mx.nd.array(np.zeros(512, "f")),
            "fc2_weight": mx.nd.array(
                (rng.randn(512, 512) / 23).astype("f")),
            "fc2_bias": mx.nd.array(np.zeros(512, "f")),
            "fc3_weight": mx.nd.array(
                (rng.randn(16, 512) / 23).astype("f")),
            "fc3_bias": mx.nd.array(np.zeros(16, "f")),
        }
        return sym, args, {}, example
    if network == "resnet-50":
        from mxnet_tpu import models
        sym = models.get_symbol("resnet-50", num_classes=1000,
                                layout="NHWC")
        example = (224, 224, 3)
        # Xavier-init through a throwaway CPU module
        import mxnet_tpu as mx
        mod = mx.mod.Module(symbol=sym, context=mx.cpu())
        mod.bind(for_training=False,
                 data_shapes=[mx.io.DataDesc("data", (1,) + example)])
        mod.init_params(initializer=mx.init.Xavier(magnitude=2.0))
        arg_p, aux_p = mod.get_params()
        return sym, arg_p, aux_p, example
    raise SystemExit("unknown network %r (mlp, resnet-50)" % network)


def single_request_baseline(sym, args, aux, example, n=300, seed=1):
    """The pre-serving path: one Predictor, one request at a time.
    Returns sustained rate + latency percentiles."""
    import tempfile
    import mxnet_tpu as mx
    from mxnet_tpu.predictor import Predictor

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "m.params")
        blob = {"arg:" + k: v for k, v in args.items()}
        blob.update({"aux:" + k: v for k, v in aux.items()})
        mx.nd.save(path, blob)
        with open(path, "rb") as f:
            param_bytes = f.read()
    p = Predictor(sym.tojson(), param_bytes, {"data": (1,) + example})
    rng = np.random.RandomState(seed)
    x = rng.randn(1, *example).astype("f")
    for _ in range(5):                         # compile + warm
        p.predict(data=x)
    lat = []
    t0 = time.perf_counter()
    for _ in range(n):
        t1 = time.perf_counter()
        p.predict(data=x)
        lat.append(time.perf_counter() - t1)
    elapsed = time.perf_counter() - t0
    lat_ms = np.sort(np.asarray(lat)) * 1e3
    return {
        "requests": n,
        "rps": round(n / elapsed, 1),
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
    }


# ----------------------------------------------------------------------
def _mixed_payloads(example, rows_mix, count, seed):
    rng = np.random.RandomState(seed)
    sizes = rng.choice(rows_mix, size=count)
    return [rng.randn(int(s), *example).astype("f") for s in sizes]


def arrival_schedule(n, rate_rps, seed):
    """A Poisson open-loop arrival schedule: ``n`` cumulative arrival
    times at ``rate_rps``, SEEDED and reusable — the autotuner compares
    two configs against the *identical* arrival sequence instead of two
    random draws (numpy's ``exponential(scale)`` is ``scale *``
    standard draws, so the same seed at any rate yields the same
    sequence shape, just rescaled)."""
    rng = np.random.RandomState(seed)
    return np.cumsum(rng.exponential(1.0 / rate_rps, size=n))


def _open_loop_submit(server, payloads, rate_rps, model=None, seed=2,
                      shed_exceptions=(), arrivals=None,
                      input_name="data"):
    """The shared open-loop arrival engine: a Poisson schedule fixed up
    front (``arrivals`` — or drawn here from ``seed``) and honored
    regardless of how far behind the server falls.  Submits shed with
    one of ``shed_exceptions`` are counted (and timed) instead of
    raised.  Returns
    ``(futures, rejected, reject_max_ms, submit_elapsed_s, t0)``."""
    if arrivals is None:
        arrivals = arrival_schedule(len(payloads), rate_rps, seed)
    futures = []
    rejected, reject_max_ms = 0, 0.0
    t0 = time.perf_counter()
    i = 0
    while i < len(payloads):
        now = time.perf_counter() - t0
        while i < len(payloads) and arrivals[i] <= now:
            ts = time.perf_counter()
            try:
                futures.append(server.submit(
                    {input_name: payloads[i]}, model=model))
            except shed_exceptions:
                rejected += 1
                reject_max_ms = max(
                    reject_max_ms, (time.perf_counter() - ts) * 1e3)
            i += 1
        if i < len(payloads):
            time.sleep(min(0.002, max(0.0, arrivals[i]
                                      - (time.perf_counter() - t0))))
    return (futures, rejected, reject_max_ms,
            time.perf_counter() - t0, t0)


def poisson_run(server, payloads, rate_rps, model=None, seed=2,
                arrivals=None, input_name="data"):
    """Open-loop Poisson arrivals at ``rate_rps`` requests/s (a shed —
    possible since queues are bounded by default — propagates: this
    sweep stays at loads the server keeps up with)."""
    futures, _, _, _, t0 = _open_loop_submit(server, payloads, rate_rps,
                                             model=model, seed=seed,
                                             arrivals=arrivals,
                                             input_name=input_name)
    ok, failed, lat = 0, 0, []
    for f in futures:
        try:
            f.result(timeout=60)
            ok += 1
            lat.append(f.latency_s)
        except Exception:                          # noqa: BLE001
            failed += 1
    elapsed = time.perf_counter() - t0
    rows = int(sum(p.shape[0] for p in payloads))
    out = {
        "offered_rps": round(rate_rps, 1),
        "requests": len(payloads),
        "completed": ok,
        "failed": failed,
        "achieved_rps": round(ok / elapsed, 1),
        "achieved_rows_per_sec": round(rows / elapsed, 1),
    }
    if lat:
        lat_ms = np.sort(np.asarray(lat)) * 1e3
        out.update({
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
            "max_ms": round(float(lat_ms[-1]), 3),
        })
    return out


def overload_run(server, payloads, rate_rps, deadline_s, model=None,
                 seed=2, arrivals=None):
    """Open-loop arrivals at ``rate_rps`` against a server with
    admission control on.  A submit the server sheds
    (:class:`ServeOverload` / :class:`ServeUnavailable`) counts as a
    fast rejection — the whole point is that saying *no* takes
    microseconds; ``reject_max_ms`` records the slowest one."""
    from mxnet_tpu.serving import ServeOverload, ServeUnavailable

    futures, rejected, reject_max_ms, submit_elapsed, t0 = \
        _open_loop_submit(server, payloads, rate_rps, model=model,
                          seed=seed, arrivals=arrivals,
                          shed_exceptions=(ServeOverload,
                                           ServeUnavailable))
    good, late, failed, lat = 0, 0, 0, []
    for f in futures:
        try:
            f.result(timeout=60)
            lat.append(f.latency_s)
            if f.latency_s <= deadline_s:
                good += 1
            else:
                late += 1
        except Exception:                          # noqa: BLE001
            failed += 1                            # shed/expired in queue
    elapsed = time.perf_counter() - t0
    n = len(payloads)
    out = {
        "offered_rps": round(rate_rps, 1),
        # the open loop can only offer as fast as one thread submits;
        # report what was actually put on the wire so a saturated
        # producer is visible, not silently flattering
        "arrived_rps": round(n / submit_elapsed, 1),
        "requests": n,
        "accepted": len(futures),
        "rejected_at_submit": rejected,
        "reject_max_ms": round(reject_max_ms, 3),
        "completed_in_deadline": good,
        "completed_late": late,
        "failed": failed,
        "goodput_rps": round(good / elapsed, 1),
        "shed_rate": round((rejected + failed + late) / n, 4),
    }
    if lat:
        lat_ms = np.sort(np.asarray(lat)) * 1e3
        out.update({
            "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        })
    return out


def overload_probe(network="mlp", quick=True, buckets=None,
                   load_factors=None, seed=0):
    """Goodput-under-overload sweep: offered load 1x-8x capacity with
    the ``reject`` shedding policy, a bounded queue, and a per-request
    deadline.  Returns the INFER_BENCH ``overload`` section, including
    the degradation verdict (goodput at max load >= 0.9x goodput at
    1x) that ``tests/test_serving_overload.py`` asserts."""
    from mxnet_tpu import serving

    sym, args, aux, example = build_model(network, seed)
    load_factors = sorted(load_factors or (1.0, 2.0, 4.0, 8.0))
    n_base = 120 if quick else 300
    per_load = 250 if quick else 1000
    deadline_ms = 250          # generous at 1x even on a loaded host;
    queue_cap = 64             # ~2 full batches of backlog bounds p99

    base = single_request_baseline(sym, args, aux, example, n=n_base)
    cap = base["rps"]

    loads = []
    for f in load_factors:
        server = serving.ModelServer(
            buckets=buckets, queue_cap=queue_cap, shed_policy="reject",
            timeout_ms=deadline_ms)
        server.add_model("m", sym, args, aux,
                         input_shapes={"data": example})
        with server:
            rng = np.random.RandomState(seed + int(f * 10))
            payloads = [rng.randn(1, *example).astype("f")
                        for _ in range(per_load)]
            run = overload_run(server, payloads,
                               rate_rps=max(1.0, f * cap),
                               deadline_s=deadline_ms / 1e3)
            server.assert_no_retrace()
            st = server.stats()
        run["load_factor"] = f
        run["shed_deadline"] = st["shed_deadline"]
        run["expired_after_dispatch"] = st["expired_after_dispatch"]
        loads.append(run)
    # the degradation baseline is the 1x run when swept (the honest
    # "at capacity" anchor); with custom factors the lowest one is the
    # baseline and base_load_factor says so — never mislabeled as 1x
    base_f = 1.0 if 1.0 in load_factors else load_factors[0]
    g1 = next(r["goodput_rps"] for r in loads
              if r["load_factor"] == base_f)
    gmax = loads[-1]["goodput_rps"]
    return {
        "network": network,
        "policy": {"shed_policy": "reject", "queue_cap_rows": queue_cap,
                   "deadline_ms": deadline_ms},
        "single_request_rps": cap,
        "loads": loads,
        "base_load_factor": base_f,
        "goodput_base_rps": g1,
        "goodput_max_load_rps": gmax,
        "max_load_factor": load_factors[-1],
        "degradation_ratio": round(gmax / g1, 3) if g1 else None,
        # the invariant: past saturation goodput stays FLAT (>= 0.9x
        # the 1x goodput) because the excess is shed at admission, not
        # queued into everyone's p99
        "degradation_ok": bool(g1 and gmax >= 0.9 * g1),
        "retraces": 0,         # assert_no_retrace() passed per factor
    }


def fault_demo(server, example, model=None, n=12, seed=3):
    """One poisoned + two slow requests inside a burst: the poisoned
    future fails ALONE, everything else completes (docs/how_to/
    resilience.md meets docs/how_to/serving.md)."""
    from mxnet_tpu import faults
    rng = np.random.RandomState(seed)
    base_rid = server.stats()["requests"]
    spec = ("poison_request@request=%d;slow_request@request=%d:count=2"
            % (base_rid + 3, base_rid + 5))
    with faults.injected(spec):
        futs = [server.submit(data=rng.randn(1, *example).astype("f"),
                              model=model) for _ in range(n)]
        poisoned = sum(1 for f in futs if f.exception(timeout=60)
                       is not None)
    lat_ms = sorted((f.latency_s or 0) * 1e3 for f in futs)
    return {"injected": spec, "requests": n, "failed": poisoned,
            "completed": n - poisoned,
            "p99_ms": round(float(np.percentile(lat_ms, 99)), 3)}


# ----------------------------------------------------------------------
def _warm_restart_probe(serving, sym, args, aux, example, buckets):
    """The serving cold-start with a WARM program cache
    (docs/how_to/compiled_programs.md): against a probe-local cache
    dir (or the caller's MXTPU_PROGRAM_CACHE), one untimed start()
    fills the cache, then — with the in-memory keyed cache cleared, a
    fresh process's state — a timed start() deserializes every bucket
    executable instead of compiling it.  Runs AFTER the main sweep so
    the sweep's `aot_compile_s` stays a pure trace+compile figure
    (persist cost never rides the cold timing), and cleans up its env
    var / temp dir on every exit path."""
    import shutil as _shutil
    import tempfile as _tempfile
    own_cache = None
    had_cache = os.environ.get("MXTPU_PROGRAM_CACHE")
    try:
        if not had_cache:
            own_cache = _tempfile.mkdtemp(
                prefix="mxtpu-serve-progcache-")
            os.environ["MXTPU_PROGRAM_CACHE"] = own_cache

        def fresh_start():
            serving.clear_cache()
            srv = serving.ModelServer(buckets=buckets)
            srv.add_model("m", sym, args, aux,
                          input_shapes={"data": example})
            t0 = time.perf_counter()
            srv.start()
            dt = time.perf_counter() - t0
            loaded = srv.stats()["warmup_loaded"]
            srv.stop()
            return dt, loaded

        fresh_start()                      # fill the cache (untimed)
        return fresh_start()               # measure the warm restart
    finally:
        if own_cache is not None:
            os.environ.pop("MXTPU_PROGRAM_CACHE", None)
            _shutil.rmtree(own_cache, ignore_errors=True)


def serving_probe(network="mlp", quick=True, buckets=None,
                  rows_mix=(1, 2, 4), load_factors=None, seed=0):
    """The full sweep; returns the INFER_BENCH ``serving`` section."""
    from mxnet_tpu import serving

    sym, args, aux, example = build_model(network, seed)
    n_base = 150 if quick else 400
    per_load = 250 if quick else 1000
    load_factors = list(load_factors
                        or ((0.5, 1.0, 2.0) if quick
                            else (0.25, 0.5, 1.0, 2.0, 4.0)))

    base = single_request_baseline(sym, args, aux, example, n=n_base)
    cap = base["rps"]

    server = serving.ModelServer(buckets=buckets)
    server.add_model("m", sym, args, aux,
                     input_shapes={"data": example})
    # the COLD timing must stay pure trace+compile even when the
    # operator exports MXTPU_PROGRAM_CACHE (a populated dir would turn
    # this into a disk load and make the cold/warm comparison vacuous);
    # _warm_restart_probe measures the cache path separately
    _prior_cache = os.environ.pop("MXTPU_PROGRAM_CACHE", None)
    try:
        t0 = time.perf_counter()
        server.start()
        aot_s = time.perf_counter() - t0
    finally:
        if _prior_cache is not None:
            os.environ["MXTPU_PROGRAM_CACHE"] = _prior_cache

    loads = []
    with server:
        for f in load_factors:
            payloads = _mixed_payloads(example, rows_mix, per_load,
                                       seed + int(f * 100))
            run = poisson_run(server, payloads, rate_rps=max(1.0, f * cap))
            run["load_factor"] = f
            loads.append(run)
        server.assert_no_retrace()     # mixed shapes, zero retraces
        st = server.stats()
        demo = fault_demo(server, example)
    warm_s, warm_loaded = _warm_restart_probe(serving, sym, args, aux,
                                              example, buckets)
    return {
        "network": network,
        "buckets": st["buckets"],
        "request_rows_mix": list(int(r) for r in rows_mix),
        "aot_compiles": st["aot_compiles"],
        "aot_compile_s": round(aot_s, 2),
        # server cold start, cold vs warm program cache: warmup_s_cold
        # traces+compiles every bucket, warmup_s_warm deserializes them
        # (warmup_loaded_warm counts the skipped execute-once warmups)
        "warmup_s_cold": round(aot_s, 3),
        "warmup_s_warm": round(warm_s, 3),
        "warmup_loaded_warm": warm_loaded,
        "retraces": st["retraces"],
        "single_request": base,
        "loads": loads,
        "occupancy": st["occupancy"],
        "padding_frac": st["padding_frac"],
        # fixed-bucket percentiles over every COMPLETED request of the
        # sweep (the registry-backed histogram behind stats(); the
        # p50/p99 above are exact per-load sorts, this is what a
        # steady-state scrape of the server itself reports)
        "latency_hist_ms": {name: pm["latency_ms"]
                            for name, pm in st["per_model"].items()},
        "batched_ge_single": all(
            r["achieved_rps"] >= min(r["offered_rps"], cap) * 0.95
            for r in loads),
        "fault_demo": demo,
    }


# ----------------------------------------------------------------------
def quant_probe(quick=True, seed=0, vocab=400_000, dim=512, slots=256,
                classes=32, rows=32, requests=None):
    """Quantized serving vs f32, same arrivals: the INFER_BENCH
    ``quant`` section.

    The workload is the case int8 serving exists for — a bag-of-ids
    pooling ranker whose per-request cost is gathering ``rows x slots``
    random rows out of a table far bigger than any cache
    (``vocab x dim`` f32 = hundreds of MB).  The table is quantized
    through the full deploy path (``calibrate_model`` -> accuracy gate
    -> int8-tier tenant), so the section carries the gate verdict next
    to the latency numbers: a speed win that failed its accuracy gate
    is not reportable.  Only the table is quantized
    (``quantize_op_names=("Embedding",)``) — dense-layer dequant GEMMs
    are a per-platform call the autotuner owns, while the
    gather-then-dequant pattern (1 byte/row-element moved instead of 4,
    dequantized AFTER the gather against per-row scales) wins on
    bandwidth on every tier.

    Both tenants serve IDENTICAL seeded Poisson arrivals at
    ``rows``-row payloads (>= 32 per the acceptance bar — at batch 1
    the dequant overhead wins instead, see ``benchmark_score.py``
    ``vs_f32``), and the probe re-binds the quantized model under the
    warm program cache to assert ZERO compiles (the quantized tier is a
    first-class program-cache citizen, not a retrace source)."""
    from mxnet_tpu import program, serving
    from mxnet_tpu.contrib import quantization
    import mxnet_tpu as mx
    from tools.quantize import demo_pool_ranker, evaluate_gate, score

    demo = demo_pool_ranker(seed=seed, vocab=vocab, dim=dim,
                            slots=slots, classes=classes,
                            n_holdout=256)
    it = mx.io.NDArrayIter({"ids": demo["calib"]["ids"]}, None, 64)
    qsym, qargs, qaux, calib = quantization.calibrate_model(
        demo["sym"], demo["args"], demo["aux"], calib_iter=it,
        quantize_op_names=("Embedding",))

    ref = score(demo["sym"], demo["args"], demo["aux"],
                demo["holdout"], demo["data_names"], 64)
    got = score(qsym, qargs, qaux, demo["holdout"],
                demo["data_names"], 64)
    from mxnet_tpu import envknobs
    gate = evaluate_gate(
        ref, got, demo["labels"],
        envknobs.get_float("MXTPU_QUANT_MIN_AGREEMENT", 0.99),
        envknobs.get_float("MXTPU_QUANT_MAX_TOP1_DELTA", 0.5))
    gate["calibration_digest"] = calib.digest

    n_req = requests or (80 if quick else 400)
    rng = np.random.RandomState(seed + 7)
    payloads = [rng.randint(0, vocab, (rows, slots)).astype(np.int32)
                for _ in range(n_req)]

    def make_server(precision, sym, args, aux):
        srv = serving.ModelServer(buckets=[rows], max_wait_us=200,
                                  precision=precision)
        srv.add_model("ranker", sym, args, aux,
                      input_shapes={"ids": (slots,)})
        return srv

    # capacity estimate on the f32 tenant -> one arrival schedule BOTH
    # tenants replay (identical offered load, identical sequence)
    with make_server("float32", demo["sym"], demo["args"],
                     demo["aux"]) as srv:
        srv.predict(ids=payloads[0])                       # warm
        t0 = time.perf_counter()
        for p in payloads[:10]:
            srv.predict(ids=p)
        per_req = (time.perf_counter() - t0) / 10
    rate = 0.6 / per_req
    arrivals = arrival_schedule(n_req, rate, seed + 11)

    runs = {}
    for precision, (s, a, x) in (
            ("float32", (demo["sym"], demo["args"], demo["aux"])),
            ("int8", (qsym, qargs, qaux))):
        with make_server(precision, s, a, x) as srv:
            runs[precision] = poisson_run(srv, payloads, rate,
                                          arrivals=arrivals,
                                          input_name="ids")
            srv.assert_no_retrace()
            st = srv.stats()
            runs[precision]["weight_bytes_on_device"] = \
                st["per_model"]["ranker"]["weight_bytes_on_device"]

    f32, q = runs["float32"], runs["int8"]
    vs = {"p50": round(f32["p50_ms"] / q["p50_ms"], 3),
          "p99": round(f32["p99_ms"] / q["p99_ms"], 3),
          "goodput_rows_per_sec": round(
              q["achieved_rows_per_sec"]
              / f32["achieved_rows_per_sec"], 3),
          "weight_bytes": round(
              f32["weight_bytes_on_device"]
              / q["weight_bytes_on_device"], 2)}

    # warm-cache re-bind: constructing the SAME quantized tenant again
    # must compile nothing — loads/hits only (program keys carry the
    # quant tag, so the int8 tier has its own stable entries)
    cache_was = os.environ.get("MXTPU_PROGRAM_CACHE")
    if not cache_was:
        import tempfile
        os.environ["MXTPU_PROGRAM_CACHE"] = tempfile.mkdtemp(
            prefix="mxtpu-quant-bench-")
    try:
        with make_server("int8", qsym, qargs, qaux) as srv:
            srv.predict(ids=payloads[0])                   # seed cache
        with program.stats_delta() as warm:
            with make_server("int8", qsym, qargs, qaux) as srv:
                srv.predict(ids=payloads[0])
    finally:
        if not cache_was:
            os.environ.pop("MXTPU_PROGRAM_CACHE", None)

    return {
        "model": {"network": "pool-ranker", "vocab": vocab, "dim": dim,
                  "slots": slots, "classes": classes,
                  "quantized": "embedding table (per-row scales, "
                               "dequant after gather)",
                  "config": calib.config},
        "gate": gate,
        "request_rows": rows,
        "offered_rps": round(rate, 1),
        "f32": f32,
        "int8": q,
        "vs_f32": vs,
        "warm_cache": {"compiles": warm["compiles"],
                       "loads": warm["loads"],
                       "cache_hit": warm["cache_hit"]},
        "retraces": 0,
    }


# ----------------------------------------------------------------------
def _fleet_window(fleet, payloads, rate_rps, seed, deadline_s,
                  trigger_i=None, trigger=None):
    """Open-loop Poisson window against a :class:`FleetRouter`, with an
    optional mid-window ``trigger`` (kill / rollout) fired from a side
    thread when arrival ``trigger_i`` is reached.  Returns per-arrival
    records ``(segment, outcome, latency_s)`` — outcome ``good`` /
    ``late`` / ``shed`` (synchronous refusal after failover retries) /
    ``dropped`` (an accepted future that later failed) — plus the
    segment wallclock boundaries for per-segment goodput."""
    n = len(payloads)
    arrivals = arrival_schedule(n, rate_rps, seed)
    futures, shed = [None] * n, [False] * n
    thr = None
    t0 = time.perf_counter()
    i = 0
    while i < n:
        now = time.perf_counter() - t0
        while i < n and arrivals[i] <= now:
            if trigger is not None and thr is None and i >= trigger_i:
                thr = threading.Thread(target=trigger, daemon=True)
                thr.start()
            try:
                futures[i] = fleet.submit({"data": payloads[i]})
            except Exception:                      # noqa: BLE001
                shed[i] = True                     # refused even after
            i += 1                                 # failover retries
        if i < n:
            time.sleep(min(0.002, max(0.0, arrivals[i]
                                      - (time.perf_counter() - t0))))
    if thr is not None:
        thr.join(timeout=120)
    records = []
    for k in range(n):
        seg = min(2, 3 * k // n)
        if shed[k]:
            records.append((seg, "shed", None))
            continue
        try:
            futures[k].result(timeout=60)
            lat = futures[k].latency_s
            records.append((seg, "good" if lat <= deadline_s else "late",
                            lat))
        except Exception:                          # noqa: BLE001
            records.append((seg, "dropped", None))
    elapsed = time.perf_counter() - t0
    bounds = [arrivals[0], arrivals[n // 3], arrivals[2 * n // 3],
              arrivals[-1]]
    return records, bounds, elapsed


def _segment_goodput(records, bounds):
    """Per-arrival-third (goodput_rps, in-deadline fraction) pairs.
    The RATE carries the Poisson draw's own variance (a third whose
    exponential gaps ran long divides by a bigger denominator); the
    FRACTION of offered requests served in deadline is what recovery
    is judged on — the offered process is identical-rate across
    segments, so fraction ratios isolate the service effect."""
    segs = []
    for s in range(3):
        n = sum(1 for seg, _, _ in records if seg == s)
        good = sum(1 for seg, out, _ in records if seg == s
                   and out == "good")
        dur = bounds[s + 1] - bounds[s]
        segs.append((round(good / dur, 1) if dur > 0 else 0.0,
                     round(good / n, 4) if n else 0.0))
    return segs


def fleet_probe(network="mlp", quick=True, replicas=3, pace_rps=120.0,
                seed=0):
    """The replicated-tier sweep: the INFER_BENCH ``fleet`` section.

    Three windows against a :class:`~mxnet_tpu.serving.FleetRouter`,
    each replica paced to ``pace_rps`` rows/s (``MXTPU_SERVE_PACE_RPS``
    semantics: a fixed per-replica service rate, so on the 1-2 core CPU
    tier the fleet properties measured here — scaling, failover,
    rollout — are properties of the ROUTER, not of how many host cores
    the replicas fight over):

    * **scaling** — the same offered load (0.9x the 3-replica capacity)
      and the same arrival schedule against ONE replica and against the
      fleet.  The single replica is capacity-bound and sheds the rest;
      the gate is ``fleet_goodput_rps >= 2.2x single_goodput_rps``.
    * **churn** — moderate load (0.6x capacity), one replica killed at
      the 1/3 mark; its in-flight futures fail fast, traffic re-spreads,
      autoheal respawns a warm replacement.  The gate compares
      last-third goodput to first-third: ``recovery_ratio >= 0.9``.
    * **rollout** — same load, ``roll_weights`` fired at the 1/3 mark
      (drain -> hot-swap -> canary per replica).  The gates:
      ``dropped == 0`` (every accepted request completes), zero
      retraces, and ``spinup_compiles == 0`` across every fleet
      spin-up, heal and swap (warm starts only).
    """
    from mxnet_tpu.serving import FleetRouter, ReplicaSpec

    sym, args, aux, example = build_model(network, seed)
    deadline_ms = 500
    spec = ReplicaSpec(sym, args, aux, {"data": example},
                       server_kw=dict(buckets=[1, 2, 4, 8],
                                      queue_cap=32, shed_policy="reject",
                                      timeout_ms=deadline_ms,
                                      max_wait_us=500,
                                      pace_rps=pace_rps))
    scale = 1 if quick else 2
    deadline_s = deadline_ms / 1e3
    rng = np.random.RandomState(seed + 1)

    def payload_set(n):
        return [rng.randn(1, *example).astype("f") for _ in range(n)]

    # -- scaling: identical offered load + arrival schedule, 1 vs N ----
    offered = replicas * pace_rps * 0.9
    n_scale = int(600 * scale)
    payloads = payload_set(n_scale)
    arrivals = arrival_schedule(n_scale, offered, seed + 2)
    with spec.build() as srv:          # also warms the compile caches:
        single = overload_run(srv, payloads, offered, deadline_s,
                              model=spec.model, arrivals=arrivals)
        srv.assert_no_retrace()        # every later spin-up must be 0
    spinup_compiles = 0
    with FleetRouter(spec, n=replicas, check_interval_s=0.2,
                     seed=seed) as fleet:
        fleet_run = overload_run(fleet, payloads, offered, deadline_s,
                                 arrivals=arrivals)
        fleet.assert_no_retrace()
        st = fleet.stats()
        spinup_compiles += sum(r["spinup_compiles"]
                               for r in st["replicas"].values())
        retraces_scaling = st["merged"].get("retraces", 0)
    scaling_x = (round(fleet_run["goodput_rps"] / single["goodput_rps"],
                       2) if single["goodput_rps"] else None)

    # -- churn: kill one replica at the 1/3 mark, autoheal ------------
    offered_mid = replicas * pace_rps * 0.6
    n_mid = int(540 * scale)
    with FleetRouter(spec, n=replicas, check_interval_s=0.1,
                     seed=seed) as fleet:
        recs, bounds, _ = _fleet_window(
            fleet, payload_set(n_mid), offered_mid, seed + 3, deadline_s,
            trigger_i=n_mid // 3,
            trigger=lambda: fleet.kill_replica(fleet.live_replicas()[0]))
        # give autoheal until end-of-window accounting to be visible
        segs = _segment_goodput(recs, bounds)
        st = fleet.stats()
        healed = len(fleet.live_replicas()) == replicas
        spinup_compiles += sum(r["spinup_compiles"]
                               for r in st["replicas"].values())
        churn = {
            "offered_rps": round(offered_mid, 1),
            "killed_at_request": n_mid // 3,
            "failed_fast": sum(1 for _, out, _ in recs
                               if out == "dropped"),
            "segment_goodput_rps": [s[0] for s in segs],
            "segment_good_frac": [s[1] for s in segs],
            # last third vs first third, on the in-deadline FRACTION of
            # the identical-rate offered process (see _segment_goodput)
            "recovery_ratio": (round(segs[2][1] / segs[0][1], 3)
                               if segs[0][1] else None),
            "healed": healed,
            "epoch": fleet.epoch,
            "failovers": st["router"]["failovers"],
        }

    # -- rollout: zero dropped requests across a full weight roll -----
    args2 = {k: v * 1.001 for k, v in args.items()}
    roll_res = {}
    with FleetRouter(spec, n=replicas, check_interval_s=0.2,
                     seed=seed) as fleet:
        def do_roll():
            roll_res.update(fleet.roll_weights(args2, aux, version=2,
                                               drain_s=5.0))

        recs, bounds, elapsed = _fleet_window(
            fleet, payload_set(n_mid), offered_mid, seed + 4, deadline_s,
            trigger_i=n_mid // 3, trigger=do_roll)
        fleet.assert_no_retrace()
        st = fleet.stats()
        spinup_compiles += sum(r["spinup_compiles"]
                               for r in st["replicas"].values())
        good = sum(1 for _, out, _ in recs if out == "good")
        rollout = {
            "offered_rps": round(offered_mid, 1),
            "rolled_at_request": n_mid // 3,
            "requests": n_mid,
            "completed_in_deadline": good,
            "completed_late": sum(1 for _, out, _ in recs
                                  if out == "late"),
            "shed": sum(1 for _, out, _ in recs if out == "shed"),
            "dropped": sum(1 for _, out, _ in recs
                           if out == "dropped"),
            "goodput_rps": round(good / elapsed, 1),
            "swapped": roll_res.get("swapped"),
            "rolled_back": roll_res.get("rolled_back"),
            "version": st["version"],
        }

    return {
        "network": network,
        "replicas": replicas,
        "policy": os.environ.get("MXTPU_ROUTER_POLICY", "p2c"),
        "pace_rps_per_replica": pace_rps,
        "deadline_ms": deadline_ms,
        "offered_rps": round(offered, 1),
        "single": single,
        "fleet": fleet_run,
        "single_goodput_rps": single["goodput_rps"],
        "fleet_goodput_rps": fleet_run["goodput_rps"],
        "fleet_scaling_x": scaling_x,
        "churn": churn,
        "rollout": rollout,
        "spinup_compiles": spinup_compiles,
        "retraces": int(retraces_scaling),
        # the gates ``main`` exits non-zero on, in one place
        "scaling_ok": bool(scaling_x and scaling_x >= 2.2),
        "recovery_ok": bool(churn["recovery_ratio"]
                            and churn["recovery_ratio"] >= 0.9),
        "rollout_ok": bool(rollout["dropped"] == 0
                           and not rollout["rolled_back"]
                           and spinup_compiles == 0),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--network", default="mlp",
                    help="mlp (CPU-fast) or resnet-50")
    ap.add_argument("--quick", action="store_true",
                    help="bounded sweep")
    ap.add_argument("--buckets", default=None,
                    help="comma batch buckets (default MXTPU_SERVE_BUCKETS"
                         " or 1,4,8,16,32)")
    ap.add_argument("--rows-mix", default="1,2,4",
                    help="comma request row counts to mix")
    ap.add_argument("--out", default=None,
                    help="merge 'serving' + 'overload' sections into "
                         "this INFER_BENCH.json artifact")
    ap.add_argument("--no-overload", action="store_true",
                    help="skip the goodput-under-overload sweep")
    ap.add_argument("--quant", action="store_true",
                    help="also run the quantized-vs-f32 ranker sweep "
                         "(the INFER_BENCH 'quant' section)")
    ap.add_argument("--fleet", action="store_true",
                    help="also run the replicated-tier sweep "
                         "(the INFER_BENCH 'fleet' section)")
    args = ap.parse_args(argv)

    from mxnet_tpu import program
    program.place_compile_cache()
    buckets = [int(b) for b in args.buckets.split(",")] \
        if args.buckets else None
    section = serving_probe(
        network=args.network, quick=args.quick, buckets=buckets,
        rows_mix=tuple(int(r) for r in args.rows_mix.split(",")))
    import jax
    device = "%s (%s)" % (jax.devices()[0].device_kind,
                          jax.default_backend())
    section["device"] = device
    print(json.dumps(section, indent=1))
    overload = None
    if not args.no_overload:
        overload = overload_probe(network=args.network,
                                  quick=args.quick, buckets=buckets)
        overload["device"] = device
        print(json.dumps(overload, indent=1))
        if not overload["degradation_ok"]:
            print("overload degradation invariant FAILED: goodput at "
                  "%sx (%.1f rps) < 0.9x goodput at %sx (%.1f rps)"
                  % (overload["max_load_factor"],
                     overload["goodput_max_load_rps"],
                     overload["base_load_factor"],
                     overload["goodput_base_rps"]), file=sys.stderr)
    quant = None
    if args.quant:
        quant = quant_probe(quick=args.quick)
        quant["device"] = device
        print(json.dumps(quant, indent=1))
    fleet = None
    if args.fleet:
        fleet = fleet_probe(network=args.network, quick=args.quick)
        fleet["device"] = device
        print(json.dumps(fleet, indent=1))
        for gate in ("scaling_ok", "recovery_ok", "rollout_ok"):
            if not fleet[gate]:
                print("fleet gate FAILED: %s" % gate, file=sys.stderr)
    if args.out:
        artifact = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                artifact = json.load(f)
        artifact["serving"] = section
        if overload is not None:
            artifact["overload"] = overload
        if quant is not None:
            artifact["quant"] = quant
        if fleet is not None:
            artifact["fleet"] = fleet
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
            f.write("\n")
        print("wrote serving%s section -> %s"
              % ("" if overload is None else "+overload", args.out),
              file=sys.stderr)
    if overload is not None and not overload["degradation_ok"]:
        return 1
    if fleet is not None and not (fleet["scaling_ok"]
                                  and fleet["recovery_ok"]
                                  and fleet["rollout_ok"]):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
