"""Driver benchmark: ResNet-50 ImageNet training throughput (img/s) on one
chip through the **Module path** — the same code path as
``examples/image-classification/train_imagenet.py`` (``Module.fit``'s inner
loop: ``forward(is_train=True)``, ``update()``, ``update_metric``), with
``kvstore=dist_sync_tpu`` and synthetic data (the reference's
``--benchmark 1`` mode).  The Module auto-routes onto the fused Trainer:
fwd+bwd+allreduce+SGD-momentum update as ONE jitted XLA computation, bf16
compute with f32 master weights.

Real-data pipeline, measured in TWO configurations (docs/how_to/perf.md
"Input pipeline"):

* **cached** (the TPU-native steady state): the decoded dataset lives in
  HBM (``io.DeviceCacheIter``); per-batch host traffic is one index
  vector, crop/mirror run on-chip.  This is the headline
  ``pipeline_img_per_sec``.
* **stream** (datasets beyond device memory): the OVERLAPPED pipeline —
  RecordIO -> native C++ JPEG decode (uint8 NHWC, crop before the wire)
  -> ``DeviceUploadIter`` chunked async H2D staging (batch N+1 ships
  while batch N computes) -> ``StreamAugmentIter`` on-device mirror ->
  fused step.  Bound is ``max(decode, wire, compute)`` per batch, not
  their sum; reported as ``stream_*`` fields incl.
  ``stream_overlap_efficiency``.

Each timed window is preceded by one warm-up cycle that compiles the
step and is closed by a ``metric.get()`` drain.

Baseline: the reference's best published single-device number — ResNet-50
batch-32 training on P100, 181.53 img/s (``docs/how_to/perf.md:151-183``,
copied in BASELINE.md).  Prints ONE JSON line.
"""
import json
import os

import sys
import time

import numpy as np

BASELINE_IMG_S = 181.53  # reference single-P100 ResNet-50 train, batch 32
PIPE_BATCH = 256
PIPE_IMAGES = 512


def _pipe_steps():
    return int(os.environ.get("MXTPU_BENCH_PIPELINE_STEPS", "24"))


def _ensure_rec(n_images=PIPE_IMAGES):
    """Synthetic 256x256 JPEG RecordIO file (created once, reused)."""
    from mxnet_tpu import recordio
    rec_path = "/tmp/mxtpu_bench_%d.rec" % n_images
    if not os.path.exists(rec_path):
        from PIL import Image
        import io as pio
        rng = np.random.RandomState(0)
        tmp_path = rec_path + ".tmp.%d" % os.getpid()
        rec = recordio.MXRecordIO(tmp_path, "w")
        for i in range(n_images):
            img = Image.fromarray(
                rng.randint(0, 255, (256, 256, 3), dtype=np.uint8))
            buf = pio.BytesIO()
            img.save(buf, format="JPEG", quality=90)
            rec.write(recordio.pack(
                recordio.IRHeader(0, float(i % 1000), i, 0), buf.getvalue()))
        rec.close()
        os.rename(tmp_path, rec_path)   # atomic: no truncated cache reuse
    return rec_path


def _build_module(mx, models, batch, image):
    # channels-last: the TPU-native layout (lanes = channels keeps convs
    # on the MXU without relayout transposes); ~6% over NCHW here.  The
    # remaining ceiling is HBM bandwidth: tools/roofline.py measures this
    # chip at ~181 TF/s bf16 / ~587 GB/s (ROOFLINE.json); XLA's cost
    # analysis puts the step's byte traffic at the bandwidth roofline, so
    # the step runs ~37% MFU — ResNet's low-arithmetic-intensity stages
    # (stem, BN, early blocks) are bandwidth-bound, not MXU-bound.
    sym = models.get_symbol("resnet-50", num_classes=1000, layout="NHWC")
    mod = mx.mod.Module(context=mx.tpu(), symbol=sym,
                        compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", (batch, image, image, 3))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2))
    kv = mx.kvstore.create("dist_sync_tpu")
    mod.init_optimizer(kvstore=kv, optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / batch})
    assert mod._trainer is not None, "bench must measure the fused path"
    return mod


def _timed_window(mod, metric, next_batch, steps, batch):
    """One pipeline window with the NAMED contiguous budget.

    One warm-up cycle closed by a ``metric.get()`` drain compiles the
    step program.  The window's closing ``metric.get()`` is the
    completion barrier: it drains every queued upload and step, so
    ``elapsed`` covers all the real work (on the local chip it agrees
    with ``block_until_ready``, PERF.md).  Budget parts sum to elapsed by
    construction (``budget_coverage``); upload/wire time that overlaps
    dispatch shows up in the dispatch and tail slots."""
    for _ in range(4):
        b = next_batch()
        mod.forward(b, is_train=True)
        mod.update()
        mod.update_metric(metric, b.label)
    metric.get()
    metric.reset()

    in_s = disp_s = met_s = 0.0
    fresh = 0
    t0 = time.perf_counter()
    for _ in range(steps):
        t1 = time.perf_counter()
        b = next_batch()
        t2 = time.perf_counter()
        fresh += batch - (b.pad or 0)  # count only real images
        mod.forward(b, is_train=True)
        mod.update()
        t3 = time.perf_counter()
        mod.update_metric(metric, b.label)
        t4 = time.perf_counter()
        in_s += t2 - t1
        disp_s += t3 - t2
        met_s += t4 - t3
    metric.get()                       # the draining completion barrier
    elapsed = time.perf_counter() - t0
    tail_s = elapsed - in_s - disp_s - met_s
    return {
        "img_per_sec": round(fresh / elapsed, 2),
        "steps_timed": steps,
        "budget_input_wait_s_per_batch": round(in_s / steps, 3),
        "budget_dispatch_s_per_batch": round(disp_s / steps, 3),
        "budget_metric_s_per_batch": round(met_s / steps, 3),
        "budget_tail_barrier_s_per_batch": round(tail_s / steps, 3),
        "budget_coverage": round((in_s + disp_s + met_s + tail_s)
                                 / elapsed, 3),
    }


def _cycling(it):
    """next_batch() that wraps epochs (and resets the epoch iterator)."""
    def next_batch():
        try:
            return it.next()
        except StopIteration:
            it.reset()
            return it.next()
    return next_batch


def _cached_pipeline(mx, mod, metric, steps=None, batch=PIPE_BATCH):
    """HBM-cached real-data pipeline (io.DeviceCacheIter): decode the
    RecordIO set once at storage size, upload once, then gather +
    random-crop + mirror ON CHIP per batch.  Steady-state host traffic:
    one int32 index vector per batch."""
    from mxnet_tpu.io import DeviceCacheIter, NativeImageRecordIter

    steps = _pipe_steps() if steps is None else steps
    rec_path = _ensure_rec()
    loader = NativeImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, 256, 256), batch_size=batch,
        layout="NHWC", output="numpy", dtype="uint8",
        preprocess_threads=max(2, os.cpu_count() or 1))
    t0 = time.perf_counter()
    it = DeviceCacheIter(loader, data_shape=(224, 224), rand_crop=True,
                         rand_mirror=True, shuffle=True, seed=7)
    build_s = time.perf_counter() - t0

    win = _timed_window(mod, metric, _cycling(it), steps, batch)
    out = {"pipeline_img_per_sec": win.pop("img_per_sec"),
           "pipeline_steps_timed": win.pop("steps_timed"),
           "cache_build_s": round(build_s, 2),
           "cache_mb": round(it.cache_nbytes() / 1e6, 1),
           "cache_images": it.num_data}
    out.update({"pipeline_" + k if not k.startswith("budget") else k: v
                for k, v in win.items()})
    return out


class _EndlessIter:
    """Epoch-free view of an iterator: ``next()`` wraps epochs by
    resetting the inner iterator INSIDE the pipeline, so the staging
    worker ahead of it never sees an end-of-epoch and the ring stays
    full across the whole timed window (a 512-image rec at batch 256 is
    a 2-batch epoch — without this the pipeline would drain and refill
    12 times per window)."""

    def __init__(self, it):
        self.it = it
        self.batch_size = it.batch_size
        self.provide_data = it.provide_data
        self.provide_label = it.provide_label

    def next(self):
        try:
            return self.it.next()
        except StopIteration:
            self.it.reset()
            return self.it.next()

    def reset(self):
        self.it.reset()


def _stream_pipeline(mx, mod, metric, staged_img_s, steps=None,
                     batch=PIPE_BATCH):
    """OVERLAPPED streaming pipeline (datasets beyond HBM): RecordIO ->
    native C++ JPEG decode pool (uint8 NHWC host batches; random crop
    happens BEFORE the wire because crop shrinks the bytes shipped) ->
    ``DeviceUploadIter`` (dedicated uploader thread, chunked async H2D
    into committed depth-D staging buffers: batch N+1's wire transfer
    rides under batch N's step) -> ``StreamAugmentIter`` (random mirror
    on device — byte-neutral augments live after the wire) -> fused
    step (on-device u8->bf16 cast).

    The per-batch bound is ``max(decode, h2d, compute)`` — the
    overlapped-pipeline model (tools/step_breakdown.overlap_attribution
    states it once for the bench and the tool) — not their sum;
    ``stream_overlap_efficiency`` reports how much of that bound the
    measured window achieves.  The wire rate inside ``h2d`` is weather
    (15-80 MB/s minutes apart), so compare efficiency, not raw img/s,
    across sessions."""
    import jax
    from mxnet_tpu.io import (DeviceUploadIter, NativeImageRecordIter,
                              StreamAugmentIter)
    from tools.step_breakdown import overlap_attribution

    steps = _pipe_steps() if steps is None else steps
    rec_path = _ensure_rec()

    def make_iter():
        return NativeImageRecordIter(
            path_imgrec=rec_path, data_shape=(3, 224, 224),
            batch_size=batch, rand_crop=True, rand_mirror=False,
            layout="NHWC", output="numpy", dtype="uint8",
            preprocess_threads=max(2, os.cpu_count() or 1))

    # stage budget 1: raw decode rate (loader alone, no model, no H2D).
    # The loader decodes EVERY slot of a batch (wrap-padding included),
    # so a timed call is worth `batch` decodes regardless of pad.
    raw = make_iter()
    probe = next(iter(raw)).data[0]                     # pool warmup
    t0 = time.perf_counter()
    dec_images = 0
    while dec_images < 2 * batch:
        try:
            raw.next()
            dec_images += batch
        except StopIteration:
            raw.reset()
    decode_img_s = dec_images / (time.perf_counter() - t0)

    # stage budget 2: one upload at the bytes the pipeline ships —
    # REAL decoded pixels, not zeros: the transport compresses, and
    # zero-filled probes ship 2-4x faster than image bytes (perf.md),
    # which would overstate the bound and understate the efficiency.
    n_probes = 5
    jax.block_until_ready(jax.device_put(probe))        # warm path
    samples = []
    for _ in range(n_probes):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(probe))
        samples.append(time.perf_counter() - t0)
    samples.sort()
    h2d_s = samples[n_probes // 2]

    # stage budget 3: the step itself, from the synthetic window
    compute_s = batch / staged_img_s if staged_img_s else 0.0

    depth = int(os.environ.get("MXTPU_STREAM_DEPTH", "2"))
    chunks = int(os.environ.get("MXTPU_STREAM_CHUNKS", "4"))
    up = DeviceUploadIter(_EndlessIter(make_iter()), depth=depth,
                          chunks=chunks)
    it = StreamAugmentIter(up, rand_mirror=True, seed=11)
    try:
        win = _timed_window(mod, metric, it.next, steps, batch)
    finally:
        up._shutdown_worker()

    img_s = win.pop("img_per_sec")
    att = overlap_attribution(batch / decode_img_s, h2d_s, compute_s,
                              batch / img_s if img_s else None)
    st = up.stats()
    staged = max(1, st["batches_staged"])
    out = {"img_per_sec": img_s,
           "bound_img_per_sec": round(batch / att["bound_s_per_batch"], 2)
           if att["bound_s_per_batch"] else None,
           "overlap_efficiency": att.get("overlap_efficiency"),
           "binding_stage": att["binding_stage"],
           "exposed_s_per_batch": att.get("exposed_s_per_batch"),
           "decode_img_per_sec": round(decode_img_s, 1),
           "decode_s_per_batch": att["decode_s_per_batch"],
           "h2d_serialize_s_per_batch": round(h2d_s, 3),
           "compute_s_per_batch": att["compute_s_per_batch"],
           "h2d_probes": n_probes,
           "h2d_s_spread": [round(samples[0], 3), round(samples[-1], 3)],
           "pipeline_depth": depth,
           "upload_chunks": chunks,
           "stage_upload_s_per_batch": round(st["upload_s"] / staged, 3),
           "stage_decode_wait_s_per_batch": round(
               st["decode_wait_s"] / staged, 3),
           "ready_ahead_frac": st["ready_ahead_frac"],
           "host_cpu_cores": os.cpu_count()}
    out.update(win)
    return out


class CommModelDrift(RuntimeError):
    """The static comm-plan prediction left the 5% band around the
    analytic gradient-wire model — a GATE failure, distinct from a mere
    trace failure (which reads as ``comm_model_error``)."""


def _assert_comm_model(line, trainer):
    """Fill ``comm_model_gb_per_step`` from the static comm plan and
    assert <= 5% disagreement with the analytic
    ``grad_comm_gb_per_step`` (``line`` may be a bench line or a
    ``zero_ab`` row — both carry the analytic field)."""
    from mxnet_tpu.analysis import comm_passes
    plan = trainer.comm_plan()
    model_gb = comm_passes.plan_wire_gb(plan)
    line["comm_model_gb_per_step"] = round(model_gb, 6)
    analytic_gb = trainer.grad_comm_bytes_per_step() / 1e9
    if abs(model_gb - analytic_gb) > 0.05 * max(analytic_gb, 1e-9):
        raise CommModelDrift(
            "static comm model disagrees with the analytic gradient-"
            "wire model: comm_model_gb_per_step=%.6f vs "
            "grad_comm_gb_per_step=%.6f (>5%%) — the comm-plan byte "
            "predictor (analysis/comm_passes.py) and "
            "collectives.lowp_comm_bytes have drifted"
            % (model_gb, analytic_gb))


class MemModelDrift(RuntimeError):
    """The static liveness peak prediction left the documented band
    around XLA's measured live-buffer accounting — a GATE failure,
    distinct from a mere trace failure (``mem_model_error``)."""


# predicted/measured band for the liveness model.  The static model
# prices every UNFUSED intermediate, so it predictably lands ABOVE
# what fusion actually materializes (calibrated on this CPU tier:
# 1.18x on the resnet-50 bench step, 1.25x on the tune MLP) — the
# band is a drift alarm for the walker (a double-counted body reads
# >=2x, a dropped scope <0.5x), not a byte-exact claim.  Documented in
# docs/how_to/static_analysis.md "Memory analysis".
_MEM_MODEL_BAND = (0.5, 2.0)


def _assert_mem_model(line, trainer, batch_vals):
    """Fill ``mem_model_peak_gb`` from the static liveness timeline
    (``analysis/mem_passes.py``) and assert it stays inside
    ``_MEM_MODEL_BAND`` of the measured live-buffer peak — XLA's
    compiled-step memory accounting (arguments + outputs + temps -
    aliased), the same figure tools/remat_sweep.py reports.  Backends
    whose ``memory_analysis()`` reports nothing get the prediction
    recorded without a gate."""
    predicted = int(trainer.predicted_peak_bytes())
    line["mem_model_peak_gb"] = round(predicted / 1e9, 6)
    from tools.stepcost import compile_step
    comp = compile_step(trainer, batch_vals)
    mem = comp.memory_analysis()
    if mem is None:
        return
    measured = int(mem.argument_size_in_bytes
                   + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    if measured <= 0:
        return
    line["mem_measured_peak_gb"] = round(measured / 1e9, 6)
    ratio = predicted / measured
    line["mem_model_ratio"] = round(ratio, 3)
    lo, hi = _MEM_MODEL_BAND
    if not lo <= ratio <= hi:
        raise MemModelDrift(
            "static memory model disagrees with the measured live-"
            "buffer peak: mem_model_peak_gb=%.6f vs measured %.6f "
            "(ratio %.2fx outside the documented [%.1f, %.1f] band) — "
            "the liveness walker (analysis/mem_passes.py) has drifted "
            "from what XLA actually allocates"
            % (predicted / 1e9, measured / 1e9, ratio, lo, hi))


def _zero_ab(mx, n_steps=4):
    """ZeRO-1 / grad-dtype A/B on a small MLP over ALL local devices
    (docs/how_to/perf.md "Optimizer sharding"): per-chip optimizer-state
    bytes and the analytic per-chip gradient wire bytes for each
    (zero, grad_dtype) corner, plus the max param divergence from the
    replicated-f32 corner after ``n_steps`` identical steps.  Expected
    shape of the result: state bytes ~1/n under zero=1, wire bytes
    exactly halved under bf16, divergence 0.0 for zero (same math, same
    bits) and ~1e-4 for bf16 (two bf16 roundings per grad element)."""
    import jax
    import numpy as np
    from mxnet_tpu import parallel

    devices = jax.devices()
    if len(devices) < 2:
        return {"skipped": "single-device host (A/B needs a >=2-way "
                           "data mesh)"}
    mesh = parallel.make_mesh({"data": len(devices)}, devices)
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=512, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=16, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    batch = 16 * len(devices)
    rng = np.random.RandomState(0)
    x = rng.randn(batch, 64).astype("f")
    y = rng.randint(0, 16, (batch,)).astype("f")
    w_init = None
    rows, base = [], None
    for zero, gdtype in ((0, "f32"), (1, "f32"), (0, "bf16"),
                         (1, "bf16")):
        t = parallel.Trainer(
            sym, mx.optimizer.create("sgd", learning_rate=0.1,
                                     momentum=0.9,
                                     rescale_grad=1.0 / batch),
            mesh=mesh, zero=zero, grad_dtype=gdtype)
        t.bind(data_shapes={"data": (batch, 64)},
               label_shapes={"softmax_label": (batch,)})
        if w_init is None:
            mx.random.seed(7)
            t.init_params(mx.init.Xavier())
            w_init = {n: v.asnumpy() for n, v in t.get_params()[0].items()}
        else:
            t.init_params(arg_params={n: mx.nd.array(v)
                                      for n, v in w_init.items()})
        for _ in range(n_steps):
            t.step({"data": x, "softmax_label": y})
        params = {n: np.asarray(v) for n, v in t.params.items()}
        row = {"zero": zero, "grad_dtype": gdtype,
               "opt_state_bytes_per_chip": t.opt_state_bytes_per_chip(),
               "grad_comm_gb_per_step": round(
                   t.grad_comm_bytes_per_step() / 1e9, 6)}
        # the static comm plan must agree with the analytic wire model
        # on every corner — this is the 4-corner check the CPU gate can
        # actually run with a real >=2-way mesh.  Only DRIFT escapes
        # (the gate); a trace hiccup is recorded on the row so the
        # other corners and the bit-identity fields still land
        try:
            _assert_comm_model(row, t)
        except CommModelDrift:
            raise
        except Exception as e:                      # noqa: BLE001
            row["comm_model_error"] = str(e)
        if base is None:
            base = params
        else:
            row["max_param_diff_vs_f32_replicated"] = float(
                max(np.abs(base[n] - params[n]).max() for n in base))
        rows.append(row)
    return {"n_devices": len(devices), "steps": n_steps, "rows": rows}


def _elastic_drill(timeout=420, cache_dir=None):
    """2-process CPU elastic recovery drill (docs/how_to/multi_host.md
    "Elastic training"): the launcher's ``--local-elastic`` runs
    ``tests/nightly/elastic_train.py`` with a ``host_dead`` fault on
    rank 1 — heartbeat detection, membership shrink 2->1, relaunch,
    checkpoint auto-resume — and reports ``elastic_recovery_s``: wall
    time from the monitor PUBLISHING the shrunk epoch (detect) to the
    resumed run completing its first step."""
    import re
    import shutil
    import subprocess
    import tempfile
    root = os.path.dirname(os.path.abspath(__file__))
    workdir = tempfile.mkdtemp(prefix="mxtpu-elastic-bench-")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_FAULTS"] = "host_dead@step=11:rank=1"
    env.pop("MXTPU_COORDINATOR", None)
    env.pop("MXTPU_ELASTIC_DIR", None)
    env.pop("MXTPU_HEARTBEAT_DIR", None)
    if cache_dir is not None:
        # persisted compiled-program cache: the relaunched survivor
        # loads its step executable instead of recompiling — recovery
        # drops to load-not-compile (docs/how_to/compiled_programs.md)
        env["MXTPU_PROGRAM_CACHE"] = cache_dir
    else:
        env.pop("MXTPU_PROGRAM_CACHE", None)
    try:
        res = subprocess.run(
            [sys.executable, os.path.join(root, "tools", "launch.py"),
             "--local-elastic", "2", "--",
             sys.executable,
             os.path.join(root, "tests", "nightly", "elastic_train.py"),
             workdir],
            env=env, cwd=root, capture_output=True, text=True,
            timeout=timeout)
        m = re.search(r"ELASTIC_RECOVERY_S=([0-9.]+)", res.stdout)
        if res.returncode != 0 or m is None:
            raise RuntimeError(
                "elastic drill failed (rc=%d): %s"
                % (res.returncode, (res.stdout + res.stderr)[-800:]))
        return round(float(m.group(1)), 2)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def _program_cache_probe(timeout=240):
    """Cold-vs-warm restart cost of the persisted compiled-program
    cache (docs/how_to/compiled_programs.md): run
    ``tests/nightly/program_warm.py`` — trainer bind+init+3 steps,
    ``Predictor.from_checkpoint``, a 2-bucket ``ModelServer.start()`` —
    twice in fresh processes sharing one ``MXTPU_PROGRAM_CACHE`` dir.
    ``cold_start_compile_s`` sums the cold run's per-path walls (full
    trace+compile); ``warm_restart_s`` the warm run's (deserialize
    only — the drill itself FAILS unless the warm run compiles zero
    programs and reproduces the cold fingerprints)."""
    import shutil
    import subprocess
    import tempfile
    root = os.path.dirname(os.path.abspath(__file__))
    cdir = tempfile.mkdtemp(prefix="mxtpu-progcache-bench-")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXTPU_PROGRAM_CACHE"] = cdir
    env.pop("XLA_FLAGS", None)
    script = os.path.join(root, "tests", "nightly", "program_warm.py")

    def run(expect):
        res = subprocess.run(
            [sys.executable, script, "--expect", expect],
            env=env, cwd=root, capture_output=True, text=True,
            timeout=timeout)
        if res.returncode != 0:
            raise RuntimeError("program-warm drill (%s) failed: %s"
                               % (expect,
                                  (res.stdout + res.stderr)[-800:]))
        line = [ln for ln in res.stdout.splitlines()
                if ln.startswith("PROGRAM_WARM ")][-1]
        return json.loads(line[len("PROGRAM_WARM "):])

    try:
        cold = run("cold")
        warm = run("warm")
    finally:
        shutil.rmtree(cdir, ignore_errors=True)
    if warm["fingerprints"] != cold["fingerprints"]:
        raise RuntimeError(
            "program-cache drill: warm fingerprints %s diverge from "
            "cold %s — a loaded executable computed something "
            "different" % (warm["fingerprints"], cold["fingerprints"]))
    return {
        "cold_start_compile_s": round(sum(cold["wall"].values()), 3),
        "warm_restart_s": round(sum(warm["wall"].values()), 3),
        "cold_wall": cold["wall"],
        "warm_wall": warm["wall"],
        "compiles_cold": cold["compiles"],
        "compiles_warm": warm["compiles"],
        "loads_warm": warm["loads"],
        "server_warmups_loaded": warm["warmup_loaded"],
    }


def _parallel_probe(timeout=900):
    """Large-model parallelism workloads (docs/how_to/perf.md
    "Large-model parallelism"): run ``tools/parallel_bench.py`` on the
    virtual 8-device CPU mesh in a fresh subprocess — sparse-vs-dense
    MoE dispatch A/B, causal-skip ring attention A/B, interleaved-vs-
    gpipe pipeline A/B, then the composed transformer-large training
    window and the long-context ring-attention LM window through
    CompiledPrograms (zero-retrace gated, kill-and-resume bit-parity
    drilled).  A second run with ``--only transformer,ringattn``
    against the SAME ``MXTPU_PROGRAM_CACHE`` dir gates the warm
    restart: zero compiles, loads only.  The script exits non-zero on
    any gate failure — the probe re-raises with its tail."""
    import shutil
    import subprocess
    import tempfile
    root = os.path.dirname(os.path.abspath(__file__))
    cdir = tempfile.mkdtemp(prefix="mxtpu-parallel-bench-")
    env = dict(os.environ)
    env["MXTPU_PROGRAM_CACHE"] = cdir
    env.pop("XLA_FLAGS", None)          # the script sets its own
    script = os.path.join(root, "tools", "parallel_bench.py")
    steps = os.environ.get("MXTPU_BENCH_PARALLEL_STEPS", "3")

    def run(argv, expect):
        res = subprocess.run(
            [sys.executable, script, "--steps", steps,
             "--expect", expect] + argv,
            env=env, cwd=root, capture_output=True, text=True,
            timeout=timeout)
        lines = [ln for ln in res.stdout.splitlines()
                 if ln.startswith("PARALLEL_BENCH ")]
        if res.returncode != 0 or not lines:
            raise RuntimeError("parallel bench (%s) failed: %s"
                               % (expect,
                                  (res.stdout + res.stderr)[-800:]))
        return json.loads(lines[-1][len("PARALLEL_BENCH "):])

    try:
        cold = run([], "cold")
        warm = run(["--only", "transformer,ringattn"], "warm")
    finally:
        shutil.rmtree(cdir, ignore_errors=True)
    return {
        "moe": cold["moe"],
        "ring": cold["ring"],
        "pipeline": cold["pipeline"],
        "transformer_large_tok_per_sec":
            cold["transformer_large_tok_per_sec"],
        "ringattn_tok_per_sec": cold["ringattn_tok_per_sec"],
        "resume_bit_parity": cold["transformer"]["resume_bit_parity"],
        "moe_dropped_frac": cold["transformer"]["moe_dropped_frac"],
        "compiles_cold": cold["program_compiles"],
        "compiles_warm": warm["program_compiles"],
        "loads_warm": warm["program_loads"],
        "warm_tok_per_sec": warm["transformer_large_tok_per_sec"],
    }


def _integrity_drill():
    """Detect→recovered wall time for the silent-data-corruption
    protocol (docs/how_to/resilience.md "Silent data corruption"): a
    small MLP trains with the integrity check armed, a ``bitflip``
    fault corrupts one replica's state on device, and the clock runs
    from the IntegrityError raise to rollback-to-snapshot plus
    re-stepping past the divergent update (the fit-level protocol,
    driven inline).  Vote on a >=2-device host, audit fallback on one."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import faults, parallel
    from mxnet_tpu.integrity import IntegrityError
    from mxnet_tpu.parallel.trainer import Trainer

    devices = jax.devices()
    n = 2 if len(devices) >= 2 else 1
    mode = "vote" if n >= 2 else "audit"
    data = mx.sym.Variable("data")
    net = mx.symbol.FullyConnected(data, num_hidden=64, name="fc1")
    net = mx.symbol.Activation(net, act_type="relu")
    net = mx.symbol.FullyConnected(net, num_hidden=8, name="fc2")
    sym = mx.symbol.SoftmaxOutput(net, name="softmax")
    batch = 8 * n
    mesh = parallel.make_mesh({"data": n}, devices[:n]) if n > 1 else None
    t = Trainer(sym, mx.optimizer.create("sgd", learning_rate=0.1,
                                         momentum=0.9,
                                         rescale_grad=1.0 / batch),
                mesh=mesh, integrity=mode, integrity_period=4)
    t.bind(data_shapes={"data": (batch, 32)},
           label_shapes={"softmax_label": (batch,)})
    mx.random.seed(11)
    t.init_params(mx.init.Xavier())
    rng = np.random.RandomState(5)
    bs = [(rng.randn(batch, 32).astype("f"),
           rng.randint(0, 8, batch).astype("f")) for _ in range(10)]

    def feed(b):
        t.step({"data": mx.nd.array(b[0]), "softmax_label": mx.nd.array(b[1])})

    for b in bs[:5]:
        feed(b)
    # the "verified checkpoint": a host snapshot at update 5
    arg = {k: v.asnumpy() for k, v in t.get_params()[0].items()}
    aux = {k: v.asnumpy() for k, v in t.get_params()[1].items()}
    blob = t.get_opt_states()
    # vote: flip lands at 7, detected at the period-4 check entering 8;
    # audit: the replay only sees corruption DURING the audited step,
    # so flip ON the check step
    faults.configure("bitflip@step=%d:rank=%d:leaf=fc1_weight"
                     % (7 if mode == "vote" else 8, n - 1))
    try:
        try:
            for b in bs[5:]:
                feed(b)
            raise RuntimeError("integrity drill: corruption undetected")
        except IntegrityError:
            t0 = time.perf_counter()
        t.set_params({k: mx.nd.array(v) for k, v in arg.items()},
                     {k: mx.nd.array(v) for k, v in aux.items()})
        t.set_opt_states(blob)
        for b in bs[5:]:
            feed(b)
        recovery_s = time.perf_counter() - t0
    finally:
        faults.configure(None)       # restore the env-armed spec
    return {"mode": mode, "world": n,
            "recovery_s": round(recovery_s, 3)}


def main():
    # fuse the Module step on every backend (the default for tpu contexts)
    os.environ.setdefault("MXTPU_MODULE_FUSED", "always")
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import io, models

    from mxnet_tpu import program

    device = jax.devices()[0]
    if device.platform != "tpu":
        print("bench.py measures the chip and JAX found none (platform "
              "%r); nothing was measured" % device.platform,
              file=sys.stderr)
        return 1
    program.place_compile_cache()
    batch, image = 256, 224
    # enough steps that fixed overheads (the closing drain, dispatch
    # jitter) are a small part of the timed region
    steps = 150

    mod = _build_module(mx, models, batch, image)

    metric = mx.metric.create("acc")

    # --- HBM-cached real-data pipeline
    pipe = None
    pipe_err = None
    try:
        pipe = _cached_pipeline(mx, mod, metric)
    except Exception as e:                      # noqa: BLE001
        print("pipeline bench failed: %s" % e, file=sys.stderr)
        pipe_err = str(e)
    metric.reset()

    rng = np.random.RandomState(0)
    x = rng.normal(0, 1, (batch, image, image, 3)).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.float32)
    # stage once in HBM (synthetic-data mode measures compute, not PCIe)
    data_batch = io.DataBatch(data=[mx.nd.array(x)],
                              label=[mx.nd.array(y)], pad=0)

    # Module.fit inner loop (fwd+update+metric, device-side metric
    # accumulation), warmup covering compile + the one-time donated-
    # buffer relayout recompile, and metric.get() as the completion
    # barrier — shared with the perf tools (tools/stepcost.py)
    from tools.stepcost import timed_module_steps
    elapsed, _ = timed_module_steps(mod, metric, data_batch, steps,
                                    warmup=5)

    img_s = batch * steps / elapsed
    line = {
        "metric": "resnet50_train_img_per_sec_per_chip",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": len(jax.devices())},
    }
    if pipe_err is not None:
        line["pipeline_error"] = pipe_err
    if pipe is not None:
        # the cached pipeline's bound is the step itself: per-batch host
        # work is one index upload, everything else is on-chip
        bound = img_s
        pipe["pipeline_bound_img_per_sec"] = round(bound, 2)
        pipe["pipeline_vs_bound"] = round(
            pipe["pipeline_img_per_sec"] / bound, 3)
        line.update(pipe)
    try:
        # one code path with the autotuner's surrogate and the nightly
        # byte-budget gate (tools/step_breakdown.step_cost)
        from tools.step_breakdown import step_cost
        roof = json.load(open(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "ROOFLINE.json")))
        sc = step_cost(mod._trainer, {
            k: v.data for k, v in
            zip(["data", "softmax_label"],
                data_batch.data + data_batch.label)})
        flops, byts = sc["flops"], sc["bytes"]
        step_tflops = flops * (img_s / batch) / 1e12
        line["remat_policy"] = mod._trainer.remat
        line["achieved_tflops"] = round(step_tflops, 1)
        # a utilization only against a peak measured on THIS kind of
        # device (tools/roofline.py writes ROOFLINE.json["device"])
        roof_here = roof.get("device") == device.device_kind
        if roof_here:
            line["mfu_vs_measured_peak"] = round(
                step_tflops / roof["bf16_matmul_tflops"], 3)
        else:
            line["mfu_vs_measured_peak"] = None
            line["roofline_device"] = roof.get("device")
        # the byte side of the same accounting (round-3 verdict: both
        # sides or neither).  Two independent accountings agree on the
        # NOMINAL traffic (XLA cost model 80.7 GB/step; the
        # per-instruction HLO walk in tools/step_breakdown.py 82 GB) and
        # the cost model calibrates exactly 1.0 on streaming kernels
        # (tools/roofline.py) — but nominal bytes x step rate exceeds
        # the measured streaming peak, because fusion operands are
        # counted at FULL size even when partially read.  So
        # achieved_gbps_cost_model is an UPPER bound on true traffic
        # and hbm_frac_upper_bound > 1 quantifies that overcount, not
        # faster-than-peak streaming; the step runs AT the HBM roofline
        # for its program shape (STEP_BREAKDOWN.json: measured step <
        # sum of per-instruction roofline times; REMAT_SWEEP.json: all
        # remat policies add traffic and slow it down).
        line["cost_model_gb_per_step"] = round(byts / 1e9, 2)
        line["achieved_gbps_cost_model"] = round(
            byts * (img_s / batch) / 1e9, 1)
        if roof_here and roof.get("hbm_gbps"):
            line["hbm_frac_upper_bound"] = round(
                byts * (img_s / batch) / 1e9 / roof["hbm_gbps"], 3)
        # trace-time lint finding counts alongside the byte accounting
        # (the CI gate is `tools/graph_lint.py --check`; this line keeps
        # the hazard counts next to cost_model_gb_per_step so a byte
        # regression and a new lint hazard are read together —
        # docs/how_to/graph_lint.md).  Own except like the budget diff.
        try:
            from mxnet_tpu import analysis
            lint_sym = analysis.lint_symbol(
                mod._symbol,
                shapes={"data": (batch, image, image, 3),
                        "softmax_label": (batch,)},
                trace=False, model="resnet-50")
            lint_step = mod._trainer.lint()
            counts = lint_sym.counts()
            for sev, n in lint_step.counts().items():
                counts[sev] += n
            line["lint_findings"] = counts
            line["lint_errors_by_rule"] = dict(
                lint_sym.by_rule("error"), **lint_step.by_rule("error"))
        except Exception as e:                      # noqa: BLE001
            line["lint_error"] = str(e)
        # byte-budget diff (informational here; the nightly tier gates
        # via `tools/step_breakdown.py --check` — docs/how_to/perf.md
        # "Byte diet").  Own except: a malformed budget file must not
        # masquerade as an MFU-accounting failure.
        try:
            line["dtype_policy"] = mod._trainer.dtype_policy or "bytediet"
            from tools.step_breakdown import check_byte_budget, load_budget
            budget = load_budget() or {}
            entry = budget.get("tpu")
            if entry is not None:
                ok, delta_pct = check_byte_budget(
                    byts / 1e9, entry, budget.get("tolerance_pct"))
                line["byte_budget_gb"] = entry["cost_model_gb_per_step"]
                line["byte_budget_delta_pct"] = delta_pct
                line["byte_budget_ok"] = ok
        except Exception as e:                      # noqa: BLE001
            line["byte_budget_error"] = str(e)
    except Exception as e:                          # noqa: BLE001
        # never silently lose the MFU fields again (round-3 verdict #6)
        line["mfu_error"] = str(e)

    # --- step-sentinel overhead: rebuild with MXTPU_SENTINEL=skip and
    # time the SAME window (docs/how_to/resilience.md).  Reported beside
    # the byte and lint columns; the acceptance budget is < 2%.  Costs
    # one extra fused-step compile — MXTPU_BENCH_SENTINEL=0 skips.
    prior_sentinel = os.environ.get("MXTPU_SENTINEL")
    if os.environ.get("MXTPU_BENCH_SENTINEL", "1") != "0" and \
            prior_sentinel in (None, "", "off"):
        # (with the sentinel ALREADY armed process-wide the base module
        # has it too — a skip-vs-skip comparison would read ~0; skip the
        # probe rather than report a false 'free')
        try:
            os.environ["MXTPU_SENTINEL"] = "skip"
            try:
                mod_s = _build_module(mx, models, batch, image)
            finally:
                if prior_sentinel is None:
                    os.environ.pop("MXTPU_SENTINEL", None)
                else:
                    os.environ["MXTPU_SENTINEL"] = prior_sentinel
            # re-time the BASE module back-to-back with the sentinel
            # window: comparing against the first window of the process
            # reads allocator/cache warm-up drift as sentinel cost
            metric.reset()
            base_s, _ = timed_module_steps(mod, metric, data_batch,
                                           steps, warmup=2)
            metric.reset()
            elapsed_s, _ = timed_module_steps(mod_s, metric, data_batch,
                                              steps, warmup=5)
            line["sentinel_skips"] = mod_s._trainer.sentinel_skips
            line["sentinel_overhead_pct"] = round(
                (elapsed_s / base_s - 1.0) * 100.0, 2)
        except Exception as e:                      # noqa: BLE001
            line["sentinel_error"] = str(e)
    elif mod._trainer.sentinel != "off":
        # sentinel armed process-wide: report the run's own skip count
        line["sentinel_skips"] = mod._trainer.sentinel_skips

    # --- optimizer sharding / gradient comm accounting
    # (docs/how_to/perf.md "Optimizer sharding"): the main module's
    # per-chip state bytes + analytic gradient wire bytes, and the
    # zero on/off x grad-dtype A/B on a data mesh over the local
    # devices.  MXTPU_BENCH_ZERO_AB=0 skips the A/B compiles.
    line["zero"] = mod._trainer.zero
    line["grad_accum"] = mod._trainer.grad_accum
    line["grad_dtype"] = mod._trainer.grad_dtype
    line["opt_state_bytes_per_chip"] = \
        mod._trainer.opt_state_bytes_per_chip()
    line["grad_comm_gb_per_step"] = round(
        mod._trainer.grad_comm_bytes_per_step() / 1e9, 6)
    # static comm-plan prediction beside the analytic figure
    # (docs/how_to/static_analysis.md "Communication analysis"): the
    # jaxpr-extracted + SPMD-synthesized plan's wire bytes MUST agree
    # with grad_comm_gb_per_step within 5% — a drifting static model
    # would silently mis-gate COMM_BASELINE.json and mis-feed the
    # autotuner's cheap surrogate.  Asserted, not just reported (the
    # MULTICHIP_PARITY pattern); own except so a trace failure reads as
    # comm_model_error, never a fake agreement — and never a fake gate:
    # only the dedicated drift type re-raises (MXNetError and jax's
    # XlaRuntimeError both subclass RuntimeError, so a bare
    # RuntimeError re-raise would abort the bench on a trace hiccup).
    try:
        _assert_comm_model(line, mod._trainer)
    except CommModelDrift:
        raise
    except Exception as e:                          # noqa: BLE001
        line["comm_model_error"] = str(e)
    # static liveness-peak prediction beside the MEASURED live-buffer
    # peak (docs/how_to/static_analysis.md "Memory analysis"): the
    # lower().compile() here shares the jit executable cache with the
    # steps already timed, so the probe costs no extra compile.  Same
    # except discipline as the comm gate: only the dedicated drift
    # type escapes.
    try:
        import jax.numpy as jnp
        _assert_mem_model(line, mod._trainer,
                          {"data": jnp.asarray(x),
                           "softmax_label": jnp.asarray(y)})
    except MemModelDrift:
        raise
    except Exception as e:                          # noqa: BLE001
        line["mem_model_error"] = str(e)
    if os.environ.get("MXTPU_BENCH_ZERO_AB", "1") != "0":
        try:
            line["zero_ab"] = _zero_ab(mx)
        except CommModelDrift:
            # the 4-corner drift assertion inside _zero_ab is a GATE —
            # it must not be swallowed into zero_ab_error
            raise
        except Exception as e:                      # noqa: BLE001
            line["zero_ab_error"] = str(e)

    # --- serving probe (docs/how_to/serving.md): the continuous-
    # batching ModelServer under a bounded Poisson sweep — p50/p99
    # latency, achieved vs offered rps, batch-occupancy, and the
    # zero-steady-state-retrace assertion, next to the offline img/s
    # numbers.  The committed INFER_BENCH.json `serving` section comes
    # from the full `tools/serve_bench.py` run; this quick probe keeps
    # the gate honest about the serve path.  MXTPU_BENCH_SERVING=0
    # skips (5 small AOT compiles + ~2 s of load).
    if os.environ.get("MXTPU_BENCH_SERVING", "1") != "0":
        try:
            from tools.serve_bench import overload_probe, serving_probe
            line["serving"] = serving_probe(quick=True)
            # goodput under overload (docs/how_to/serving.md "Overload
            # & degradation"): 1x-8x offered load with admission
            # control on — the quick sweep, asserted below
            line["overload"] = overload_probe(quick=True)
        except Exception as e:                      # noqa: BLE001
            line["serving_error"] = str(e)
        ov = line.get("overload")
        if ov is not None and not ov.get("degradation_ok", True):
            # the degradation invariant is a GATE, not a statistic: a
            # server whose goodput collapses past saturation has no
            # overload story, whatever its peak numbers say
            raise RuntimeError(
                "overload degradation invariant FAILED: goodput at %sx "
                "offered load (%.1f rps) < 0.9x goodput at %sx (%.1f "
                "rps) — see INFER_BENCH.json 'overload'"
                % (ov["max_load_factor"], ov["goodput_max_load_rps"],
                   ov["base_load_factor"], ov["goodput_base_rps"]))

    # --- fleet serving (docs/how_to/serving.md "Fleet serving"): the
    # replicated tier under its three windows — scaling (1 vs 3 paced
    # replicas on one arrival schedule), churn (kill one mid-window,
    # autoheal), rollout (hot weight swap mid-window).  All three
    # verdicts are GATES: a fleet that doesn't scale, doesn't recover,
    # or drops requests across a rollout has no fleet story.
    # MXTPU_BENCH_FLEET=0 skips (~15 s of paced load).
    if os.environ.get("MXTPU_BENCH_FLEET", "1") != "0":
        fl = None
        try:
            from tools.serve_bench import fleet_probe
            fl = line["fleet"] = fleet_probe(quick=True)
        except Exception as e:                      # noqa: BLE001
            line["fleet_error"] = str(e)
        if fl is not None:
            if not fl["scaling_ok"]:
                raise RuntimeError(
                    "fleet scaling gate FAILED: %s replicas reached "
                    "%.1f rps vs %.1f rps single (%sx < 2.2x) — see "
                    "INFER_BENCH.json 'fleet'"
                    % (fl["replicas"], fl["fleet_goodput_rps"],
                       fl["single_goodput_rps"], fl["fleet_scaling_x"]))
            if not fl["recovery_ok"]:
                raise RuntimeError(
                    "fleet churn gate FAILED: goodput after the kill "
                    "recovered to %sx the steady state (< 0.9x) — "
                    "segments %s" % (fl["churn"]["recovery_ratio"],
                                     fl["churn"]["segment_goodput_rps"]))
            if fl["rollout"]["dropped"] or fl["rollout"]["rolled_back"]:
                raise RuntimeError(
                    "fleet rollout gate FAILED: dropped=%s "
                    "rolled_back=%s — a weight roll must lose nothing"
                    % (fl["rollout"]["dropped"],
                       fl["rollout"]["rolled_back"]))
            if fl["spinup_compiles"] or fl["retraces"]:
                raise RuntimeError(
                    "fleet warm-start gate FAILED: spinup_compiles=%s "
                    "retraces=%s (every fleet spin-up, heal and swap "
                    "must be compile-free)"
                    % (fl["spinup_compiles"], fl["retraces"]))

    # --- tune-plan A/B (docs/how_to/autotune.md): when a persisted
    # TUNE_PLAN.json exists (checked in at the repo root, or pointed at
    # via MXTPU_TUNE_PLAN), A/B its serving config against the built-in
    # defaults on one identical seeded arrival sequence and record the
    # headline delta — the figure the committed plan's win rests on.
    # Every timed window also appends a (config, measured) row to
    # TUNE_CORPUS.jsonl.  MXTPU_BENCH_TUNE=0 skips.
    if os.environ.get("MXTPU_BENCH_TUNE", "1") != "0":
        plan_path = os.environ.get("MXTPU_TUNE_PLAN") or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "TUNE_PLAN.json")
        if os.path.exists(plan_path):
            try:
                from tools.autotune import plan_ab
                line["tune"] = plan_ab(plan_path, quick=True)
            except Exception as e:                  # noqa: BLE001
                line["tune_error"] = str(e)

    # --- telemetry overhead (docs/how_to/observability.md): the span
    # recorder + JSONL exporter must stay inside 5% of the serving hot
    # path when armed (MXTPU_OBS=1) — alternating OFF/ON closed-loop
    # windows over one warmed server, median of per-pair ratios (the
    # anti-noise shape the integrity probe established for shared CI
    # hosts).  MXTPU_BENCH_OBS=0 skips.
    if os.environ.get("MXTPU_BENCH_OBS", "1") != "0":
        probe = None
        try:
            from tools.serve_bench import obs_overhead_probe
            probe = obs_overhead_probe()
        except Exception as e:                      # noqa: BLE001
            line["obs_error"] = str(e)
        if probe is not None:
            line["obs_overhead_pct"] = probe["obs_overhead_pct"]
            line["obs_overhead_saturated_pct"] = \
                probe["obs_overhead_saturated_pct"]
            if probe["obs_overhead_pct"] >= 5.0:
                raise RuntimeError(
                    "obs overhead budget FAILED: MXTPU_OBS=1 serving "
                    "sweep is %.2f%% over the disabled sweep (budget "
                    "< 5%%; pairs: %s)"
                    % (probe["obs_overhead_pct"], probe["pairs"]))

    # --- elastic recovery drill (docs/how_to/multi_host.md "Elastic
    # training"): detect->resumed-first-step wall time from a real
    # 2-process kill-shrink-resume on CPU.  Subprocess-heavy (~1 min);
    # MXTPU_BENCH_ELASTIC=0 skips.
    if os.environ.get("MXTPU_BENCH_ELASTIC", "1") != "0":
        try:
            # children pinned to the CPU: stamped so
            elastic = line["elastic"] = {"platform": "cpu"}
            elastic["elastic_recovery_s"] = _elastic_drill()
            # warm-restart variant (docs/how_to/compiled_programs.md):
            # the same kill-shrink-resume against a persisted program
            # cache.  One drill populates the cache (the 2-world AND
            # the shrunk 1-world programs persist), the next measures
            # recovery as pure load-not-compile.
            import shutil
            import tempfile
            cdir = tempfile.mkdtemp(prefix="mxtpu-progcache-")
            try:
                _elastic_drill(cache_dir=cdir)        # populate
                elastic["elastic_recovery_warm_s"] = \
                    _elastic_drill(cache_dir=cdir)    # measure warm
            finally:
                shutil.rmtree(cdir, ignore_errors=True)
        except Exception as e:                      # noqa: BLE001
            line["elastic_error"] = str(e)

    # --- persisted compiled-program cache (docs/how_to/
    # compiled_programs.md): the warm-restart drill — trainer bind+init+
    # step, Predictor from_checkpoint, ModelServer 2-bucket start — run
    # twice in fresh processes against one cache dir.  cold = full
    # trace+compile, warm = deserialize only (the drill ASSERTS the
    # warm run compiles zero programs).  MXTPU_BENCH_PROGRAM=0 skips.
    if os.environ.get("MXTPU_BENCH_PROGRAM", "1") != "0":
        try:
            line["program_cache"] = dict(_program_cache_probe(),
                                         platform="cpu")
        except Exception as e:                      # noqa: BLE001
            line["program_cache_error"] = str(e)

    # --- large-model parallelism workloads (docs/how_to/perf.md
    # "Large-model parallelism"): sparse-MoE / causal-skip-ring /
    # interleaved-pipeline A/Bs plus the composed transformer-large
    # and ringattn-long-context headline windows, all gated inside
    # tools/parallel_bench.py (subprocess: the 8-device virtual mesh
    # needs XLA_FLAGS before jax init).  ~2 min on CPU;
    # MXTPU_BENCH_PARALLEL=0 skips.
    if os.environ.get("MXTPU_BENCH_PARALLEL", "1") != "0":
        try:
            line["parallel"] = dict(_parallel_probe(), platform="cpu")
        except Exception as e:                      # noqa: BLE001
            line["parallel_error"] = str(e)

    # --- silent-data-corruption defense (docs/how_to/resilience.md
    # "Silent data corruption"): rebuild the module with the in-step
    # state fingerprint armed at period=100 and re-time the SAME window
    # (acceptance budget < 2% — off-period steps execute nothing
    # extra), then run the detect→rollback→re-step drill and report its
    # wall time.  One extra fused-step compile + a small drill;
    # MXTPU_BENCH_INTEGRITY=0 skips.
    prior_integ = os.environ.get("MXTPU_INTEGRITY_MODE")
    prior_period = os.environ.get("MXTPU_INTEGRITY_PERIOD")
    if os.environ.get("MXTPU_BENCH_INTEGRITY", "1") != "0":
        if prior_integ in (None, "", "off"):
            # (with integrity ALREADY armed process-wide the base
            # module has it too — skip rather than report a false 0)
            try:
                # apples to apples: a FRESH baseline module next
                # to the fresh armed one, stepped in lockstep from
                # identical state (re-timing the long-used `mod`
                # conflates module age with integrity cost)
                mod_b = _build_module(mx, models, batch, image)
                os.environ["MXTPU_INTEGRITY_MODE"] = "vote"
                os.environ["MXTPU_INTEGRITY_PERIOD"] = "100"
                try:
                    mod_i = _build_module(mx, models, batch, image)
                finally:
                    if prior_integ is None:
                        os.environ.pop("MXTPU_INTEGRITY_MODE", None)
                    else:
                        os.environ["MXTPU_INTEGRITY_MODE"] = \
                            prior_integ
                    if prior_period is None:
                        os.environ.pop("MXTPU_INTEGRITY_PERIOD",
                                       None)
                    else:
                        os.environ["MXTPU_INTEGRITY_PERIOD"] = \
                            prior_period
                metric.reset()
                timed_module_steps(mod_i, metric, data_batch,
                                   steps, warmup=5)  # compile+warm
                import jax as _jax
                import jax.numpy as _jnp
                tr_b, tr_i = mod_b._trainer, mod_i._trainer
                tr_i.params = _jax.tree.map(_jnp.copy, tr_b.params)
                tr_i.aux = _jax.tree.map(_jnp.copy, tr_b.aux)
                tr_i.opt_state = _jax.tree.map(_jnp.copy,
                                               tr_b.opt_state)
                # the update counter is part of "identical state":
                # it phases the period-100 checks inside the timed
                # window and feeds lr_scheduler/fold_in
                tr_i.num_update = tr_b.num_update
                tr_i.optimizer.num_update = tr_b.num_update
                metric.reset()
                base_i, _ = timed_module_steps(mod_b, metric,
                                               data_batch, steps,
                                               warmup=2)
                metric.reset()
                elapsed_i, _ = timed_module_steps(mod_i, metric,
                                                  data_batch,
                                                  steps, warmup=2)
                line["integrity_mode"] = mod_i._trainer._integ_mode
                line["integrity_period"] = \
                    mod_i._trainer.integrity_period
                line["integrity_overhead_pct"] = round(
                    (elapsed_i / base_i - 1.0) * 100.0, 2)
            except Exception as e:                  # noqa: BLE001
                line["integrity_error"] = str(e)
        try:
            drill = _integrity_drill()
            line["integrity_recovery_s"] = drill["recovery_s"]
            line["integrity_drill_mode"] = drill["mode"]
        except Exception as e:                      # noqa: BLE001
            line["integrity_recovery_error"] = str(e)

    # --- streaming pipeline (datasets beyond HBM), wire-paced
    if os.environ.get("MXTPU_BENCH_STREAM_PROBE", "1") != "0":
        try:
            metric.reset()
            for k, v in _stream_pipeline(mx, mod, metric, img_s).items():
                line["stream_" + k] = v
        except Exception as e:                      # noqa: BLE001
            line["stream_error"] = str(e)

    # --- tune corpus: the bench headline is itself a (config, measured)
    # pair — append it so every bench run grows the TpuGraphs-style
    # accumulation a learned cost model will train on
    # (docs/how_to/autotune.md "The corpus")
    try:
        from mxnet_tpu import tuneplan
        tr = mod._trainer
        tuneplan.append_corpus({
            "kind": "train", "tool": "bench",
            "config": {"model": "resnet-50", "batch": batch,
                       "image": image,
                       "dtype_policy": tr.dtype_policy,
                       "remat": tr.remat, "zero": tr.zero,
                       "grad_accum": tr.grad_accum,
                       "grad_dtype": tr.grad_dtype,
                       "sentinel": tr.sentinel,
                       "integrity": tr._integ_mode},
            "measured": {
                "img_per_sec": line["value"],
                "cost_model_gb_per_step":
                    line.get("cost_model_gb_per_step"),
                "grad_comm_gb_per_step":
                    line.get("grad_comm_gb_per_step"),
                "achieved_tflops": line.get("achieved_tflops")}})
    except Exception as e:                          # noqa: BLE001
        line["tune_corpus_error"] = str(e)

    print(json.dumps(line))
    failed = sorted(_error_fields(line))
    if failed:
        print("bench.py: failed phases: %s" % ", ".join(failed),
              file=sys.stderr)
    return 1 if failed else 0


def _error_fields(obj, prefix=""):
    """Every ``*_error`` key in the result, nested blocks included: a
    phase that caught its failure into such a field fails the run."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            if k.endswith("_error"):
                yield prefix + k
            else:
                yield from _error_fields(v, prefix + k + ".")
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _error_fields(v, "%s%d." % (prefix, i))


if __name__ == "__main__":
    sys.exit(main())
