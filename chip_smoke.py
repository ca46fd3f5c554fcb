#!/usr/bin/env python
"""The quickest proof that the framework still starts on the chip.

One process, one TPU chip, the public entry points at real width:

* ``train``  — ResNet-50 NHWC bf16, batch 256 at 224^2, through
  ``Module.bind`` / ``init_params`` / ``init_optimizer(dist_sync_tpu)``
  onto the fused ``Trainer`` step.
* ``serve``  — the same network in bf16 through ``Predictor`` and a
  ``ModelServer`` with the default bucket ladder, mixed request sizes
  from several threads.
* ``kernel`` — the transformer LM symbol at GPT-2 medium's widths (depth
  cut) through the same ``Module`` path, the Pallas flash kernel found
  in the compiled step, and kernel-vs-reference parity on the chip.
* ``ops``    — ``tests/nightly/consistency.py``: the CPU backend against
  the TPU backend, op by op, in this process.

``--chips 4`` runs none of these.  It runs the ``train`` model on a
``{"data": 4}`` mesh and on a one-device mesh in the same process and
holds them to parity and to per-device placement.

Every phase prints one JSON line.  The last line of standard output is
``{"ok": true, "device": {...}}`` with the device as JAX reports it, and
is printed only when every phase passed.  Without an accelerator the
script exits non-zero and prints no result.  These are smoke numbers,
not a benchmark.
"""
import argparse
import contextlib
import gc
import json
import os
import sys
import tempfile
import threading
import time
from unittest import mock

import numpy as np

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.abspath(__file__))

# the sizes a user would call real; a rehearsal on the CPU passes its own
SIZES = {
    "on_chip": True,            # hold arrays and kernels to the TPU
    "net": "resnet-50", "batch": 256, "image": 224, "classes": 1000,
    "train_steps": 10, "timed_steps": 10,
    "serve_requests": 40, "serve_threads": 4,
    # GPT-2 medium: hidden 1024, 16 heads of 64, sequence 1024,
    # vocabulary 50,257; 24 layers published, cut for time
    "lm": {"num_hidden": 1024, "num_heads": 16, "seq_len": 1024,
           "vocab_size": 50257, "num_layers": 4, "batch": 8},
    "attn_shapes": [(8, 1024, 16, 64), (8, 1000, 16, 64)],
    "ops_sample": 8,
    "parity_steps": 5,
}

# stated tolerances
SERVE_RTOL, SERVE_ATOL = 3e-2, 1e-4   # bf16 probabilities, bucket vs direct
ATTN_TOL = 5e-2                       # max|kernel-ref| / max|ref|, bf16
PARITY_FORWARD_TOL = 1e-2             # first loss, before any update
PARITY_LOSS_TOL = 0.1                 # |loss4 - loss1| / max(1, |loss1|)
PARITY_PARAM_TOL = 0.25               # |dw4 - dw1| / |dw1| in L2, first step
PARITY_LEAVES = 6                     # the largest weights are compared


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(phase, **facts):
    print(json.dumps(dict({"phase": phase}, **facts)), flush=True)


def counters():
    from mxnet_tpu import obs, program
    c = obs.snapshot()["counters"]
    stats = program.cache_stats()
    return {"compiles": stats["compiles"], "traces": stats["traces"],
            "loads": stats["loads"], "cache_hit": stats["cache_hit"],
            "cache_stale": stats["cache_stale"],
            "plan_foreign": int(c.get("tune.plan_foreign", 0))}


def dir_mb(path):
    """Megabytes of files under ``path``, sub-directories left out."""
    if not os.path.isdir(path):
        return 0.0
    with os.scandir(path) as entries:
        return round(sum(e.stat().st_size for e in entries
                         if e.is_file()) / 1e6, 1)


def on_devices(tree, platform):
    """Every leaf of ``tree`` lives on ``platform`` devices only."""
    return all(d.platform == platform
               for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


def cross_entropy(probs, labels):
    """Mean -log p[label], reduced where ``probs`` lives (``labels`` is
    a host array, so a sharded output needs no gather)."""
    p = jnp.take_along_axis(probs.astype(jnp.float32),
                            labels[:, None], axis=1)
    return float(-jnp.mean(jnp.log(jnp.maximum(p, 1e-30))))


# ----------------------------------------------------------------------
# train
def build_module(mx, sym, data_shape, label_shape, context, seed,
                 arg_params=None, aux_params=None):
    """The benchmark's recipe (``benchmark/lib/trainjob.py``): Module ->
    fused Trainer via dist_sync_tpu.  Gradients are summed over the
    labels, so they are rescaled by their count.  The rate is a fifth of
    the benchmark's 0.02: a few steps on one fixed batch have to fall,
    and two runs of them have to stay comparable."""
    mod = mx.mod.Module(context=context, symbol=sym,
                        compute_dtype="bfloat16")
    mod.bind(data_shapes=[("data", data_shape)],
             label_shapes=[("softmax_label", label_shape)])
    mx.random.seed(seed)
    mod.init_params(mx.init.Xavier(rnd_type="gaussian", factor_type="in",
                                   magnitude=2),
                    arg_params=arg_params, aux_params=aux_params)
    mod.init_optimizer(
        kvstore=mx.kvstore.create("dist_sync_tpu"), optimizer="sgd",
        optimizer_params={"learning_rate": 0.02, "momentum": 0.9,
                          "rescale_grad": 1.0 / np.prod(label_shape)})
    check(mod._trainer is not None, "Module did not take the fused path")
    return mod


def image_batch(mx, sizes, seed):
    rng = np.random.RandomState(seed)
    b, hw = sizes["batch"], sizes["image"]
    x = rng.normal(0, 1, (b, hw, hw, 3)).astype(np.float32)
    y = rng.randint(0, sizes["classes"], (b,)).astype(np.float32)
    return mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)],
                           pad=0)


def fit_step(mod, batch, metric):
    """One step of Module.fit's inner loop."""
    mod.forward(batch, is_train=True)
    mod.update()
    mod.update_metric(metric, batch.label)


def train_steps(mod, batch, metric, n):
    """n steps; the loss after each."""
    labels = batch.label[0].asnumpy().reshape(-1).astype(np.int32)
    losses = []
    for _ in range(n):
        fit_step(mod, batch, metric)
        losses.append(cross_entropy(mod.get_outputs()[0].data, labels))
    return losses


def step_time_block(mod, batch, metric, n):
    """Seconds per step, the window closed by block_until_ready on the
    updated parameters."""
    jax.block_until_ready(mod._trainer.params)
    t0 = time.perf_counter()
    for _ in range(n):
        fit_step(mod, batch, metric)
    jax.block_until_ready(mod._trainer.params)
    return (time.perf_counter() - t0) / n


def decode_worker_batch(mx, seed):
    """One batch from spawned decode workers while this process holds
    the chip: they must start, decode and exit without claiming it."""
    import io as pio
    from PIL import Image
    rng = np.random.RandomState(seed)
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="mxtpu-smoke-") as d:
        rec_path = os.path.join(d, "smoke.rec")
        rec = mx.recordio.MXRecordIO(rec_path, "w")
        for i in range(8):
            buf = pio.BytesIO()
            Image.fromarray(rng.randint(0, 255, (40, 40, 3), dtype=np.uint8)
                            ).save(buf, format="JPEG")
            rec.write(mx.recordio.pack(
                mx.recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
        rec.close()
        it = mx.io.PyImageRecordIter(
            path_imgrec=rec_path, data_shape=(3, 32, 32), batch_size=8,
            preprocess_mode="process", decode_workers=2, output="numpy")
        try:
            b = it.next()
            shape = tuple(b.data[0].shape)
            labels = sorted(np.asarray(b.label[0]).reshape(-1).tolist())
        finally:
            it.close()
    check(shape == (8, 32, 32, 3), "decode batch shape %s" % (shape,))
    check(labels == [float(i) for i in range(8)],
          "decode labels %s" % labels)
    return round(time.perf_counter() - t0, 2)


def phase_train(mx, sizes, seed):
    from mxnet_tpu import models
    from tools.stepcost import timed_module_steps
    t_phase = time.perf_counter()
    b, hw = sizes["batch"], sizes["image"]
    sym = models.get_symbol(sizes["net"], num_classes=sizes["classes"],
                            layout="NHWC")
    mod = build_module(mx, sym, (b, hw, hw, 3), (b,), mx.tpu(), seed)
    arg0, aux0 = mod.get_params()
    weights = ({k: v.astype("bfloat16") for k, v in arg0.items()},
               {k: v.astype("bfloat16") for k, v in aux0.items()})
    batch = image_batch(mx, sizes, seed)
    metric = mx.metric.create("acc")

    before = counters()
    t0 = time.perf_counter()
    losses = train_steps(mod, batch, metric, 1)
    metric.get()
    jax.block_until_ready(mod._trainer.params)
    compile_s = time.perf_counter() - t0
    warm = counters()

    losses += train_steps(mod, batch, metric, sizes["train_steps"] - 1)
    check(all(np.isfinite(losses)), "loss not finite: %s" % losses)
    check(losses[-1] < losses[0], "loss did not fall: %s" % losses)

    metric.reset()
    n = sizes["timed_steps"]
    block_s = step_time_block(mod, batch, metric, n)
    metric.reset()
    drain_s = timed_module_steps(mod, metric, batch, n, warmup=1)[0] / n
    after = counters()
    check(after["compiles"] == warm["compiles"]
          and after["traces"] == warm["traces"],
          "compiled after the warm-up step: %s -> %s" % (warm, after))

    tr = mod._trainer
    state = (tr.params, tr.aux, tr.opt_state)
    if sizes["on_chip"]:
        check(on_devices(state, "tpu"),
              "trainer state is not on the TPU device")
    device = sorted({str(d) for leaf in jax.tree_util.tree_leaves(tr.params)
                     for d in leaf.devices()})
    decode_s = decode_worker_batch(mx, seed)
    emit("train", ok=True, net=sizes["net"], batch=b, image=hw,
         compute_dtype="bfloat16", fused=True, param_devices=device,
         steps=len(losses), loss_first=round(losses[0], 4),
         loss_last=round(losses[-1], 4),
         compile_s=round(compile_s, 2),
         program_compiles=warm["compiles"] - before["compiles"],
         program_loads=warm["loads"] - before["loads"],
         compiles_after_warmup=after["compiles"] - warm["compiles"],
         step_ms_block_until_ready=round(block_s * 1e3, 3),
         step_ms_metric_get=round(drain_s * 1e3, 3),
         img_per_sec_block_until_ready=round(b / block_s, 1),
         decode_worker_batch_s=decode_s,
         wall_s=round(time.perf_counter() - t_phase, 2))
    return sym, weights


# ----------------------------------------------------------------------
# serve
def phase_serve(mx, sizes, seed, sym, weights):
    from mxnet_tpu import serving
    from mxnet_tpu.predictor import Predictor
    t_phase = time.perf_counter()
    hw = sizes["image"]
    args, aux = weights
    before = counters()
    srv = serving.ModelServer()
    top = srv.buckets[-1]
    srv.add_model("net", sym, args, aux, input_shapes={"data": (hw, hw, 3)})
    t0 = time.perf_counter()
    srv.start()
    start_s = time.perf_counter() - t0
    warm = counters()

    rng = np.random.RandomState(seed + 1)
    rows = [int(r) for r in rng.choice([1, 1, 2, 3, 4, 5, 8, 11, 16],
                                       sizes["serve_requests"])]
    reqs = [rng.normal(0, 1, (r, hw, hw, 3)).astype(np.float32)
            for r in rows]
    answers = [None] * len(reqs)
    errors = []

    def client(tid):
        try:
            for i in range(tid, len(reqs), sizes["serve_threads"]):
                if i % 2:
                    answers[i] = srv.predict(data=reqs[i])[0]
                else:
                    answers[i] = srv.submit(data=reqs[i]).result(
                        timeout=120)[0]
        except Exception as e:                      # noqa: BLE001
            errors.append("client %d: %s: %s" % (tid, type(e).__name__, e))

    threads = [threading.Thread(target=client, args=(t,),
                                name="smoke-client-%d" % t)
               for t in range(sizes["serve_threads"])]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    load_s = time.perf_counter() - t0
    check(not any(t.is_alive() for t in threads), "a client thread hung")
    check(not errors, "; ".join(errors))
    srv.assert_no_retrace()
    stats = srv.stats()
    srv.stop()
    check(not any(t.name == "mxtpu-serve-sched" and t.is_alive()
                  for t in threading.enumerate()),
          "the scheduler thread outlived stop()")
    shed = {k: stats[k] for k in (
        "failed", "timeouts", "rejected_overload", "rejected_breaker",
        "shed_deadline", "expired_after_dispatch", "cancelled",
        "batch_failures")}
    check(not any(shed.values()), "requests shed or failed: %s" % shed)
    check(stats["completed"] == len(reqs) and stats["retraces"] == 0,
          "completed %d of %d, retraces %d"
          % (stats["completed"], len(reqs), stats["retraces"]))

    # the direct forward: a Predictor over a checkpoint of the same
    # weights, at the top bucket's batch (it shares that program)
    with tempfile.TemporaryDirectory(prefix="mxtpu-smoke-") as d:
        prefix = os.path.join(d, "net")
        mx.model.save_checkpoint(prefix, 1, sym, args, aux)
        pred = Predictor.from_checkpoint(prefix, 1,
                                         {"data": (top, hw, hw, 3)})
    if sizes["on_chip"]:
        check(on_devices((pred._params, srv._models["net"].params), "tpu"),
              "served weights are not on the TPU device")
    worst = 0.0
    for x, got in zip(reqs, answers):
        pad = np.zeros((top - x.shape[0],) + x.shape[1:], np.float32)
        want = pred.predict(data=np.concatenate([x, pad]))[0][:x.shape[0]]
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        check(got.shape == want.shape and np.all(np.isfinite(got)),
              "answer shape %s vs %s, or not finite"
              % (got.shape, want.shape))
        worst = max(worst, float(np.max(
            np.abs(got - want) / (SERVE_ATOL + SERVE_RTOL * np.abs(want)))))
    check(worst <= 1.0, "served answers differ from the direct forward: "
          "%.2fx the tolerance (rtol %g, atol %g)"
          % (worst, SERVE_RTOL, SERVE_ATOL))
    after = counters()
    check(after["compiles"] == warm["compiles"]
          and after["traces"] == warm["traces"],
          "compiled after start(): %s -> %s" % (warm, after))
    emit("serve", ok=True, net=sizes["net"], dtype="bfloat16",
         buckets=stats["buckets"], requests=len(reqs),
         request_rows=sorted(set(rows)), threads=sizes["serve_threads"],
         start_compile_s=round(start_s, 2),
         program_compiles=warm["compiles"] - before["compiles"],
         program_loads=warm["loads"] - before["loads"],
         warmup_loaded=stats["warmup_loaded"],
         retraces=stats["retraces"], batches=stats["batches"],
         padding_frac=stats["padding_frac"], load_s=round(load_s, 2),
         err_over_tol=round(worst, 4), rtol=SERVE_RTOL, atol=SERVE_ATOL,
         compiles_after_start=after["compiles"] - warm["compiles"],
         wall_s=round(time.perf_counter() - t_phase, 2))


# ----------------------------------------------------------------------
# kernel
def attention_parity(shape, on_chip):
    """flash_attention forward and gradient against attention_reference
    on the same device, causal bf16.  Returns the four relative errors."""
    from mxnet_tpu.op.pallas.flash_attention import flash_attention
    from mxnet_tpu.parallel.ring_attention import attention_reference
    ks = jax.random.split(jax.random.key(shape[1]), 4)
    qkvw = [jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16)
            for kk in ks]

    def run(attn, cast):
        def go(q, k, v, w):
            def loss(q, k, v):
                o = attn(cast(q), cast(k), cast(v), causal=True)
                return jnp.sum(o.astype(jnp.float32)
                               * w.astype(jnp.float32)), o
            (_, o), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (o,) + grads
        return jax.jit(go)

    flash = run(flash_attention, lambda x: x)
    if on_chip:
        check("tpu_custom_call" in flash.lower(*qkvw).compile().as_text(),
              "flash_attention at %s did not compile to a Pallas kernel"
              % (shape,))
    with jax.default_matmul_precision("highest"):
        ref = run(attention_reference,
                  lambda x: x.astype(jnp.float32))(*qkvw)
    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), flash(*qkvw), ref):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        check(np.all(np.isfinite(a)), "%s not finite at %s" % (name, shape))
        errs[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
    return errs


def phase_kernel(mx, sizes, seed):
    from mxnet_tpu import models
    from tools.stepcost import compile_step
    t_phase = time.perf_counter()
    lm = dict(sizes["lm"])
    b, t = lm.pop("batch"), lm["seq_len"]
    sym = models.get_symbol("transformer", **lm)
    mod = build_module(mx, sym, (b, t), (b, t), mx.tpu(), seed)
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, lm["vocab_size"], (b, t + 1))
    batch = mx.io.DataBatch(
        data=[mx.nd.array(tokens[:, :-1].astype(np.float32))],
        label=[mx.nd.array(tokens[:, 1:].astype(np.float32))], pad=0)
    metric = mx.metric.create("acc")
    t0 = time.perf_counter()
    losses = train_steps(mod, batch, metric, 1)
    compile_s = time.perf_counter() - t0
    losses += train_steps(mod, batch, metric, 2)
    check(all(np.isfinite(losses)), "LM loss not finite: %s" % losses)
    check(losses[-1] < losses[0], "LM loss did not fall: %s" % losses)
    tr = mod._trainer
    if sizes["on_chip"]:
        check(on_devices(tr.params, "tpu"),
              "LM parameters are not on the TPU device")
        text = compile_step(tr, tr._device_batch(
            mod._fused_batch_dict(batch))).as_text()
        check("tpu_custom_call" in text,
              "the compiled LM step holds no Pallas kernel")
    del mod, tr
    gc.collect()
    errs = {}
    for shape in sizes["attn_shapes"]:
        e = attention_parity(tuple(shape), sizes["on_chip"])
        check(max(e.values()) <= ATTN_TOL,
              "flash_attention vs attention_reference at %s: %s (tol %g)"
              % (shape, e, ATTN_TOL))
        errs["x".join(map(str, shape))] = {k: round(v, 5)
                                            for k, v in e.items()}
    emit("kernel", ok=True, model="transformer-lm", widths=sizes["lm"],
         published_layers=24, steps=len(losses),
         loss_first=round(losses[0], 4), loss_last=round(losses[-1], 4),
         compile_s=round(compile_s, 2),
         tpu_custom_call=bool(sizes["on_chip"]),
         attention_rel_err=errs, attention_tol=ATTN_TOL,
         wall_s=round(time.perf_counter() - t_phase, 2))


# ----------------------------------------------------------------------
# ops
def phase_ops(sizes):
    t_phase = time.perf_counter()
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    sys.path.insert(0, os.path.join(ROOT, "tests", "nightly"))
    import consistency
    with contextlib.redirect_stdout(sys.stderr):
        matched, failed = consistency.run(sizes["ops_sample"])
    check(matched > 0 and failed == 0,
          "cpu-vs-tpu consistency: %d matched, %d failed (stderr names "
          "them)" % (matched, failed))
    emit("ops", ok=True, sample=sizes["ops_sample"], matched=matched,
         failed=failed, wall_s=round(time.perf_counter() - t_phase, 2))


# ----------------------------------------------------------------------
# --chips 4
def shard_devices(tree):
    """The set of devices holding a shard of the largest leaf."""
    leaf = max(jax.tree_util.tree_leaves(tree), key=lambda a: a.size)
    return {s.device for s in leaf.addressable_shards}, leaf


def phase_parity(mx, sizes, seed, n_dev):
    """Data-parallel training over ``n_dev`` chips against one chip: the
    ``train`` model on the mesh the Module builds by itself and on a
    one-device mesh, from the same weights on the same global batch.

    The loss is held over all the steps.  The parameters are held after
    the FIRST step only: this network at init is chaotic in its deepest
    convolutions, and what rounding leaves between two runs there grows
    about tenfold with every further step.  On one chip the same batch in
    another order (the same gradient in exact arithmetic) moves the
    update of those weights by 0.065 of its norm after one step and 0.87
    after five (PERF.md); four chips against one can agree no better."""
    from mxnet_tpu import models, parallel
    t_phase = time.perf_counter()
    devs = jax.local_devices()
    check(len(devs) >= n_dev, "%d devices, %d wanted" % (len(devs), n_dev))
    b, hw = sizes["batch"], sizes["image"]
    sym = models.get_symbol(sizes["net"], num_classes=sizes["classes"],
                            layout="NHWC")
    batch = image_batch(mx, sizes, seed)

    def run(context, init):
        # ZeRO-1 the way a Module user asks for it
        with mock.patch.dict(os.environ, {"MXTPU_ZERO": "1"}):
            mod = build_module(mx, sym, (b, hw, hw, 3), (b,), context,
                               seed, *init)
        start = tuple({k: v.asnumpy() for k, v in p.items()}
                      for p in mod.get_params())
        big = sorted(start[0], key=lambda k: -start[0][k].size)
        metric = mx.metric.create("acc")
        t0 = time.perf_counter()
        loss = train_steps(mod, batch, metric, 1)
        update = {k: np.asarray(mod._trainer.params[k], np.float32)
                  - start[0][k] for k in big[:PARITY_LEAVES]}
        loss += train_steps(mod, batch, metric, sizes["parity_steps"] - 1)
        first_s = time.perf_counter() - t0
        step_s = step_time_block(mod, batch, metric, sizes["timed_steps"])
        return mod, start, loss, update, step_s, first_s

    # the Module builds the data mesh over every local device itself
    mod, start, loss_n, update_n, step_n, first_n = run(mx.tpu(), ())
    tr = mod._trainer
    check(dict(tr.mesh.shape) == {"data": n_dev},
          "Module built mesh %s, not data:%d" % (dict(tr.mesh.shape), n_dev))
    staged = tr._device_batch(mod._fused_batch_dict(batch))
    batch_devs, _ = shard_devices(staged)
    opt_devs, opt_leaf = shard_devices(tr.opt_state)
    shard_shape = opt_leaf.addressable_shards[0].data.shape
    check(len(batch_devs) == n_dev, "batch shards on %s" % batch_devs)
    check(len(opt_devs) == n_dev and shard_shape != opt_leaf.shape,
          "zero=1 optimizer state: shards on %s, shard %s of %s"
          % (opt_devs, shard_shape, opt_leaf.shape))
    in_use = None
    if sizes["on_chip"]:
        in_use = [d.memory_stats()["bytes_in_use"] for d in devs[:n_dev]]
        check(min(in_use) > 50e6, "bytes_in_use per device: %s" % in_use)
    del mod, tr, staged, opt_leaf
    gc.collect()

    one = parallel.make_mesh({"data": 1}, devs[:1])
    init = tuple({k: mx.nd.array(v) for k, v in p.items()} for p in start)
    _, _, loss_1, update_1, step_1, first_1 = run(one, init)

    gaps = {k: round(float(np.linalg.norm(update_n[k] - update_1[k])
                           / np.linalg.norm(update_1[k])), 5)
            for k in update_1}
    facts = dict(
        net=sizes["net"], global_batch=b, image=hw, mesh={"data": n_dev},
        zero=1, steps=len(loss_1),
        loss_n_dev=[round(x, 4) for x in loss_n],
        loss_one_dev=[round(x, 4) for x in loss_1],
        loss_rel_gap=round(max(abs(a - c) / max(1.0, abs(c))
                               for a, c in zip(loss_n, loss_1)), 5),
        loss_tol=PARITY_LOSS_TOL,
        first_update_rel_gap=gaps, first_update_tol=PARITY_PARAM_TOL,
        batch_shard_devices=sorted(map(str, batch_devs)),
        opt_state_shard_devices=sorted(map(str, opt_devs)),
        opt_state_shard_shape=list(shard_shape),
        bytes_in_use_per_device=in_use,
        step_ms_n_dev=round(step_n * 1e3, 3),
        step_ms_one_dev=round(step_1 * 1e3, 3),
        first_steps_s_n_dev=round(first_n, 2),
        first_steps_s_one_dev=round(first_1, 2))
    held = [
        (all(np.isfinite(loss_n + loss_1)), "finite loss"),
        # the first loss is a forward of equal weights, before any update
        (abs(loss_n[0] - loss_1[0]) <= PARITY_FORWARD_TOL * abs(loss_1[0]),
         "first loss"),
        (facts["loss_rel_gap"] <= PARITY_LOSS_TOL, "loss"),
        (max(gaps.values()) <= PARITY_PARAM_TOL, "first update"),
    ]
    broken = [name for ok, name in held if not ok]
    check(not broken, "%d devices against one, out of tolerance: %s: %s"
          % (n_dev, ", ".join(broken), json.dumps(facts)))
    emit("parity", ok=True, wall_s=round(time.perf_counter() - t_phase, 2),
         **facts)


# ----------------------------------------------------------------------
def run_phases(sizes, seed, chips):
    """Run the phases; returns the names of those that failed."""
    import mxnet_tpu as mx
    from mxnet_tpu import _native, program
    root = program.place_compile_cache(programs=True)
    _native.lib(), _native.dataloader_lib()
    emit("setup", jax=jax.__version__, compile_cache=root,
         program_cache=program.cache_dir(), seed=seed,
         native_libraries=_native.loaded())
    failed = []

    def guarded(name, fn, *args):
        try:
            return fn(*args)
        except Exception as e:                      # noqa: BLE001
            import traceback
            traceback.print_exc()
            failed.append(name)
            emit(name, ok=False, error="%s: %s" % (type(e).__name__, e))

    if chips > 1:
        guarded("parity", phase_parity, mx, sizes, seed, chips)
    else:
        trained = guarded("train", phase_train, mx, sizes, seed)
        gc.collect()
        if trained is not None:
            guarded("serve", phase_serve, mx, sizes, seed, *trained)
        else:
            failed.append("serve")
            emit("serve", ok=False, error="no weights: train failed")
        gc.collect()
        guarded("kernel", phase_kernel, mx, sizes, seed)
        gc.collect()
        # last: it raises jax_default_matmul_precision for the process
        guarded("ops", phase_ops, sizes)
    emit("counters", compile_cache_mb=dir_mb(root),
         program_cache_mb=dir_mb(program.cache_dir()), **counters())
    return failed


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: data-parallel parity over four chips, and "
                         "no other phase")
    ap.add_argument("--seed", type=int, default=0)
    opts = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU (platform %r)" % dev.platform,
              file=sys.stderr)
        return 1
    if len(jax.devices()) != opts.chips:
        print("chip_smoke: --chips %d but JAX reports %d device(s)"
              % (opts.chips, len(jax.devices())), file=sys.stderr)
        return 1
    failed = run_phases(SIZES, opts.seed, opts.chips)
    if failed:
        print("chip_smoke: failed phases: %s" % ", ".join(failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
