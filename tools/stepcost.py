"""Shared helpers over the fused Trainer's compiled step.

``chip_smoke.py``, ``tools/autotune.py`` and
``examples/memcost/inception_memcost.py`` need the same things:
lower+compile the step for a concrete batch, read XLA's aggregate cost
analysis, time Module-path steps up to a completion barrier, and price a
trainer config without running it (:func:`cost_model`).  Keeping them
here means the private ``Trainer._step_fn`` call signature is stated
once — a signature change breaks these helpers loudly.
"""
import time

import numpy as np


def compile_step(trainer, batch_vals, lr=0.1):
    """Lower + compile the fused step for concrete batch values.  With
    the step sentinel armed the signature gains the sentinel-state arg
    after opt_state (see Trainer._build)."""
    import jax.numpy as jnp
    sent = getattr(trainer, "_sent", None)
    args = (trainer.params, trainer.aux, trainer.opt_state)
    args += (sent,) if sent is not None else ()
    args += (batch_vals, jnp.float32(lr), jnp.int32(1), trainer._key)
    return trainer._step_fn.lower(*args).compile()


def cost_analysis(comp):
    """{"flops": float, "bytes": float} from a compiled step."""
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def timed_module_steps(mod, metric, data_batch, steps, warmup=5):
    """Run the Module.fit inner loop (forward/update/update_metric) and
    return (seconds_for_timed_steps, warmup_seconds).  ``metric.get()``
    drains the device accumulator, which depends on every step's
    outputs, so it closes the window.  On the local v5e it agrees with
    ``jax.block_until_ready`` on the updated parameters to 0.3 ms in a
    698 ms step, after one warm-up cycle (``chip_smoke.py``'s ``train``
    phase times both; PERF.md)."""
    def one_step():
        mod.forward(data_batch, is_train=True)
        mod.update()
        mod.update_metric(metric, data_batch.label)

    t0 = time.perf_counter()
    if warmup:                                 # warmup=0 stays cold
        for _ in range(warmup):
            one_step()
        metric.get()
        metric.reset()
    warm_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    metric.get()
    return time.perf_counter() - t0, warm_s


# ----------------------------------------------------------------------
# the importable byte cost model: the autotuner's training surrogate
# (tools/autotune.py: train_surrogate)
def step_cost(trainer, batch_vals, lr=0.1):
    """Compile the fused step for concrete batch values and return
    XLA's aggregate cost-model accounting::

        {"bytes", "flops", "gb_per_step", "tflop_per_step"}

    Pure trace+compile — nothing executes."""
    ca = cost_analysis(compile_step(trainer, batch_vals, lr=lr))
    return {"bytes": ca["bytes"], "flops": ca["flops"],
            "gb_per_step": ca["bytes"] / 1e9,
            "tflop_per_step": ca["flops"] / 1e12}


# the knobs cost_model understands; a typo'd key is a loud error with
# a did-you-mean (the envknobs/faults discipline — a surrogate that
# silently ignored "grad_acum" would "tune" nothing)
_COST_CONFIG_DEFAULTS = {
    "model": "mlp", "batch": 16, "image": 64, "num_classes": None,
    "devices": 1, "compute_dtype": None, "dtype_policy": None,
    "remat": None, "zero": None, "grad_accum": None, "grad_dtype": None,
}


def build_cost_trainer(config=None, **overrides):
    """Build the fused Trainer + concrete batch for a cost/surrogate
    config — the ONE workload constructor :func:`cost_model` (XLA byte
    accounting) and the memory-model test (``tests/test_mem_lint.py``)
    share, so the two never describe different programs.  Returns
    ``(trainer, batch_vals, cfg)``."""
    cfg = dict(_COST_CONFIG_DEFAULTS)
    given = dict(config or {}, **overrides)
    unknown = sorted(set(given) - set(cfg))
    if unknown:
        import difflib
        close = difflib.get_close_matches(unknown[0], sorted(cfg), n=1)
        raise ValueError(
            "unknown cost_model config key(s) %s%s — known: %s"
            % (unknown, (" (did you mean %r?)" % close[0]) if close
               else "", "/".join(sorted(cfg))))
    cfg.update(given)

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.parallel.trainer import Trainer

    batch = int(cfg["batch"])
    if cfg["model"] == "mlp":
        # THE tune workload — the same symbol serve_bench builds (and
        # the one the emitted plan is keyed to), not a lookalike: a
        # private copy here would fork the digest (and the program-
        # cache keyspace) from the timed trials
        from tools.serve_bench import build_model
        if cfg["num_classes"] not in (None, 16):
            raise ValueError("the mlp tune workload has a fixed "
                             "16-class head (num_classes=%r)"
                             % (cfg["num_classes"],))
        ncls = 16
        sym = build_model("mlp", 0)[0]
        data_shape = (batch, 64)
    elif cfg["model"] == "resnet-50":
        from mxnet_tpu import models
        ncls = int(cfg["num_classes"] or 1000)
        sym = models.get_symbol("resnet-50", num_classes=ncls,
                                layout="NHWC")
        image = int(cfg["image"])
        data_shape = (batch, image, image, 3)
    else:
        raise ValueError("unknown cost_model model %r (mlp|resnet-50)"
                         % (cfg["model"],))

    mesh = None
    n = int(cfg["devices"])
    if n > 1:
        devices = jax.devices()
        if len(devices) < n:
            raise RuntimeError(
                "cost_model config wants a %d-way data mesh but only "
                "%d local devices exist" % (n, len(devices)))
        mesh = parallel.make_mesh({"data": n}, devices[:n])

    t = Trainer(sym, mx.optimizer.create(
        "sgd", learning_rate=0.1, momentum=0.9,
        rescale_grad=1.0 / batch),
        mesh=mesh, compute_dtype=cfg["compute_dtype"],
        dtype_policy=cfg["dtype_policy"], remat=cfg["remat"],
        zero=cfg["zero"], grad_accum=cfg["grad_accum"],
        grad_dtype=cfg["grad_dtype"])
    t.bind(data_shapes={"data": data_shape},
           label_shapes={"softmax_label": (batch,)})
    mx.random.seed(3)
    t.init_params(mx.init.Xavier())
    rng = np.random.RandomState(0)
    batch_vals = {
        "data": jnp.asarray(rng.normal(0, 1, data_shape)
                            .astype(np.float32)),
        "softmax_label": jnp.asarray(
            rng.randint(0, ncls, (batch,)).astype(np.float32))}
    return t, batch_vals, cfg


def cost_model(config=None, **overrides):
    """``cost_model(config) -> {"gb_per_step", ...}`` — the importable
    training-side surrogate: build the fused Trainer for ``config``,
    compile (never execute) its step, and return the XLA cost-model
    bytes/flops.  Config knobs: ``model`` (``mlp`` — CPU-tier seconds —
    or ``resnet-50``), ``batch``, ``image`` (resnet), ``num_classes``,
    ``devices`` (data-mesh degree over the local devices; >1 enables
    the zero/grad_dtype corners), and the trainer knobs
    ``compute_dtype``/``dtype_policy``/``remat``/``zero``/
    ``grad_accum``/``grad_dtype``.

    A repeated config against a warm ``MXTPU_PROGRAM_CACHE`` re-uses
    the persisted executable, so the dominant cost — tracing — is paid
    once per distinct config, ever (docs/how_to/compiled_programs.md).
    """
    t, batch_vals, cfg = build_cost_trainer(config, **overrides)
    sc = step_cost(t, batch_vals)
    # static liveness peak (trace-only, no compile): the memory-
    # feasibility axis of the surrogate — bytes MOVED (gb_per_step)
    # says how fast a config is, bytes RESIDENT says whether it runs
    # at all (tools/mem_lint.py; autotune prunes on it)
    try:
        peak = t.predicted_peak_bytes()
    except Exception:  # noqa: BLE001 — the surrogate must not die
        peak = 0       # on an analyzer gap; 0 = "unknown, don't prune"
    return {"gb_per_step": round(sc["gb_per_step"], 6),
            "tflop_per_step": round(sc["tflop_per_step"], 6),
            "bytes": sc["bytes"], "flops": sc["flops"],
            "opt_state_bytes_per_chip": t.opt_state_bytes_per_chip(),
            "grad_comm_gb_per_step": round(
                t.grad_comm_bytes_per_step() / 1e9, 6),
            "predicted_peak_bytes": peak,
            "config": {k: v for k, v in cfg.items()}}
