"""Shared helpers over the fused Trainer's compiled step.

bench.py, tools/remat_sweep.py, and tools/step_breakdown.py all need
the same three things: lower+compile the step for a concrete batch,
read XLA's aggregate cost analysis, and time Module-path steps up to a
completion barrier.  Keeping them here means the private
``Trainer._step_fn`` call signature is stated once — a signature change
breaks these helpers loudly instead of silently voiding three copies'
artifact fields.
"""
import time


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
