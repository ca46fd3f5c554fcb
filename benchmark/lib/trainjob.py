"""The program's side of a training cell: the Module built as a user
builds it, seeded weights and batches made on the device, the first
steps that ``correct`` reads, and the measured window.

From the program this takes the system under test (``Module`` on the
fused ``Trainer``), its counters and its kernel names; nothing from
``tools/``, ``bench.py`` or ``chip_smoke.py``.
"""
import contextlib
import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import refsteps
from .jobs import Laps, step_times

# leaves of the parameters' change are read after this many steps
STEPS = refsteps.STEPS


def make_batches(inp, batch, pool, key, shardings=None):
    """``pool`` distinct batches on the device from ``key``: a function
    i -> (data, label).  Labels and token ids are int32: the trainer
    casts every floating input to the compute type, and a class or
    token id does not survive bfloat16."""
    kw = {} if shardings is None else {"out_shardings": (
        shardings["data"], shardings["softmax_label"])}

    if inp["kind"] == "image":
        def make(k):
            kd, kl = jax.random.split(k)
            return (jax.random.normal(kd, (batch,) + tuple(inp["shape"]),
                                      jnp.float32),
                    jax.random.randint(kl, (batch,), 0, inp["classes"],
                                       jnp.int32))
    elif inp["kind"] == "tokens":
        def make(k):
            ids = jax.random.randint(k, (batch, inp["seq_len"] + 1), 0,
                                     inp["vocab"], jnp.int32)
            return ids[:, :-1], ids[:, 1:]
    else:
        raise ValueError("unknown input kind %r" % inp["kind"])
    make = jax.jit(make, **kw)
    made = [make(jax.random.fold_in(key, i)) for i in range(pool)]
    return lambda i: made[i % pool]


def input_shapes(inp, batch):
    if inp["kind"] == "image":
        return (batch,) + tuple(inp["shape"]), (batch,)
    return (batch, inp["seq_len"]), (batch, inp["seq_len"])


class TrainJob:
    """One Module, one compiled step, from set-up through the window."""

    def __init__(self, cell, seed):
        import mxnet_tpu as mx
        from mxnet_tpu import models
        self.mx = mx
        self.cell = cell
        cfg, tr = cell.config, cell.traffic
        self.batch = int(tr["batch"])
        ref = cell.reference
        self.init_fn = jax.jit(lambda k: ref.init(cfg, k))
        self.laps = Laps()
        sym = models.get_symbol(cfg["symbol"]["network"],
                                **cfg["symbol"]["kwargs"])
        dshape, lshape = input_shapes(cfg["input"], self.batch)
        _same_leaves(ref.param_shapes(cfg), sym, dshape, lshape)
        env = {k: str(v) for k, v in tr.get("env", {}).items()}
        with _environ(env):
            self.mod = mod = mx.mod.Module(
                context=mx.tpu(), symbol=sym,
                compute_dtype=cfg["compute_dtype"])
            mod.bind(data_shapes=[("data", dshape)],
                     label_shapes=[("softmax_label", lshape)])
            self.laps.lap("symbol_and_bind")
            self._seed_weights(seed)
            opt = dict(cfg["optimizer"])
            name = opt.pop("name")
            opt["rescale_grad"] = 1.0 / float(np.prod(lshape))
            mod.init_optimizer(kvstore=mx.kvstore.create(cfg["kvstore"]),
                               optimizer=name, optimizer_params=opt)
        self.laps.lap("init_optimizer")
        if mod._trainer is None:
            raise RuntimeError("Module did not take the fused path")
        mesh = mod._trainer.mesh
        have_mesh = None if mesh is None or mesh.size == 1 \
            else {k: int(v) for k, v in dict(mesh.shape).items()}
        if have_mesh != tr.get("mesh"):
            raise RuntimeError("Module built mesh %s, the traffic names %s"
                               % (have_mesh, tr.get("mesh")))
        self._seed_batches()
        self.laps.lap("batches_from_seed")
        self.metric = mx.metric.create("acc")
        self._xent = jax.jit(_xent)
        self._norms = jax.jit(refsteps.leaf_norms)
        self._tick = jax.jit(lambda x: x + 0)
        self._smallest = min(self.params(),
                             key=lambda n: self.params()[n].size)

    def _seed_weights(self, seed):
        """Weights made on the device from the seed by the reference's
        ``init``, in one jitted call, and given to the Module."""
        key = jax.random.key(seed % (2 ** 63))
        self.init_key, self.data_key = jax.random.split(key)
        params, aux = self.init_fn(self.init_key)
        jax.block_until_ready(params)
        self.laps.lap("weights_from_seed")
        nd = self.mx.nd.NDArray
        self.mod.init_params(
            initializer=None, force_init=True,
            arg_params={n: nd(v) for n, v in params.items()},
            aux_params={n: nd(v) for n, v in aux.items()})
        self.laps.lap("init_params")

    def _seed_batches(self):
        self.batches = make_batches(
            self.cell.config["input"], self.batch,
            int(self.cell.traffic["pool"]), self.data_key,
            self.mod._trainer._batch_shardings)
        self.steps_done = 0

    def reseed(self, seed):
        """The same Module and compiled step on another seed's weights
        and batches (the readings over many seeds need one set-up)."""
        self._seed_weights(seed)
        self.mod._trainer.num_update = 0
        self._seed_batches()
        self.metric.reset()

    # -- the loop ------------------------------------------------------
    def databatch(self, i):
        data, label = self.batches(i)
        nd = self.mx.nd.NDArray
        return self.mx.io.DataBatch(data=[nd(data)], label=[nd(label)],
                                    pad=0)

    def fit_step(self):
        """One step of ``Module.fit``'s inner loop on the next batch."""
        batch = self.databatch(self.steps_done)
        self.mod.forward(batch, is_train=True)
        self.mod.update()
        self.mod.update_metric(self.metric, batch.label)
        self.steps_done += 1

    def params(self):
        return self.mod._trainer.params

    def barrier(self):
        jax.block_until_ready(self.params())

    def tick(self):
        """A few numbers that exist once the step just dispatched has
        run: a copy of the smallest parameter, dispatched behind the
        step and before the next one donates that buffer.  It costs the
        device microseconds whatever the model's size."""
        return self._tick(self.params()[self._smallest])

    # -- what correct reads of the program -----------------------------
    def first_steps(self):
        """Drive the first three steps through the window's own call and
        feed, and read each step's loss, the first gradient as the
        optimizer got it (momentum after one step is -rate x gradient)
        and the parameters' change after the three."""
        assert self.steps_done == 0
        lr = float(self.cell.config["optimizer"]["learning_rate"])
        losses, grad = [], None
        for i in range(STEPS):
            _, label = self.batches(i)
            self.fit_step()
            losses.append(self._xent(self.mod.get_outputs()[0].data, label))
            if i == 0:
                grad = self._norms(self.mod._trainer.opt_state)
        change = refsteps.change_norms(self.params(), self.init_fn,
                                       self.init_key)
        out = jax.device_get((losses, grad, change))
        return {"loss": [float(x) for x in out[0]],
                "grad": {n: float(v) / lr for n, v in out[1].items()},
                "change": {n: float(v) for n, v in out[2].items()}}

    def close(self):
        """Free the program's state on the device."""
        self.mod = self.metric = self.batches = None
        gc.collect()

    # -- what jobs.run asks of a job ------------------------------------
    def set_up(self, say, lap):
        self.barrier()
        lap("build_and_weights")
        say(phase="build", where=self.laps)
        self.got = self.first_steps()
        lap("first_steps_load_or_compile")
        warm = warm_up(self)
        say(phase="warm_up", step_done_ms=step_times(warm["done"]))
        lap("warm_up")

    def window(self, seconds, annotate=None):
        return window(self, seconds, annotate)

    def end_to_end(self, win):
        tr = self.cell.traffic
        samples = win["steps"] * self.batch * int(tr["samples_per_row"])
        return {tr["rate_metric"]: samples / win["seconds"]}

    def costs(self):
        return self.cell.reference.costs(self.cell.config, self.batch)

    def compared(self, say):
        cell, init_fn, init_key = self.cell, self.init_fn, self.init_key
        batches = self.batches
        self.close()
        t_ref = time.perf_counter()
        want = reference_steps(cell, init_fn, init_key, batches)
        del batches
        numbers = refsteps.compare(self.got, want)
        say(phase="reference", seconds=round(time.perf_counter() - t_ref, 3),
            program_loss=self.got["loss"], reference_loss=want["loss"],
            numbers=numbers)
        return numbers


Job = TrainJob


def reference_steps(cell, init_fn, init_key, batches, **kw):
    """The plain reference's first steps on the cell's weights and
    batches; ``cast`` and ``fault`` make it the control or a fault."""
    return refsteps.run(
        cell.reference, cell.config, cell.config["optimizer"], init_fn,
        init_key, batches,
        row_block=cell.traffic.get("reference_row_block"), **kw)


def _same_leaves(want, sym, dshape, lshape):
    """The reference's parameters and auxiliary states are the program
    symbol's, name for name and shape for shape; else an error."""
    arg_s, _, aux_s = sym.infer_shape(data=dshape, softmax_label=lshape)
    have = ({n: s for n, s in zip(sym.list_arguments(), arg_s)
             if n not in ("data", "softmax_label")},
            dict(zip(sym.list_auxiliary_states(), aux_s)))
    for w, h, what in zip(want, have, ("parameters", "auxiliary states")):
        w = {n: tuple(s) for n, s in w.items()}
        h = {n: tuple(s) for n, s in h.items()}
        if w != h:
            raise RuntimeError(
                "the reference's %s are not the program's: %s"
                % (what, sorted(set(h.items()) ^ set(w.items()))[:6]))


def _xent(probs, label):
    """Mean -log p[label] of the program's softmax output."""
    p = jnp.take_along_axis(probs.astype(jnp.float32),
                            label.reshape(-1, 1), axis=1)
    return -jnp.mean(jnp.log(jnp.maximum(p, 1e-30)))


@contextlib.contextmanager
def _environ(env):
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ----------------------------------------------------------------------
def loop(job, enough, span):
    """The timed loop: Module.fit's inner loop with the host one step
    ahead of the device and no further.  After dispatching step i it
    waits for step i-1, so the queue never runs dry and the work does
    not depend on how far dispatch could run ahead.  ``enough(done)``
    sees the completion times so far and says when to stop; the loop
    then closes by ``block_until_ready`` on the updated parameters.
    Returns steps, seconds, the host's time inside the Module calls, and
    every step's completion time."""
    t_open = time.perf_counter()
    host = 0.0
    done = []
    ticks = []
    while True:
        t0 = time.perf_counter()
        with span("bench.module_calls"):
            job.fit_step()
        host += time.perf_counter() - t0
        ticks.append(job.tick())
        if len(ticks) > 1:
            with span("bench.wait_device"):
                ticks[-2].block_until_ready()
            done.append(time.perf_counter() - t_open)
            ticks[-2] = None
            if enough(done):
                break
    with span("bench.wait_device"):
        job.barrier()
    t_close = time.perf_counter() - t_open
    done.append(t_close)
    return {"steps": len(done), "attempted": len(done), "failed": 0,
            "seconds": t_close, "host_s": host, "done": done}


def _no_span(name):
    return contextlib.nullcontext()


def warm_up(job, agree=3, within=0.005, most=10):
    """The window's own loop, run until ``agree`` consecutive steps took
    the same time within ``within``, ``most`` steps at the most: every
    program the window calls has then run, and the device has settled."""
    def enough(done):
        steps = [b - a for a, b in zip([0.0] + done[:-1], done)][1:]
        last = steps[-agree:]
        return len(done) + 1 >= most or (
            len(last) == agree
            and max(last) - min(last) <= within * min(last))
    job.barrier()
    return loop(job, enough, _no_span)


def window(job, seconds, annotate=None):
    """Steps for ``seconds`` and on to the step boundary that follows;
    all the work over all the time, to the closing barrier."""
    span = annotate or _no_span
    job.barrier()
    with span("bench.window"):
        return loop(job, lambda done: done[-1] >= seconds, span)
