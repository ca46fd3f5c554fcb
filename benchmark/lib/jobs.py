"""One run of a cell, from set-up to the result line."""
import contextlib
import shutil
import tempfile
import time

import jax
import numpy as np

from . import spec


def place_caches():
    """JAX's persistent cache and the program's own, both at fixed paths
    inside the checkout, every entry kept however quick its compile."""
    from mxnet_tpu import program
    root = program.place_compile_cache(programs=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return root


def counters():
    from mxnet_tpu import program
    stats = program.cache_stats()
    return {k: int(stats[k]) for k in ("compiles", "traces", "loads")}


def delta(after, before):
    return {k: after[k] - before[k] for k in after}


class Laps(dict):
    """Seconds between one ``lap`` and the next, by name."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._clock = time.perf_counter()

    def lap(self, name):
        now = time.perf_counter()
        self[name] = round(now - self._clock, 3)
        self._clock = now


def step_times(done):
    return [round((b - a) * 1e3, 3) for a, b in zip([0.0] + done[:-1], done)]


def memory_peak(device):
    """The allocator's peak of live buffers plus the peak of what the
    runtime reserved for the temporaries of compiled programs: on this
    runtime ``peak_bytes_in_use`` leaves a program's scratch out and
    ``peak_bytes_reserved`` holds it."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) \
        + stats.get("peak_bytes_reserved", 0)


def device_facts(devices):
    peak = max(memory_peak(d) for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def verdict(numbers, limits):
    """[(name, number, limit, held)] for every number that has a limit;
    a number that is not finite does not hold."""
    rows = []
    for name, limit in sorted(limits.items()):
        x = numbers[name]
        rows.append((name, x, limit, bool(np.isfinite(x) and x <= limit)))
    return rows


def run(cell, seed, seconds, trace, t_start, say, marks=None):
    """One run of ``cell``.  What kind of job a cell is, its traffic
    file says (``job``), and ``lib/<job>job.py`` holds it: a class
    ``Job`` built from (cell, seed) with ``set_up(say, lap)``,
    ``window(seconds, annotate)`` (a dict with ``steps``, ``attempted``,
    ``failed`` and ``seconds`` at least), ``end_to_end(win)`` (the
    cell's metrics but ``setup_s``), ``costs()`` and ``compared(say)``,
    which frees the program's state, runs the plain reference and
    returns the numbers that ``correct`` rests on."""
    kind = spec.job(cell.traffic["job"])
    t = Laps(marks or {})
    place_caches()
    before = counters()
    t.lap("import_program")
    job = kind.Job(cell, seed)
    job.set_up(say, t.lap)
    at_open = counters()
    setup_s = time.time() - t_start
    say(phase="setup", setup_s=round(setup_s, 3),
        where=dict(t),
        programs=delta(at_open, before))

    trace_dir = None
    annotate = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        annotate = jax.profiler.TraceAnnotation
        ctxm = jax.profiler.trace(trace_dir)
    else:
        ctxm = contextlib.nullcontext()
    try:
        with ctxm:
            win = job.window(seconds, annotate)
        at_close = counters()
        devices = jax.local_devices()
        device = device_facts(devices)
        say(phase="memory", stats=devices[0].memory_stats())
        say(phase="window", steps=win["steps"],
            seconds=round(win["seconds"], 4),
            step_done_ms=step_times(win["done"]))

        if trace:
            # what a per-layer metric's reader may read
            from . import tracered
            ctx = {"window": win, "device": device,
                   "trace": tracered.load(trace_dir, win["steps"]),
                   "counters": {"setup": delta(at_open, before),
                                "window": delta(at_close, at_open)},
                   "costs": job.costs(),
                   "peaks": spec.peaks(device["kind"])}
            device["busy_s"] = ctx["trace"].busy_s()
            device["window_s"] = ctx["trace"].window_s()
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference runs last: the peak is read, the program's state freed
    compared = verdict(job.compared(say), cell.limits["limits"])

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        values = {}
        for m in cell.per_layer:
            value = spec.reader(m["reader"]).read(ctx, **m.get("args", {}))
            if value is not None:
                values[m["name"]] = value
    else:
        values = dict(job.end_to_end(win), setup_s=setup_s)
    result = {"correct": all(held for _, _, _, held in compared),
              "attempted": win["attempted"], "failed": win["failed"],
              "metrics": {name: {"value": value, "unit": units[name]}
                          for name, value in values.items()},
              "device": device}
    if trace:
        result["breakdown"] = ctx["trace"].breakdown()
    result["compared"] = compared
    result["checked"] = {name: {"value": value, "limit": limit}
                         for name, value, limit, _ in compared}
    return result
