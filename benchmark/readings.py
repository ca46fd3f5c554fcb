#!/usr/bin/env python3
"""The readings a training cell's limits are set from, taken on the chip
at the cell's own size in one process (PERF.md gives the readings and
limits).

    python3 benchmark/readings.py --workload <cell> --seeds 12 --control-seeds 4

First the program: one Module and compiled step, the timed path,
re-seeded for every seed and driven through its first three steps.  Then,
the program's state freed, for every seed the float32 reference; and for
the first ``--control-seeds`` of them the float8 control and each fault
the cell can have, planted in the reference put in the program's place.
Each is put through the comparison a run makes, against the cell's
limits as committed: one line a number, held or NOT HELD, and at the end
whether every sound run held every limit and the control and each fault
failed one.  ``--raw`` keeps the norms leaf by leaf.  Needs the chips
the cell asks for; it measures no speed.
"""
import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from lib import spec                                      # noqa: E402

FAULTS = ["half_batch"]       # a state left unchanged reads 1, unrun


def read(cell, seeds, control_seeds, raw=None, say=print):
    """Whether every sound run held every limit of ``cell`` and the
    control and each fault failed one on every seed; each number on a
    line of its own through ``say``."""
    from lib import jobs, refsteps, trainjob
    job = trainjob.TrainJob(cell, seeds[0])
    got, keys = {}, {}
    for seed in seeds:
        if seed != seeds[0]:
            job.reseed(seed)
        got[seed] = job.first_steps()
        keys[seed] = (job.init_key, job.data_key)
    init_fn = job.init_fn
    job.close()

    limits = cell.limits["limits"]
    held = {}                   # who -> [every limit held, a seed]
    for i, seed in enumerate(seeds):
        init_key, data_key = keys[seed]
        batches = trainjob.make_batches(
            cell.config["input"], int(cell.traffic["batch"]),
            refsteps.STEPS, data_key)

        def ref(**kw):
            return trainjob.reference_steps(cell, init_fn, init_key,
                                            batches, **kw)

        runs = {"reference": ref(), "program": got[seed]}
        if i < control_seeds:
            runs["control_fp8"] = ref(cast="fp8")
            for fault in FAULTS:
                runs["fault_" + fault] = ref(fault=fault)
        if raw:
            raw.write(json.dumps(dict(runs, seed=seed)) + "\n")
            raw.flush()
        want = runs.pop("reference")
        for who, run in runs.items():
            numbers = refsteps.compare(run, want)
            rows = jobs.verdict(numbers, limits)
            held.setdefault(who, []).append(all(r[3] for r in rows))
            say(json.dumps({"cell": cell.name, "seed": seed, "who": who,
                            "numbers": numbers}))
            for name, value, limit, ok in rows:
                say("%s seed %d %s %.6g limit %.6g %s"
                    % (who, seed, name, value, limit,
                       "held" if ok else "NOT HELD"))
    sound = True
    for who, oks in held.items():
        ok = all(oks) if who == "program" else not any(oks)
        sound = sound and ok
        say("%s: %d of %d runs held every limit, %s"
            % (who, sum(oks), len(oks), "as it has to" if ok else
               "WHICH IS WRONG: " + (
                   "a sound run holds every limit" if who == "program"
                   else "it has to fail one on every seed")))
    return sound


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--raw", help="a file for every run's norms, a line "
                    "a seed")
    opts = ap.parse_args(argv)
    cell = spec.Cell(opts.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell.chips:
        print("readings: %s needs %d TPU chip(s)" % (cell.name, cell.chips),
              file=sys.stderr)
        return 1
    from lib import jobs
    jobs.place_caches()
    seeds = [opts.first_seed + 7919 * i for i in range(opts.seeds)]
    with open(opts.raw, "w") if opts.raw else contextlib.nullcontext() as raw:
        sound = read(cell, seeds, opts.control_seeds, raw,
                     lambda line: print(line, flush=True))
    return 0 if sound else 4


if __name__ == "__main__":
    sys.exit(main())
