#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chips it asks for and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``).  Without a TPU, or with another number of chips
than the cell's, it exits 1 and prints no result.  There is no CPU mode:
``benchmark/checks/`` rehearses the harness's functions on the CPU and
prints no metric.
"""
import time
T_START = time.time()

import argparse                                           # noqa: E402
import json                                               # noqa: E402
import os                                                 # noqa: E402
import sys                                                # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from lib import spec                                      # noqa: E402


def say(**facts):
    """An earlier line: where the time went, for a reader of the log."""
    print(json.dumps(facts), flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    try:
        bench = spec.benchmark()
        cell = spec.Cell(opts.workload, bench)
    except spec.SpecError as e:
        print("benchmark: %s" % e, file=sys.stderr)
        return 1
    seconds = opts.seconds if opts.seconds is not None \
        else float(bench["run_seconds"])

    marks = {"python_and_spec": round(time.time() - T_START, 3)}
    import jax
    devices = jax.devices()
    marks["jax_and_tpu_runtime"] = round(
        time.time() - T_START - marks["python_and_spec"], 3)
    if devices[0].platform != "tpu":
        print("benchmark: JAX found no TPU (platform %r)"
              % devices[0].platform, file=sys.stderr)
        return 1
    if len(devices) != cell.chips:
        print("benchmark: %s asks for %d chip(s), JAX reports %d"
              % (cell.name, cell.chips, len(devices)), file=sys.stderr)
        return 1

    from lib import jobs
    result = jobs.run(cell, opts.seed, seconds, bool(opts.trace),
                      t_start=T_START, say=say, marks=marks)
    for name, value, limit, held in result.pop("compared"):
        print("compared %s %.6g limit %.6g %s"
              % (name, value, limit, "held" if held else "NOT HELD"),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
