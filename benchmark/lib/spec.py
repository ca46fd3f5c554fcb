"""Everything the harness knows about a cell, found by name.

``BENCHMARK.json`` names cells, configurations and metrics; each
configuration, traffic mix, per-layer metric and set of limits is a file
of its own under ``benchmark/``.  A later PR adds files and entries and
edits nothing here.
"""
import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


class SpecError(Exception):
    pass


def _json(*parts):
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError("no such file: %s" % os.path.relpath(path, ROOT))


def _module(directory, name):
    """A python file loaded by path: names such as ``gpt2-medium`` are
    no identifiers, and nothing under ``benchmark/`` is a package a
    later PR would have to register in."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise SpecError("no such file: benchmark/%s/%s.py"
                        % (directory, name))
    spec = importlib.util.spec_from_file_location(
        "benchmark_%s_%s" % (directory, name.replace("-", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    return _json(ROOT, "BENCHMARK.json")


def peaks(device_kind):
    table = _json(HERE, "peaks.json")
    if device_kind not in table:
        raise SpecError("device kind %r is not in benchmark/peaks.json"
                        % device_kind)
    return table[device_kind]


def reference(name):
    return _module("reference", name)


def reader(name):
    return _module("readers", name)


def job(kind):
    """``lib/<kind>job.py``: what a cell of that kind of traffic does
    from set-up to the numbers compared (``jobs.run`` says what it has
    to offer)."""
    name = "%s.%sjob" % (__package__, kind)
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise SpecError("no such file: benchmark/lib/%sjob.py" % kind)


class Cell:
    """One entry of ``workloads`` with its files read in."""

    def __init__(self, name, bench=None):
        bench = bench or benchmark()
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise SpecError("BENCHMARK.json has no workload %r (it has %s)"
                            % (name, [w["name"] for w in bench["workloads"]]))
        row = rows[0]
        self.name = name
        self.chips = int(row["chips"])
        conf = [c for c in bench["configs"] if c["name"] == row["config"]][0]
        self.config = _json(ROOT, conf["file"])
        self.traffic = _json(HERE, "traffic", row["traffic"] + ".json")
        self.limits = _json(HERE, "limits", name + ".json")
        self.reference = reference(self.config["reference"])
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [
            dict(m, **_json(HERE, "metrics", m["name"] + ".json"))
            for m in bench["per_layer"]
            if name in m.get("workloads", [name])]
        if int(self.traffic["chips"]) != self.chips:
            raise SpecError("%s: traffic wants %s chips, the cell %d"
                            % (name, self.traffic["chips"], self.chips))

    @classmethod
    def of(cls, name, config, traffic, limits=None):
        """A cell from parts, for the checks: no ``BENCHMARK.json``."""
        self = cls.__new__(cls)
        self.name, self.config, self.traffic = name, config, traffic
        self.chips = int(traffic["chips"])
        self.limits = limits or {}
        self.reference = reference(config["reference"])
        self.end_to_end, self.per_layer = [], []
        return self
