"""Finds what belongs to a cell by name.

``BENCHMARK.json`` (the repo's root) names cells, configurations and
metrics; everything that belongs to one of them is a file of its own
under this directory, found by that name:

    configs/<config>.json      the configuration as it is run
    traffic/<traffic>.json     parameters of the traffic mix; ``kind``
                               picks the driver (train.py, serve.py)
    limits/<cell>.json         the limits ``correct`` holds the cell to
    models/<family>.py         how a family of configurations is built,
                               fed and referred to (``family`` in the
                               configuration's file)
    metrics/<metric>.json      a per-layer metric: ``reader`` names the
                               file under metrics/ whose ``read(ctx,
                               spec)`` takes it from the run's spans,
                               counters and trace, or returns None

A later PR adds a cell, a configuration or a metric by adding such
files and an entry in BENCHMARK.json; it edits none that is there.
"""
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class Cell:
    def __init__(self, name, config_name, config, traffic_name,
                 traffic, chips, limits):
        self.name = name
        self.config_name, self.config = config_name, config
        self.traffic_name, self.traffic = traffic_name, traffic
        self.chips = chips
        self.limits = limits


class Harness:
    def __init__(self, bench_dir=HERE, benchmark_json=None):
        self.dir = bench_dir
        path = benchmark_json or os.path.join(
            os.path.dirname(bench_dir), "BENCHMARK.json")
        with open(path) as f:
            self.spec = json.load(f)

    def _path(self, *parts):
        """A file of this harness's directory, or, where it has none
        of that name, the benchmark's own."""
        path = os.path.join(self.dir, *parts)
        return path if os.path.exists(path) \
            else os.path.join(HERE, *parts)

    def _json(self, *parts, missing_ok=False):
        path = self._path(*parts)
        if missing_ok and not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)

    def _module(self, sub, filename):
        path = self._path(sub, filename)
        package = f"{__package__}.{sub}" if __package__ else sub
        name = f"{package}.{os.path.splitext(filename)[0]}"
        if os.path.dirname(path) == os.path.join(HERE, sub):
            return importlib.import_module(name)
        # a directory of someone else's (a test's): load by path, as a
        # member of this package so that its relative imports hold
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def cell(self, name):
        for w in self.spec["workloads"]:
            if w["name"] == name:
                break
        else:
            known = [w["name"] for w in self.spec["workloads"]]
            raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                           f"(cells: {known})")
        return Cell(
            name, w["config"],
            self._json("configs", w["config"] + ".json"),
            w["traffic"],
            self._json("traffic", w["traffic"] + ".json"),
            w["chips"],
            self._json("limits", name + ".json", missing_ok=True))

    def family(self, config):
        return self._module("models", config["family"] + ".py")

    def metrics(self, cell_name, group):
        """The metrics of ``group`` (end_to_end or per_layer) that
        ``cell_name`` reports.  A metric without ``workloads`` is every
        cell's that reports the end-to-end metric it moves."""
        mine = []
        e2e = {m["name"]: m for m in self.spec["end_to_end"]}
        for m in self.spec[group]:
            cells = m.get("workloads")
            if cells is None and group == "per_layer":
                cells = e2e[m["moves"]].get("workloads")
            if cells is None or cell_name in cells:
                mine.append(m)
        return mine

    def read_per_layer(self, cell_name, ctx):
        """name -> {"value", "unit"} of every per-layer metric of the
        cell whose reader found something to read."""
        out = {}
        for m in self.metrics(cell_name, "per_layer"):
            spec = self._json("metrics", m["name"] + ".json")
            reader = self._module("metrics", spec["reader"])
            value = reader.read(ctx, spec)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def peaks(self, device_kind):
        table = self._json("peaks.json")
        if device_kind not in table:
            raise KeyError(
                f"no peaks for device kind {device_kind!r} in "
                f"peaks.json (known: {sorted(table)}): add a row with "
                "its source, never a default")
        return table[device_kind]
