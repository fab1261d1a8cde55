"""Finding the benchmark's data files by the names `BENCHMARK.json`
gives them. A later PR adds a configuration, a mix, a cell, a metric or
a limit by adding files and entries; nothing here names any of them.

  configs/<config>.json     sizes, source, engine and server options
  traffic/<traffic>.json    the mix's parameters (see traffic.py)
  metrics/<metric>.json     {"reader": <module under readers/>, "args": {}}
  limits/<workload>.json    the limits of the numbers `correct` compares
  reference/<family>.py     the configuration's plain reference
  systems/<system>.py       the adapter to the system under test
"""

from __future__ import annotations

import importlib
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load(*parts) -> dict:
    with open(os.path.join(_HERE, *parts)) as f:
        return json.load(f)


class Benchmark:
    def __init__(self, root=None):
        """`root`: a directory holding a `BENCHMARK.json` (the checkout's
        root by default; the self-check passes its test data)."""
        root = root or os.path.dirname(_HERE)
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self._root = root

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(has {[w['name'] for w in self.doc['workloads']]})")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self._root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load("traffic", name + ".json")

    def limits(self, workload: str) -> dict:
        return _load("limits", workload + ".json")

    def metrics_for(self, workload: str, *, trace: bool) -> list:
        """The metric entries this run must report: the cell's end-to-end
        metrics without a trace, its per-layer metrics with one."""
        kind = "per_layer" if trace else "end_to_end"
        return [m for m in self.doc[kind]
                if "workloads" not in m or workload in m["workloads"]]

    def read_metric(self, name: str, cap):
        """The metric's value from its own reader, or None where the
        reader finds nothing to read."""
        m = _load("metrics", name + ".json")
        reader = importlib.import_module("benchmark.readers." + m["reader"])
        return reader.read(cap, **m.get("args", {}))
