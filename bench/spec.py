"""Find each piece of a cell by the name ``BENCHMARK.json`` gives it.

  * a configuration: the ``file`` of its entry in ``configs``;
  * a traffic mix: ``traffic/<traffic>.json``;
  * a cell's fixed load (its rate): ``cells/<workload>.json``;
  * a metric: ``metrics/<name>.py``, whose ``read(run)`` returns a number,
    or None where the run has nothing for it to read.

New cells, mixes, configurations and metrics are new files and new entries;
no file that is here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class Spec:
    def __init__(self, root: str, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self._readers = {}

    def _json(self, *parts):
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return self._json(self.root, c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._json(self.bench_dir, "traffic", f"{name}.json")

    def cell(self, workload: str) -> dict:
        return self._json(self.bench_dir, "cells", f"{workload}.json")

    def metrics(self, workload: str, traced: bool) -> list:
        """The metrics a run of ``workload`` reports: the end-to-end ones
        untraced, the per-layer ones traced; each only where its
        ``workloads`` list (if any) names the cell."""
        group = self.doc["per_layer" if traced else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, name: str):
        if name not in self._readers:
            path = os.path.join(self.bench_dir, "metrics", f"{name}.py")
            mod_name = "bench_metric_" + name.replace(".", "_").replace(
                "-", "_")
            s = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(s)
            s.loader.exec_module(mod)
            self._readers[name] = mod.read
        return self._readers[name]
