"""Find each piece of a cell by the name ``BENCHMARK.json`` gives it.

  * a configuration: the ``file`` of its entry in ``configs``;
  * a model family: ``families/<family>.py``, named by the configuration
    file's ``family`` key (``families/__init__.py`` says what one gives);
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
import re
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class Spec:
    def __init__(self, root: str, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self._readers = {}
        self._families = {}

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
                cfg = self._json(self.root, c["file"])
                if "family" not in cfg:
                    raise KeyError(f"{c['file']} has no \"family\" key: name "
                                   f"the model family (families/<family>.py)")
                return cfg
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

    def family(self, name: str):
        """The module ``families/<name>.py`` of this root.  Where the
        importable ``families`` package is this root's, it is that
        package's ``families.<name>``, so that a family built on another
        imports it by that name; a file of another root is loaded by its
        own path."""
        if name not in self._families:
            path = os.path.join(self.bench_dir, "families", f"{name}.py")
            pkg = importlib.import_module("families")
            if os.path.dirname(os.path.abspath(path)) in map(
                    os.path.abspath, pkg.__path__):
                mod = importlib.import_module(f"families.{name}")
            else:
                mod = _load("bench_family_" + re.sub(r"\W", "_", path),
                            path)
            self._families[name] = mod
        return self._families[name]

    def reader(self, name: str):
        if name not in self._readers:
            path = os.path.join(self.bench_dir, "metrics", f"{name}.py")
            mod_name = "bench_metric_" + name.replace(".", "_").replace(
                "-", "_")
            self._readers[name] = _load(mod_name, path).read
        return self._readers[name]


def _load(mod_name: str, path: str):
    s = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(s)
    sys.modules[mod_name] = mod
    s.loader.exec_module(mod)
    return mod
