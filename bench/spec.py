"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives them.

* configuration: the ``file`` of its ``configs`` entry (JSON), whose
  ``generator`` names ``bench/tables/<generator>.py``;
* traffic mix: ``bench/traffic/<traffic>.json``;
* per-layer metric: ``bench/metrics/<name>.py``, whose ``read(run)``
  returns the number or None.

A new cell, configuration, mix or metric is new files and new entries;
nothing here names one.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class Benchmark:
    def __init__(self, root: str, path: Optional[str] = None):
        self.root = root
        with open(path or os.path.join(root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)

    def _named(self, key: str, name: str) -> dict:
        for entry in self.spec[key]:
            if entry["name"] == name:
                return entry
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        with open(os.path.join(self.root, self._named("configs", name)["file"])) as f:
            return json.load(f)

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.root, "bench", "traffic", name + ".json")) as f:
            return json.load(f)

    def metrics(self, workload: str, kind: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or workload in m["workloads"]]

    def metric_reader(self, name: str):
        return metric_reader(name, os.path.join(self.root, "bench", "metrics"))


def generator(name: str):
    return importlib.import_module(f"bench.tables.{name}")


def metric_reader(name: str, directory: str = os.path.join(BENCH_DIR, "metrics")):
    path = os.path.join(directory, name + ".py")
    mod_name = "bench.metrics." + name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
