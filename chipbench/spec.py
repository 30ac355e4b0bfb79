"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix.  The configuration's file is
the one its entry in ``configs`` gives; the traffic mix is
``chipbench/traffic/<traffic>.json``; each metric is read by
``chipbench/metrics/<metric>.py``; the traffic names its loop,
``chipbench/loops/<loop>.py``, and the configuration its reference,
``chipbench/reference/<reference>.py``.  Nothing here names a cell.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import a file by path (metric names may hold dots, so these files
    are not importable under their own names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Benchmark:
    """The parsed ``BENCHMARK.json`` of a checkout."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.data = _json(os.path.join(root, "BENCHMARK.json"))
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.cells = {w["name"]: w for w in self.data["workloads"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise SystemExit(f"chipbench: no workload {name!r}; have "
                             f"{sorted(self.cells)}")
        return self.cells[name]

    def config(self, cell: dict) -> dict:
        return _json(os.path.join(self.root,
                                  self.configs[cell["config"]]["file"]))

    def traffic(self, cell: dict) -> dict:
        return _json(os.path.join(self.root, "chipbench", "traffic",
                                  cell["traffic"] + ".json"))

    def metrics(self, cell: dict, kind: str) -> list[dict]:
        """The cell's ``end_to_end`` or ``per_layer`` metrics: those that
        list it, or that list no cells at all."""
        return [m for m in self.data[kind]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric: str):
        path = os.path.join(self.root, "chipbench", "metrics", metric + ".py")
        return load_module(path, "chipbench_metric_" + metric.replace(".", "_"))


def loop(name: str):
    return importlib.import_module(f"chipbench.loops.{name}")


def reference(name: str):
    return importlib.import_module(f"chipbench.reference.{name}")
