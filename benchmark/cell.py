"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` reads:

- the configuration named by ``configs[].file`` (policy sizes, the
  protocols its connections speak, ``daemon`` overrides of
  ``DaemonConfig``);
- ``benchmark/traffic/<traffic>.json`` (parameters of ``mixgen.py``);
- ``benchmark/metrics/<metric>.py`` for each per-layer metric the cell
  reports (a reader, see ``layers.py``).
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Cell:
    def __init__(self, name: str, root: str = ROOT):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        cfg = {c["name"]: c for c in bench["configs"]}[self.spec["config"]]
        self.config_path = os.path.join(root, cfg["file"])
        self.traffic_path = os.path.join(
            root, "benchmark", "traffic", f"{self.spec['traffic']}.json")
        with open(self.config_path) as f:
            self.config = json.load(f)
        with open(self.traffic_path) as f:
            self.traffic = json.load(f)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in moved)
        ]
        self.root = root

    def reader(self, metric: str):
        path = os.path.join(self.root, "benchmark", "metrics",
                            f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark.metrics.{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
