"""BENCHMARK.json and the data files a cell is made of, found by name.

A later PR adds a configuration, a traffic mix, a cell, a driver or a
per-layer reader by adding files and entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = "benchmarks"


def load_json(path: str) -> Dict[str, Any]:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Spec:
    """One cell of BENCHMARK.json with its configuration, traffic mix and
    sizes, and the metrics it reports."""

    def __init__(self, root: str, workload: str):
        self.root = root
        self.bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(
                f"unknown workload {workload!r}; have {sorted(cells)}")
        self.cell = cells[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        cfg_entry = next(c for c in self.bench["configs"]
                         if c["name"] == self.cell["config"])
        self.config = load_json(os.path.join(root, cfg_entry["file"]))
        self.traffic = load_json(self.path(
            "traffic", self.cell["traffic"] + ".json"))
        self.sizes = load_json(self.path("cells", workload + ".json"))

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, BENCH_DIR, *parts)

    def metrics(self, kind: str) -> List[Dict[str, Any]]:
        """The cell's entries of `end_to_end` or `per_layer`."""
        return [m for m in self.bench[kind]
                if "workloads" not in m or self.name in m["workloads"]]

    def load_module(self, folder: str, name: str):
        """benchmarks/<folder>/<name>.py, or the file of the longest
        dotted prefix of `name` (so `device.idle_pct.online` and
        `device.idle_pct.train` share `device.idle_pct.py`)."""
        parts = name.split(".")
        for n in range(len(parts), 0, -1):
            stem = ".".join(parts[:n])
            path = self.path(folder, stem + ".py")
            if os.path.exists(path):
                modname = f"_bench_{folder}_" + stem.replace(".", "_") \
                    .replace("-", "_")
                spec = importlib.util.spec_from_file_location(modname, path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod
        return None
