"""Everything a run needs, found by name: BENCHMARK.json's cells and
metrics, `configs/<name>.json`, `traffic/<name>.json` and the reader of each
metric, `metrics/<name>.py` (a module with `read(run) -> float | None`).

A cell, configuration, traffic mix or metric is added by adding its files
and its entry in BENCHMARK.json; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Bench:
    spec: dict  # BENCHMARK.json
    root: str  # directory holding configs/, traffic/, metrics/

    @classmethod
    def load(cls, spec_path: str | None = None,
             root: str = BENCH_DIR) -> "Bench":
        if spec_path is None:
            spec_path = os.path.join(os.path.dirname(root), "BENCHMARK.json")
        with open(spec_path) as f:
            return cls(spec=json.load(f), root=root)

    def _json(self, kind: str, name: str) -> dict:
        path = os.path.join(self.root, kind, f"{name}.json")
        if not os.path.isfile(path):
            raise LookupError(f"no {kind} file {path}")
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        raise LookupError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def metrics(self, cell: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics (trace off) or per-layer metrics
        (trace on), in BENCHMARK.json's order."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        reported = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", ()) or
                ("workloads" not in m and m["moves"] in reported)]

    def reader(self, metric: str):
        path = os.path.join(self.root, "metrics", f"{metric}.py")
        if not os.path.isfile(path):
            raise LookupError(f"no reader {path} for metric {metric!r}")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
