"""What ``BENCHMARK.json`` names, found by name: a cell's parameters under
``cells/``, its configuration's file, its traffic mix under ``traffic/`` and
each per-layer metric's reader under ``metrics/``.  A later PR adds a
configuration, a cell or a metric by adding files and entries; nothing here
lists them.
"""

from __future__ import annotations

import importlib.util
import json
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Spec:
    def __init__(self, root: str = REPO_ROOT):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self.dir = os.path.join(root, self.bench["paths"][0])

    def _json(self, *parts):
        with open(os.path.join(self.dir, *parts)) as f:
            return json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.bench['workloads']]}")

    def cell(self, name: str) -> dict:
        """The cell's parameter file merged over its BENCHMARK.json entry."""
        return {**self._json("cells", f"{name}.json"), **self.workload(name)}

    def config(self, name: str) -> dict:
        for c in self.bench["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._json("traffic", f"{name}.json")

    def metrics_of(self, cell_name: str, kind: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        out = []
        for m in self.bench[kind]:
            cells = m.get("workloads")
            if cells is None:
                if kind == "per_layer":
                    moved = next(e for e in self.bench["end_to_end"] if e["name"] == m["moves"])
                    cells = moved.get("workloads")
            if cells is None or cell_name in cells:
                out.append(m)
        return out

    def reader(self, metric_name: str):
        """``read(ctx) -> number | None`` of a per-layer metric, from the file
        ``metrics/<name>.py``."""
        path = os.path.join(self.dir, "metrics", f"{metric_name}.py")
        spec = importlib.util.spec_from_file_location(
            "perfbench_metric_" + metric_name.replace(".", "_").replace("-", "_"), path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
