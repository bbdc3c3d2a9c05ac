"""BENCHMARK.json and the files its names point to.

A later PR adds a cell, a configuration, a traffic mix, a schedule or a
per-layer metric by adding files and entries; nothing here names one of them.

  configuration  ``file`` of its entry in ``configs`` (sizes, ``family``)
  family         ``benchmark/families/<family>.py``   model, loss, batches, FLOPs, plain forward
  traffic mix    ``benchmark/traffic/<traffic>.json`` batch shape, bf optimizer, schedule, step counts
  schedule       ``benchmark/schedules/<name>.py``    what is set on the optimizer before each step
  layer metric   ``benchmark/layer_metrics/<name>.py``  ``read(run)`` over the reduced trace and the spans
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Manifest:
    """The benchmark as one checkout holds it (``root`` is the checkout)."""

    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self.dir = os.path.join(root, self.doc["paths"][0])

    def _entry(self, section: str, name: str) -> dict:
        for entry in self.doc[section]:
            if entry["name"] == name:
                return entry
        known = sorted(e["name"] for e in self.doc[section])
        raise SystemExit(f"BENCHMARK.json has no {section} entry {name!r}; known: {known}")

    def _json(self, path: str) -> dict:
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        return self._json(os.path.join(self.root, self._entry("configs", name)["file"]))

    def traffic(self, name: str) -> dict:
        return self._json(os.path.join(self.dir, "traffic", name + ".json"))

    def plugin(self, kind: str, name: str):
        """The module ``benchmark/<kind>/<name>.py``, loaded by path (a metric's
        name need not be a Python identifier)."""
        path = os.path.join(self.dir, kind, name + ".py")
        if not os.path.isfile(path):
            raise SystemExit(f"no {kind} file {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def metrics(self, section: str, cell: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports: all
        that do not list ``workloads``, and those that list this cell."""
        return [m for m in self.doc[section]
                if cell in m.get("workloads", [cell])]
