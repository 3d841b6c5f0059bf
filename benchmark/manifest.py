"""Find a cell's pieces by name, from files alone.

``BENCHMARK.json`` (at the checkout's root) names the cells, the
configurations and the metrics. Everything else is a file of its own
under the benchmark's directory, found by the name the manifest gives:

- ``configs/<config>.json``: a configuration (data, index, search,
  guarantees), at the path the manifest's ``file`` gives;
- ``traffic/<traffic>.json``: a traffic mix, parameters for one of the
  drivers in ``drivers.py``;
- ``systems/<system>.py``: how a configuration's ``system`` is built
  and searched through the program's public entries;
- ``metrics/<metric>.py``: the reader of one per-layer metric. A metric
  split by the end-to-end metric it moves (``idle_share.latency``,
  ``idle_share.throughput``) shares the reader of the name before its
  first dot (``metrics/idle_share.py``) unless it has one of its own;
- ``limits/<cell>.json``: the limits of the comparison that decides
  ``correct`` in that cell, with the readings they were set from;
- ``peaks.json``: the device peak table, keyed by ``device_kind``.

A new cell, mix, configuration, system or metric is new files and
manifest entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


class Bench:
    """One benchmark tree: the manifest at ``root/BENCHMARK.json`` and
    the files under ``bench_dir`` (default ``root/benchmark``)."""

    def __init__(self, root: Optional[str] = None,
                 bench_dir: Optional[str] = None):
        self.root = root or os.path.dirname(HERE)
        self.dir = bench_dir or os.path.join(self.root, "benchmark")
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.manifest = json.load(f)
        self._modules: Dict[str, ModuleType] = {}

    # -- entries of the manifest ------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for cell in self.manifest["workloads"]:
            if cell["name"] == name:
                return cell
        known = [c["name"] for c in self.manifest["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {known}")

    def config(self, name: str) -> Dict[str, Any]:
        for entry in self.manifest["configs"]:
            if entry["name"] == name:
                return self._json(os.path.join(self.root, entry["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def metrics(self, cell: str, trace: bool) -> List[Dict[str, Any]]:
        """The cell's end-to-end metrics, or with ``trace`` its
        per-layer ones: every metric without a ``workloads`` key, and
        those whose key lists the cell."""
        group = self.manifest["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if cell in m.get("workloads", [cell])]

    # -- files found by name ------------------------------------------------
    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json(os.path.join(self.dir, "traffic", name + ".json"))

    def limits(self, cell: str) -> Dict[str, Any]:
        return self._json(os.path.join(self.dir, "limits", cell + ".json"))

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        table = self._json(os.path.join(self.dir, "peaks.json"))
        if device_kind not in table["kinds"]:
            raise KeyError(f"device kind {device_kind!r} is not in the peak "
                           f"table ({sorted(table['kinds'])}); add its "
                           "published peaks with their source")
        return table["kinds"][device_kind]

    def system(self, name: str) -> ModuleType:
        return self._module(os.path.join(self.dir, "systems", name + ".py"))

    def reader(self, metric: str) -> ModuleType:
        own = os.path.join(self.dir, "metrics", metric + ".py")
        if "." in metric and not os.path.exists(own):
            own = os.path.join(self.dir, "metrics",
                               metric.split(".", 1)[0] + ".py")
        return self._module(own)

    @staticmethod
    def _json(path: str) -> Dict[str, Any]:
        with open(path) as f:
            return json.load(f)

    def _module(self, path: str) -> ModuleType:
        if path not in self._modules:
            if not os.path.exists(path):
                raise KeyError(f"no file {path}")
            name = "_bench_" + os.path.relpath(path, self.dir).replace(
                os.sep, "_").replace(".", "_")
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]
