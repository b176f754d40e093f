"""A cell, assembled from files found by name.

``BENCHMARK.json`` (at the checkout root) names each cell's configuration
and traffic mix.  Everything else is a file under ``bench/`` named after
what uses it, so that adding a cell adds files and a ``workloads`` entry
and edits none:

* the configuration: the file its ``configs`` entry names;
* the traffic mix: ``bench/traffic/<traffic>.json``;
* the window that drives the traffic: ``bench/windows/<kind>.py``, by the
  traffic file's ``kind``;
* the data generator: ``bench/gen/<name>.py``, by the configuration's
  ``generator.name``;
* the control: ``bench/controls/<name>.py``, by the configuration's
  ``control``;
* a per-layer metric's reader: ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

#: The checkout root: the directory that holds ``BENCHMARK.json``.
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str

    def module(self, kind: str, name: str):
        return load_module(self.root, kind, name)

    def reader(self, metric: str):
        """The reader of one per-layer metric."""
        return self.module("metrics", metric)

    def window(self):
        return self.module("windows", self.traffic["kind"])

    def control(self):
        return self.module("controls", self.config["control"])

    def block(self, seed: int):
        """The configuration's seeded block of records:
        ``(data, rec_end, str_bytes)``."""
        spec = self.config["generator"]
        return self.module("gen", spec["name"]).make(
            seed, self.config["distinct_bytes"], spec)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


_MODULES: Dict[str, object] = {}


def load_module(root: str, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py`` as a module (loaded once)."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind} file {path}")
        mod_name = "bench_%s_%s" % (kind, "".join(
            ch if ch.isalnum() else "_" for ch in name))
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def load(name: str, root: str = ROOT) -> Cell:
    """The cell called ``name`` in ``<root>/BENCHMARK.json``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic", f"{w['traffic']}.json")) as f:
        traffic = json.load(f)
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        root=root)
