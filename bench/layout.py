"""Finds a cell's parts by name.

Nothing about one configuration, traffic mix or metric lives in a
shared file: a cell in ``BENCHMARK.json`` names its configuration (whose
entry names its file) and its traffic mix, found as
``<bench>/traffic/<mix>.json``; the mix names its driver,
``<bench>/drivers/<kind>.py``; the configuration names its graph
family, ``<bench>/graphs/<family>.py``; and each metric is read by
``<bench>/metrics/<name>.py``, or, for a metric ``base.suffix`` that has
no file of its own, by ``<bench>/metrics/<base>.py``.  Adding a cell,
a mix or a metric therefore adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # BENCHMARK.json entries this cell reports
    per_layer: tuple


class Layout:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path | str = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = self.root / self.spec["paths"][0]

    def _reports(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        w = cells[name]
        conf = {c["name"]: c for c in self.spec["configs"]}[w["config"]]
        config = json.loads((self.root / conf["file"]).read_text())
        traffic = self.traffic(w["traffic"])
        return Cell(
            name=name, chips=int(w["chips"]), config=config, traffic=traffic,
            end_to_end=tuple(m for m in self.spec["end_to_end"]
                             if self._reports(m, name)),
            per_layer=tuple(m for m in self.spec["per_layer"]
                            if self._reports(m, name)))

    def traffic(self, mix: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{mix}.json").read_text())

    def module(self, kind: str, name: str):
        """``<bench>/<kind>/<name>.py`` loaded as a module."""
        path = self.bench / kind / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(path)
        key = f"bench._found.{kind}.{name}@{path}"
        if key not in sys.modules:
            spec = importlib.util.spec_from_file_location(key, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[key] = mod
            spec.loader.exec_module(mod)
        return sys.modules[key]

    def reader(self, metric: str):
        """The module whose ``read(run)`` gives ``metric``."""
        path = self.bench / "metrics" / f"{metric}.py"
        return self.module("metrics", metric if path.is_file()
                           else metric.split(".")[0])
