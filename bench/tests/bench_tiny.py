"""Tiny cells on the CPU: a copy of the benchmark's files under a
temporary root, with small configurations and mixes dropped in beside
the real ones (new files only, as a later change would add them).

A helper module, not a ``conftest.py``: the repo's ``tests/`` import
their own ``conftest`` by name, and a second one would shadow it.
Test files import ``tiny_root`` from here to use the fixture."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIGS = {
    "kron-s8": {"graph": {"family": "kronecker", "scale": 8,
                          "edge_factor": 16, "generator_seed": 3,
                          "initiator": [0.57, 0.19, 0.19, 0.05]},
                "solver": {"backend": "auto"}},
    "grid-s8": {"graph": {"family": "grid", "side": 8, "generator_seed": 4,
                          "weight_range": [0.05, 1.0]},
                "solver": {"backend": "auto"},
                "service": {"backend": "auto", "batch": 8, "planner": True,
                            "bidirectional": True, "landmarks": None}},
}
TINY_TRAFFIC = {
    "tiny-roots": {"driver": "batch", "lanes": 4},
    "tiny-matrix": {"driver": "batch", "lanes": 8},
    "tiny-zipf": {"driver": "service", "rate_qps": 6.0, "generator_seed": 5,
                  "origins": {"hot": 8, "hot_share": 0.8, "zipf_s": 1.2},
                  "delta": {"period_s": 1.0, "share": 0.05,
                            "scale": [0.5, 2.0]}},
    "tiny-uniform": {"driver": "service", "rate_qps": 6.0,
                     "generator_seed": 6,
                     "origins": {"hot": 0, "hot_share": 0.0},
                     "delta": None},
}
# each tiny cell reports the metrics of the real cell it mirrors
# (grid.uniform: the service with no hot origins and no deltas)
MIRRORS = {"kron.roots": "g500-s17.roots", "grid.matrix": "road-city.matrix8",
           "grid.zipf": "road-city.zipf-live",
           "grid.uniform": "road-city.zipf-live"}
TINY_CELLS = {
    "kron.roots": ("kron-s8", "tiny-roots"),
    "grid.matrix": ("grid-s8", "tiny-matrix"),
    "grid.zipf": ("grid-s8", "tiny-zipf"),
    "grid.uniform": ("grid-s8", "tiny-uniform"),
}


def make_tiny_root(dest: Path) -> Path:
    """``dest`` with the benchmark's files, tiny configurations, mixes
    and cells added, and the real ``BENCHMARK.json`` extended."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name, conf in TINY_CONFIGS.items():
        path = dest / "bench" / "configs" / f"{name}.json"
        path.write_text(json.dumps(conf))
        spec["configs"].append({"name": name, "source": "tiny",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "CPU test"})
    for name, mix in TINY_TRAFFIC.items():
        (dest / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(mix))
    for cell, (conf, mix) in TINY_CELLS.items():
        spec["workloads"].append({"name": cell, "config": conf,
                                  "traffic": mix, "chips": 1,
                                  "why": "CPU test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        for cell, real in MIRRORS.items():
            if real in m.get("workloads", ()):
                m["workloads"].append(cell)
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return dest


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_tiny_root(tmp_path_factory.mktemp("bench_root"))


def run_tiny(root: Path, cell: str, *, seed: int = 7, seconds: float = 2.0,
             trace: bool = False, system_factory=None) -> dict:
    from bench.harness import run_cell
    from bench.layout import Layout

    return run_cell(cell, seed, seconds, trace, t_start=time.perf_counter(),
                    layout=Layout(root), require_chip=False,
                    system_factory=system_factory)
