"""BENCHMARK.json keeps the contract's shape, every name it holds is
found as a file, and a configuration, mix or metric dropped in as new
files is found by name with no file edited."""
from __future__ import annotations

import json
import re

import pytest

from bench_tiny import ROOT, run_tiny, tiny_root  # noqa: F401

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_keys(spec):
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["name"] not in names
        names.add(m["name"])
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e


def test_every_cell_reports_what_its_metrics_move(spec):
    cells = {w["name"] for w in spec["workloads"]}

    def reports(m, cell):
        return "workloads" not in m or cell in m["workloads"]

    for cell in cells:
        e2e = {m["name"] for m in spec["end_to_end"] if reports(m, cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in spec["per_layer"] if reports(m, cell)]
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (cell, m["name"])


def test_every_name_is_found_as_a_file(spec):
    from bench.layout import Layout

    layout = Layout(ROOT)
    for w in spec["workloads"]:
        cell = layout.cell(w["name"])
        assert (layout.bench / "drivers"
                / f"{cell.traffic['driver']}.py").is_file()
        assert (layout.bench / "graphs"
                / f"{cell.config['graph']['family']}.py").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(layout.reader(m["name"]).read)


def test_dropped_in_files_are_found_by_name(tiny_root):
    """A new metric reader and its BENCHMARK.json entry appear in a
    run's result; the tiny configurations and mixes of conftest were
    dropped in the same way."""
    reader = tiny_root / "bench" / "metrics" / "answered_share.py"
    reader.write_text(
        "def read(run):\n"
        "    return 100.0 * (run.records.attempted - run.records.failed)"
        " / run.records.attempted\n")
    spec_path = tiny_root / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    spec["end_to_end"].append({"name": "answered_share", "unit": "%",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["kron.roots"]})
    spec_path.write_text(json.dumps(spec))
    res = run_tiny(tiny_root, "kron.roots", seconds=0.5)
    assert res["correct"]
    assert res["metrics"]["answered_share"]["value"] == 100.0
    assert set(res["metrics"]) == {"teps", "setup_s", "answered_share"}
    assert list(res)[-1] == "checks"
