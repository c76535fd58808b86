"""CPU rehearsal of ``chip_smoke.py``: its phases at tiny sizes.

The chip run drives the same phase functions at the sizes set at the top
of ``chip_smoke.py``; here they run on the CPU backend (Pallas in
interpret mode), without the device gate, so a wrong path, argument or
check shows up at no chip time.
"""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

_SPEC = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
cs = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(cs)


def test_device_gate_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as ei:
        cs.main([])
    assert "no TPU found" in str(ei.value.code)
    assert "cpu" in str(ei.value.code)
    assert capsys.readouterr().out == ""     # no result line


def test_serve_phase_tiny(capsys):
    g = cs.grid_graph(12, seed=0)
    cs.serve_phase(g, queries=32, hot=6, batch=4, landmarks=2,
                   check_sources=4)
    out = capsys.readouterr().out
    assert '"check": "ok"' in out and '"backend": "frontier"' in out


def test_serve_phase_check_bites(monkeypatch):
    g = cs.grid_graph(8, seed=1)
    monkeypatch.setattr(cs, "reference",
                        lambda g, srcs: np.zeros((len(srcs), g.n)))
    with pytest.raises(cs.SmokeCheckFailed):
        cs.serve_phase(g, queries=16, hot=4, batch=4, landmarks=2)


def test_road_phase_tiny(capsys):
    side = 16
    s, t = cs.road_pair(side, 6, seed=0)
    assert abs(s // side - t // side) + abs(s % side - t % side) == 6
    g = cs.grid_graph(side, seed=0)
    cs.road_phase(g, s, t)
    out = capsys.readouterr().out
    assert out.count('"check": "ok"') == len(cs.ROAD_BACKENDS)


def test_road_phase_check_bites(monkeypatch):
    g = cs.grid_graph(8, seed=1)
    monkeypatch.setattr(cs, "reference",
                        lambda g, srcs: np.zeros((len(srcs), g.n)))
    with pytest.raises(cs.SmokeCheckFailed):
        cs.road_phase(g, *cs.road_pair(8, 4, seed=0))


def test_backends_phase_tiny(capsys):
    g = cs.grid_graph(10, seed=2)
    cs.backends_phase(g, cs.spread_sources(g.n, 4, seed=0))
    out = capsys.readouterr().out
    assert out.count('"check": "ok"') == len(cs.BACKENDS)


def test_gnp_and_distributed_phases_tiny(capsys):
    g = cs.gnp_graph(300, 16, seed=3)
    cs.gnp_phase(g, cs.spread_sources(g.n, 4, seed=1))
    cs.distributed_phase(g, cs.spread_sources(g.n, 2, seed=2))
    assert capsys.readouterr().out.count('"check": "ok"') == 2
