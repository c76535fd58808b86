"""The bytes model gives the counts PERF.md derives, and the trace
reduction gives busy time, top operations and named idle gaps."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import bytes_model, peaks, trace

DATA = Path(__file__).resolve().parent / "data"


def test_round_bytes_match_perf_md():
    # graph500-s17 at seed-typical 4,192,966 arcs, 4 lanes: 44 B per arc
    # per sweep, two sweeps -> 368,981,008 B per round
    assert bytes_model.round_bytes(4_192_966, 4) == 368_981_008
    # road-city-s128: 65,024 arcs, 8 lanes: 76 B per arc per sweep
    assert bytes_model.round_bytes(65_024, 8) == 9_883_648
    s = bytes_model.sweep_seconds(4_192_966, 4, 20, 819e9)
    assert s == pytest.approx(20 * 368_981_008 / 819e9)


def test_peaks_known_and_unknown():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("cpu")


def test_reduce_busy_union_and_named_gaps():
    ms = 1_000_000
    host = [("window", 0, 100 * ms), ("solve_batch", 0, 40 * ms),
            ("fetch", 40 * ms, 50 * ms), ("wait", 60 * ms, 90 * ms)]
    dev = {"/device:TPU:0": [("fusion.1", 5 * ms, 30 * ms),
                             ("fusion.2", 30 * ms, 35 * ms),
                             ("copy", 45 * ms, 50 * ms),
                             ("late", 95 * ms, 120 * ms)]}
    r = trace.reduce(dev, host)
    assert r.window_s == pytest.approx(0.1)
    # busy: [5, 35] + [45, 50] + [95, 100] = 40 ms
    assert r.busy_s == pytest.approx(0.040)
    assert r.idle_share == pytest.approx(0.6)
    assert r.device_ops[0] == ["fusion.1", pytest.approx(0.025)]
    # a loop's operation spans its body: its own time leaves the body out
    nested = trace.self_times([("%while.1 = f32[] while(%t)", 0, 10 * ms),
                               ("%fusion.3 = f32[] fusion(%a)", 2 * ms,
                                5 * ms)])
    assert nested == {"%while.1 while": pytest.approx(0.007),
                      "%fusion.3 fusion": pytest.approx(0.003)}
    assert r.idle_gaps[0] == ["wait", pytest.approx(0.045)]
    assert ["solve_batch", pytest.approx(0.005)] in r.idle_gaps
    assert ["fetch", pytest.approx(0.010)] in r.idle_gaps  # mid-gap span


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: a two-lane solve on a side-16
    grid inside a ``window`` span with ``solve_batch`` and ``fetch``."""
    r = trace.reduce_file(str(DATA / "tiny_tpu.xplane.pb"))
    assert r.devices == 1
    assert 0 < r.busy_s < r.window_s
    assert r.device_ops and all(t > 0 for _, t in r.device_ops)
    assert {name for name, _ in r.idle_gaps} <= set(trace.SPANS) | {
        "harness"}
