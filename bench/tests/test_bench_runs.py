"""Every kind of cell runs end to end on the CPU at a tiny size and
comes out correct, with the result line the contract asks for."""
from __future__ import annotations

import json
import subprocess
import sys

import pytest

from bench_tiny import ROOT, run_tiny, tiny_root  # noqa: F401

EXPECT = {
    "kron.roots": {"teps", "setup_s"},
    "grid.matrix": {"teps", "setup_s"},
    "grid.zipf": {"mean_latency_ms", "setup_s"},
    "grid.uniform": {"mean_latency_ms", "setup_s"},
}


@pytest.mark.parametrize("cell", sorted(EXPECT))
def test_tiny_cell_is_correct(tiny_root, cell):
    res = run_tiny(tiny_root, cell, seed=2**31 + 77)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == EXPECT[cell]
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_traced_run_reports_per_layer_metrics(tiny_root):
    """On the CPU the trace has no TPU plane: the device readers find
    nothing and leave their metrics out; the counters are read."""
    res = run_tiny(tiny_root, "kron.roots", trace=True, seconds=1.0)
    assert res["correct"]
    assert set(res["metrics"]) == {"rounds_per_solve.teps", "round_ms.teps"}
    assert res["device"]["busy_s"] == 0.0
    assert res["device"]["window_s"] > 0.9
    assert "breakdown" in res


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s17.roots",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert "correct" not in json.loads(line) if line.startswith(
            "{") else True


def test_latency_runs_from_scheduled_arrival_to_answer():
    import numpy as np

    from bench.drivers.service import Records
    from bench.metrics import mean_latency_ms

    rec = Records(arrival=np.array([0.0, 1.0, 2.0]),
                  pairs=np.zeros((3, 2), np.int32),
                  answer=np.array([1.0, 2.0, 3.0]),
                  answered_at=np.array([0.5, 3.0, 2.25]),
                  version=np.zeros(3, int), seconds=2.5)
    np.testing.assert_allclose(rec.latency_ms, [500.0, 2000.0, 250.0])

    class R:
        records = rec
    # the query answered after the close counts with its wait
    assert mean_latency_ms.read(R) == 2750.0 / 3


def test_service_deltas_come_from_the_run_seed(tiny_root):
    from bench.layout import Layout

    Program = Layout(tiny_root).module("drivers", "service").Program
    seen = {}

    def recording(seed):
        class Recording(Program):
            def apply_delta(self, src, dst, new_w):
                seen.setdefault(seed, []).append(tuple(src.tolist()))
                super().apply_delta(src, dst, new_w)
        return Recording

    # 11 and 19 turn the grid alike, so only the draw can move the arcs
    for seed in (11, 19):
        res = run_tiny(tiny_root, "grid.zipf", seed=seed, seconds=1.5,
                       system_factory=recording(seed))
        assert res["correct"]
    # past the warm-up's two deltas, the window's own
    assert seen[11][2:] and seen[19][2:] and seen[11][2:] != seen[19][2:]
