"""The service cells' check: the control and each fault the cell can
have come out not correct, with the harness's chip check skipped and
the timed path broken underneath."""
from __future__ import annotations

import numpy as np
import pytest

from bench.control import ControlService
from bench.layout import Layout
from bench_tiny import run_tiny, tiny_root  # noqa: F401


def program(root):
    return Layout(root).module("drivers", "service").Program


def altered(root):
    class Altered(program(root)):
        """One answer of every wave off by one part in a thousand."""

        def serve(self, pairs):
            out = super().serve(pairs)
            j = np.flatnonzero(np.isfinite(out) & (out > 0))
            if j.size:
                out[j[0]] *= 1.001
            return out
    return Altered


def half_wave(root):
    class HalfWave(program(root)):
        """Only the first half of each wave is served."""

        def serve(self, pairs):
            out = np.full(len(pairs), np.nan)
            k = max(1, len(pairs) // 2) if len(pairs) > 1 else 0
            if k:
                out[:k] = super().serve(pairs[:k])
            return out
    return HalfWave


def unchanged(root):
    class Unchanged(program(root)):
        """A weight delta leaves the service's graph as it was."""

        def apply_delta(self, src, dst, new_w):
            return None
    return Unchanged


CASES = [
    ("grid.zipf", altered, "dist_rel_err"),
    ("grid.zipf", half_wave, "unanswered"),
    ("grid.zipf", unchanged, "dist_rel_err"),
    ("grid.zipf", lambda root: ControlService, "dist_rel_err"),
    ("grid.uniform", altered, "dist_rel_err"),
    ("grid.uniform", half_wave, "unanswered"),
    ("grid.uniform", lambda root: ControlService, "dist_rel_err"),
]


@pytest.mark.parametrize("cell,fault,number", CASES)
def test_fault_is_not_correct(tiny_root, cell, fault, number):
    res = run_tiny(tiny_root, cell, seconds=3.0,
                   system_factory=fault(tiny_root))
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]
