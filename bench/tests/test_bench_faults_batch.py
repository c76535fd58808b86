"""The batch cells' check: the control and each fault the cell can
have come out not correct, with the harness's chip check skipped and
the timed path broken underneath."""
from __future__ import annotations

import numpy as np
import pytest

from bench.control import ControlBatch
from bench.layout import Layout
from bench_tiny import run_tiny, tiny_root  # noqa: F401


def program(root):
    return Layout(root).module("drivers", "batch").Program


def altered(root):
    class Altered(program(root)):
        """One distance of every batch off by one part in a thousand."""

        def fetch(self, handle):
            dist, rounds = super().fetch(handle)
            dist = dist.copy()
            j = np.flatnonzero(np.isfinite(dist[0]) & (dist[0] > 0))[0]
            dist[0, j] *= 1.001
            return dist, rounds
    return Altered


def half_batch(root):
    class HalfBatch(program(root)):
        """Only the first half of the lanes solved; the rest repeat them."""

        def solve(self, roots):
            return super().solve(np.resize(roots[: len(roots) // 2],
                                           len(roots)))
    return HalfBatch


def unchanged(root):
    class Unchanged(program(root)):
        """Every solve returns the first one's state."""

        first = None

        def solve(self, roots):
            if self.first is None:
                self.first = super().solve(roots)
            return self.first
    return Unchanged


@pytest.mark.parametrize("fault,number", [
    (altered, "dist_rel_err"), (half_batch, "dist_rel_err"),
    (unchanged, "dist_rel_err"), (lambda root: ControlBatch, "dist_rel_err"),
])
@pytest.mark.parametrize("cell", ["kron.roots", "grid.matrix"])
def test_fault_is_not_correct(tiny_root, cell, fault, number):
    res = run_tiny(tiny_root, cell, seconds=0.5,
                   system_factory=fault(tiny_root))
    assert not res["correct"]
    c = res["checks"][number]
    assert c["value"] > c["limit"]
