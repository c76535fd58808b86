"""The reference comparison flags what it must, and the bfloat16
control lies outside the limit the program's float32 lies inside."""
from __future__ import annotations

import numpy as np
import pytest

from bench import reference


def tiny_graph():
    #  0 -> 1 twice (parallel arcs 2.0 and 0.5), 1 -> 2, 0 -> 2; 3 alone
    src = np.array([0, 0, 1, 0], np.int32)
    dst = np.array([1, 1, 2, 2], np.int32)
    w = np.array([2.0, 0.5, 1.0, 3.0], np.float32)
    return 4, src, dst, w


def test_parallel_arcs_take_their_minimum():
    n, src, dst, w = tiny_graph()
    d = reference.distances(reference.adjacency(n, src, dst, w), [0])[0]
    np.testing.assert_array_equal(d, [0.0, 0.5, 1.5, np.inf])


def test_tally_passes_exact_answers():
    t = reference.Tally()
    t.add([0.0, 0.5, 1.5, np.inf], [0.0, 0.5, 1.5, np.inf])
    assert reference.correct(t.checks())
    assert t.compared == 4


@pytest.mark.parametrize("got,number", [
    ([0.0, 0.5, 1.5 * (1 + 1e-3), np.inf], "dist_rel_err"),
    ([0.0, 0.5, np.inf, np.inf], "reach_mismatch"),
    ([0.0, 0.5, 1.5, 7.0], "reach_mismatch"),
    ([1e-3, 0.5, 1.5, np.inf], "dist_rel_err"),
])
def test_tally_flags_one_perturbed_distance(got, number):
    t = reference.Tally()
    t.add(got, [0.0, 0.5, 1.5, np.inf])
    checks = t.checks()
    assert not reference.correct(checks)
    assert checks[number]["value"] > checks[number]["limit"]


def test_float32_sums_stay_inside_the_limit():
    """Distances summed in float32 along a 2,000-arc path (longer than
    any shortest path of the cells) against the float64 sums."""
    w = np.random.default_rng(0).uniform(0.05, 1.0, 2000).astype(np.float32)
    t = reference.Tally()
    t.add(np.cumsum(w, dtype=np.float32), np.cumsum(w.astype(np.float64)))
    assert reference.correct(t.checks())


def test_bf16_control_fails_on_a_long_path():
    """A chain of 300 arcs: the bfloat16 sums drift far past the limit."""
    n = 301
    src = np.arange(300, dtype=np.int32)
    dst = src + 1
    w = np.random.default_rng(1).uniform(0.05, 1.0, 300).astype(np.float32)
    ctl, sweeps = reference.Bf16Fixpoint(n, src, dst).distances(w, [0, 5])
    want = reference.distances(reference.adjacency(n, src, dst, w), [0, 5])
    t = reference.Tally()
    t.add(ctl, want)
    assert sweeps >= 300
    assert t.checks()["dist_rel_err"]["value"] > 10 * reference.LIMITS[
        "dist_rel_err"]
