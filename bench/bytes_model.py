"""Bytes the paper's round needs, from arcs, lanes and rounds alone.

One round of the lower-bound engine is, per lane, one relaxation sweep
(``D[u] + w`` min-scattered at ``v`` over every arc) and one Eqn (1)
sweep (``C[u] + w`` min-scattered at ``v``).  Per sweep the arc ids
(``src``, ``dst``: int32) and the weights (float32) are read once for
all lanes, and each lane gathers one float32 at the tail and
min-scatters one float32 at the head:

    bytes_per_round = SWEEPS * arcs * (ARC_BYTES + lanes * LANE_BYTES)

with ``SWEEPS = 2``, ``ARC_BYTES = 4 + 4 + 4`` and
``LANE_BYTES = 4 + 4``.  The count depends on nothing the program
chooses (kernel names, fusion, layout), so a change that implements
the same round with fewer bytes shows up as a higher share, and one
that does more work per round as a lower one.
"""
from __future__ import annotations

SWEEPS = 2
ARC_BYTES = 4 + 4 + 4      # src id, dst id, weight
LANE_BYTES = 4 + 4         # gather at the tail, min-scatter at the head


def round_bytes(arcs: int, lanes: int) -> int:
    return SWEEPS * int(arcs) * (ARC_BYTES + int(lanes) * LANE_BYTES)


def sweep_seconds(arcs: int, lanes: int, rounds: int,
                  hbm_bytes_per_s: float) -> float:
    """Least time the chip's HBM bandwidth allows for ``rounds`` rounds."""
    return round_bytes(arcs, lanes) * int(rounds) / float(hbm_bytes_per_s)
