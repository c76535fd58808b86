"""Share of the HBM roofline: the least time the paper's round needs
for the rounds run in the traced window (``bench.bytes_model``: one
relaxation and one Eqn (1) sweep over every arc per lane) at the
chip's published bandwidth, over the device's busy time there."""
import numpy as np

from bench.bytes_model import sweep_seconds


def read(run):
    rounds = getattr(run.records, "rounds", None)
    red = run.reduction
    if not rounds or red is None or red.busy_s <= 0:
        return None
    lanes = len(run.records.roots[0])
    total = int(sum(np.max(r) for r in rounds))
    need = sweep_seconds(run.arcs, lanes, total,
                         run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / red.busy_s
