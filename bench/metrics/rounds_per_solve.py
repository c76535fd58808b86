"""Rounds each batch ran (the most over its lanes, which run in lock
step), averaged over the window's batches."""
import numpy as np


def read(run):
    rounds = getattr(run.records, "rounds", None)
    if not rounds:
        return None
    return float(np.mean([np.max(r) for r in rounds]))
