"""Window time over the rounds run in it (host clock)."""
import numpy as np


def read(run):
    rounds = getattr(run.records, "rounds", None)
    if not rounds:
        return None
    total = int(sum(np.max(r) for r in rounds))
    return 1e3 * run.records.window_s / total if total else None
