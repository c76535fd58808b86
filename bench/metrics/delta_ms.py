"""Mean harness-clock time of a blocked ``apply_delta`` in the window."""
import numpy as np


def read(run):
    d = getattr(run.records, "delta_ms", None)
    if not d:
        return None
    return float(np.mean(d))
