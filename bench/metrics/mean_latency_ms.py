"""Mean latency over every query of the window: from its scheduled
arrival to its answer, so time queued behind a wave or a delta counts,
and a query answered after the close counts with its wait."""
import numpy as np


def read(run):
    lat = getattr(run.records, "latency_ms", None)
    if lat is None or not len(lat):
        return None
    return float(np.mean(lat))
