"""Engine rounds per weight delta in the window: the service's
``delta_rounds`` (the warm refresh's rounds, the most over its lanes,
plus any cold or pair refresh's) over ``deltas``."""


def read(run):
    c = getattr(run.records, "counters", None) or {}
    if "delta_rounds" not in c or not c.get("deltas"):
        return None
    return c["delta_rounds"] / c["deltas"]
