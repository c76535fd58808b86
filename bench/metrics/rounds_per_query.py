"""Engine rounds per query in the window: the service's
``query_rounds`` (per query-path dispatch, its rounds, the most over
its lanes) over ``queries``."""


def read(run):
    c = getattr(run.records, "counters", None) or {}
    if "query_rounds" not in c or not c.get("queries"):
        return None
    return c["query_rounds"] / c["queries"]
