"""Share of the window's queries the service answered from its caches
(``stats["cache_hits"] / stats["queries"]``)."""


def read(run):
    c = getattr(run.records, "counters", None)
    if not c or not c.get("queries"):
        return None
    return 100.0 * c["cache_hits"] / c["queries"]
