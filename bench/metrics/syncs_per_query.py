"""Blocking device->host reads per query in the window: the service's
``host_syncs`` (its ``sssp.fetch`` reads while serving) over
``queries``."""


def read(run):
    c = getattr(run.records, "counters", None) or {}
    if "host_syncs" not in c or not c.get("queries"):
        return None
    return c["host_syncs"] / c["queries"]
