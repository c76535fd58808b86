"""Solver dispatches per query in the window: batched solves (full
and targeted waves, ``stats["batches"]``) plus bidirectional pair
solves (``stats["bidi_solves"]``, one dispatch each).  The service's
``p2p_solves`` counts the lanes of the targeted waves, which
``batches`` already counts as dispatches."""


def read(run):
    c = getattr(run.records, "counters", None)
    if not c or not c.get("queries"):
        return None
    return (c["batches"] + c["bidi_solves"]) / c["queries"]
