"""Traversed edges per second (Graph500): for each root solved, the
arcs whose tail it reaches, summed over the window and divided by the
window's time up to the last completed batch."""


def read(run):
    if run.kind != "batch":
        return None
    return run.records.reached_arcs / run.records.window_s
