"""Device time of the round's arc passes per round in the traced
window: the operations whose outermost named scope is ``sssp.relax``,
``sssp.lb`` or ``sssp.inw`` (``bench.scopes``), over the window's
rounds (batches: each batch's, the most over its lanes; the service:
``query_rounds`` plus ``delta_rounds``)."""
from bench.scopes import ARC, pass_ms


def read(run):
    return pass_ms(run, ARC)
