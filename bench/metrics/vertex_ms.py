"""Device time of the round's vertex passes per round in the traced
window: outermost named scope ``sssp.fix``, ``sssp.frontier``,
``sssp.freeze`` or ``sssp.count`` (``bench.scopes``), over the rounds
``arc_ms`` divides by."""
from bench.scopes import VERTEX, pass_ms


def read(run):
    return pass_ms(run, VERTEX)
