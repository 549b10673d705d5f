"""Device seconds of the anchored solve (``lowrank.solve_anchored``:
whitening eigensolve, Gram-matrix SVD, factor products) per compressed
layer.  When the trace drops its later events, the layers it holds are counted
from the programs' executions (``yardstick.traced_layers``)."""

from bench import yardstick

PROGRAMS = ("solve_anchored",)


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_seconds(PROGRAMS)
    layers = yardstick.traced_layers(run, "solve_anchored")
    return None if t is None or not layers else t / layers
