"""Device seconds of calibration per compressed layer: the unit forwards
(``pipeline.unit_apply``, jitted as ``fn``: tapped calibration forwards,
anchors and stream propagation) and the covariance updates
(``calibration.update_covs``).  When the trace drops its later events, the layers it holds are counted
from the programs' executions (``yardstick.traced_layers``)."""

from bench import yardstick

PROGRAMS = ("fn", "update_covs")


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_seconds(PROGRAMS)
    layers = yardstick.traced_layers(run, "update_covs")
    return None if t is None or not layers else t / layers
