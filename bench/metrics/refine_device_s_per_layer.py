"""Device seconds of block refinement (``core/refine.py``'s jitted
programs: ``step1``, the per-batch loss ``loss_fn``, and the scanned
``run_all``/``run_epoch``/``eval_scan``) per compressed layer."""

PROGRAMS = ("step1", "loss_fn", "run_all", "run_epoch", "eval_scan")


def read(run):
    if run.trace is None:
        return None
    t = run.trace.module_seconds(PROGRAMS)
    return None if t is None else t / run.counters["layers"]
