"""Share of the chip's bf16 peak that a whole compression job reaches:
the FLOPs the job needs (``yardstick.compress_layer_flops``: calibration
forwards, covariance products, refinement forward and backward, solve
matmuls, eigensolves at a nominal sweep count) times layers per second
of the window."""

from bench import yardstick


def read(run):
    if not run.peaks:
        return None
    tr = run.cell.traffic
    per_layer = yardstick.compress_layer_flops(
        run.model, tr["compress"], tr["calib_tokens"], tr["calib_sequences"])
    rate = run.counters["layers"] / run.window_s
    return 100.0 * per_layer * rate / run.peaks["bf16_flops_per_s"]
