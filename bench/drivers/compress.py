"""Compression jobs: AA-SVD (``compress_model``) on the first layers of a
configuration, back to back.

The traffic file gives the calibration set (``calib_sequences`` ×
``calib_tokens`` token ids from the seed) and the ``CompressConfig``; the
configuration's ``compress.layers_per_job`` gives the layers of one job.
Set-up makes the dense params and the calibration set from the seed and
runs one whole job as warm-up: the program keys its jitted unit forwards
on the model config and its refinement steps on the schedule length, so
only a job of the same config, calibration size and epochs warms every
program the window calls.  The window then runs whole jobs, starting
another only while it would end inside ``seconds``, and always at least
one.  ``compress_layer_s`` is the window's wall time over the layers it
compressed.

``correct``: the first window job's compressed layers and the plain
reference's (``bench/reference/aasvd.py``, from the same seed) both run
through the plain float32 forward on the calibration stream, beside the
dense layers.  Compared are ``out_gap`` = ‖Y_prog − Y_ref‖ / ‖Y_ref −
Y_dense‖, how far the program's compressed function lies from the
reference's, as a share of what compression changes, and ``err_gap`` =
|e_prog − e_ref| / e_ref with e = ‖Y − Y_dense‖² / ‖Y_dense‖², how much
worse or better the program's compression error is.
"""

from __future__ import annotations

import gc
import sys
import time
from typing import Dict, List

import numpy as np

from bench import harness, weights

def job_model(cell) -> Dict:
    return {**cell.config["model"],
            "num_layers": cell.config["compress"]["layers_per_job"]}


def calibration(cell, seed: int, vocab: int) -> np.ndarray:
    t = cell.traffic
    return weights.tokens(seed, t["calib_sequences"], t["calib_tokens"],
                          vocab)


def embedded_stream(params, tokens: np.ndarray, mb: int) -> List:
    import jax.numpy as jnp
    table = jnp.asarray(params["embed"]["table"], jnp.float32)
    return [table[jnp.asarray(tokens[i:i + mb])]
            for i in range(0, tokens.shape[0], mb)]


def compare(prog_layers, ref_layers, dense_layers, m, x0,
            prec: str = "f32") -> Dict[str, float]:
    """The numbers ``correct`` compares (see the module docstring)."""
    from bench.reference import aasvd

    def out(layers):
        return [np.asarray(y, np.float64)
                for y in aasvd.stream_output(layers, m, x0, prec)]

    yd, yp, yr = out(dense_layers), out(prog_layers), out(ref_layers)
    sq = lambda a, b: sum(float(np.sum((x - y) ** 2))  # noqa: E731
                          for x, y in zip(a, b))
    dense_sq = sum(float(np.sum(y ** 2)) for y in yd)
    e_prog = sq(yp, yd) / dense_sq
    e_ref = sq(yr, yd) / dense_sq
    return {"out_gap": float(np.sqrt(sq(yp, yr) / sq(yr, yd))),
            "err_gap": abs(e_prog - e_ref) / e_ref,
            "e_prog": e_prog, "e_ref": e_ref}


def reference_layers(cell, seed: int, prec: str = "f32"):
    """(compressed, dense) per-layer params of the plain reference, and
    the embedded calibration stream."""
    from bench.reference import aasvd
    from bench.reference import decoder as D
    m = job_model(cell)
    job = cell.traffic["compress"]
    cfg = weights.model_config(cell.config, num_layers=m["num_layers"])
    dense = weights.make_params(cfg, seed)
    x0 = embedded_stream(dense, calibration(cell, seed, m["vocab_size"]),
                         job["microbatch"])
    dense_layers = [D.layer_params(dense, m, i)
                    for i in range(m["num_layers"])]
    ref = aasvd.compress(dense_layers, m, x0, ratio=job["ratio"],
                         rank_multiple=job["rank_multiple"],
                         epochs=job["refine_epochs"] if job["refine"] else 0,
                         prec=prec)
    return ref, dense_layers, x0


def run(cell, *, seed: int, seconds: float, trace: bool, t0: float):
    import jax
    import jax.numpy as jnp
    from repro.core import CompressConfig, compress_model
    from bench.reference import decoder as D

    log = harness.CompileLog()
    m = job_model(cell)
    with harness.span("bench.setup"):
        cfg = weights.model_config(cell.config, num_layers=m["num_layers"])
        params = weights.make_params(cfg, seed)
        calib = {"tokens": jnp.asarray(calibration(cell, seed,
                                                   m["vocab_size"]))}
        ccfg = CompressConfig(**cell.traffic["compress"])
        with harness.span("bench.warmup"):
            jax.block_until_ready(compress_model(params, cfg, calib, ccfg))
    before = log.snapshot()
    setup_s = time.monotonic() - t0
    jobs, first, reports = 0, None, []
    # the Jacobi eigensolves run millions of small ops a job: read the
    # trace by program only
    with harness.traced(trace, ops=False) as tr:
        with harness.span(harness.WINDOW_SPAN):
            start = time.monotonic()
            while True:
                with harness.span("bench.job"):
                    tj = time.monotonic()
                    out, report = compress_model(params, cfg, calib, ccfg)
                    jax.block_until_ready(out)
                    dt = time.monotonic() - tj
                jobs += 1
                reports.append(report)
                if first is None:
                    first = out
                del out
                if time.monotonic() - start + dt > seconds:
                    break
            window_s = time.monotonic() - start
    in_window = harness.CompileLog.delta(before, log.snapshot())
    peak = harness.peak_bytes()
    layers = jobs * m["num_layers"]
    prog_layers = [jax.tree.map(np.asarray, D.layer_params(first, m, i))
                   for i in range(m["num_layers"])]
    del params, calib, first
    gc.collect()

    with harness.span("bench.reference"):
        t_ref = time.monotonic()
        ref, dense_layers, x0 = reference_layers(cell, seed)
        nums = compare(prog_layers, ref, dense_layers, m, x0)
        ref_s = time.monotonic() - t_ref
    print(f"[compress] jobs={jobs} layers={layers} window_s={window_s!r} "
          f"setup_s={setup_s!r} reference_s={ref_s!r} "
          f"e_prog={nums['e_prog']!r} e_ref={nums['e_ref']!r}", flush=True)
    mse = [(u.get("pre_refine_mse"), u.get("post_refine_mse"))
           for u in reports[0]["units"]]
    print(f"[compress] unit mse (pre, post refinement) {mse}", flush=True)
    print(f"[compress] in_window={in_window}", file=sys.stderr)
    return harness.Run(
        cell=cell, model=m,
        end_to_end={"compress_layer_s": window_s / layers,
                    "setup_s": setup_s},
        counters={"memory_peak_bytes": peak, "jobs": jobs, "layers": layers,
                  "reports": reports, "in_window": in_window},
        checks=harness.checks(cell, nums), attempted=jobs, failed=0,
        window_s=window_s, peaks=harness.device_peaks(), trace=tr.summary)
