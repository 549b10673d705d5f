#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers over many
seeds, and the control's.

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--program 0|1] [--control 0|1]

The control is the plain reference put in the program's place and
computed in the precision below the one the configuration states (fp8
matmul operands for bfloat16; bfloat16 statistics for the float32 solve).
For each seed this prints one JSON line with the numbers ``correct``
compares, for the program (one compression job, or one round of requests
at the cell's load: one per slot) and for the control, on the same
inputs.  The benchmark's own runs never run this; ``bench/limits/`` holds
the readings and the limits set from them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def compress_readings(cell, seed: int, program: bool, control: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import weights
    from bench.drivers import compress as C
    from bench.reference import decoder as D

    m = C.job_model(cell)
    out = {"seed": seed}
    prog_layers = None
    if program:
        from repro.core import CompressConfig, compress_model
        cfg = weights.model_config(cell.config, num_layers=m["num_layers"])
        params = weights.make_params(cfg, seed)
        calib = {"tokens": jnp.asarray(C.calibration(cell, seed,
                                                     m["vocab_size"]))}
        comp, _ = compress_model(params, cfg, calib,
                                 CompressConfig(**cell.traffic["compress"]))
        prog_layers = [jax.tree.map(np.asarray, D.layer_params(comp, m, i))
                       for i in range(m["num_layers"])]
        del params, comp
    ref, dense, x0 = C.reference_layers(cell, seed)
    if prog_layers is not None:
        out["program"] = C.compare(prog_layers, ref, dense, m, x0)
    if control:
        low, _, _ = C.reference_layers(cell, seed, prec="low")
        out["control"] = C.compare(low, ref, dense, m, x0)
    return out


def serve_readings(cell, seed: int, program: bool, control: bool):
    import numpy as np
    from repro.launch.serve import ContinuousBatchingServer, Request
    from bench.drivers import serve as S

    sv = cell.config["serve"]
    m = S.served_model(cell)
    reqs = S.requests(cell.traffic, seed, sv["slots"], m["vocab_size"])
    cfg, params = S.make_params(cell, seed)
    server = ContinuousBatchingServer(cfg, params, max_len=sv["max_len"],
                                      slots=sv["slots"])
    results = server.run([Request(rid=r["rid"], prompt=r["prompt"],
                                  steps=r["steps"]) for r in reqs])
    del server, params
    out = {"seed": seed}
    if program:
        out["program"] = {"logit_gap": S.check_gap(cell, seed, reqs,
                                                   results)}
    if control:
        out["control"] = {"logit_gap": S.check_gap(cell, seed, reqs,
                                                   results, prec="low")}
    return out


def readings(cell, seed: int, program: bool = True, control: bool = True):
    kind = cell.traffic["kind"]
    fn = {"compress": compress_readings, "serve": serve_readings}[kind]
    return fn(cell, seed, program, control)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=int, choices=(0, 1), default=1)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    harness.prepare_env()
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"[control] {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, bool(args.program),
                                  bool(args.control))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
