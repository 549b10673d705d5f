#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Weights and inputs come from ``--seed``; set-up (weights, compile-cache
loads, kernel autotuning, warm-up of the cell's shapes) is timed as
``setup_s`` from process start to the window.  The window then runs for
about ``--seconds`` of fixed work; with ``--trace 1`` it runs under the
profiler and the per-layer metrics are printed instead of the end-to-end
ones.  After the window the device's peak memory is read, the program's
state is freed, and the plain reference decides ``correct``.  The numbers
compared, each beside its limit, are the last lines of standard error and
the ``checks`` key of the result, the last line of standard output.

Exits non-zero, printing no result, without a TPU or with fewer chips than
the cell asks for.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    harness.prepare_env()
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    try:
        device = harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    print(f"[bench] device platform={device['platform']} "
          f"kind={device['kind']} count={device['count']}",
          file=sys.stderr, flush=True)
    run = harness.driver(cell).run(cell, seed=args.seed,
                                   seconds=args.seconds,
                                   trace=bool(args.trace), t0=T0)
    device["memory_peak_bytes"] = run.counters["memory_peak_bytes"]
    line = harness.result_line(run, device, bool(args.trace))
    if run.trace is not None:
        for name, sec in run.trace.top_modules():
            print(f"[trace] program {name} {sec!r} s", file=sys.stderr)
    for name, c in run.checks.items():
        print(f"[check] {name} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
