"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  compression_quality  — Tables 1/2/5 (method × ratio × refinement PPL
                         matrix) + adaptive-vs-uniform rank budgets at
                         aggressive ratios (claim_I5, ISSUE 5)
  error_evolution      — Figures 1/4 (per-depth MSE / cosine distance)
  calibration_size     — Figure 3 (quality vs calibration budget) + the
                         streaming-engine forward counts, incl. the
                         drop-free MoE bank-folding rows (ISSUE 9:
                         dp=8 cuts per-device MoE forwards 64 -> 8)
  refine_speed         — stage-2 scanned-dispatch claim (ISSUE 4)
  memory_speedup       — App. B.3/B.4 + Table 4 (ratio math, params, serving)
  kernel_bench         — Pallas kernel motivations (traffic models + timings)
  roofline_report      — §Roofline summary from the dry-run artifacts
  wallclock            — tracked perf trajectory (ISSUE 6): tuned-vs-default
                         kernel wall, stage-1/stage-2 wall, BENCH_<n>.json
  serving_throughput   — continuous-batching engine under a Poisson trace
                         (ISSUE 7): tokens/sec + p50/p99, compressed-vs-
                         dense decode at equal batch, flash-decode kernel
  zoo_matrix           — arch-zoo conformance matrix (ISSUE 10): per-arch
                         compress -> checkpoint -> serve roundtrip rows +
                         claim_I10_zoo_roundtrip (``--zoo`` only; not in
                         the default sweep — it re-compresses every arch)

``--wallclock`` runs ONLY the wall-clock benchmark (with a shorter train
substrate); ``--serving`` runs ONLY the serving benchmark.  Both emit the
versioned BENCH_<n>.json artifact (repo root by default) — the CI smoke
jobs' entry points:

    python benchmarks/run.py --wallclock --out-dir artifacts/
    python benchmarks/run.py --serving --out-dir artifacts/
    python benchmarks/run.py --zoo --out-dir artifacts/
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main(argv=None) -> None:
    import time

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--wallclock", action="store_true",
                    help="run only the wall-clock benchmark + artifact")
    ap.add_argument("--serving", action="store_true",
                    help="run only the serving-throughput benchmark "
                         "+ artifact")
    ap.add_argument("--zoo", action="store_true",
                    help="run only the arch-zoo conformance matrix "
                         "+ artifact")
    ap.add_argument("--archs", nargs="*", default=None,
                    help="with --zoo: restrict the matrix to these archs")
    ap.add_argument("--out-dir", default=None,
                    help="BENCH_<n>.json directory (default: repo root)")
    ap.add_argument("--steps", type=int, default=None,
                    help="train steps for the substrate model")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    t0 = time.time()
    print("name,us_per_call,derived")
    if args.wallclock:
        from benchmarks import wallclock
        doc = wallclock.collect(steps=args.steps or 60)
        path = wallclock.emit(doc, args.out_dir)
        for row in wallclock.summary_rows(doc):
            print(row)
        print(f"wallclock_artifact,0.0,{path}")
        print(f"total_benchmark_wall,{(time.time() - t0) * 1e6:.0f},"
              "end-to-end")
        return
    if args.serving:
        from benchmarks import serving_throughput, wallclock
        doc = serving_throughput.collect()
        path = wallclock.emit(doc, args.out_dir)
        for row in wallclock.summary_rows(doc):
            print(row)
        print(f"serving_artifact,0.0,{path}")
        print(f"total_benchmark_wall,{(time.time() - t0) * 1e6:.0f},"
              "end-to-end")
        return
    if args.zoo:
        from benchmarks import wallclock, zoo_matrix
        doc = zoo_matrix.collect(args.archs)
        path = wallclock.emit(doc, args.out_dir)
        for row in wallclock.summary_rows(doc):
            print(row)
        print(f"zoo_artifact,0.0,{path}")
        print(f"total_benchmark_wall,{(time.time() - t0) * 1e6:.0f},"
              "end-to-end")
        return

    from benchmarks import (calibration_size, compression_quality,
                            error_evolution, kernel_bench, memory_speedup,
                            refine_speed, roofline_report,
                            serving_throughput, wallclock)
    from benchmarks.common import train_small_model

    cfg, params, final_loss = train_small_model(steps=args.steps or 200)
    print(f"train_substrate_200steps,0.0,final_loss={final_loss:.3f}")
    ctx = {"cfg": cfg, "params": params}
    for mod in (compression_quality, error_evolution, calibration_size,
                refine_speed, memory_speedup, kernel_bench,
                roofline_report, wallclock, serving_throughput):
        for row in mod.run(ctx):
            print(row)
    print(f"total_benchmark_wall,{(time.time() - t0) * 1e6:.0f},end-to-end")


if __name__ == "__main__":
    main()
