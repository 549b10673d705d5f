"""Quickstart: compress a model with AA-SVD in ~30 lines.

    PYTHONPATH=src python examples/quickstart.py [--arch llama-7b]

Trains nothing — takes a randomly-initialized smoke-scale model, runs the
full Algorithm 2 pipeline (anchored objective + block refinement) and shows
the parameter reduction and that the compressed model serves.
"""

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

# pipeline progress goes through logging; surface INFO here
logging.basicConfig(level=logging.INFO, format="%(message)s")

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.core import CompressConfig, compress_model
from repro.core.pipeline import compress_ratio_report
from repro.data import calibration_set, synthetic_tokens
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.serve import Server
from repro.models import model as M


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="llama-7b")
    ap.add_argument("--ratio", type=float, default=0.6)
    ap.add_argument("--calib-mode", default="auto",
                    choices=["sequential", "fused", "hybrid", "auto"],
                    help="collection strategy; auto picks hybrid for MoE "
                         "archs and fused otherwise")
    ap.add_argument("--calib-dp", type=int, default=0,
                    help="shard stage-1 collection data-parallel over up to "
                         "this many devices (0 = off; try "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                         "on CPU; the mesh also runs stage-2 refinement DP)")
    ap.add_argument("--rank-mode", default="uniform",
                    choices=["uniform", "adaptive"],
                    help="rank budget policy: uniform (paper default) or "
                         "adaptive (global water-filling over whitened-"
                         "spectrum loss estimates — non-uniform per-layer "
                         "ranks under the same parameter budget)")
    ap.add_argument("--replay-taps", default=None, choices=["auto"],
                    help="'auto' (hybrid mode): replay groups flagged by "
                         "measured shift drift instead of the static "
                         "expert-bank list")
    ap.add_argument("--refine-epochs", type=int, default=6,
                    help="block-refinement epochs (paper default 25; smoke "
                         "default 6)")
    ap.add_argument("--no-refine", action="store_true",
                    help="skip stage-2 block refinement (closed-form solve "
                         "only)")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    mode = args.calib_mode
    if mode == "auto":
        is_moe = cfg.moe is not None and cfg.moe.num_experts
        mode = "hybrid" if is_moe else "fused"
    if args.replay_taps == "auto" and mode != "hybrid":
        # drift-driven replay only engages under hybrid collection — that
        # combination IS the dense-arch story (fused drift gets replayed
        # exactly where it is measured), so promote rather than silently
        # ignoring the flag
        print(f"--replay-taps auto: promoting calib mode {mode!r} -> "
              "'hybrid' (auto-replay needs hybrid collection)")
        mode = "hybrid"

    # data-parallel sharded collection: each DP worker runs the tapped
    # calibration forwards for its own microbatches
    calib_mesh = None
    if args.calib_dp > 0:
        from repro.launch.mesh import make_calib_mesh
        calib_mesh = make_calib_mesh(args.calib_dp)
        print("calib mesh:", dict(calib_mesh.shape))

    # 1. calibration set (the paper uses 256×2048; smoke scale here)
    calib = calibration_set(cfg, n=16, seq_len=64)

    # 2. AA-SVD: anchored-adaptive closed form + block-level refinement
    compressed, report = compress_model(
        params, cfg, calib,
        CompressConfig(ratio=args.ratio, objective="anchored",
                       refine=not args.no_refine,
                       refine_epochs=args.refine_epochs, calib_mode=mode,
                       rank_mode=args.rank_mode,
                       replay_taps=args.replay_taps or (),
                       calib_mesh=calib_mesh, verbose=True))
    print(compress_ratio_report(params, compressed))
    print("calibration:", report["calibration"])
    if args.rank_mode == "adaptive":
        spread = [l["rank"] for u in report["units"]
                  for l in u.get("linears", [])]
        print(f"adaptive ranks: min {min(spread)} max {max(spread)} "
              f"({report['calibration']['rank_mode']['rank_groups']} "
              "rank groups)")
    if not args.no_refine:
        print("refinement:", report["refinement"])

    # 3. the compressed model is a drop-in for serving
    server = Server(cfg, compressed, max_len=64)
    prompts = synthetic_tokens(jax.random.PRNGKey(1), 2, 16, cfg.vocab_size)
    tokens = server.generate(prompts, steps=8)
    print("generated:", tokens)


if __name__ == "__main__":
    main()
