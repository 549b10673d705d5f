"""Serve an AA-SVD-compressed model with batched requests.

    PYTHONPATH=src python examples/serve_compressed.py --ratio 0.6

Train-free path: initialize → compress (Algorithm 2) → batched generation,
comparing tokens/s and parameter footprint against the dense model.  The
same ``serve_step`` is what the multi-pod dry-run lowers for the
decode_32k / long_500k cells.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import ALL_ARCHS, get_smoke_config
from repro.core import CompressConfig, compress_model
from repro.data import calibration_set, synthetic_tokens
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.serve import Server
from repro.models import model as M


def bench(server, prompts, steps=16):
    out = server.generate(prompts, steps=steps)  # includes compile
    t0 = time.time()
    out = server.generate(prompts, steps=steps)
    dt = time.time() - t0
    return out, prompts.shape[0] * steps / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="qwen3-0.6b")
    ap.add_argument("--ratio", type=float, default=0.6)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_smoke_config(args.arch).replace(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    n_dense = sum(x.size for x in jax.tree.leaves(params))

    calib = calibration_set(cfg, 8, 64)
    compressed, _ = compress_model(
        params, cfg, calib,
        CompressConfig(ratio=args.ratio, refine_epochs=4))
    n_comp = sum(x.size for x in jax.tree.leaves(compressed))

    prompts = synthetic_tokens(jax.random.PRNGKey(1), args.batch, 16,
                               cfg.vocab_size)
    _, tps_dense = bench(Server(cfg, params, max_len=64), prompts)
    out, tps_comp = bench(Server(cfg, compressed, max_len=64), prompts)

    print(f"[serve] params {n_dense / 1e3:.0f}k -> {n_comp / 1e3:.0f}k "
          f"({n_comp / n_dense:.2f}x)")
    print(f"[serve] dense {tps_dense:.1f} tok/s | "
          f"aa-svd(r={args.ratio}) {tps_comp:.1f} tok/s")
    print("[serve] sample:", out[0, :12])


if __name__ == "__main__":
    main()
