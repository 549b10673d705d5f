"""End-to-end driver: train a ~100M-parameter model for a few hundred steps.

    PYTHONPATH=src python examples/train_100m.py --steps 300

Uses the full production substrate: deterministic data pipeline, AdamW +
cosine schedule, checkpoint/restart (kill it mid-run and start again — it
resumes), preemption handling, and pjit sharding on the host mesh.  The
config is a scaled-down llama (12L × 768d ≈ 100M params).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs import get_config
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.train import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt_100m")
    ap.add_argument("--d-model", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_config("llama-7b").replace(
        name="llama-100m",
        num_layers=args.layers,
        d_model=args.d_model,
        num_heads=12, num_kv_heads=12, head_dim=64,
        d_ff=2048, vocab_size=32000,
        dtype="float32",
    )
    print(f"[example] {cfg.name}: {cfg.param_count() / 1e6:.0f}M params")
    _, info = train(cfg, steps=args.steps, batch=args.batch,
                    seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
                    ckpt_every=50, lr=3e-4)
    print(f"[example] done at step {info['step']}; "
          f"losses tail: {info.get('losses', [])[-3:]}")


if __name__ == "__main__":
    main()
