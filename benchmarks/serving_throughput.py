"""Serving throughput under a Poisson trace (ISSUE 7).

Drives the continuous-batching engine (``repro.launch.serve``) with a
seeded Poisson arrival trace over mixed prompt lengths and measures
end-to-end tokens/sec, request-latency percentiles (p50/p99), and the
steady-state batched decode-step wall — for the dense model (ratio 1.0)
and AA-SVD-factorized deployments (latent KV cache + fused flash-decode)
at a sweep of compression ratios, all through the SAME scheduler at equal
batch.  A second architecture (qwen3 smoke, dense) runs the same trace to
keep the scheduler honest across model families, and one row times the
Pallas flash-decode kernel itself in interpret mode.

The benchmark model is deliberately GQA-heavy (8 query / 2 KV heads):
with few KV heads the per-step dense attention cost is dominated by the
O(L·KV·D) cache reads and k/v projections that factorization shrinks, so
the compression ratio should convert into decode throughput — that is
``claim_I7_compressed_decode_not_slower``.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_schema import SCHEMA_VERSION, validate

STEPS = 24          # generated tokens per request
N_REQUESTS = 8
SLOTS = 4
MAX_LEN = 96
PROMPT_LENS = (8, 12, 24, 32)
RATIOS = (1.0, 0.6, 0.35)


def _bench_cfg():
    """GQA serving substrate: 8 query heads on 2 KV heads, d_model 256."""
    from repro.configs.base import ModelConfig
    return ModelConfig(name="serve-bench", family="dense", num_layers=4,
                       d_model=256, num_heads=8, num_kv_heads=2, d_ff=1024,
                       vocab_size=512, dtype="float32",
                       param_dtype="float32")


def _trace(cfg, seed: int, mean_gap_s: float = 0.01):
    """Seeded Poisson arrivals with mixed prompt lengths."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(mean_gap_s, size=N_REQUESTS))
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=(int(rng.choice(PROMPT_LENS)),),
                                        dtype=np.int32),
                    steps=STEPS, arrival=float(arrivals[i]))
            for i in range(N_REQUESTS)]


def _serve_one(cfg, params, tag: str, *, seed: int = 0) -> Dict[str, dict]:
    """Run one engine config over the trace -> named rows."""
    from repro.launch.serve import ContinuousBatchingServer
    eng = ContinuousBatchingServer(cfg, params, max_len=MAX_LEN, slots=SLOTS)
    reqs = _trace(cfg, seed)
    eng.run(reqs)                                   # warmup: traces all jits
    results = eng.run(_trace(cfg, seed))
    makespan = max(r["done"] for r in results.values())
    total_tokens = sum(len(r["tokens"]) for r in results.values())
    lat = np.asarray(sorted(r["done"] - r["arrival"]
                            for r in results.values()))
    ttft = np.asarray(sorted(r["first_token"] - r["arrival"]
                             for r in results.values()))
    step_us = np.asarray(eng.decode_step_times) * 1e6
    med_step = float(np.median(step_us))
    return {
        f"serving_{tag}_throughput": {
            "us": makespan * 1e6,
            "meta": {"tokens_per_s": round(total_tokens / makespan, 1),
                     "total_tokens": total_tokens, "requests": N_REQUESTS,
                     "slots": SLOTS, "steps": STEPS}},
        f"serving_{tag}_latency": {
            "us": float(np.percentile(lat, 50)) * 1e6,
            "meta": {"p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
                     "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
                     "ttft_p50_ms": round(
                         float(np.percentile(ttft, 50)) * 1e3, 2)}},
        f"serving_{tag}_decode_step": {
            "us": med_step,
            "meta": {"decode_steps": len(step_us), "batch": SLOTS,
                     "slot_tokens_per_s": round(SLOTS / (med_step / 1e6), 1)}},
    }


def _kernel_row() -> dict:
    """Time the fused flash-decode Pallas kernel in interpret mode on the
    serve-bench decode shape (the Mosaic path runs the same program)."""
    from benchmarks.common import time_call
    from repro.kernels import ops
    b, h, kv, d, l, r = SLOTS, 8, 2, 32, MAX_LEN, 24
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(k1, (b, h, d), jnp.float32)
    lk = jax.random.normal(k2, (b, r, l), jnp.float32)    # rank-major
    lv = jax.random.normal(k1, (b, r, l), jnp.float32)
    uk = jax.random.normal(k2, (r, kv * d), jnp.float32) / 8
    uv = jax.random.normal(k1, (r, kv * d), jnp.float32) / 8
    lengths = jnp.full((b,), l // 2, jnp.int32)
    cos = jax.random.normal(k1, (l, d // 2), jnp.float32)
    sin = jax.random.normal(k2, (l, d // 2), jnp.float32)
    interp = jax.default_backend() != "tpu"
    kn, vn = lk[:, :, 0], lv[:, :, 0]
    us = time_call(lambda: ops.flash_decode(q, lk, lv, uk, uv, lengths,
                                            cos, sin, kn, vn,
                                            force_pallas=True,
                                            interpret=interp))
    return {"name": "flash_decode_kernel",
            "us": us, "meta": {"b": b, "h": h, "kv": kv, "d": d, "l": l,
                               "rank": r,
                               "mode": "interpret" if interp else "mosaic"}}


def collect(ctx: Optional[dict] = None, *, seed: int = 0) -> dict:
    """Measure the serving sweep and return a schema-valid artifact doc."""
    from repro.core.factorized import factorize_params
    from repro.models import model as M

    t0 = time.time()
    cfg = _bench_cfg()
    dense_params = M.init_params(cfg, jax.random.PRNGKey(0))
    rows: List[dict] = []
    named: Dict[str, dict] = {}
    for ratio in RATIOS:
        tag = f"r{ratio:g}".replace(".", "p")
        # rank_multiple=8: the default 128-multiple padding rounds the
        # 64-wide kv projections up to near-full rank (no compression)
        params = (dense_params if ratio >= 1.0 else
                  factorize_params(dense_params, cfg, ratio=ratio,
                                   rank_multiple=8))
        named.update(_serve_one(cfg, params, tag, seed=seed))
    # scheduler generality: a zoo arch (dense) through the same engine
    qcfg = __import__("repro.configs", fromlist=["get_smoke_config"]) \
        .get_smoke_config("qwen3-0.6b").replace(dtype="float32")
    qparams = M.init_params(qcfg, jax.random.PRNGKey(1))
    named.update(_serve_one(qcfg, qparams, "qwen3_dense", seed=seed))
    rows.extend({"name": k, **v} for k, v in named.items())
    rows.append(_kernel_row())

    dense_step = named["serving_r1_decode_step"]["us"]
    comp_step = named["serving_r0p35_decode_step"]["us"]
    dense_tps = SLOTS / (dense_step / 1e6)
    comp_tps = SLOTS / (comp_step / 1e6)
    doc = {
        "schema_version": SCHEMA_VERSION,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "mode": ("interpret" if jax.default_backend() != "tpu"
                 else "mosaic"),
        "rows": rows,
        "claims": [{
            # steady-state batched decode at EQUAL batch: the factorized
            # latent-cache path must not be slower than dense (5% wall
            # jitter margin on shared CI runners)
            "name": "claim_I7_compressed_decode_not_slower",
            "pass": bool(comp_step <= dense_step * 1.05),
            "detail": (f"decode step dense {dense_step:.0f}us "
                       f"({dense_tps:.0f} tok/s) vs ratio-0.35 "
                       f"{comp_step:.0f}us ({comp_tps:.0f} tok/s) "
                       f"at batch {SLOTS}"),
        }],
        "wall_s": round(time.time() - t0, 2),
    }
    problems = validate(doc)
    assert not problems, problems
    return doc


def run(ctx) -> List[str]:
    """Suite entry point: measure and return harness CSV rows."""
    from benchmarks import wallclock
    doc = collect(ctx)
    path = wallclock.emit(doc)
    return wallclock.summary_rows(doc) + [f"serving_artifact,0.0,{path}"]
