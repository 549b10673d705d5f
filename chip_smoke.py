#!/usr/bin/env python3
"""Smoke test of the system's main path on TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips: expert-parallel serving

Everything runs in this one process; it starts no other.  With no
arguments it needs one TPU chip and runs three phases in order:

1. device  — platform, ``device_kind`` and count.  Anything but a TPU
   exits non-zero before any work.
2. kernels — each of the five Pallas kernels through ``repro.kernels.ops``
   at qwen3-0.6b's widths (``grouped_matmul`` at deepseek-v2-lite's expert
   widths, 16 experts: one chip's share of 64 over four), in float32,
   against its ``kernels/ref.py`` reference computed at HIGHEST matmul
   precision.  The compiled text of every kernel must hold a
   ``tpu_custom_call`` (no interpret mode, no reference dispatch).
3. main    — qwen3-0.6b at its published widths from a seed: AA-SVD
   compression at ratio 0.6 on 16×256 calibration tokens, a checkpoint
   round trip (``CheckpointManager`` → ``ContinuousBatchingServer.
   from_checkpoint``), then 4 requests (128-token prompts, 32 new tokens)
   served from the dense model, the in-memory compressed model and the
   reloaded one.  Reloaded params must be bit-equal to the in-memory
   ones, the two compressed engines must emit the same greedy tokens, and
   every logit over the served sequences must be finite.

``--chips 4`` runs only the expert-parallel phase: deepseek-v2-lite-16b
with its 64 experts split 16 per chip.  A 3-layer cut (1 dense + 2 MoE
layers, float32) runs on one chip and on the 2x2 mesh: greedy tokens must
match and prefill logits agree within ``EP_LOGIT_TOL``.  Then the full
27-layer model in bf16 (~31 GB, more than one chip holds), its params
sharded at init through ``jit`` ``out_shardings``, answers the requests.

Each phase prints one line with its compile seconds (XLA compile or
persistent-cache load), the rest of its wall time, and
``peak_bytes_in_use`` per device.  The last line of standard output is a
JSON object ``{"ok": true, "device": {...}}``; any failed check raises
before it is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint.manager import CheckpointManager  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import CompressConfig, compress_model  # noqa: E402
from repro.core.zoo import bit_mismatches  # noqa: E402
from repro.data import calibration_set, synthetic_tokens  # noqa: E402
from repro.distributed import sharding as SH  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.serve import (ContinuousBatchingServer, Request,  # noqa: E402
                                Server)
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402

SEED = 0
# An fp32 matmul the MXU takes in one bf16 pass rounds each operand to 8
# mantissa bits (2^-9 relative per product); a fault in tiling, masking or
# block indexing moves the result by O(1).  1e-2 relative Frobenius error
# sits between the two.
KERNEL_TOL = 1e-2
# 1-chip vs 4-chip logits of the float32 cut at HIGHEST precision differ
# only by the summation order of the expert-shard psum.
EP_LOGIT_TOL = 1e-3

MAIN_ARCH = "qwen3-0.6b"
MAIN_RATIO = 0.6
MAIN_CALIB = (16, 256)          # sequences × tokens
MAIN_REFINE_EPOCHS = 2
REQUESTS = (4, 128, 32)         # requests × prompt tokens × new tokens
EP_ARCH = "deepseek-v2-lite-16b"


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


# ---------------------------------------------------------------------------
# phase accounting


class _CompileClock:
    """Sums XLA compile (or persistent-cache load) seconds and cache hits
    from JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def peak_bytes():
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
            for d in jax.devices()]


def run_phase(clock, name, fn, *args):
    c0, h0, m0 = clock.seconds, clock.hits, clock.misses
    t0 = time.monotonic()
    out = fn(*args)
    wall = time.monotonic() - t0
    comp = clock.seconds - c0
    print(f"[phase] {name} wall_s={wall!r} compile_s={comp!r} "
          f"run_s={wall - comp!r} cache_hits={clock.hits - h0} "
          f"cache_misses={clock.misses - m0} "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)
    return out


def rel_err(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# phase 1: device


def phase_device(chips: int):
    devs = jax.devices()
    d0 = devs[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d0.platform == "tpu", f"no TPU: JAX reports {d0.platform!r}")
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase 2: kernels


def kernel_cases(key):
    """(name, kernel call through ops, reference, args) at real widths."""
    ks = iter(jax.random.split(key, 32))
    nrm = lambda *s: jax.random.normal(next(ks), s, jnp.float32)  # noqa: E731
    t = MAIN_CALIB[0] * MAIN_CALIB[1]
    cases = []
    for n in (1024, 2048, 3072):        # d_model, H·head_dim, d_ff inputs
        cases.append((f"cov_accum_n{n}", ops.cov_accum, ref.cov_accum_ref,
                      (nrm(t, n), nrm(t, n))))
    # gate/up at ratio 0.6: rank 464 of the 1024→3072 map
    cases.append(("lowrank_matmul", ops.lowrank_matmul,
                  ref.lowrank_matmul_ref,
                  (nrm(t, 1024), nrm(1024, 464) / 32, nrm(464, 3072) / 22)))
    sizes = np.random.default_rng(SEED).multinomial(t, np.ones(16) / 16)
    cases.append(("grouped_matmul", ops.grouped_matmul,
                  ref.grouped_matmul_ref,
                  (nrm(t, 2048), nrm(16, 2048, 1408) / 45,
                   jnp.asarray(sizes, jnp.int32))))
    cases.append(("flash_attention", ops.flash_attention,
                  ref.flash_attention_ref,
                  (nrm(1, 16, 2048, 128), nrm(1, 8, 2048, 128),
                   nrm(1, 8, 2048, 128))))
    b, h, kv, d, l, r = 8, 16, 8, 128, 2048, 256
    cos, sin = L.rope_table(jnp.arange(l), d, 1e6)
    lengths = jnp.asarray(
        np.random.default_rng(SEED + 1).integers(1, l + 1, b), jnp.int32)

    def decode_ref(q, lk, lv, uk, uv, lens, c, s, kn, vn):
        split = lambda u: u.reshape(r, kv, d).transpose(1, 0, 2)  # noqa: E731
        rows = jnp.arange(b)
        lk = lk.at[rows, :, lens - 1].set(kn)
        lv = lv.at[rows, :, lens - 1].set(vn)
        out = ref.flash_decode_ref(q, jnp.swapaxes(lk, 1, 2),
                                   jnp.swapaxes(lv, 1, 2), split(uk),
                                   split(uv), lens, c, s)
        return out, lk, lv

    # the latent cache is rank-major, (B, r, L)
    cases.append(("flash_decode", ops.flash_decode, decode_ref,
                  (nrm(b, h, d), nrm(b, r, l), nrm(b, r, l), nrm(r, kv * d)
                   / 16, nrm(r, kv * d) / 16, lengths, cos, sin, nrm(b, r),
                   nrm(b, r))))
    return cases


def phase_kernels():
    for name, kern, reference, args in kernel_cases(
            jax.random.PRNGKey(SEED)):
        t0 = time.monotonic()
        compiled = jax.jit(kern).lower(*args).compile()
        t_comp = time.monotonic() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no Pallas kernel in the compiled program")
        t0 = time.monotonic()
        got = jax.block_until_ready(compiled(*args))
        t_run = time.monotonic() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(*args)
        errs = [rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                              jax.tree.leaves(want))]
        print(f"[kernel] {name} lower_compile_s={t_comp!r} "
              f"first_call_s={t_run!r} rel_err={max(errs)!r} "
              f"tol={KERNEL_TOL}", flush=True)
        check(all(np.isfinite(errs)) and max(errs) <= KERNEL_TOL,
              f"{name}: relative error {max(errs)} > {KERNEL_TOL}")


# ---------------------------------------------------------------------------
# phase 3: compress -> checkpoint -> serve


def model_logits(cfg, params, tokens, mesh=None):
    """Logits (B, L, V) fp32 of the model over ``tokens``; under ``mesh``
    the MoE layers take the mesh's expert-parallel path."""
    def fwd(p, t):
        with SH.use_mesh(mesh, mode="use", cfg=cfg):
            hidden, _ = M.forward_hidden(p, cfg, {"tokens": t}, train=False)
            return M.logits_from_hidden(p, cfg, hidden)

    out = None if mesh is None else SH.replicated(mesh)
    return jax.jit(fwd, out_shardings=out)(params, tokens)


def serve_requests(server, prompts, steps):
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]), steps=steps)
            for i in range(prompts.shape[0])]
    res = server.run(reqs)
    return np.stack([res[i]["tokens"] for i in range(prompts.shape[0])])


def phase_main(cfg, *, calib=MAIN_CALIB, requests=REQUESTS,
               refine_epochs=MAIN_REFINE_EPOCHS):
    key = jax.random.PRNGKey(SEED)
    params = M.init_params(cfg, key)
    data = calibration_set(cfg, *calib)
    t0 = time.monotonic()
    comp, report = compress_model(
        params, cfg, data,
        CompressConfig(ratio=MAIN_RATIO, refine_epochs=refine_epochs,
                       verbose=True))
    jax.block_until_ready(comp)
    n_dense = sum(x.size for x in jax.tree.leaves(params))
    n_comp = sum(x.size for x in jax.tree.leaves(comp))
    print(f"[main] compressed {cfg.name} ({cfg.num_layers} layers) in "
          f"{time.monotonic() - t0!r}s: {len(report['units'])} units, "
          f"params {n_dense} -> {n_comp}", flush=True)

    n_req, plen, steps = requests
    max_len = plen + steps + 8
    prompts = synthetic_tokens(jax.random.PRNGKey(SEED + 1), n_req, plen,
                               cfg.vocab_size)
    with tempfile.TemporaryDirectory() as ckpt:
        mgr = CheckpointManager(ckpt, async_save=False)
        mgr.save(0, comp, blocking=True,
                 meta={"arch": cfg.name, "ratio": MAIN_RATIO})
        reloaded = ContinuousBatchingServer.from_checkpoint(
            cfg, ckpt, step=0, max_len=max_len, slots=n_req)
    bad = bit_mismatches(comp, reloaded.params)
    check(not bad, f"reloaded params differ from in-memory: {bad[:4]}")
    check(reloaded.checkpoint_meta.get("arch") == cfg.name,
          "checkpoint meta lost")

    tok_dense = serve_requests(
        ContinuousBatchingServer(cfg, params, max_len=max_len, slots=n_req),
        prompts, steps)
    tok_mem = serve_requests(
        ContinuousBatchingServer(cfg, comp, max_len=max_len, slots=n_req),
        prompts, steps)
    tok_rel = serve_requests(reloaded, prompts, steps)
    for toks in (tok_dense, tok_mem, tok_rel):
        check(toks.shape == (n_req, steps)
              and int(toks.min()) >= 0 and int(toks.max()) < cfg.vocab_size,
              f"served tokens malformed: shape {toks.shape}")
    check(np.array_equal(tok_mem, tok_rel),
          "reloaded engine's greedy tokens differ from the in-memory one's")

    seq_dense = jnp.concatenate([prompts, jnp.asarray(tok_dense)], 1)
    seq_comp = jnp.concatenate([prompts, jnp.asarray(tok_mem)], 1)
    lg_dense = model_logits(cfg, params, seq_dense)
    lg_comp = model_logits(cfg, comp, seq_comp)
    check(bool(jnp.isfinite(lg_dense).all()), "dense logits not finite")
    check(bool(jnp.isfinite(lg_comp).all()), "compressed logits not finite")
    pre_dense = model_logits(cfg, params, prompts)
    pre_comp = model_logits(cfg, comp, prompts)
    top1 = float(jnp.mean(jnp.argmax(pre_dense, -1)
                          == jnp.argmax(pre_comp, -1)))
    print(f"[main] prefill logits compressed vs dense: rel_err="
          f"{rel_err(pre_comp, pre_dense)!r} top1_agree={top1!r}", flush=True)
    print(f"[main] tokens dense={tok_dense[:, :8].tolist()} "
          f"compressed={tok_mem[:, :8].tolist()}", flush=True)


# ---------------------------------------------------------------------------
# --chips 4: expert-parallel serving


def init_sharded(cfg, mesh, key):
    """Params initialized directly in their serving layout: no device
    ever holds more than its shard."""
    shapes = jax.eval_shape(lambda: M.init_params(cfg, key))
    psh = SH.param_shardings(shapes, mesh, mode="serve", cfg=cfg)
    return jax.jit(lambda: M.init_params(cfg, key), out_shardings=psh)()


def serve_on(cfg, mesh, prompts, steps):
    params = init_sharded(cfg, mesh, jax.random.PRNGKey(SEED))
    server = Server(cfg, params, max_len=prompts.shape[1] + steps + 8,
                    batch=prompts.shape[0], mesh=mesh)
    toks = np.asarray(server.generate(prompts, steps=steps))
    logits = np.asarray(model_logits(cfg, server.params, prompts, mesh))
    shard_bytes = [0] * len(jax.devices())
    index = {d: i for i, d in enumerate(jax.devices())}
    for leaf in jax.tree.leaves(server.params):
        for s in leaf.addressable_shards:
            shard_bytes[index[s.device]] += s.data.nbytes
    return toks, logits, shard_bytes


def ep_configs(base=None, cut_layers=3):
    base = base or get_config(EP_ARCH)
    # drop-free dispatch: outputs do not depend on how tokens are grouped,
    # so one chip and the mesh must agree token for token
    base = base.replace(moe=dataclasses.replace(base.moe, dispatch="dropfree"))
    cut = base.replace(num_layers=cut_layers, dtype="float32",
                       param_dtype="float32")
    full = base.replace(param_dtype="bfloat16")
    return cut, full


def phase_ep(cut, full, *, chips=4, requests=REQUESTS):
    devs = jax.devices()[:chips]
    one = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    mesh = make_mesh((1, chips), ("data", "model"), devices=devs)
    n_req, plen, steps = requests
    prompts = synthetic_tokens(jax.random.PRNGKey(SEED + 1), n_req, plen,
                               cut.vocab_size)
    with jax.default_matmul_precision("highest"):
        tok1, lg1, _ = serve_on(cut, one, prompts, steps)
        tok4, lg4, _ = serve_on(cut, mesh, prompts, steps)
    err = rel_err(lg4, lg1)
    print(f"[ep] {cut.num_layers}-layer cut 1 vs {chips} chips: "
          f"tokens_equal={np.array_equal(tok1, tok4)} logit_rel_err={err!r} "
          f"tol={EP_LOGIT_TOL}", flush=True)
    check(np.isfinite(lg1).all() and np.isfinite(lg4).all(),
          "cut logits not finite")
    check(np.array_equal(tok1, tok4),
          f"greedy tokens differ between 1 and {chips} chips")
    check(err <= EP_LOGIT_TOL, f"logit error {err} > {EP_LOGIT_TOL}")

    toks, lg, shard_bytes = serve_on(full, mesh, prompts, steps)
    shapes = jax.eval_shape(lambda: M.init_params(full,
                                                  jax.random.PRNGKey(SEED)))
    model_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(shapes))
    peaks = peak_bytes()     # process-lifetime peaks: device 0 also ran the cut
    print(f"[ep] full {full.num_layers} layers on {chips} chips: "
          f"param_bytes={model_bytes} per_device_param_bytes={shard_bytes} "
          f"peak_bytes_in_use={peaks} tokens={toks[:, :8].tolist()}",
          flush=True)
    check(np.isfinite(lg).all(), "full-depth logits not finite")
    check(toks.shape == (n_req, steps) and int(toks.max()) < full.vocab_size,
          "full-depth tokens malformed")
    check(max(peaks[:chips]) < 0.5 * model_bytes,
          f"a device peaked at {max(peaks[:chips])} bytes of a "
          f"{model_bytes}-byte model")


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    # compression progress (one line per unit) goes through logging
    logging.basicConfig(level=logging.INFO, stream=sys.stdout,
                        format="%(asctime)s %(message)s")
    cache_dir = setup_compile_cache()
    clock = _CompileClock()
    print(f"[setup] compile cache {cache_dir}", flush=True)
    device = run_phase(clock, "device", phase_device, args.chips)
    if args.chips == 4:
        run_phase(clock, "expert_parallel", phase_ep, *ep_configs())
    else:
        run_phase(clock, "kernels", phase_kernels)
        run_phase(clock, "main", phase_main, get_config(MAIN_ARCH))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
