"""Serving engine for (optionally AA-SVD-compressed) models.

Two entry points share one jitted step family:

``Server`` — fixed-batch convenience frontend: one ``generate`` call
prefills every prompt together and decodes lock-step.  Requests are padded
to the advertised slot count (it is an error to submit more), and the
decode position starts at the TRUE prefill length — modality frontends
that prepend extra embeddings (vision patches) occupy cache positions
before the text tokens.

``ContinuousBatchingServer`` — the real engine.  Scheduler contract:

* The KV cache is allocated ONCE for ``slots`` sequences of ``max_len``
  positions.  Layout is chosen per sub-block by ``models.model.init_cache``:
  attention blocks whose k/v projections are AA-SVD-factorized store the
  rank-r latent per token ({"lk","lv"}), up-projected in-kernel by the
  fused flash-decode kernel; everything else keeps dense {"k","v"}
  (``cache_layout="dense"`` forces the dense layout everywhere).
* ``run(requests)`` drives a host-side loop: requests are admitted into
  free slots once their ``arrival`` offset has elapsed, prefilled
  individually (``cache_slot_take`` -> prefill -> ``cache_slot_put``), and
  then decoded as ONE batched step over all slots with a per-slot position
  vector — finishing one sequence never restarts the others.
* Prefill is decoupled from decode: ``prefill_chunk > 0`` streams the
  prompt through a fixed-width chunked-attention prefill (logits identical
  to whole-prompt prefill); width-padding retraces per chunk width, not
  per prompt length.  Architectures that cannot resume mid-sequence or
  tolerate right-padding (SSM, hybrid, sliding-window ring caches) are
  prefilled whole at exact length; requests carrying modality extras
  (patches / frames) are prefilled whole in a single chunk.
* Parked (empty) slots ride along in the decode batch at position 0;
  every position they touch is either overwritten by the next admission's
  prefill or masked by the per-slot attention length, so they never leak
  into live sequences.
* Per-request ``arrival`` / ``admitted`` / ``first_token`` / ``done``
  timestamps (seconds from ``run`` start) are returned for latency
  accounting, with ``admit_host``, the admission's host seconds outside
  its blocking first-token copy; ``decode_step_times`` keeps the per-step
  decode wall times of the last run for throughput accounting.
* The decode step writes and reads a scanned stage's latent cache where
  it lies, at the layer index; ``decode_inplace_layers``, set when the
  decode program is first traced (None before), is ``(in_place, sliced)``:
  the attention layers that take that path, and those whose cache is
  sliced out per layer and written back (``models.model.decode_paths``).
* Spans (``repro.analysis.spans``, off unless enabled) mark each
  admission (``repro.serve.admit``: slot take, prefill per chunk, slot
  put, first-token copy), each decode step (``repro.serve.step``, with
  ``live`` slots and the ``inplace``/``sliced`` layer counts: dispatch,
  wait, bookkeeping) and each wait for an arrival, with the request id
  on every span of an admission.

  python -m repro.launch.serve --arch qwen3-0.6b --smoke --ratio 0.6
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.spans import span
from repro.configs import ALL_ARCHS, get_config, get_smoke_config
from repro.core import CompressConfig, compress_model
from repro.data import calibration_set, synthetic_tokens
from repro.distributed import sharding as SH
from repro.launch import steps as S
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import serving_mesh
from repro.models import model as M


def _pad_batch(x, n: int):
    """Pad axis 0 of ``x`` with zeros up to ``n`` rows."""
    pad = n - x.shape[0]
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


def _prefill_extra_len(cfg) -> int:
    """Cache positions written by prefill BEYOND the text tokens.

    Vision frontends concatenate ``num_patches`` patch embeddings before
    the tokens, so the decoder cache holds patches + prompt.  Audio frames
    go to the encoder (cross-attn cache only) — decoder self-attn length
    stays at the text length.
    """
    return cfg.num_patches if cfg.frontend == "vision" else 0


class Server:
    """Fixed-batch serving frontend (one prefill + lock-step decode).

    Under a mesh, params are laid out by ``distributed.sharding`` (expert
    banks split over ``model``, TP elsewhere) and the cache by its
    per-leaf rules; every step is jitted with those shardings, so no
    device holds more than its share.  Params that already carry that
    layout (e.g. initialized through ``jit`` ``out_shardings``) stay
    where they are."""

    def __init__(self, cfg, params, *, max_len: int = 256, batch: int = 4,
                 mesh=None):
        self.cfg = cfg
        self.max_len = max_len
        self.batch = batch
        mesh = serving_mesh(mesh)
        init_cache = functools.partial(M.init_cache, cfg, batch, max_len)
        serve = S.make_serve_step(cfg, mesh)
        prefill = S.make_prefill_step(cfg, mesh)
        if mesh is None:
            # host arrays (a checkpoint restore) go to the device once, not
            # on every step
            self.params = jax.device_put(params)
            self._init_cache = init_cache
            self._serve = jax.jit(serve)
            self._prefill = jax.jit(prefill)
            return
        psh, csh = S.decode_shardings(cfg, mesh, params,
                                      jax.eval_shape(init_cache))
        rep = SH.replicated(mesh)
        self.params = jax.device_put(params, psh)
        self._init_cache = jax.jit(init_cache, out_shardings=csh)
        self._serve = jax.jit(serve, in_shardings=(psh, csh, rep, None),
                              out_shardings=(rep, csh))
        self._prefill = jax.jit(prefill, in_shardings=(psh, rep, csh),
                                out_shardings=(rep, csh))

    @classmethod
    def from_checkpoint(cls, cfg, directory: str, *, step: int = None,
                        max_len: int = 256, batch: int = 4, mesh=None):
        """Reload served params from a :class:`CheckpointManager` directory.

        Rebuilds the pytree purely from the manifest (``restore_tree``), so
        the serving process needs only the arch config and the checkpoint
        path — no template params.  The manifest ``meta`` dict lands on
        ``server.checkpoint_meta``.
        """
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(directory, async_save=False)
        _, params, meta = mgr.restore_tree(step)
        server = cls(cfg, params, max_len=max_len, batch=batch, mesh=mesh)
        server.checkpoint_meta = meta
        return server

    def generate(self, prompts: jnp.ndarray, *, steps: int = 32,
                 extras: Optional[dict] = None) -> jnp.ndarray:
        """prompts: (b, prompt_len) int32, b <= batch -> (b, steps)."""
        b, plen = prompts.shape
        if b > self.batch:
            raise ValueError(
                f"got {b} prompts but the server advertises batch="
                f"{self.batch} decode slots; split the request or raise "
                "Server(batch=...)")
        prefill_len = plen + _prefill_extra_len(self.cfg)
        if prefill_len + steps > self.max_len:
            # the decode cache holds max_len positions; past it the write
            # indices leave the buffer and the attention window silently
            # corrupts (dynamic-update clamping) — fail loudly instead.
            # The bound counts every position prefill writes, including
            # frontend extras (vision patches) that precede the tokens.
            raise ValueError(
                f"prefill length ({prefill_len}) + steps ({steps}) = "
                f"{prefill_len + steps} exceeds the cache capacity max_len "
                f"({self.max_len}); raise Server(max_len=...) or generate "
                "fewer steps")
        prompts = _pad_batch(prompts, self.batch)
        extras = {k: _pad_batch(jnp.asarray(v), self.batch)
                  for k, v in (extras or {}).items()}
        cache = self._init_cache()
        batch = {"tokens": prompts, **extras}
        next_tok, cache = self._prefill(self.params, batch, cache)
        out = [next_tok[:, None]]
        pos = prefill_len
        tok = next_tok[:, None]
        for _ in range(steps - 1):
            tok, cache = self._serve(self.params, cache, tok, pos)
            out.append(tok)
            pos += 1
        return jnp.concatenate(out, axis=1)[:b]


@dataclasses.dataclass
class Request:
    """One serving request for :class:`ContinuousBatchingServer`.

    ``arrival`` is the offset (seconds from ``run`` start) at which the
    request becomes visible to the scheduler — 0 means immediately.
    """

    rid: int
    prompt: np.ndarray               # (prompt_len,) int32
    steps: int
    extras: Optional[dict] = None    # modality inputs, leading axis 1
    arrival: float = 0.0


def _bucket(n: int, lo: int = 16) -> int:
    """Next power-of-two width >= n (floor ``lo``) — bounds retraces."""
    w = lo
    while w < n:
        w *= 2
    return w


class ContinuousBatchingServer:
    """Slot-level continuous batching over one shared decode cache."""

    def __init__(self, cfg, params, *, max_len: int = 256, slots: int = 4,
                 prefill_chunk: int = 0, mesh=None,
                 cache_layout: str = "auto"):
        self.cfg = cfg
        self.params = jax.device_put(params)
        self.max_len = max_len
        self.slots = slots
        self.prefill_chunk = prefill_chunk
        # SSM state and ring caches can neither resume mid-sequence nor
        # tolerate right-padded prompts -> exact-length whole prefill.
        self._exact = (cfg.family in ("ssm", "hybrid")
                       or cfg.attention == "sliding_mix")
        mesh = serving_mesh(mesh)
        self.decode_inplace_layers = None
        step = S.make_serve_step(cfg, mesh)

        @functools.wraps(step)
        def serve_step(params, cache, tokens, pos):
            # runs while the decode program is traced
            self.decode_inplace_layers = M.decode_paths(cfg, cache)
            return step(params, cache, tokens, pos)

        self._decode = jax.jit(serve_step, donate_argnums=(1,))
        self._pre_whole = jax.jit(S.make_slot_prefill_step(cfg, mesh,
                                                           chunked=False))
        self._pre_chunk = jax.jit(S.make_slot_prefill_step(cfg, mesh,
                                                           chunked=True))

        # one program each, whatever the slot: the trace names them.  The
        # put donates nothing, so it writes a fresh whole cache as the
        # eager ops did
        def cache_slot_take(cache, slot):
            return M.cache_slot_take(cfg, cache, slot)

        def cache_slot_put(cache, slot_cache, slot):
            return M.cache_slot_put(cfg, cache, slot_cache, slot)

        self._slot_take = jax.jit(cache_slot_take)
        self._slot_put = jax.jit(cache_slot_put)
        self._cache_params = None if cache_layout == "dense" else params
        self.decode_step_times: List[float] = []
        # rid -> which prefill path served it ("whole_exact" |
        # "whole_extras" | "whole_padded" | "chunked"); reset per run().
        self.prefill_routes: Dict[int, str] = {}

    @classmethod
    def from_checkpoint(cls, cfg, directory: str, *, step: int = None,
                        max_len: int = 256, slots: int = 4,
                        prefill_chunk: int = 0, mesh=None,
                        cache_layout: str = "auto"):
        """Engine twin of :meth:`Server.from_checkpoint`."""
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(directory, async_save=False)
        _, params, meta = mgr.restore_tree(step)
        server = cls(cfg, params, max_len=max_len, slots=slots,
                     prefill_chunk=prefill_chunk, mesh=mesh,
                     cache_layout=cache_layout)
        server.checkpoint_meta = meta
        return server

    # ------------------------------------------------------------------
    def _admit(self, req: Request, cache, slot: int):
        """Prefill ``req`` into ``slot``.  Returns (first token, cache,
        prefill length, seconds spent waiting for the first token)."""
        cfg = self.cfg
        prompt = np.asarray(req.prompt, np.int32)
        plen = int(prompt.shape[0])
        extra = _prefill_extra_len(cfg)
        total = plen + extra
        if total + req.steps > self.max_len:
            raise ValueError(
                f"request {req.rid}: prefill length ({total}) + steps "
                f"({req.steps}) exceeds max_len ({self.max_len})")
        extras = {k: jnp.asarray(v) for k, v in (req.extras or {}).items()}
        chunk = self.prefill_chunk
        route = ("whole_exact" if self._exact
                 else "whole_extras" if extras
                 else "whole_padded" if chunk <= 0
                 else "chunked")
        self.prefill_routes[req.rid] = route
        if self._exact:
            width = plen                         # exact length, no padding
        elif route == "chunked":
            width = -(-plen // chunk) * chunk
        else:
            width = min(_bucket(plen), self.max_len - extra)
        rid = req.rid
        with span("repro.serve.admit", rid=rid, slot=slot, prompt=plen,
                  width=width, route=route):
            with span("repro.serve.slot_take", rid=rid):
                slot_cache = self._slot_take(cache, slot)
            if route != "chunked":
                if self._exact:
                    toks = prompt[None]
                    last_idx = total - 1
                else:
                    toks = np.zeros((1, width), np.int32)
                    toks[0, :plen] = prompt
                    last_idx = extra + plen - 1
                with span("repro.serve.prefill", rid=rid):
                    tok, slot_cache = self._pre_whole(
                        self.params, {"tokens": jnp.asarray(toks), **extras},
                        slot_cache, jnp.int32(0), jnp.int32(last_idx))
            else:
                buf = np.zeros((width,), np.int32)
                buf[:plen] = prompt
                tok = None
                for c0 in range(0, width, chunk):
                    last = c0 + chunk >= width
                    last_idx = (plen - 1 - c0) if last else (chunk - 1)
                    with span("repro.serve.prefill", rid=rid, chunk=c0):
                        tok, slot_cache = self._pre_chunk(
                            self.params,
                            {"tokens": jnp.asarray(buf[None, c0:c0 + chunk])},
                            slot_cache, jnp.int32(c0), jnp.int32(last_idx))
            with span("repro.serve.slot_put", rid=rid):
                cache = self._slot_put(cache, slot_cache, slot)
            t_wait = time.monotonic()
            with span("repro.serve.first_token", rid=rid):
                tok0 = int(np.asarray(tok)[0])
            wait = time.monotonic() - t_wait
        return tok0, cache, total, wait

    # ------------------------------------------------------------------
    def run(self, requests: List[Request]) -> Dict[int, Dict[str, Any]]:
        """Serve every request; returns {rid: {tokens, arrival, admitted,
        first_token, done}} with times in seconds from run start."""
        cfg = self.cfg
        queue = sorted(requests, key=lambda r: (r.arrival, r.rid))
        cache = M.init_cache(cfg, self.slots, self.max_len,
                             params=self._cache_params)
        tokens_np = np.zeros((self.slots, 1), np.int32)
        pos_np = np.zeros((self.slots,), np.int32)
        active: List[Optional[dict]] = [None] * self.slots
        results: Dict[int, Dict[str, Any]] = {}
        self.decode_step_times = []
        self.prefill_routes = {}
        start = time.monotonic()
        now = lambda: time.monotonic() - start  # noqa: E731
        qi = 0

        def finish(slot):
            st = active[slot]
            results[st["req"].rid] = {
                "tokens": np.asarray(st["out"], np.int32),
                "arrival": st["req"].arrival, "admitted": st["admitted"],
                "first_token": st["first_token"], "done": now(),
                "admit_host": st["admit_host"]}
            active[slot] = None
            pos_np[slot] = 0
            tokens_np[slot, 0] = 0

        while qi < len(queue) or any(s is not None for s in active):
            # ---- admission: refill every free slot whose request arrived
            for slot in range(self.slots):
                if active[slot] is not None or qi >= len(queue):
                    continue
                if queue[qi].arrival > now():
                    continue
                req = queue[qi]
                qi += 1
                t_admit = now()
                tok0, cache, total, wait = self._admit(req, cache, slot)
                t_first = now()
                active[slot] = {"req": req, "out": [tok0],
                                "remaining": req.steps - 1,
                                "admitted": t_admit, "first_token": t_first,
                                "admit_host": t_first - t_admit - wait}
                tokens_np[slot, 0] = tok0
                pos_np[slot] = total
                if active[slot]["remaining"] <= 0:
                    finish(slot)
            if not any(s is not None for s in active):
                if qi < len(queue):      # idle until the next arrival
                    with span("repro.serve.arrival_wait"):
                        time.sleep(max(0.0, queue[qi].arrival - now()))
                continue
            # ---- one batched decode step over ALL slots (parked slots sit
            # at position 0; their writes are overwritten or masked)
            live = sum(s is not None for s in active)
            in_place, sliced = self.decode_inplace_layers or (None, None)
            t_step = time.monotonic()
            with span("repro.serve.step", step=len(self.decode_step_times),
                      live=live, inplace=in_place, sliced=sliced):
                with span("repro.serve.dispatch"):
                    tok_dev, cache = self._decode(self.params, cache,
                                                  jnp.asarray(tokens_np),
                                                  jnp.asarray(pos_np))
                with span("repro.serve.wait"):
                    tok_host = np.asarray(tok_dev)
                t_got = time.monotonic()
                with span("repro.serve.bookkeep"):
                    for slot in range(self.slots):
                        st = active[slot]
                        if st is None:
                            continue
                        st["out"].append(int(tok_host[slot, 0]))
                        tokens_np[slot, 0] = tok_host[slot, 0]
                        pos_np[slot] += 1
                        st["remaining"] -= 1
                        if st["remaining"] <= 0:
                            finish(slot)
            self.decode_step_times.append(t_got - t_step)
        return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS, default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ratio", type=float, default=1.0,
                    help="<1: AA-SVD-compress before serving")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--engine", action="store_true",
                    help="route through the continuous-batching engine")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key)
    if args.ratio < 1.0:
        calib = calibration_set(cfg, 8, 64)
        params, report = compress_model(
            params, cfg, calib,
            CompressConfig(ratio=args.ratio, refine_epochs=4))
        print(f"[serve] compressed to ratio {args.ratio}; "
              f"{len(report['units'])} blocks")

    max_len = args.prompt_len + _prefill_extra_len(cfg) + args.steps + 8
    prompts = synthetic_tokens(key, args.batch, args.prompt_len,
                               cfg.vocab_size)
    extras = {}
    if cfg.frontend == "vision":
        extras["patches"] = 0.02 * jax.random.normal(
            key, (args.batch, cfg.num_patches, cfg.d_model))
    if cfg.frontend == "audio":
        extras["frames"] = 0.02 * jax.random.normal(
            key, (args.batch, cfg.encoder_seq_len, cfg.d_model))
    t0 = time.time()
    if args.engine:
        server = ContinuousBatchingServer(cfg, params, max_len=max_len,
                                          slots=args.batch)
        reqs = [Request(rid=i, prompt=np.asarray(prompts[i]),
                        steps=args.steps,
                        extras={k: v[i:i + 1] for k, v in extras.items()}
                        or None)
                for i in range(args.batch)]
        results = server.run(reqs)
        toks = jnp.stack([jnp.asarray(results[i]["tokens"])
                          for i in range(args.batch)])
    else:
        server = Server(cfg, params, max_len=max_len, batch=args.batch)
        toks = server.generate(prompts, steps=args.steps, extras=extras)
    dt = time.time() - t0
    print(f"[serve] generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.steps / dt:.1f} tok/s)")
    print(toks[:, :16])


if __name__ == "__main__":
    main()
