"""Top-level model: embed → stage program → final norm → head.

Functional API (no framework):
    init_params(cfg, key)                         -> params pytree
    loss_fn(params, cfg, batch)                   -> (loss, metrics)
    init_cache(cfg, batch, max_len)               -> cache pytree
    prefill(params, cfg, batch, cache)            -> (logits_last, cache)
    decode_step(params, cfg, cache, tokens, pos)  -> (logits, cache)

Batches are dicts: ``tokens`` (B, Lt) int32, ``labels`` (B, L) int32 for
training; VLM adds ``patches`` (B, P, d) (stub frontend: precomputed patch
embeddings); enc-dec adds ``frames`` (B, Le, d) (stub audio frontend).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import blocks as B
from repro.models import layers as L

PyTree = Any


# ---------------------------------------------------------------------------
# init


def init_params(cfg, key) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": L.embedding_init(keys[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": L.norm_init(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.linear_init(keys[1], cfg.d_model, cfg.vocab_size,
                                          dtype=dtype)

    def init_stage(stage: B.Stage, key):
        ks = jax.random.split(key, len(stage.kinds))
        stage_params = []
        for kind, k in zip(stage.kinds, ks):
            if kind in B.SHARED_KINDS:
                stage_params.append(None)
                continue
            if stage.scan and stage.n > 1:
                stage_params.append(
                    jax.vmap(lambda kk: B.init_sub_block(kind, kk, cfg))(
                        jax.random.split(k, stage.n)))
            else:
                stage_params.append(B.init_sub_block(kind, k, cfg))
        return stage_params

    stages = B.stage_program(cfg)
    skeys = jax.random.split(keys[2], len(stages))
    params["stages"] = [init_stage(st, k) for st, k in zip(stages, skeys)]

    shared_kinds = sorted({k for st in stages for k in st.kinds
                           if k in B.SHARED_KINDS})
    if shared_kinds:
        params["shared"] = {
            kind: B.init_sub_block(kind, k, cfg)
            for kind, k in zip(shared_kinds,
                               jax.random.split(keys[3], len(shared_kinds)))}

    enc_stages = B.encoder_stages(cfg)
    if enc_stages:
        ekeys = jax.random.split(keys[4], len(enc_stages))
        params["encoder"] = {
            "stages": [init_stage(st, k) for st, k in zip(enc_stages, ekeys)],
            "final_norm": L.norm_init(cfg.d_model, cfg.norm),
        }
    params = _cast_floats(params, dtype)
    return params


def _cast_floats(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


# ---------------------------------------------------------------------------
# context (rope tables etc.)


def _rope_dim(cfg) -> int:
    if cfg.mla is not None and cfg.mla.kv_lora_rank:
        return cfg.mla.qk_rope_head_dim
    return cfg.head_dim


def make_ctx(cfg, positions, *, constrain=None) -> Dict[str, Any]:
    ctx: Dict[str, Any] = {"constrain": constrain or (lambda x: x)}
    rd = _rope_dim(cfg)
    ctx["cos"], ctx["sin"] = L.rope_table(positions, rd, cfg.rope_theta)
    if cfg.rope_theta_global:
        ctx["cos_global"], ctx["sin_global"] = L.rope_table(
            positions, rd, cfg.rope_theta_global)
    return ctx


def sinusoid_positions(positions, d):
    half = d // 2
    freqs = 10000.0 ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# stage execution: forward (no cache)


def _run_stage_forward(stage: B.Stage, stage_params, shared, x, cfg, ctx,
                       train: bool):
    from repro.distributed import sharding as SH

    def iteration(x, per_kind_params):
        aux = jnp.zeros((), jnp.float32)
        for kind, p in zip(stage.kinds, per_kind_params):
            if kind in B.SHARED_KINDS:
                p = shared[kind]
            p = SH.param_use_hints(p)   # ZeRO-3: per-layer weight gather
            x, a = B.apply_sub_block(kind, p, x, cfg, ctx)
            aux = aux + a
        return ctx["constrain"](x), aux

    if stage.scan and stage.n > 1:
        def body(carry, xs):
            x, aux = carry
            x, a = iteration(x, xs)
            return (x, aux + a), None

        if train and cfg.remat:
            body = jax.checkpoint(body, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   tuple(stage_params))
        return x, aux
    x, aux = iteration(x, stage_params)
    return x, aux


def _embed_inputs(params, cfg, batch):
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(params["embed"], batch["tokens"], dtype)
    if cfg.frontend == "vision" and "patches" in batch:
        x = jnp.concatenate([batch["patches"].astype(dtype), x], axis=1)
    return x


def _run_encoder(params, cfg, frames, train: bool):
    dtype = jnp.dtype(cfg.dtype)
    le = frames.shape[1]
    x = frames.astype(dtype) + sinusoid_positions(
        jnp.arange(le), cfg.d_model).astype(dtype)[None]
    ctx = make_ctx(cfg, jnp.arange(le))
    for st, sp in zip(B.encoder_stages(cfg), params["encoder"]["stages"]):
        x, _ = _run_stage_forward(st, sp, {}, x, cfg, ctx, train)
    return L.apply_norm(params["encoder"]["final_norm"], x, eps=cfg.norm_eps)


def forward_hidden(params, cfg, batch, *, train: bool = True, constrain=None):
    """Returns (hidden (B, L, d), aux_loss)."""
    x = _embed_inputs(params, cfg, batch)
    l = x.shape[1]
    ctx = make_ctx(cfg, jnp.arange(l), constrain=constrain)
    if cfg.family == "encdec":
        ctx["enc_out"] = _run_encoder(params, cfg, batch["frames"], train)
        x = x + sinusoid_positions(jnp.arange(l), cfg.d_model).astype(x.dtype)[None]
    x = ctx["constrain"](x)
    aux = jnp.zeros((), jnp.float32)
    for st, sp in zip(B.stage_program(cfg), params["stages"]):
        x, a = _run_stage_forward(st, sp, params.get("shared", {}), x, cfg,
                                  ctx, train)
        aux = aux + a
    return L.apply_norm(params["final_norm"], x, eps=cfg.norm_eps), aux


def _head_params(params, cfg):
    if cfg.tie_embeddings:
        return {"w": params["embed"]["table"].T}
    return params["lm_head"]


def loss_fn(params, cfg, batch, *, constrain=None):
    hidden, aux = forward_hidden(params, cfg, batch, train=True,
                                 constrain=constrain)
    ce = L.chunked_cross_entropy(hidden, _head_params(params, cfg),
                                 batch["labels"], chunk=cfg.logits_chunk)
    return ce + aux, {"ce": ce, "aux": aux}


def logits_from_hidden(params, cfg, hidden):
    return L.linear(_head_params(params, cfg), hidden.astype(jnp.float32),
                    dtype=jnp.float32)


# ---------------------------------------------------------------------------
# cache


def init_cache(cfg, batch: int, max_len: int, *,
               params: Optional[PyTree] = None) -> PyTree:
    """Zero decode cache.  When ``params`` is given (real arrays or
    eval_shape structs), attention sub-blocks whose kv projections are
    factorized get the latent {"lk", "lv"} layout — rank-r floats per token
    instead of num_kv_heads*head_dim — which the flash-decode kernel
    up-projects in-kernel.  Without ``params`` the layout is always dense
    (back-compatible)."""
    dtype = jnp.dtype(cfg.dtype)
    cache = []
    stage_params = params.get("stages") if params is not None else None
    for si, st in enumerate(B.stage_program(cfg)):
        per_kind = []
        for ki, kind in enumerate(st.kinds):
            p = None
            if params is not None:
                p = (params.get("shared", {}).get(kind)
                     if kind in B.SHARED_KINDS else stage_params[si][ki])
            c = B.init_sub_cache(kind, cfg, batch, max_len, dtype, params=p)
            if st.scan and st.n > 1:
                c = jax.tree.map(
                    lambda x: jnp.zeros((st.n,) + x.shape, x.dtype), c)
            per_kind.append(c)
        cache.append(per_kind)
    return cache


def cache_slot_take(cfg, cache, slot) -> PyTree:
    """Extract ONE scheduler slot's cache as a batch=1 cache pytree.

    Scanned stages stack their cache leaves on a leading layer axis, so the
    batch axis is 1 there and 0 on unrolled leaves.  ``slot`` may be traced
    (one jit covers every slot)."""
    out = []
    for st, per_kind in zip(B.stage_program(cfg), cache):
        axis = 1 if (st.scan and st.n > 1) else 0
        out.append([jax.tree.map(
            lambda x, a=axis: jax.lax.dynamic_slice_in_dim(x, slot, 1,
                                                           axis=a), c)
            for c in per_kind])
    return out


def cache_slot_put(cfg, cache, slot_cache, slot) -> PyTree:
    """Write a batch=1 slot cache back into slot ``slot`` of the full cache
    (inverse of :func:`cache_slot_take`)."""
    out = []
    for st, per_kind, per_new in zip(B.stage_program(cfg), cache, slot_cache):
        axis = 1 if (st.scan and st.n > 1) else 0
        out.append([jax.tree.map(
            lambda buf, upd, a=axis: jax.lax.dynamic_update_slice_in_dim(
                buf, upd.astype(buf.dtype), slot, axis=a), c, cn)
            for c, cn in zip(per_kind, per_new)])
    return out


# ---------------------------------------------------------------------------
# prefill / decode


# latent cache leaves that a decode step writes and reads where they lie
_IN_PLACE = ("lk", "lv")


def _latent(cache) -> bool:
    """Whether a sub-block's cache is the latent {"lk", "lv"} layout,
    which the flash-decode kernel reads where it lies."""
    return isinstance(cache, dict) and "lk" in cache


def _run_stage_cached(stage: B.Stage, stage_params, shared, x, stage_cache,
                      cfg, ctx, fn, *, in_place: bool = False):
    """fn = B.prefill_sub_block (returns x, cache, aux) or decode wrapper.

    ``in_place`` (decode): latent leaves of a scanned stage stay stacked;
    the sub-block gets them whole with ``ctx["layer"]`` and returns them
    updated, so no layer of them is sliced out or written back."""

    from repro.distributed import sharding as SH

    def iteration(x, per_kind_params, per_kind_cache, ctx):
        new_cache = []
        aux = jnp.zeros((), jnp.float32)
        for kind, p, c in zip(stage.kinds, per_kind_params, per_kind_cache):
            if kind in B.SHARED_KINDS:
                p = shared[kind]
            p = SH.param_use_hints(p)
            out = fn(kind, p, x, c, cfg, ctx)
            if len(out) == 3:
                x, c, a = out
                aux = aux + a
            else:
                x, c = out
            new_cache.append(c)
        return ctx["constrain"](x), new_cache, aux

    if stage.scan and stage.n > 1:
        # fori_loop with the stacked cache as loop CARRY (perf iteration C2):
        # lax.scan would thread the cache through xs→ys, which XLA cannot
        # alias — a full O(cache) copy per layer per decode step (528 GiB per
        # token on llama decode_32k).  Carried-buffer dynamic updates alias
        # in place; stacked layer params are dynamic-index reads (slice-only
        # traffic).  A layer slice that feeds a Pallas call is a buffer of
        # its own, though, so in-place leaves are never sliced.
        keep = [in_place and _latent(c) for c in stage_cache]

        def take(leaf, i):
            return jax.lax.dynamic_index_in_dim(leaf, i, 0, keepdims=False)

        def put(leaf, upd, i):
            return jax.lax.dynamic_update_index_in_dim(
                leaf, upd.astype(leaf.dtype), i, 0)

        def body(i, val):
            x, cache, aux = val
            p_i = jax.tree.map(lambda a: take(a, i), tuple(stage_params))
            c_i = [{k: v if k in _IN_PLACE else take(v, i)
                    for k, v in c.items()} if kp
                   else jax.tree.map(lambda a: take(a, i), c)
                   for c, kp in zip(cache, keep)]
            x, c_new, a = iteration(x, list(p_i), c_i,
                                    {**ctx, "layer": i} if any(keep)
                                    else ctx)
            cache = tuple(
                {k: u if k in _IN_PLACE else put(c[k], u, i)
                 for k, u in cn.items()} if kp
                else jax.tree.map(lambda buf, upd: put(buf, upd, i), c, cn)
                for c, cn, kp in zip(cache, c_new, keep))
            return x, cache, aux + a

        x, new_cache, aux = jax.lax.fori_loop(
            0, stage.n, body,
            (x, tuple(stage_cache), jnp.zeros((), jnp.float32)))
        return x, list(new_cache), aux
    x, new_cache, aux = iteration(x, stage_params, stage_cache, ctx)
    return x, new_cache, aux


def prefill(params, cfg, batch, cache, *, pos: int = 0,
            chunked: bool = False, last_idx=None, constrain=None):
    """Run the prompt, fill caches.  Returns (last-token logits, cache).

    ``pos`` is the absolute position of batch["tokens"][:, 0] (may be
    traced).  ``chunked=True`` attends against the whole cache with
    absolute-position masking so a prompt can be prefilled in chunks
    (unsupported for SSM/ring blocks).  ``last_idx`` (traced scalar) picks
    the logits row — needed when the prompt is right-padded to a chunk
    multiple; defaults to the last row."""
    x = _embed_inputs(params, cfg, batch)
    l = x.shape[1]
    ctx = make_ctx(cfg, pos + jnp.arange(l), constrain=constrain)
    ctx["pos"] = pos
    if chunked:
        ctx["chunked"] = True
    if cfg.family == "encdec":
        ctx["enc_out"] = _run_encoder(params, cfg, batch["frames"], False)
        x = x + sinusoid_positions(pos + jnp.arange(l),
                                   cfg.d_model).astype(x.dtype)[None]
    x = ctx["constrain"](x)
    new_cache = []
    for st, sp, sc in zip(B.stage_program(cfg), params["stages"], cache):
        x, c, _ = _run_stage_cached(st, sp, params.get("shared", {}), x, sc,
                                    cfg, ctx, B.prefill_sub_block)
        new_cache.append(c)
    hidden = L.apply_norm(params["final_norm"], x, eps=cfg.norm_eps)
    if last_idx is None:
        last = hidden[:, -1:]
    else:
        last = jax.lax.dynamic_slice_in_dim(hidden, last_idx, 1, axis=1)
    logits = logits_from_hidden(params, cfg, last)[:, 0]
    return logits, new_cache


def decode_paths(cfg, cache):
    """``(in_place, sliced)``: how many attention layers :func:`decode_step`
    writes and reads where their latent cache lies (stacked, at the layer
    index, in a scanned stage), and how many take the other path (a
    dense, MLA or ring cache, sliced out of a scanned stage per layer and
    written back).  ``cache`` may be arrays or shape structs."""
    counts = [0, 0]
    for st, per_kind in zip(B.stage_program(cfg), cache):
        for kind, c in zip(st.kinds, per_kind):
            if kind not in ("mamba1", "mamba2"):
                counts[0 if _latent(c) else 1] += st.n
    return tuple(counts)


def decode_step(params, cfg, cache, tokens, pos, *, constrain=None):
    """One decode step.  tokens: (B, 1) int32; pos: scalar int32 (0-based
    absolute position of this token) or a per-slot (B,) vector when every
    scheduler slot sits at its own length.  Returns (logits (B, V), cache).
    :func:`decode_paths` says which layers keep their cache in place."""
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed(params["embed"], tokens, dtype)
    per_slot = jnp.ndim(pos) == 1
    positions = pos[:, None] if per_slot else jnp.atleast_1d(pos)
    ctx = make_ctx(cfg, positions, constrain=constrain)
    ctx["pos"] = pos
    if cfg.family == "encdec":
        se = sinusoid_positions(jnp.reshape(positions, (-1,)),
                                cfg.d_model).astype(dtype)
        x = x + (se[:, None] if per_slot else se[None])
    x = ctx["constrain"](x)

    def dec(kind, p, x, c, cfg, ctx):
        return B.decode_sub_block(kind, p, x, c, cfg, ctx)

    new_cache = []
    for st, sp, sc in zip(B.stage_program(cfg), params["stages"], cache):
        x, c, _ = _run_stage_cached(st, sp, params.get("shared", {}), x, sc,
                                    cfg, ctx, dec, in_place=True)
        new_cache.append(c)
    hidden = L.apply_norm(params["final_norm"], x, eps=cfg.norm_eps)
    logits = logits_from_hidden(params, cfg, hidden)[:, 0]
    return logits, new_cache
