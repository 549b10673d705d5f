"""Block assembly: sub-block kinds, stage programs, caches.

Every architecture is expressed as a *stage program*: an ordered list of
stages, each either scanned (``n`` iterations of a homogeneous group of
sub-blocks, params stacked on a leading axis → one compact HLO while-loop)
or unrolled (heterogeneous leading/trailing blocks, e.g. DeepSeek's first
dense block).  A group may contain several sub-block *kinds* (gemma3's
5-local+1-global period; zamba2's 6-mamba+shared-attention period).

Weight-shared kinds (zamba2's shared block) read params from the model's
``shared`` slot instead of the stage stack, while their KV caches stay
per-invocation-site (stacked along the scan axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import mlp as M
from repro.models import ssm as S


@dataclasses.dataclass(frozen=True)
class Stage:
    kinds: Tuple[str, ...]   # sub-block kinds applied per iteration
    n: int                   # iterations (scan length; 1 -> unrolled)
    scan: bool = True


SHARED_KINDS = ("shared_attn",)


# ---------------------------------------------------------------------------
# stage programs per architecture family


def stage_program(cfg) -> List[Stage]:
    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        groups, rem = divmod(cfg.num_layers, every)
        stages = [Stage(("mamba2",) * every + ("shared_attn",), groups)]
        if rem:
            stages.append(Stage(("mamba2",), rem))
        return stages
    if cfg.family == "ssm":
        kind = "mamba1" if cfg.ssm.version == 1 else "mamba2"
        return [Stage((kind,), cfg.num_layers)]
    if cfg.attention == "sliding_mix":
        period = cfg.global_every
        groups, rem = divmod(cfg.num_layers, period)
        stages = [Stage(("attn_local",) * (period - 1) + ("attn_global",), groups)]
        if rem:
            stages.append(Stage(("attn_local",), rem))
        return stages
    if cfg.moe is not None and cfg.moe.num_experts:
        attn = "mla" if cfg.attention == "mla" else "attn"
        stages = []
        if cfg.moe.first_k_dense:
            stages.append(Stage((f"{attn}_dense_first",), cfg.moe.first_k_dense,
                                scan=cfg.moe.first_k_dense > 1))
        stages.append(Stage((f"{attn}_moe",),
                            cfg.num_layers - cfg.moe.first_k_dense))
        return stages
    if cfg.family == "encdec":
        return [Stage(("dec_attn",), cfg.num_layers)]
    return [Stage(("attn",), cfg.num_layers)]


def encoder_stages(cfg) -> List[Stage]:
    if cfg.num_encoder_layers:
        return [Stage(("enc_attn",), cfg.num_encoder_layers)]
    return []


# ---------------------------------------------------------------------------
# per-kind init


def _attn_ffn_init(key, cfg, *, d_ff=None, moe=False, mla=False):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": L.norm_init(cfg.d_model, cfg.norm),
        "ln2": L.norm_init(cfg.d_model, cfg.norm),
        "attn": A.mla_init(k1, cfg) if mla else A.gqa_init(k1, cfg),
    }
    if moe:
        p["ffn"] = M.moe_init(k2, cfg)
    else:
        p["ffn"] = M.ffn_init(k2, cfg.d_model, d_ff or cfg.d_ff, cfg.act_fn,
                              cfg.num_layers)
    return p


def init_sub_block(kind: str, key, cfg):
    if kind in ("attn", "attn_local", "attn_global", "shared_attn", "enc_attn"):
        return _attn_ffn_init(key, cfg)
    if kind == "attn_moe":
        return _attn_ffn_init(key, cfg, moe=True)
    if kind == "attn_dense_first":
        return _attn_ffn_init(key, cfg, d_ff=cfg.moe.dense_d_ff)
    if kind == "mla_moe":
        return _attn_ffn_init(key, cfg, moe=True, mla=True)
    if kind == "mla_dense_first":
        return _attn_ffn_init(key, cfg, d_ff=cfg.moe.dense_d_ff, mla=True)
    if kind == "mamba1":
        return {"ln": L.norm_init(cfg.d_model, cfg.norm),
                "mixer": S.mamba1_init(key, cfg)}
    if kind == "mamba2":
        return {"ln": L.norm_init(cfg.d_model, cfg.norm),
                "mixer": S.mamba2_init(key, cfg)}
    if kind == "dec_attn":
        k1, k2 = jax.random.split(key)
        p = _attn_ffn_init(k1, cfg)
        p["ln_x"] = L.norm_init(cfg.d_model, cfg.norm)
        p["xattn"] = A.gqa_init(k2, cfg)
        return p
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# rope table selection per kind


def _tables(kind, ctx):
    if kind == "attn_global" and "cos_global" in ctx:
        return ctx["cos_global"], ctx["sin_global"]
    return ctx["cos"], ctx["sin"]


def _window(kind, cfg) -> int:
    return cfg.sliding_window if kind == "attn_local" else 0


def _theta(kind, cfg) -> float:
    if kind == "attn_global" and cfg.rope_theta_global:
        return cfg.rope_theta_global
    return cfg.rope_theta


# ---------------------------------------------------------------------------
# forward (train / plain forward, no cache)


def apply_sub_block(kind: str, p, x, cfg, ctx):
    """x: (B, L, d) -> (x, aux_loss)."""
    zero = jnp.zeros((), jnp.float32)
    if kind in ("mamba1", "mamba2"):
        fwd = S.mamba1_forward if kind == "mamba1" else S.mamba2_forward
        with L.scope("mixer"):
            out = fwd(p["mixer"], L.apply_norm(p["ln"], x, eps=cfg.norm_eps),
                      cfg)
        return x + out, zero

    cos, sin = _tables(kind, ctx)
    h = L.apply_norm(p["ln1"], x, eps=cfg.norm_eps)
    rope = kind not in ("enc_attn", "dec_attn")
    with L.scope("attn"):
        if kind.startswith("mla"):
            attn_out = A.mla_prefill(p["attn"], h, cfg, cos, sin)
        elif kind == "enc_attn":
            attn_out = A.gqa_prefill(p["attn"], h, cfg, cos, sin,
                                     causal=False, rope=rope)
        else:
            attn_out = A.gqa_prefill(p["attn"], h, cfg, cos, sin,
                                     window=_window(kind, cfg), rope=rope)
    x = x + attn_out
    if kind == "dec_attn":
        hx = L.apply_norm(p["ln_x"], x, eps=cfg.norm_eps)
        with L.scope("xattn"):
            ek, ev = A.cross_attention_kv(p["xattn"], ctx["enc_out"], cfg)
            x = x + A.cross_attention(p["xattn"], hx, ek, ev, cfg)
    h2 = L.apply_norm(p["ln2"], x, eps=cfg.norm_eps)
    with L.scope("ffn"):
        if kind.endswith("_moe"):
            y, aux = M.moe_apply(p["ffn"], h2, cfg)
            return x + y, aux
        return x + M.ffn_apply(p["ffn"], h2, cfg.act_fn), zero


# ---------------------------------------------------------------------------
# caches


def latent_layout(kind: str, params, cfg) -> Optional[Tuple[int, int]]:
    """(rank_k, rank_v) when this sub-block can store the factorized rank-r
    kv latent instead of dense k/v — requires bias-free factorized wk AND
    wv (``A.latent_ranks``), no post-projection qk-norm (applied after the
    up-projection, so it can't be absorbed), no logit softcap (the
    flash-decode kernel doesn't implement it), and an absolute-position
    (non-ring, non-MLA) cache."""
    if params is None or cfg.qk_norm or cfg.attn_logit_softcap:
        return None
    if kind in ("mamba1", "mamba2", "attn_local", "enc_attn"):
        return None
    if kind.startswith("mla"):
        return None
    return A.latent_ranks(params.get("attn")) if isinstance(params, dict) \
        else None


def init_sub_cache(kind: str, cfg, batch: int, max_len: int, dtype,
                   params=None):
    """Zero cache for one sub-block.  When ``params`` (the sub-block's param
    dict) is given and the kv projections are factorized, attention caches
    use the latent {"lk", "lv"} layout (rank-r per token, rank-major
    (B, r, L)) instead of dense {"k", "v"} — the AA-SVD serving-path
    footprint win."""
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    if kind in ("mamba1", "mamba2"):
        init = S.mamba1_init_state if kind == "mamba1" else S.mamba2_init_state
        return init(None, cfg, batch, dtype)
    if kind == "attn_local":
        w = min(cfg.sliding_window, max_len)
        return {"k": jnp.zeros((batch, w, kv, hd), dtype),
                "v": jnp.zeros((batch, w, kv, hd), dtype)}
    if kind.startswith("mla"):
        m = cfg.mla
        return {"c": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                "kr": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype)}
    if kind == "enc_attn":
        return {}
    ranks = latent_layout(kind, params, cfg)
    if ranks is not None:
        # rank-major (B, r, L): the layout the TPU gives a rank off the
        # lane grid anyway, which the flash-decode kernel reads in place
        base = {"lk": jnp.zeros((batch, ranks[0], max_len), dtype),
                "lv": jnp.zeros((batch, ranks[1], max_len), dtype)}
    else:
        base = {"k": jnp.zeros((batch, max_len, kv, hd), dtype),
                "v": jnp.zeros((batch, max_len, kv, hd), dtype)}
    if kind == "dec_attn":
        base["xk"] = jnp.zeros((batch, cfg.encoder_seq_len, kv, hd), dtype)
        base["xv"] = jnp.zeros((batch, cfg.encoder_seq_len, kv, hd), dtype)
    return base


# ---------------------------------------------------------------------------
# prefill (forward + cache construction)


def _write_ring(cache, new, start):
    """Write new (B, L, ...) into ring cache (B, W, ...) at absolute pos start."""
    w = cache.shape[1]
    l = new.shape[1]
    if l >= w:
        tail = new[:, l - w:]
        slots = (start + l - w + jnp.arange(w)) % w
        return cache.at[:, slots].set(tail.astype(cache.dtype))
    slots = (start + jnp.arange(l)) % w
    return cache.at[:, slots].set(new.astype(cache.dtype))


def prefill_sub_block(kind: str, p, x, cache, cfg, ctx):
    """Forward over the prompt, filling the cache.  start pos = ctx['pos'].

    ``ctx['chunked']`` switches attention kinds to the cached-attention
    path: this chunk's keys are written into the cache first, then queries
    attend against the WHOLE cache with absolute-position masking, so a
    prompt can be prefilled chunk by chunk with logits equal to whole-
    prompt prefill.  SSM and ring (sliding-window) blocks don't support it.
    """
    start = ctx.get("pos", 0)
    chunked = bool(ctx.get("chunked"))
    zero = jnp.zeros((), jnp.float32)
    if kind in ("mamba1", "mamba2"):
        if chunked:
            raise ValueError("chunked prefill unsupported for SSM blocks")
        fwd = S.mamba1_forward if kind == "mamba1" else S.mamba2_forward
        y, state = fwd(p["mixer"], L.apply_norm(p["ln"], x, eps=cfg.norm_eps),
                       cfg, return_state=True)
        return x + y, state, zero

    cos, sin = _tables(kind, ctx)
    h = L.apply_norm(p["ln1"], x, eps=cfg.norm_eps)
    if kind.startswith("mla"):
        cache = dict(cache)
        if chunked:
            attn_out, cache["c"], cache["kr"] = A.mla_prefill_cached(
                p["attn"], h, cache["c"], cache["kr"], start, cfg, cos, sin)
        else:
            attn_out, (c, kr) = A.mla_prefill(p["attn"], h, cfg, cos, sin,
                                              return_cache=True)
            cache["c"] = jax.lax.dynamic_update_slice_in_dim(
                cache["c"], c.astype(cache["c"].dtype), start, axis=1)
            cache["kr"] = jax.lax.dynamic_update_slice_in_dim(
                cache["kr"], kr.astype(cache["kr"].dtype), start, axis=1)
    elif "lk" in cache:
        cache = dict(cache)
        attn_out, cache["lk"], cache["lv"] = A.gqa_prefill_latent(
            p["attn"], h, cache["lk"], cache["lv"], start, cfg, cos, sin,
            theta=_theta(kind, cfg), rope=kind != "dec_attn")
    elif chunked:
        if kind == "attn_local":
            raise ValueError("chunked prefill unsupported for ring caches")
        cache = dict(cache)
        attn_out, cache["k"], cache["v"] = A.gqa_prefill_cached(
            p["attn"], h, cache["k"], cache["v"], start, cfg, cos, sin,
            rope=kind != "dec_attn")
    else:
        attn_out, (k, v) = A.gqa_prefill(p["attn"], h, cfg, cos, sin,
                                         window=_window(kind, cfg),
                                         return_kv=True,
                                         rope=kind != "dec_attn")
        cache = dict(cache)
        if kind == "attn_local":
            cache["k"] = _write_ring(cache["k"], k, start)
            cache["v"] = _write_ring(cache["v"], v, start)
        else:
            cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), start, axis=1)
            cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), start, axis=1)
    x = x + attn_out
    if kind == "dec_attn":
        hx = L.apply_norm(p["ln_x"], x, eps=cfg.norm_eps)
        ek, ev = A.cross_attention_kv(p["xattn"], ctx["enc_out"], cfg)
        cache["xk"] = ek.astype(cache["xk"].dtype)
        cache["xv"] = ev.astype(cache["xv"].dtype)
        x = x + A.cross_attention(p["xattn"], hx, ek, ev, cfg)
    h2 = L.apply_norm(p["ln2"], x, eps=cfg.norm_eps)
    if kind.endswith("_moe"):
        y, aux = M.moe_apply(p["ffn"], h2, cfg)
        return x + y, cache, aux
    return x + M.ffn_apply(p["ffn"], h2, cfg.act_fn), cache, zero


# ---------------------------------------------------------------------------
# decode (one token, cache update)


def decode_sub_block(kind: str, p, x, cache, cfg, ctx):
    """x: (B, 1, d) -> (x, new_cache).  ctx['pos'] is the current position.
    ``ctx['layer']``, where present, is the index of this layer in a
    scanned stage whose latent {"lk", "lv"} leaves arrive stacked: they are
    written and read at that layer where they lie."""
    pos = ctx["pos"]
    if kind in ("mamba1", "mamba2"):
        dec = S.mamba1_decode if kind == "mamba1" else S.mamba2_decode
        y, state = dec(p["mixer"], L.apply_norm(p["ln"], x, eps=cfg.norm_eps),
                       cache, cfg)
        return x + y, state

    cos, sin = _tables(kind, ctx)
    h = L.apply_norm(p["ln1"], x, eps=cfg.norm_eps)
    cache = dict(cache)
    if kind.startswith("mla"):
        attn_out, cache["c"], cache["kr"] = A.mla_decode(
            p["attn"], h, cache["c"], cache["kr"], pos, cfg, cos, sin)
    elif kind == "attn_local":
        attn_out, cache["k"], cache["v"] = A.ring_decode(
            p["attn"], h, cache["k"], cache["v"], pos, cfg, cos, sin,
            window=cfg.sliding_window)
    elif "lk" in cache:
        attn_out, cache["lk"], cache["lv"] = A.gqa_decode_latent(
            p["attn"], h, cache["lk"], cache["lv"], pos, cfg, cos, sin,
            theta=_theta(kind, cfg), rope=kind != "dec_attn",
            layer=ctx.get("layer"))
    else:
        attn_out, cache["k"], cache["v"] = A.gqa_decode(
            p["attn"], h, cache["k"], cache["v"], pos, cfg, cos, sin,
            rope=kind != "dec_attn")
    x = x + attn_out
    if kind == "dec_attn":
        hx = L.apply_norm(p["ln_x"], x, eps=cfg.norm_eps)
        x = x + A.cross_attention(p["xattn"], hx, cache["xk"], cache["xv"], cfg)
    h2 = L.apply_norm(p["ln2"], x, eps=cfg.norm_eps)
    if kind.endswith("_moe"):
        y, _ = M.moe_apply(p["ffn"], h2, cfg)
        return x + y, cache
    return x + M.ffn_apply(p["ffn"], h2, cfg.act_fn), cache
