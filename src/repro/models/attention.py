"""Attention: GQA flash (chunked online-softmax), sliding-window, MLA, decode.

Memory discipline: prefill/train attention never materializes the (Lq × Lk)
score matrix — we scan over KV chunks with running (max, denom, acc)
statistics (the flash-attention recurrence), so a 32k prefill lowers with
O(Lq × chunk) live memory.  The Pallas TPU kernel in ``repro.kernels``
implements the same blockwise algorithm; this pure-JAX version is the
portable path and its oracle.

Layouts:  q (B, Lq, H, D);  k, v (B, Lk, KV, D) with H % KV == 0 (GQA).
KV caches for decode are (B, Lmax, KV, D).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# flash attention (pure JAX, scan over KV chunks)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset=0, chunk: int = 512, softcap: float = 0.0):
    """Online-softmax attention.

    q: (B, Lq, H, D); k/v: (B, Lk, KV, D).  ``q_offset`` is the absolute
    position of q[0] (decode: the current length) — a scalar, or a (B,)
    vector for slot-batched decode where every sequence sits at its own
    position (continuous batching).  ``window``>0 restricts keys to
    (q_pos - window, q_pos].  Returns (B, Lq, H, D) in q.dtype.
    """
    b, lq, h, d = q.shape
    _, lk, kv, _ = k.shape
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    per_slot = jnp.ndim(q_offset) == 1

    chunk = min(chunk, lk)
    n_chunks = -(-lk // chunk)
    pad = n_chunks * chunk - lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # Perf iteration A (EXPERIMENTS.md §Perf): GQA by repeating KV to the
    # full head axis BEFORE the scan — heads stay one dim, so TP sharding
    # survives (the earlier (KV, G)-grouped layout forced GSPMD to replicate
    # and all-reduce the 6.4 GiB/layer score tensors).  Score/PV einsums keep
    # bf16 operands with fp32 accumulation (preferred_element_type) instead
    # of materializing fp32 casts; probabilities are cast to the value dtype
    # for the PV GEMM; running (m, l, acc) stats stay fp32.  The body is
    # jax.checkpoint'd so backward recomputes per-chunk probabilities rather
    # than stacking (n_chunks × B × H × Lq × C) residuals.
    q_pos = (q_offset[:, None] if per_slot else q_offset) + jnp.arange(lq)

    def body(carry, idx):
        # dynamic-slice chunk reads from the ORIGINAL (B, L, KV, D) layout —
        # a scan over pre-transposed xs would materialize a full transposed
        # copy of the KV cache per decode step, and a pre-repeated GQA cache
        # would read G× the bytes (perf iteration C3).  The chunk-sized
        # repeat keeps the head axis whole for TP sharding (iteration A1).
        m, l_sum, acc = carry
        k_c = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, axis=1)
        v_c = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, axis=1)
        if g > 1:
            k_c = jnp.repeat(k_c, g, axis=2)
            v_c = jnp.repeat(v_c, g, axis=2)
        key_pos = idx * chunk + jnp.arange(chunk)
        # scores: (B, H, Lq, C), bf16 operands, fp32 accumulation
        s = jnp.einsum("bqhd,bchd->bhqc", q, k_c,
                       preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        # mask shape: (Lq, C) for scalar q_offset, (B, Lq, C) per-slot
        mask = jnp.ones(q_pos.shape + (chunk,), bool)
        if causal:
            mask = mask & (key_pos <= q_pos[..., None])
        if window:
            mask = mask & (key_pos > q_pos[..., None] - window)
        mask = mask & (key_pos < lk)
        s = jnp.where(mask[:, None] if per_slot else mask[None, None],
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l_sum * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqc,bchd->bhqd", p.astype(v_c.dtype), v_c,
                        preferred_element_type=jnp.float32)
        acc = acc * corr[..., None] + pv
        return (m_new, l_new, acc), None

    if n_chunks > 1:
        body = jax.checkpoint(body, prevent_cse=False)
    m0 = jnp.full((b, h, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    a0 = jnp.zeros((b, h, lq, d), jnp.float32)
    (m, l_sum, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), jnp.arange(n_chunks))

    out = acc / jnp.maximum(l_sum, 1e-20)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,H,Lq,D)->(B,Lq,H,D)


# ---------------------------------------------------------------------------
# GQA attention layer (q/k/v/o projections around flash_attention)


def gqa_init(key, cfg, dtype=jnp.float32):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.linear_init(ks[0], d, h * hd, dtype=dtype),
        "wk": L.linear_init(ks[1], d, kv * hd, dtype=dtype),
        "wv": L.linear_init(ks[2], d, kv * hd, dtype=dtype),
        "wo": L.linear_init(ks[3], h * hd, d, dtype=dtype,
                            scale=1.0 / math.sqrt(h * hd * 2 * cfg.num_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.norm_init(hd)
        p["k_norm"] = L.norm_init(hd)
    return p


def _project_qkv(p, x, cfg, cos, sin, *, rope: bool = True):
    b, l, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    L.sow("qkv_in", x)
    q = L.linear(p["wq"], x).reshape(b, l, h, hd)
    k = L.linear(p["wk"], x).reshape(b, l, kv, hd)
    v = L.linear(p["wv"], x).reshape(b, l, kv, hd)
    if cfg.qk_norm:
        q = L.apply_norm(p["q_norm"], q, eps=cfg.norm_eps)
        k = L.apply_norm(p["k_norm"], k, eps=cfg.norm_eps)
    if rope:
        q = L.apply_rope(q, cos, sin)
        k = L.apply_rope(k, cos, sin)
    return q, k, v


def gqa_prefill(p, x, cfg, cos, sin, *, causal=True, window: int = 0,
                chunk: int = 512, return_kv: bool = False, rope: bool = True):
    q, k, v = _project_qkv(p, x, cfg, cos, sin, rope=rope)
    o = flash_attention(q, k, v, causal=causal, window=window, chunk=chunk,
                        softcap=cfg.attn_logit_softcap)
    o = o.reshape(*x.shape[:2], -1)
    L.sow("o_in", o)
    out = L.linear(p["wo"], o)
    if return_kv:
        return out, (k, v)
    return out


def _cache_write(cache, new, pos):
    """Write one decode step into a (B, Lmax, ...) cache.

    ``new`` is (B, 1, ...); ``pos`` is a scalar (all slots at the same
    position — the classic fixed-batch path) or a (B,) vector of per-slot
    positions (continuous batching: each slot sits at its own length)."""
    if jnp.ndim(pos) == 1:
        b = cache.shape[0]
        return cache.at[jnp.arange(b), pos].set(new[:, 0].astype(cache.dtype))
    return jax.lax.dynamic_update_slice_in_dim(
        cache, new.astype(cache.dtype), pos, axis=1)


def gqa_decode(p, x, cache_k, cache_v, pos, cfg, cos, sin, *,
               window: int = 0, chunk: int = 1024, rope: bool = True):
    """One-token decode.  x: (B, 1, d); caches (B, Lmax, KV, D); pos is a
    scalar or a per-slot (B,) vector."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin, rope=rope)
    cache_k = _cache_write(cache_k, k, pos)
    cache_v = _cache_write(cache_v, v, pos)
    o = _decode_attention(q, cache_k, cache_v, pos, cfg, window=window,
                          chunk=chunk)
    return L.linear(p["wo"], o.reshape(*x.shape[:2], -1)), cache_k, cache_v


def gqa_prefill_cached(p, x, cache_k, cache_v, start, cfg, cos, sin, *,
                       chunk: int = 1024, rope: bool = True):
    """Chunked prefill: write this chunk's k/v into the dense cache at
    ``start`` and flash-attend against the WHOLE cache with absolute
    positions.  Earlier chunks are visible; unwritten future positions are
    causally masked (key_pos > q_pos), so chunk-by-chunk prefill produces
    the same logits as whole-prompt prefill."""
    q, k, v = _project_qkv(p, x, cfg, cos, sin, rope=rope)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype), start, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype), start, axis=1)
    o = flash_attention(q, cache_k, cache_v, causal=True, q_offset=start,
                        chunk=chunk, softcap=cfg.attn_logit_softcap)
    out = L.linear(p["wo"], o.reshape(*x.shape[:2], -1))
    return out, cache_k, cache_v


def _decode_attention(q, cache_k, cache_v, pos, cfg, *, window: int = 0,
                      chunk: int = 1024):
    """Dispatch: sequence-parallel flash-merge when the cache is L-sharded
    over 'model' (KV heads indivisible by the model axis — kimi-k2: KV=8 on
    16 shards), else the plain chunked path.  The H-sharded GQA repeat on an
    L-sharded cache otherwise triggers XLA 'involuntary full
    rematerialization' copies of the whole cache per chunk (§Perf)."""
    from repro.distributed import sharding as SH
    mesh = SH.active_mesh()
    if mesh is not None:
        n_model = mesh.shape.get("model", 1)
        dp = SH.dp_axes(mesh)
        dp_size = SH._axis_size(mesh, dp)
        if (n_model > 1 and cfg.num_kv_heads % n_model != 0
                and cache_k.shape[1] % n_model == 0
                and cache_k.shape[0] % dp_size == 0 and q.shape[1] == 1
                and jnp.ndim(pos) == 0
                and window == 0 and not cfg.attn_logit_softcap):
            return _seqpar_flash_decode(q, cache_k, cache_v, pos, mesh,
                                        chunk=chunk)
    return flash_attention(q, cache_k, cache_v, causal=True, window=window,
                           q_offset=pos, chunk=chunk,
                           softcap=cfg.attn_logit_softcap)


def _decode_stats(q, k, v, key_offset, pos, chunk: int, vary_axes=()):
    """Unnormalized flash statistics of one L-shard.

    q: (B, 1, H, D) (full heads); k/v: (B, L_loc, KV, D).
    Returns m, l: (B, H, 1); acc: (B, H, 1, D) — fp32.
    ``vary_axes``: shard_map axes the inputs vary over (VMA bookkeeping for
    the scan carry initializers).
    """
    b, lq, h, d = q.shape
    _, lk, kv, _ = k.shape
    g = h // kv
    scale = 1.0 / math.sqrt(d)
    chunk = min(chunk, lk)
    n_chunks = lk // chunk

    def body(carry, idx):
        m, l_sum, acc = carry
        k_c = jax.lax.dynamic_slice_in_dim(k, idx * chunk, chunk, axis=1)
        v_c = jax.lax.dynamic_slice_in_dim(v, idx * chunk, chunk, axis=1)
        if g > 1:
            k_c = jnp.repeat(k_c, g, axis=2)
            v_c = jnp.repeat(v_c, g, axis=2)
        key_pos = key_offset + idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bchd->bhqc", q, k_c,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where((key_pos <= pos)[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l_sum * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqc,bchd->bhqd", p.astype(v_c.dtype), v_c,
                        preferred_element_type=jnp.float32)
        return (m_new, l_new * 1.0, acc * corr[..., None] + pv), None

    m0 = jnp.full((b, h, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    a0 = jnp.zeros((b, h, lq, d), jnp.float32)
    if vary_axes:
        # shard_map tracks varying axes: the scan carry must vary like the
        # per-shard inputs it is combined with
        m0, l0, a0 = (jax.lax.pcast(t, tuple(vary_axes), to="varying")
                      for t in (m0, l0, a0))
    (m, l_sum, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      jnp.arange(n_chunks))
    return m, l_sum, acc


def _seqpar_flash_decode(q, cache_k, cache_v, pos, mesh, *, chunk: int):
    """Sequence-parallel decode attention (perf iteration D).

    The cache stays L-sharded over 'model'; each shard computes local flash
    statistics over its cache slice, and the shards merge with the online-
    softmax identity:  m* = pmax(m);  l* = Σ l·e^{m−m*};
    acc* = Σ acc·e^{m−m*}.  The only wire traffic is the tiny (B, H, 1[,D])
    statistics — the cache never moves.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as SH

    dp = SH.dp_axes(mesh)

    def body(q_blk, k_blk, v_blk):
        l_loc = k_blk.shape[1]
        offset = jax.lax.axis_index("model") * l_loc
        m, l_sum, acc = _decode_stats(q_blk, k_blk, v_blk, offset, pos,
                                      chunk,
                                      vary_axes=tuple(dp) + ("model",))
        m_g = jax.lax.pmax(m, "model")
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l_sum * corr, "model")
        acc_g = jax.lax.psum(acc * corr[..., None], "model")
        out = acc_g / jnp.maximum(l_g, 1e-20)[..., None]   # (B, H, 1, D)
        return out.transpose(0, 2, 1, 3).astype(q_blk.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None, None), P(dp, "model", None, None),
                  P(dp, "model", None, None)),
        out_specs=P(dp, None, None, None),
    )(q, cache_k, cache_v)


def ring_decode(p, x, cache_k, cache_v, pos, cfg, cos, sin, *, window: int):
    """Decode against a ring-buffer sliding-window cache of size W=window.

    Slot ``i`` holds the key written at absolute position
    p_i = pos - ((pos - i) mod W); entries with p_i < 0 are not yet written.
    RoPE is applied at write time with absolute positions, so scores are
    computed directly against the stored keys.
    """
    b = x.shape[0]
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // kv
    w = cache_k.shape[1]
    q, k, v = _project_qkv(p, x, cfg, cos, sin)
    slot = pos % w
    cache_k = _cache_write(cache_k, k, slot)
    cache_v = _cache_write(cache_v, v, slot)

    slots = jnp.arange(w)
    if jnp.ndim(pos) == 1:
        posb = pos[:, None]                        # (B, 1) per-slot positions
        key_pos = posb - jnp.mod(posb - slots[None], w)
        valid = (key_pos >= 0) & (key_pos > posb - window)   # (B, W)
        vmask = valid[:, None, None, None, :]
    else:
        key_pos = pos - jnp.mod(pos - slots, w)    # absolute position per slot
        valid = (key_pos >= 0) & (key_pos > pos - window)
        vmask = valid[None, None, None, None]

    qg = q.reshape(b, 1, kv, g, hd).astype(jnp.float32) / math.sqrt(hd)
    s = jnp.einsum("bqkgd,bwkd->bkgqw", qg, cache_k.astype(jnp.float32))
    if cfg.attn_logit_softcap:
        s = jnp.tanh(s / cfg.attn_logit_softcap) * cfg.attn_logit_softcap
    s = jnp.where(vmask, s, NEG_INF)
    pattn = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqw,bwkd->bqkgd", pattn, cache_v.astype(jnp.float32))
    o = o.reshape(b, 1, h * hd).astype(x.dtype)
    return L.linear(p["wo"], o), cache_k, cache_v


# ---------------------------------------------------------------------------
# cross-attention (whisper decoder): KV from the encoder, precomputed


def cross_attention_kv(p, enc_out, cfg):
    b, le, _ = enc_out.shape
    kv, hd = cfg.num_kv_heads, cfg.head_dim
    L.sow("kv_in", enc_out)
    k = L.linear(p["wk"], enc_out).reshape(b, le, kv, hd)
    v = L.linear(p["wv"], enc_out).reshape(b, le, kv, hd)
    return k, v


def cross_attention(p, x, k, v, cfg, *, chunk: int = 512):
    b, l, _ = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    L.sow("q_in", x)
    q = L.linear(p["wq"], x).reshape(b, l, h, hd)
    o = flash_attention(q, k, v, causal=False, chunk=chunk)
    o = o.reshape(b, l, -1)
    L.sow("o_in", o)
    return L.linear(p["wo"], o)


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (DeepSeek-V2) with compressed KV cache


def mla_init(key, cfg, dtype=jnp.float32):
    d, h = cfg.d_model, cfg.num_heads
    m = cfg.mla
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    p = {
        # q projection (dense — V2-Lite has no q-lora)
        "wq": L.linear_init(ks[0], d, h * qd, dtype=dtype),
        # compressed kv + shared rope key
        "wkv_a": L.linear_init(ks[1], d, m.kv_lora_rank + m.qk_rope_head_dim,
                               dtype=dtype),
        "kv_norm": L.norm_init(m.kv_lora_rank),
        # decompression: kv_lora -> per-head (nope key | value)
        "wk_b": L.linear_init(ks[2], m.kv_lora_rank, h * m.qk_nope_head_dim,
                              dtype=dtype),
        "wv_b": L.linear_init(ks[3], m.kv_lora_rank, h * m.v_head_dim,
                              dtype=dtype),
        "wo": L.linear_init(ks[4], h * m.v_head_dim, d, dtype=dtype,
                            scale=1.0 / math.sqrt(h * m.v_head_dim * 2 * cfg.num_layers)),
    }
    return p


def _mla_q(p, x, cfg, cos, sin):
    b, l, _ = x.shape
    h, m = cfg.num_heads, cfg.mla
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    L.sow("qkv_in", x)
    q = L.linear(p["wq"], x).reshape(b, l, h, qd)
    q_nope, q_rope = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_rope = L.apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_ckv(p, x, cfg, cos, sin):
    m = cfg.mla
    ckv = L.linear(p["wkv_a"], x)
    c, k_rope = ckv[..., : m.kv_lora_rank], ckv[..., m.kv_lora_rank:]
    c = L.apply_norm(p["kv_norm"], c, eps=cfg.norm_eps)
    k_rope = L.apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return c, k_rope  # (B, L, r), (B, L, rope_dim)


def mla_prefill(p, x, cfg, cos, sin, *, chunk: int = 512,
                return_cache: bool = False):
    """Expanded path: decompress per-token k/v, run flash attention (MHA)."""
    b, l, _ = x.shape
    h, m = cfg.num_heads, cfg.mla
    q_nope, q_rope = _mla_q(p, x, cfg, cos, sin)
    c, k_rope = _mla_ckv(p, x, cfg, cos, sin)
    L.sow("kvb_in", c)
    k_nope = L.linear(p["wk_b"], c).reshape(b, l, h, m.qk_nope_head_dim)
    v = L.linear(p["wv_b"], c).reshape(b, l, h, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, l, h, m.qk_rope_head_dim))], -1)
    # pad v to qk head dim so flash can run on one tensor, then slice
    o = flash_attention(q, k, _pad_last(v, q.shape[-1]), causal=True,
                        chunk=chunk)[..., : m.v_head_dim]
    o = o.reshape(b, l, -1)
    L.sow("o_in", o)
    out = L.linear(p["wo"], o)
    if return_cache:
        return out, (c, k_rope)
    return out


def _pad_last(x, to):
    pad = to - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def _mla_absorbed_attend(p, q_nope, q_rope, cache_c, cache_kr, q_pos, cfg):
    """Attend against the compressed cache with W_uk/W_uv absorbed.

    q_nope/q_rope: (B, Lq, H, ·); caches (B, Lmax, r / rope_dim).  ``q_pos``
    is (1|B, Lq) absolute query positions — (1, 1) for classic decode,
    (B, 1) for per-slot decode, (1, Lq) for chunked prefill.  The W_uk
    absorption folds key decompression into the query; W_uv absorption
    folds value decompression into the output projection — FLOPs scale
    with r, not h*head_dim, and the cache stays compressed (the whole
    point of MLA).  Returns (B, Lq, H, v_head_dim) fp32.
    """
    h, m = cfg.num_heads, cfg.mla
    r = m.kv_lora_rank
    wk_b = p["wk_b"]["w"] if "w" in p["wk_b"] else p["wk_b"]["v"] @ p["wk_b"]["u"]
    wk_b = wk_b.reshape(r, h, m.qk_nope_head_dim)
    q_eff = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(jnp.float32),
                       wk_b.astype(jnp.float32))     # absorb W_uk
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = (jnp.einsum("bqhr,blr->bhql", q_eff, cache_c.astype(jnp.float32))
         + jnp.einsum("bqhd,bld->bhql", q_rope.astype(jnp.float32),
                      cache_kr.astype(jnp.float32))) * scale
    valid = jnp.arange(cache_c.shape[1])[None, None] <= q_pos[..., None]
    s = jnp.where(valid[:, None], s, NEG_INF)        # (1|B, 1, Lq, Lmax)
    pattn = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhql,blr->bqhr", pattn, cache_c.astype(jnp.float32))
    wv_b = p["wv_b"]["w"] if "w" in p["wv_b"] else p["wv_b"]["v"] @ p["wv_b"]["u"]
    wv_b = wv_b.reshape(r, h, m.v_head_dim)
    return jnp.einsum("bqhr,rhd->bqhd", ctx, wv_b.astype(jnp.float32))


def mla_decode(p, x, cache_c, cache_kr, pos, cfg, cos, sin):
    """Absorbed decode: score directly against the compressed cache.

    cache_c: (B, Lmax, r); cache_kr: (B, Lmax, rope_dim); x: (B, 1, d);
    pos is a scalar or a per-slot (B,) vector.
    """
    b, _, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, cfg, cos, sin)     # (B,1,H,nope/rope)
    c_t, kr_t = _mla_ckv(p, x, cfg, cos, sin)
    cache_c = _cache_write(cache_c, c_t, pos)
    cache_kr = _cache_write(cache_kr, kr_t, pos)
    q_pos = (pos[:, None] if jnp.ndim(pos) == 1
             else jnp.asarray(pos)[None, None])
    o = _mla_absorbed_attend(p, q_nope, q_rope, cache_c, cache_kr, q_pos, cfg)
    out = L.linear(p["wo"], o.reshape(b, 1, -1).astype(x.dtype))
    return out, cache_c, cache_kr


def mla_prefill_cached(p, x, cache_c, cache_kr, start, cfg, cos, sin):
    """Chunked prefill for MLA: write this chunk's compressed kv into the
    cache at ``start``, then run the absorbed path against the whole cache
    (unwritten future positions causally masked)."""
    b, l, _ = x.shape
    q_nope, q_rope = _mla_q(p, x, cfg, cos, sin)
    c, kr = _mla_ckv(p, x, cfg, cos, sin)
    cache_c = jax.lax.dynamic_update_slice_in_dim(
        cache_c, c.astype(cache_c.dtype), start, axis=1)
    cache_kr = jax.lax.dynamic_update_slice_in_dim(
        cache_kr, kr.astype(cache_kr.dtype), start, axis=1)
    q_pos = (start + jnp.arange(l))[None]             # (1, Lq)
    o = _mla_absorbed_attend(p, q_nope, q_rope, cache_c, cache_kr, q_pos, cfg)
    out = L.linear(p["wo"], o.reshape(b, l, -1).astype(x.dtype))
    return out, cache_c, cache_kr


# ---------------------------------------------------------------------------
# factorized latent KV cache (AA-SVD serving path)
#
# When the k/v projections are factorized (w = v @ u, bias-free), the
# per-token cache state the model actually needs is the rank-r latent
# l = x @ v — the MLA trick applied to ordinary GQA.  Decode stores only
# (B, r_k, Lmax) + (B, r_v, Lmax) and the flash-decode kernel up-projects
# keys in-kernel (U_k) while keeping the value accumulator in latent space
# (U_v applied once per head in the epilogue), so the compression ratio
# shows up directly as cache bytes AND decode FLOPs.


def latent_ranks(p):
    """(rank_k, rank_v) when BOTH k/v projections are bias-free factorized
    pairs — the layout the latent KV cache requires; else ``None``.

    Works on plain and scan-stacked (leading (n,) axis) param leaves.
    """
    def rank(w):
        if isinstance(w, dict) and "w" not in w and "b" not in w and "u" in w:
            return int(w["v"].shape[-1])
        return None
    if not isinstance(p, dict):
        return None
    rk, rv = rank(p.get("wk")), rank(p.get("wv"))
    if rk is None or rv is None:
        return None
    return rk, rv


def _latent_kv(p, x):
    """Down-projected kv latents x @ V — the only per-token state the
    factorized cache stores; U is applied inside the decode kernel."""
    lk = x @ p["wk"]["v"].astype(x.dtype)
    lv = x @ p["wv"]["v"].astype(x.dtype)
    return lk, lv


def gqa_prefill_latent(p, x, cache_lk, cache_lv, start, cfg, cos, sin, *,
                       theta: float, rope: bool = True, chunk: int = 1024):
    """Prefill into the rank-major latent cache (B, r, Lmax): write this
    chunk's rank-r latents at ``start``, up-project the whole cache once,
    and flash-attend with absolute-position masking.  Used for whole
    prompts (start=0) and for chunked prefill alike."""
    b, l, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = L.linear(p["wq"], x).reshape(b, l, h, hd)
    if rope:
        q = L.apply_rope(q, cos, sin)
    lk_c, lv_c = _latent_kv(p, x)
    cache_lk = jax.lax.dynamic_update_slice_in_dim(
        cache_lk, jnp.swapaxes(lk_c, 1, 2).astype(cache_lk.dtype), start,
        axis=2)
    cache_lv = jax.lax.dynamic_update_slice_in_dim(
        cache_lv, jnp.swapaxes(lv_c, 1, 2).astype(cache_lv.dtype), start,
        axis=2)
    lmax = cache_lk.shape[2]
    k_all = jnp.einsum("brl,rn->bln", cache_lk,
                       p["wk"]["u"].astype(cache_lk.dtype)
                       ).reshape(b, lmax, kv, hd)
    v_all = jnp.einsum("brl,rn->bln", cache_lv,
                       p["wv"]["u"].astype(cache_lv.dtype)
                       ).reshape(b, lmax, kv, hd)
    if rope:
        cos_all, sin_all = L.rope_table(jnp.arange(lmax), hd, theta)
        k_all = L.apply_rope(k_all, cos_all, sin_all)
    o = flash_attention(q, k_all, v_all, causal=True, q_offset=start,
                        chunk=chunk)
    return (L.linear(p["wo"], o.reshape(b, l, -1)), cache_lk, cache_lv)


def gqa_decode_latent(p, x, cache_lk, cache_lv, pos, cfg, cos, sin, *,
                      theta: float, rope: bool = True, layer=None):
    """One-token decode against the factorized latent cache.

    x: (B, 1, d); caches rank-major (B, r_k/r_v, Lmax), or a scanned
    stage's stacked (n, B, r_k/r_v, Lmax) with ``layer`` the index of this
    layer (written and read where it lies, never sliced out); pos scalar
    or per-slot (B,).  Dispatches to ``kernels.ops.flash_decode`` (Pallas
    on TPU, reference einsums elsewhere), which writes this token's
    latents at pos and attends with per-slot lengths = pos + 1.
    """
    from repro.kernels import ops as KO
    b = x.shape[0]
    h, hd = cfg.num_heads, cfg.head_dim
    q = L.linear(p["wq"], x).reshape(b, 1, h, hd)
    if rope:
        q = L.apply_rope(q, cos, sin)
    lk_t, lv_t = _latent_kv(p, x)
    lengths = jnp.broadcast_to(jnp.asarray(pos) + 1, (b,)).astype(jnp.int32)
    lmax = cache_lk.shape[-1]
    cos_all, sin_all = L.rope_table(jnp.arange(lmax), hd, theta)
    o, cache_lk, cache_lv = KO.flash_decode(
        q[:, 0], cache_lk, cache_lv, p["wk"]["u"], p["wv"]["u"], lengths,
        cos_all, sin_all, lk_t[:, 0], lv_t[:, 0], layer=layer, rope=rope)
    return (L.linear(p["wo"], o.reshape(b, 1, h * hd).astype(x.dtype)),
            cache_lk, cache_lv)
