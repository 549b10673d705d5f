"""Fused low-rank-KV flash-decode Pallas kernel (AA-SVD serving path).

One decode step against the *factorized* KV cache: the cache holds only the
rank-r latents  l_k = x @ V_k  and  l_v = x @ V_v  per token, and this
kernel fuses the up-projection with blockwise online-softmax attention:

* **key side** — each (bk, r_k) latent block is up-projected in-kernel
  (``l_k @ U_k`` per KV head) and RoPE'd at its absolute positions before
  scoring.  RoPE's rotate-half pairing is tied to the TRUE head dim, so the
  rotation happens here, on unpadded (bk, D) tiles — it cannot be absorbed
  into U_k.
* **value side** — the up-projection IS absorbed: the accumulator stays in
  latent space, acc (H, r_v) += p @ l_v, and U_v is applied once per head
  in the epilogue.  Per step this costs H·L·r_v + H·r_v·D instead of
  L·r_v·KV·D + H·L·D — the compression ratio converts into decode FLOPs,
  not just cache bytes (the MLA absorption trick applied to ordinary GQA).

Per-slot ``lengths`` (continuous batching: every sequence sits at its own
position) mask key blocks past each slot's live prefix.

    grid = (B, L/bk)      dimension_semantics = (parallel, arbitrary)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(scale: float, use_rope: bool, kv: int, g: int, d: int, bk: int,
            len_ref, q_ref, lk_ref, lv_ref, uk_ref, uv_ref, cos_ref, sin_ref,
            o_ref, m_ref, l_ref, acc_ref):
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    lkb = lk_ref[0].astype(jnp.float32)                       # (bk, r_k)
    half = d // 2
    rows = []
    for kvh in range(kv):
        # in-kernel key up-projection for this KV head: (bk, r_k) @ (r_k, D)
        k_h = jax.lax.dot_general(
            lkb, uk_ref[kvh].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (bk, D)
        if use_rope:
            c, s_ = cos_ref[...], sin_ref[...]                # (bk, D/2)
            k1, k2 = k_h[:, :half], k_h[:, half:]
            k_h = jnp.concatenate([k1 * c - k2 * s_, k2 * c + k1 * s_],
                                  axis=1)
        qg = q_ref[0, kvh * g:(kvh + 1) * g].astype(jnp.float32) * scale
        rows.append(jax.lax.dot_general(
            qg, k_h, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))              # (g, bk)
    s = jnp.concatenate(rows, axis=0) if kv > 1 else rows[0]  # (H, bk)
    key_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(key_pos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    # value absorption: accumulate p @ l_v in LATENT space — (H, r_v)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, lv_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == n_j - 1)
    def _finish():
        ctx = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)   # (H, r_v)
        for kvh in range(kv):
            og = jax.lax.dot_general(
                ctx[kvh * g:(kvh + 1) * g],
                uv_ref[kvh].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # (g, D)
            o_ref[0, kvh * g:(kvh + 1) * g] = og.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("use_rope", "bk", "interpret"))
def flash_decode(q, lk, lv, uk, uv, lengths, cos, sin, *,
                 use_rope: bool = True, bk: int = 256,
                 interpret: bool = False):
    """q: (B, H, D); lk/lv: (B, L, r_k / r_v); uk/uv: (KV, r_k/r_v, D);
    lengths: (B,) int32 live prefix per slot; cos/sin: (L, D//2) rope
    tables at absolute positions.  Returns (B, H, D) in q.dtype.

    L must be a bk multiple (the ops wrapper pads; padded positions are
    masked by ``lengths``).  RoPE slices at the true head dim, so D is NOT
    padded — unaligned head dims are legal (lane-padded implicitly).
    """
    b, h, d = q.shape
    _, l, rk = lk.shape
    rv = lv.shape[-1]
    kv = uk.shape[0]
    g = h // kv
    bk = min(bk, l)
    assert l % bk == 0 and h == kv * g
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_kernel, scale, use_rope, kv, g, d, bk)
    half = max(d // 2, 1)

    return pl.pallas_call(
        kernel,
        grid=(b, l // bk),
        in_specs=[
            # whole (B,) lengths vector in SMEM: a per-slot (1,) block
            # would break the TPU's (8, 128) block-tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bk, rk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, bk, rv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((kv, rk, d), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((kv, rv, d), lambda i, j: (0, 0, 0)),
            pl.BlockSpec((bk, half), lambda i, j: (j, 0)),
            pl.BlockSpec((bk, half), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rv), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lengths.astype(jnp.int32), q, lk, lv, uk, uv, cos, sin)
