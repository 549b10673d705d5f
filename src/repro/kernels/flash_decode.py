"""Fused low-rank-KV flash-decode Pallas kernel (AA-SVD serving path).

One decode step against the *factorized* KV cache: the cache holds only the
rank-r latents  l_k = x @ V_k  and  l_v = x @ V_v  per token, and this
kernel fuses the up-projection with blockwise online-softmax attention:

* **key side** — each (r_k, bk) latent block is up-projected in-kernel
  (``U_kᵀ @ l_k`` per KV head) and RoPE'd at its absolute positions before
  scoring.  RoPE's rotate-half pairing is tied to the TRUE head dim, so the
  rotation happens here, on unpadded (D, bk) tiles — it cannot be absorbed
  into U_k.
* **value side** — the up-projection IS absorbed: the accumulator stays in
  latent space, acc (H, r_v) += p @ l_vᵀ, and U_v is applied once per head
  in the epilogue.  Per step this costs H·L·r_v + H·r_v·D instead of
  L·r_v·KV·D + H·L·D — the compression ratio converts into decode FLOPs,
  not just cache bytes (the MLA absorption trick applied to ordinary GQA).

Per-slot ``lengths`` (continuous batching: every sequence sits at its own
position) mask key blocks past each slot's live prefix.  The step's own
token sits at ``lengths - 1``: the kernel writes its latents there, into
the block it reads anyway, and returns the caches (aliased, so the write
is in place) — a write from outside would have XLA pick the cache layout
for a column scatter and copy the cache for the kernel.

The cache is rank-major, ``(B, r, L)``: that is how the TPU lays out a
token-major ``(B, L, r)`` array whose rank is off the 128-lane grid (L
minor), and a Pallas operand must be row-major, so a token-major cache
would be transposed into a copy on every call.  Rank-major, the kernel
reads it where it lies, unpadded: a block spanning the whole rank is legal
however unaligned.  The latents may also carry a leading layer axis,
``(n_layers, B, r, L)``: a scanned stage keeps every layer's cache in one
stacked buffer, and the kernel reads layer ``layer`` through the latent
BlockSpecs' ``index_map`` (the index is a scalar-prefetch operand beside
``lengths``), so the decode step never copies a layer out.

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


def _kernel(scale: float, use_rope: bool, kv: int, g: int, d: int,
            layer_ref, len_ref, q_ref, lk_ref, lv_ref, kn_ref, vn_ref,
            ukt_ref, uv_ref, cos_ref, sin_ref, o_ref, lko_ref, lvo_ref,
            m_ref, l_ref, acc_ref):
    del layer_ref                        # read by the latents' index_map
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[pl.program_id(0)]
    bk = lk_ref.shape[-1]
    # this step's token, at length - 1, goes into the block that holds it
    # (the output blocks sit there for the whole row of the grid)
    off = length - 1 - j * bk
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) == off
    lkw = jnp.where(col, kn_ref[0], lk_ref[0, 0])             # (r_k, bk)
    lvw = jnp.where(col, vn_ref[0], lv_ref[0, 0])             # (r_v, bk)

    @pl.when((off >= 0) & (off < bk))
    def _write():
        lko_ref[0, 0] = lkw
        lvo_ref[0, 0] = lvw

    lkb = lkw.astype(jnp.float32)
    half = d // 2
    rows = []
    for kvh in range(kv):
        # in-kernel key up-projection for this KV head: (D, r_k) @ (r_k, bk)
        k_h = jax.lax.dot_general(
            ukt_ref[kvh].astype(jnp.float32), lkb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (D, bk)
        if use_rope:
            c, s_ = cos_ref[...], sin_ref[...]                # (D/2, bk)
            k1, k2 = k_h[:half], k_h[half:]
            k_h = jnp.concatenate([k1 * c - k2 * s_, k2 * c + k1 * s_],
                                  axis=0)
        qg = q_ref[0, kvh * g:(kvh + 1) * g].astype(jnp.float32) * scale
        rows.append(jax.lax.dot_general(
            qg, k_h, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))              # (g, bk)
    s = jnp.concatenate(rows, axis=0) if kv > 1 else rows[0]  # (H, bk)
    key_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(key_pos < length, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    # value absorption: accumulate p @ l_vᵀ in LATENT space — (H, r_v)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, lvw.astype(jnp.float32), (((1,), (1,)), ((), ())),
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
def flash_decode(q, lk, lv, uk, uv, lengths, cos, sin, lk_new, lv_new,
                 layer=None, *, use_rope: bool = True, bk: int = 256,
                 interpret: bool = False):
    """q: (B, H, D); lk/lv: rank-major latents (B, r_k / r_v, L), or a
    stacked (n_layers, B, r_k / r_v, L) with ``layer`` the (traced) index
    of the layer to read; uk/uv: (KV, r_k/r_v, D); lengths: (B,) int32
    live prefix per slot; cos/sin: (L, D//2) rope tables at absolute
    positions; lk_new/lv_new: (B, r_k / r_v) latents of the step's token,
    written at position lengths - 1 before it is attended.  Returns (out
    (B, H, D) in q.dtype, lk, lv), the caches updated in place.

    L must be a bk multiple (the ops wrapper pads, or clamps bk to L;
    padded positions are masked by ``lengths``).  Neither the ranks nor
    the head dim need lane alignment: their blocks span the whole dim.
    RoPE slices at the true head dim.
    """
    stacked = lk.ndim == 4
    if not stacked:
        lk, lv = lk[None], lv[None]
    layer = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                    jnp.int32), (1,))
    b, h, d = q.shape
    _, _, rk, l = lk.shape
    rv = lv.shape[2]
    kv = uk.shape[0]
    g = h // kv
    bk = min(bk, l)
    assert l % bk == 0 and h == kv * g
    scale = 1.0 / math.sqrt(d)
    kernel = functools.partial(_kernel, scale, use_rope, kv, g, d)
    half = max(d // 2, 1)
    # the small operands take the kernel's orientation here: keys come
    # out (D, bk), so U_k goes in as (KV, D, r_k) and the rope tables as
    # (D/2, L); the new latents as (B, r, 1) columns
    ukt = jnp.swapaxes(uk, 1, 2)
    kn = lk_new.astype(lk.dtype)[:, :, None]
    vn = lv_new.astype(lv.dtype)[:, :, None]

    def block(i, j, ly, ln):
        return (ly[0], i, 0, j)

    def written(i, j, ly, ln):           # the block that holds lengths - 1
        return (ly[0], i, 0, (ln[i] - 1) // bk)

    # index maps take the grid indices, then the scalar-prefetch refs
    # (layer, lengths); the whole (B,) lengths vector rides in SMEM, since
    # a per-slot (1,) block would break the TPU's (8, 128) tiling rule
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, l // bk),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i, j, ly, ln: (i, 0, 0)),
            pl.BlockSpec((1, 1, rk, bk), block),
            pl.BlockSpec((1, 1, rv, bk), block),
            pl.BlockSpec((1, rk, 1), lambda i, j, ly, ln: (i, 0, 0)),
            pl.BlockSpec((1, rv, 1), lambda i, j, ly, ln: (i, 0, 0)),
            pl.BlockSpec((kv, d, rk), lambda i, j, ly, ln: (0, 0, 0)),
            pl.BlockSpec((kv, rv, d), lambda i, j, ly, ln: (0, 0, 0)),
            pl.BlockSpec((half, bk), lambda i, j, ly, ln: (0, j)),
            pl.BlockSpec((half, bk), lambda i, j, ly, ln: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, h, d), lambda i, j, ly, ln: (i, 0, 0)),
            pl.BlockSpec((1, 1, rk, bk), written),
            pl.BlockSpec((1, 1, rv, bk), written),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, rv), jnp.float32),
        ],
    )
    out, lk, lv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((b, h, d), q.dtype),
                   jax.ShapeDtypeStruct(lk.shape, lk.dtype),
                   jax.ShapeDtypeStruct(lv.shape, lv.dtype)),
        # operand indices count the two scalar-prefetch operands
        input_output_aliases={3: 1, 4: 2},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(layer, lengths.astype(jnp.int32), q, lk, lv, kn, vn, ukt, uv, cos.T,
      sin.T)
    if not stacked:
        lk, lv = lk[0], lv[0]
    return out, lk, lv
