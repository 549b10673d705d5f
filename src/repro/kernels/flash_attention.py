"""Blockwise online-softmax attention (flash) Pallas kernel.

One (batch·head, q-block) program iterates sequentially over KV blocks with
running (max, denom, acc) statistics in VMEM — the same recurrence as the
pure-JAX portable path in ``repro.models.attention`` (its oracle).  Causal
and sliding-window masks are applied from absolute block offsets; GQA is
handled by mapping the q-head index to its KV head in the BlockSpec index
maps, so KV tiles are fetched once per group.

    grid = (B·H, Lq/bq, Lk/bk)   dimension_semantics = (parallel, parallel,
                                                        arbitrary)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(scale: float, causal: bool, window: int, lk_valid: int,
            bq: int, bk: int,
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    kv_idx = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kv_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale                  # (bq, d)
    k = k_ref[0].astype(jnp.float32)                          # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = kv_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    if lk_valid:
        # keys past the true sequence length are wrapper padding — without
        # this mask a zero-padded key scores 0 > NEG_INF and soaks up
        # softmax weight on every real row
        mask &= k_pos < lk_valid
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kv_idx == n_kv - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "lk_valid",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    lk_valid: int = 0,
                    bq: int = 256, bk: int = 256, interpret: bool = False):
    """q: (B, H, Lq, D); k/v: (B, KV, Lk, D) -> (B, H, Lq, D).

    ``lk_valid`` (static, 0 = all): the true key length when Lk carries
    wrapper padding — key positions >= lk_valid are masked out.
    """
    b, h, lq, d = q.shape
    _, kv, lk, _ = k.shape
    g = h // kv
    bq = min(bq, lq)
    bk = min(bk, lk)
    assert lq % bq == 0 and lk % bk == 0
    scale = 1.0 / math.sqrt(d)

    qf = q.reshape(b * h, lq, d)
    grid = (b * h, lq // bq, lk // bk)
    kernel = functools.partial(_kernel, scale, causal, window, lk_valid,
                               bq, bk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh // g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, k.reshape(b * kv, lk, d), v.reshape(b * kv, lk, d))
    return out.reshape(b, h, lq, d)
