"""Fused factorized matmul: y = (x @ V) @ U  — the AA-SVD inference GEMM.

A naive XLA lowering round-trips the rank-k intermediate t = x @ V through
HBM (2·T·k bytes of traffic).  This kernel keeps t resident in VMEM and
phase-fuses the two GEMMs into one sequential grid:

    grid = (T/bt, n/bn + m/bm)     dimension_semantics = (parallel, arbitrary)

    phase A (j < n/bn):   t  += x[i, j] @ V[j]        (accumulate in VMEM)
    phase B (j >= n/bn):  y[i, j'] = t @ U[j']        (stream U tiles)

VMEM working set: x tile (bt × bn) + V tile (bn × k) + t scratch (bt × k,
fp32) + U tile (k × bm) + y tile (bt × bm) — all 128-aligned.  k is padded
to a lane multiple by the ops wrapper.

The epilogue fuses too: an optional bias (1, m) and/or residual (T, m) are
added inside phase B while the y tile is still in VMEM, so ``y = x@V@U + b
+ r`` is a single kernel instead of kernel + separate XLA adds (which
would re-stream the (T, m) output through HBM once per addend).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(n_steps: int, has_bias: bool, has_res: bool, *refs):
    it = iter(refs)
    x_ref, v_ref, u_ref = next(it), next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_res else None
    y_ref, t_ref = next(it), next(it)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(j < n_steps)
    def _phase_a():
        t_ref[...] += jnp.dot(x_ref[...], v_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(j >= n_steps)
    def _phase_b():
        y = jnp.dot(t_ref[...].astype(u_ref.dtype), u_ref[...],
                    preferred_element_type=jnp.float32)
        if b_ref is not None:
            y = y + b_ref[...].astype(jnp.float32)
        if r_ref is not None:
            y = y + r_ref[...].astype(jnp.float32)
        y_ref[...] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "bn", "bm", "interpret"))
def lowrank_matmul(x, v, u, bias=None, residual=None, *, bt: int = 256,
                   bn: int = 512, bm: int = 512, interpret: bool = False):
    """x: (T, n); v: (n, k); u: (k, m) -> (T, m).

    T, n, m must be divisible by (bt, bn, bm); k should be a multiple of 128
    (pad factors with zeros — zero rank columns are exact no-ops).  Optional
    fused epilogue: ``bias`` (1, m) and/or ``residual`` (T, m) are added to
    the output inside phase B.
    """
    t_dim, n = x.shape
    k = v.shape[1]
    m = u.shape[1]
    bt, bn, bm = min(bt, t_dim), min(bn, n), min(bm, m)
    assert t_dim % bt == 0 and n % bn == 0 and m % bm == 0, (
        f"shape ({t_dim},{n},{m}) not divisible by blocks ({bt},{bn},{bm})")
    n_steps = n // bn
    m_steps = m // bm

    grid = (t_dim // bt, n_steps + m_steps)
    kernel = functools.partial(_kernel, n_steps,
                               bias is not None, residual is not None)
    in_specs = [
        pl.BlockSpec((bt, bn),
                     lambda i, j: (i, jnp.minimum(j, n_steps - 1))),
        pl.BlockSpec((bn, k),
                     lambda i, j: (jnp.minimum(j, n_steps - 1), 0)),
        pl.BlockSpec((k, bm),
                     lambda i, j: (0, jnp.maximum(j - n_steps, 0))),
    ]
    inputs = [x, v, u]
    if bias is not None:
        assert bias.shape == (1, m), bias.shape
        in_specs.append(pl.BlockSpec(
            (1, bm), lambda i, j: (0, jnp.maximum(j - n_steps, 0))))
        inputs.append(bias)
    if residual is not None:
        assert residual.shape == (t_dim, m), residual.shape
        in_specs.append(pl.BlockSpec(
            (bt, bm), lambda i, j: (i, jnp.maximum(j - n_steps, 0))))
        inputs.append(residual)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bt, bm),
                               lambda i, j: (i, jnp.maximum(j - n_steps, 0))),
        out_shape=jax.ShapeDtypeStruct((t_dim, m), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, k), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*inputs)
