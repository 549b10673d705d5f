"""Streaming covariance accumulation kernel: the AA-SVD calibration hot-spot.

Computes, in ONE pass over the token stream (sharing every X / X' load):

    xx   = Xᵀ X      xxp  = Xᵀ X'      xpxp = X'ᵀ X'

for X, X' of shape (T, n).  XLA would emit three separate GEMMs (3× HBM
reads of X/X'); here each (bt × bi/bj) tile is loaded once per output tile
and feeds up to three MXU contractions with fp32 accumulation in VMEM.

    grid = (n/bi, n/bj, T/bt)    dimension_semantics = (parallel, parallel,
                                                        arbitrary)

Output blocks are revisited across the sequential T dimension and
accumulated in-place (initialized at t == 0).

Call sites go through ``kernels.ops.cov_accum`` (dense (T, n) taps) and
``kernels.ops.cov_accum_banked`` (expert banks: this kernel vmapped over
the leading (E, C, n) expert axis), which handle backend dispatch and
block-multiple padding; ``core.calibration.update_covs`` routes every
calibration accumulation through those wrappers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_i, x_j, xp_i, xp_j, xx, xxp, xpxp):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        xx[...] = jnp.zeros_like(xx)
        xxp[...] = jnp.zeros_like(xxp)
        xpxp[...] = jnp.zeros_like(xpxp)

    xi = x_i[...]
    xpj = xp_j[...]
    xx[...] += jax.lax.dot_general(
        xi, x_j[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    xxp[...] += jax.lax.dot_general(
        xi, xpj, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    xpxp[...] += jax.lax.dot_general(
        xp_i[...], xpj, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("bi", "bt", "interpret"))
def cov_accum(x, xp, *, bi: int = 256, bt: int = 512,
              interpret: bool = False):
    """x, xp: (T, n) -> (xx, xxp, xpxp) each (n, n) fp32.

    T must divide by bt and n by bi (pad tokens with zero rows — they add
    zero outer products, so padding is exact).
    """
    t_dim, n = x.shape
    bi = min(bi, n)
    bt = min(bt, t_dim)
    assert t_dim % bt == 0 and n % bi == 0, (t_dim, n, bt, bi)
    grid = (n // bi, n // bi, t_dim // bt)

    out = jax.ShapeDtypeStruct((n, n), jnp.float32)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bi), lambda i, j, t: (t, i)),
            pl.BlockSpec((bt, bi), lambda i, j, t: (t, j)),
            pl.BlockSpec((bt, bi), lambda i, j, t: (t, i)),
            pl.BlockSpec((bt, bi), lambda i, j, t: (t, j)),
        ],
        out_specs=[
            pl.BlockSpec((bi, bi), lambda i, j, t: (i, j)),
            pl.BlockSpec((bi, bi), lambda i, j, t: (i, j)),
            pl.BlockSpec((bi, bi), lambda i, j, t: (i, j)),
        ],
        out_shape=[out, out, out],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, x, xp, xp)
