"""Grouped (ragged) expert GEMM: y[i] = x[i] @ W[g(i)] over sorted segments.

The drop-free MoE dispatch lays all T·k routed choices out as rows sorted
by expert id, so expert e owns the contiguous row segment
[offs[e], offs[e+1]).  This kernel is a megablox-style grouped matmul over
that ragged layout: the row dimension is tiled into bm blocks, and each
grid step processes one (row block × expert) intersection so a block that
straddles a segment boundary is visited once per expert it touches:

    num_tiles = M/bm + E - 1            (static upper bound; the remainder
                                         are no-op sentinel tiles)
    grid = (f/bf, num_tiles)            dimension_semantics = (parallel,
                                         arbitrary)

Tile metadata (which expert, which row block, first-visit flag, segment
offsets) is computed from ``group_sizes`` at trace time and handed to the
kernel through scalar prefetch (``PrefetchScalarGridSpec``), so the weight
BlockSpec can follow ``W[group[t]]`` while the grid itself stays static.
Rows outside the tile's segment are masked to zero via a 2D
``broadcasted_iota`` row-index compare (TPU has no 1D iota); revisits
accumulate into the resident output block (consecutive inner-grid steps
share the same output index, so the block never round-trips HBM between
visits).  The contraction dim d is NOT tiled — expert GEMMs are activation
rows against a (d, bf) weight slab, and d fits VMEM at every assigned
arch's d_model/d_ff.

Accumulation is fp32 (``preferred_element_type``); the output is fp32 and
the ops wrapper casts.  Padding contract (enforced by ``ops.grouped_matmul``):
rows padded to bm, d and f lane-padded to 128/bf — padded rows belong to no
segment and every real block contains at least one real row, so masking
keeps all outputs exact.  sum(group_sizes) must equal the unpadded row
count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _metadata(group_sizes, m_pad: int, bm: int, num_tiles: int):
    """Per-tile scalars from the traced group sizes.

    Returns (tile_group, tile_rowblock, tile_first, offs) where offs has
    E + 2 entries: the E segment starts, the total row count M (start of
    the empty sentinel segment), and M again (its end).  Tiles beyond the
    groups' actual block coverage are assigned to the sentinel group E —
    their row mask is empty, so they accumulate exact zeros into the last
    (already-initialized) row block.
    """
    e = group_sizes.shape[0]
    i32 = jnp.int32
    sizes = group_sizes.astype(i32)
    offs = jnp.concatenate(
        [jnp.zeros((1,), i32), jnp.cumsum(sizes, dtype=i32)])    # (E+1,)
    offs = jnp.concatenate([offs, offs[-1:]])                    # (E+2,)
    m_tiles = m_pad // bm
    first_blk = offs[:e] // bm
    last_blk = jnp.where(sizes > 0, (offs[1:e + 1] - 1) // bm, first_blk)
    tiles_per = jnp.where(sizes > 0, last_blk - first_blk + 1, 0)  # (E,)
    pad_tiles = num_tiles - jnp.sum(tiles_per)
    counts = jnp.concatenate([tiles_per, pad_tiles[None]])         # (E+1,)
    gids = jnp.arange(e + 1, dtype=i32)
    tile_group = jnp.repeat(gids, counts, total_repeat_length=num_tiles)
    cum = jnp.concatenate([jnp.zeros((1,), i32),
                           jnp.cumsum(counts, dtype=i32)])
    within = jnp.arange(num_tiles, dtype=i32) - cum[tile_group]
    first_all = jnp.concatenate(
        [first_blk, jnp.full((1,), m_tiles - 1, i32)])
    tile_rowblock = jnp.minimum(first_all[tile_group] + within, m_tiles - 1)
    tile_first = jnp.concatenate(
        [jnp.ones((1,), i32),
         (tile_rowblock[1:] != tile_rowblock[:-1]).astype(i32)])
    return tile_group, tile_rowblock, tile_first, offs


def _kernel(bm: int, e: int,
            group_ref, rowblock_ref, first_ref, offs_ref,
            x_ref, w_ref, o_ref):
    t = pl.program_id(1)
    g = group_ref[t]
    start = offs_ref[g]
    end = offs_ref[g + 1]
    rows = rowblock_ref[t] * bm \
        + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    x = jnp.where((rows >= start) & (rows < end), x_ref[...], 0)
    prod = jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)

    @pl.when(first_ref[t] == 1)
    def _init():
        o_ref[...] = prod

    @pl.when(first_ref[t] == 0)
    def _accum():
        o_ref[...] += prod


@functools.partial(jax.jit, static_argnames=("bm", "bf", "interpret"))
def grouped_matmul(x, w, group_sizes, *, bm: int = 128, bf: int = 256,
                   interpret: bool = False):
    """x: (M, d) rows sorted by group; w: (E, d, f); group_sizes: (E,)
    int32 with sum == the real row count -> (M, f) fp32.

    M must be divisible by bm, f by bf, and d lane-aligned (128) — the ops
    wrapper pads (zero rows belong to no segment; zero d/f columns are
    exact no-ops) and slices back.
    """
    m, d = x.shape
    e, _, f = w.shape
    bm, bf = min(bm, m), min(bf, f)
    assert m % bm == 0 and f % bf == 0, (
        f"shape ({m},{d},{f}) not divisible by blocks ({bm},{bf})")
    num_tiles = m // bm + e - 1
    meta = _metadata(group_sizes, m, bm, num_tiles)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(f // bf, num_tiles),
        in_specs=[
            pl.BlockSpec((bm, d),
                         lambda j, t, gr, rb, fr, of: (rb[t], 0)),
            pl.BlockSpec((1, d, bf),
                         lambda j, t, gr, rb, fr, of:
                         (jnp.minimum(gr[t], e - 1), 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf),
                               lambda j, t, gr, rb, fr, of: (rb[t], j)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, bm, e),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, f), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*meta, x, w)
