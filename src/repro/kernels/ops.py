"""Jit'd dispatch wrappers: Pallas on TPU, interpret/XLA fallback elsewhere.

``use_pallas()`` decides per-backend: real Mosaic lowering on TPU, the
pure-jnp reference on CPU/GPU (tests exercise the kernels explicitly with
``force_pallas=True, interpret=True``).  All wrappers pad shapes to kernel
block multiples and slice back, so call sites never worry about alignment;
block shapes come from ``kernels.autotune`` (measured on TPU, deterministic
heuristic elsewhere) instead of hand-picked constants.

Under a data-parallel ``mesh`` the cov wrappers stay on the fused Pallas
single-pass kernel: the call is wrapped in ``shard_map`` over the mesh's
data axes, so each DP worker runs the kernel on its local token shard and
one ``psum`` per triple combines the partial products — no fallback to the
XLA einsum, which cost an extra read of x/x' per covariance term.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import autotune, ref
from repro.kernels.cov_accum import cov_accum as _cov_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.flash_decode import flash_decode as _flash_decode_kernel
from repro.kernels.grouped_matmul import grouped_matmul as _grouped_kernel
from repro.kernels.lowrank_matmul import lowrank_matmul as _lowrank_kernel


def use_pallas() -> bool:
    return jax.default_backend() == "tpu"


# Static registry: public dispatch wrapper -> contract/lattice name.  The
# analysis layer (repro.analysis.contracts) cross-checks this against
# kernels.contracts.CONTRACTS and autotune's _LATTICES/_ANCHORS, so a new
# kernel cannot ship without a contract and an autotune lattice (or
# vice versa).  cov_accum_banked vmaps the same fused kernel, hence the
# shared contract.
REGISTERED_KERNELS: Dict[str, str] = {
    "lowrank_matmul": "lowrank_matmul",
    "cov_accum": "cov_accum",
    "cov_accum_banked": "cov_accum",
    "flash_attention": "flash_attention",
    "flash_decode": "flash_decode",
    "grouped_matmul": "grouped_matmul",
}


def _pad_dim(x, axis: int, multiple: int):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def lowrank_matmul(x, v, u, *, bias=None, residual=None,
                   force_pallas: bool = False, interpret: bool = False):
    """y = (x @ v) @ u (+ bias + residual).  x: (..., n); v: (n, k);
    u: (k, m); bias: (m,) or (1, m); residual: (..., m) like x's lead dims.

    The epilogue adds run fused inside the kernel's phase B (no extra HBM
    round-trip of the (T, m) output)."""
    if not (use_pallas() or force_pallas):
        y = ref.lowrank_matmul_ref(x, v, u)
        if bias is not None:
            y = y + bias.reshape(-1)
        if residual is not None:
            y = y + residual
        return y
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    t0 = xf.shape[0]
    # the contraction dim n needs lane alignment like every other dim:
    # zero-padding x's columns and v's rows adds exact zero contributions
    xf, _ = _pad_dim(xf, 1, 128)
    v, _ = _pad_dim(v, 0, 128)
    v, _ = _pad_dim(v, 1, 128)
    u, _ = _pad_dim(u, 0, 128)
    u, m0 = _pad_dim(u, 1, 128)
    tune = autotune.lowrank_blocks(
        t0, xf.shape[1], v.shape[1], u.shape[1], dtype=xf.dtype,
        has_bias=bias is not None, has_residual=residual is not None,
        interpret=interpret)
    bt, bn, bm = (tune.blocks[kk] for kk in ("bt", "bn", "bm"))
    xf, _ = _pad_dim(xf, 0, bt)
    xf, _ = _pad_dim(xf, 1, bn)
    v, _ = _pad_dim(v, 0, bn)
    u, _ = _pad_dim(u, 1, bm)
    bf = rf = None
    if bias is not None:
        bf, _ = _pad_dim(bias.reshape(1, -1), 1, u.shape[1])
    if residual is not None:
        rf = residual.reshape(-1, m0)
        rf, _ = _pad_dim(rf, 0, xf.shape[0])
        rf, _ = _pad_dim(rf, 1, u.shape[1])
    y = _lowrank_kernel(xf, v, u, bf, rf, bt=bt, bn=bn, bm=bm,
                        interpret=interpret)
    return y[:t0, :m0].reshape(*lead, m0)


def grouped_matmul(x, w, group_sizes, *, out_dtype=None,
                   force_pallas: bool = False, interpret: bool = False):
    """Grouped (ragged) expert GEMM: x (M, d) rows sorted by group, w
    (E, d, f) expert bank, group_sizes (E,) int32 summing to M -> (M, f).

    Row i contracts against W[group(i)] only — a pure per-row function, so
    the drop-free MoE dispatch built on it is exactly batch-size-invariant.
    fp32 accumulation; output cast to ``out_dtype`` (default x.dtype).
    Pallas on TPU (megablox-style ragged tiling over the sorted segments —
    see ``kernels/grouped_matmul.py``), ``jax.lax.ragged_dot`` elsewhere.
    """
    out_dtype = x.dtype if out_dtype is None else out_dtype
    if not (use_pallas() or force_pallas):
        return ref.grouped_matmul_ref(x, w, group_sizes).astype(out_dtype)
    m0, _ = x.shape
    e, _, f0 = w.shape
    # lane-align the contraction dim (zero columns are exact no-ops)
    x, _ = _pad_dim(x, 1, 128)
    w, _ = _pad_dim(w, 1, 128)
    tune = autotune.grouped_blocks(m0, x.shape[1], f0, e, dtype=x.dtype,
                                   interpret=interpret)
    bm, bf = tune.blocks["bm"], tune.blocks["bf"]
    # padded rows belong to no segment — the kernel's row mask zeroes them
    x, _ = _pad_dim(x, 0, bm)
    w, _ = _pad_dim(w, 2, bf)
    y = _grouped_kernel(x, w, group_sizes, bm=min(bm, x.shape[0]),
                        bf=min(bf, w.shape[2]), interpret=interpret)
    return y[:m0, :f0].astype(out_dtype)


def _accumulate(outs, acc, mesh=None):
    """Fold a covariance triple into existing fp32 accumulators.

    Keeping the add here (instead of at every call site) lets XLA alias the
    accumulator buffers in place when they are donated — the scanned
    collection step in ``core.streaming`` carries {xx, xxp, xpxp} through a
    ``lax.scan`` with donated carry, so each triple is updated without a
    fresh 3·n² allocation per microbatch.

    ``mesh`` marks accumulate-into under data-parallel sharding: the triple
    arriving here is already the psum-reduced global product (see
    ``_sharded_triple``); constraining it to the replicated ``cov_spec``
    keeps GSPMD from re-sharding the carry between updates."""
    outs = outs if acc is None else tuple(a + o for a, o in zip(acc, outs))
    if mesh is not None:
        from repro.distributed import sharding as SH
        sh = jax.sharding.NamedSharding(mesh, SH.cov_spec(mesh))
        outs = tuple(jax.lax.with_sharding_constraint(o, sh) for o in outs)
    return outs


def _cov_triple(x, xp, *, force_pallas: bool, interpret: bool):
    """Single-device fused triple on (T, n) token rows (padded + sliced)."""
    if not (use_pallas() or force_pallas):
        return ref.cov_accum_ref(x, xp)
    n0 = x.shape[-1]
    # lane-align the feature dim: zero columns give exact zero outer
    # products, so any n (e.g. 80-dim whisper taps) is safe
    x, _ = _pad_dim(x, 1, 128)
    xp, _ = _pad_dim(xp, 1, 128)
    tune = autotune.cov_blocks(x.shape[0], x.shape[1], dtype=x.dtype,
                               interpret=interpret)
    bt, bi = tune.blocks["bt"], tune.blocks["bi"]
    x, _ = _pad_dim(x, 0, bt)
    xp, _ = _pad_dim(xp, 0, bt)
    x, _ = _pad_dim(x, 1, bi)
    xp, _ = _pad_dim(xp, 1, bi)
    outs = _cov_kernel(x, xp, bi=bi, bt=bt, interpret=interpret)
    if x.shape[1] != n0:
        outs = tuple(o[:n0, :n0] for o in outs)
    return outs


def _cov_triple_banked(x, xp, *, force_pallas: bool, interpret: bool):
    """Expert-bank triple on (E, C, n): vmapped fused kernel per expert."""
    if not (use_pallas() or force_pallas):
        return ref.cov_accum_banked_ref(x, xp)
    n0 = x.shape[-1]
    x, _ = _pad_dim(x, 2, 128)
    xp, _ = _pad_dim(xp, 2, 128)
    tune = autotune.cov_blocks(x.shape[1], x.shape[2], dtype=x.dtype,
                               interpret=interpret)
    bt, bi = tune.blocks["bt"], tune.blocks["bi"]
    x, _ = _pad_dim(x, 1, bt)
    xp, _ = _pad_dim(xp, 1, bt)
    x, _ = _pad_dim(x, 2, bi)
    xp, _ = _pad_dim(xp, 2, bi)
    fn = functools.partial(_cov_kernel, bi=bi, bt=bt, interpret=interpret)
    outs = jax.vmap(fn)(x, xp)
    if x.shape[2] != n0:
        outs = tuple(o[:, :n0, :n0] for o in outs)
    return outs


def _sharded_triple(local_fn, x, xp, mesh, shard_axis: int):
    """Run ``local_fn`` (a per-shard fused triple) under ``shard_map`` over
    the mesh's data axes, sharding ``shard_axis`` of both inputs.

    Each DP worker keeps the fused Pallas single-pass path on its local
    token shard (padding the shard axis to the DP degree first — zero rows
    contribute exact zero outer products), and one ``psum`` per triple
    element combines the partials into the replicated global product."""
    from repro.distributed import sharding as SH
    dp = SH.dp_axes(mesh)
    x, _ = _pad_dim(x, shard_axis, SH.dp_degree(mesh))
    xp, _ = _pad_dim(xp, shard_axis, SH.dp_degree(mesh))
    spec_axes = [None] * x.ndim
    spec_axes[shard_axis] = dp
    spec = P(*spec_axes)

    def local(xs, xps):
        return tuple(jax.lax.psum(o, dp) for o in local_fn(xs, xps))

    fn = SH.data_shard_map(local, mesh, in_specs=(spec, spec),
                           out_specs=(P(), P(), P()))
    return fn(x, xp)


def cov_accum(x, xp, *, acc=None, mesh=None, force_pallas: bool = False,
              interpret: bool = False):
    """(T, n) x2 -> (xx, xxp, xpxp) fp32.  Token padding is exact (zero
    rows).  ``acc`` optionally supplies an existing (xx, xxp, xpxp) triple
    to accumulate into (returned as acc + products); ``mesh`` runs the fused
    kernel per DP worker under shard_map and psum-reduces the partials
    (see ``_sharded_triple``)."""
    n = x.shape[-1]
    x = x.reshape(-1, n)
    xp = xp.reshape(-1, n)
    fn = functools.partial(_cov_triple, force_pallas=force_pallas,
                           interpret=interpret)
    if mesh is None:
        return _accumulate(fn(x, xp), acc)
    return _accumulate(_sharded_triple(fn, x, xp, mesh, 0), acc, mesh)


def cov_accum_banked(x, xp, *, acc=None, mesh=None,
                     force_pallas: bool = False,
                     interpret: bool = False):
    """Expert-bank covariance triple: (E, C, n) x2 -> each (E, n, n) fp32.

    vmaps the fused single-pass kernel over the expert axis; capacity
    padding is exact (zero-padded slots add zero outer products).  ``acc``
    optionally supplies an existing triple to accumulate into; ``mesh``
    shards the capacity axis over the DP workers, each running the fused
    vmapped kernel on its slots, with one psum per triple element."""
    fn = functools.partial(_cov_triple_banked, force_pallas=force_pallas,
                           interpret=interpret)
    if mesh is None:
        return _accumulate(fn(x, xp), acc)
    return _accumulate(_sharded_triple(fn, x, xp, mesh, 1), acc, mesh)


def _cov_triple_grouped(x, xp, ids, experts: int, chunk: int = 0):
    """Per-expert triple from routed rows: segment-sum of per-row outer
    products, scanned in row chunks so the (chunk, n, n) intermediate stays
    bounded.  ids index the ORIGINAL-stream routing for all three terms;
    chunk-padding rows go to a sentinel bin that is sliced away."""
    xf = x.astype(jnp.float32)
    xpf = xp.astype(jnp.float32)
    r, n = xf.shape
    if chunk <= 0:
        chunk = max(8, min(2048, (1 << 21) // max(n * n, 1) or 8))
    pad = (-r) % chunk
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        xpf = jnp.pad(xpf, ((0, pad), (0, 0)))
    ids = jnp.pad(ids.astype(jnp.int32), (0, pad),
                  constant_values=experts)
    nb = (r + pad) // chunk
    xs = xf.reshape(nb, chunk, n)
    xps = xpf.reshape(nb, chunk, n)
    idb = ids.reshape(nb, chunk)

    def step(acc, inp):
        xc, xpc, ic = inp
        seg = lambda a, b: jax.ops.segment_sum(
            a[:, :, None] * b[:, None, :], ic,
            num_segments=experts + 1)[:experts]
        return (acc[0] + seg(xc, xc), acc[1] + seg(xc, xpc),
                acc[2] + seg(xpc, xpc)), None

    init = tuple(jnp.zeros((experts, n, n), jnp.float32) for _ in range(3))
    outs, _ = jax.lax.scan(step, init, (xs, xps, idb))
    return outs


def cov_accum_grouped(x, xp, ids, experts: int, *, acc=None, mesh=None):
    """Drop-free routed covariance triple: (R, n) choice-major rows x2 +
    (R,) int32 expert ids -> (xx, xxp, xpxp) each (E, n, n) fp32.

    The grouped analogue of ``cov_accum_banked`` for 2D drop-free taps:
    rows pair positionally per (token, choice) across the two streams and
    all three products bin by the original-stream ids.  Built on chunked
    ``segment_sum`` (no Pallas kernel: the op is a rank-1-update stream
    with data-dependent binning, and XLA's scatter-add lowering is already
    MXU/VPU-bound at the accumulator shapes involved).  ``acc``
    optionally supplies an existing triple to accumulate into; ``mesh``
    shards the row axis over the DP workers with one psum per triple
    element — zero padding rows contribute zero outer products whatever
    bin they land in, so the fold is exact."""
    n = x.shape[-1]
    x = x.reshape(-1, n)
    xp = xp.reshape(-1, n)
    ids = ids.reshape(-1)
    if mesh is None:
        return _accumulate(_cov_triple_grouped(x, xp, ids, experts), acc)
    from repro.distributed import sharding as SH
    dp = SH.dp_axes(mesh)
    deg = SH.dp_degree(mesh)
    x, _ = _pad_dim(x, 0, deg)
    xp, _ = _pad_dim(xp, 0, deg)
    pad = x.shape[0] - ids.shape[0]
    if pad:
        ids = jnp.pad(ids, (0, pad))  # zero rows: any bin is exact
    spec = P(dp, None)

    def local(xs, xps, idl):
        outs = _cov_triple_grouped(xs, xps, idl, experts)
        return tuple(jax.lax.psum(o, dp) for o in outs)

    fn = SH.data_shard_map(local, mesh, in_specs=(spec, spec, P(dp)),
                           out_specs=(P(), P(), P()))
    return _accumulate(fn(x, xp, ids), acc, mesh)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    force_pallas: bool = False, interpret: bool = False):
    """q: (B, H, Lq, D); k/v: (B, KV, Lk, D).  Non-multiple Lq/Lk are
    padded to the tuned block multiples and sliced back; padded KEY
    positions are masked inside the kernel (``lk_valid``) so they absorb
    no softmax weight, and padded query rows are simply sliced away."""
    if not (use_pallas() or force_pallas):
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    b, h, lq0, d = q.shape
    kv, lk0 = k.shape[1], k.shape[2]
    tune = autotune.flash_blocks(b, h, kv, lq0, lk0, d, dtype=q.dtype,
                                 causal=causal, window=window,
                                 interpret=interpret)
    bq, bk = tune.blocks["bq"], tune.blocks["bk"]
    q, _ = _pad_dim(q, 2, bq)
    k, _ = _pad_dim(k, 2, bk)
    v, _ = _pad_dim(v, 2, bk)
    out = _flash_kernel(q, k, v, causal=causal, window=window,
                        lk_valid=lk0 if k.shape[2] != lk0 else 0,
                        bq=min(bq, q.shape[2]), bk=min(bk, k.shape[2]),
                        interpret=interpret)
    return out[:, :, :lq0, :]


def _latent_write(cache, new, lengths, layer=None):
    """XLA twin of the kernel's write: latents ``new`` (B, r) at position
    lengths - 1 of a rank-major cache (B, r, L), or of layer ``layer`` of
    a stacked (n, B, r, L) one."""
    lead = () if layer is None else (layer,)
    idx = lead + (jnp.arange(new.shape[0]), slice(None), lengths - 1)
    return cache.at[idx].set(new.astype(cache.dtype))


def flash_decode(q, lk, lv, uk, uv, lengths, cos, sin, lk_new, lv_new, *,
                 layer=None, rope: bool = True, force_pallas: bool = False,
                 interpret: bool = False):
    """One decode step against the factorized latent KV cache: write the
    step's latents, then attend.

    q: (B, H, D) current-step queries (already RoPE'd); lk/lv: rank-major
    latent caches (B, r_k / r_v, L), or a scanned stage's stacked
    (n_layers, B, r_k / r_v, L) with ``layer`` the (traced) index of the
    layer to read; uk/uv: (r_k, KV·D) / (r_v, KV·D) — the "u" factor
    leaves exactly as stored in params; lengths: (B,) int32 live prefix
    per slot; cos/sin: (L, D//2) rope tables at absolute positions;
    lk_new/lv_new: (B, r_k / r_v) latents of the step's token, written at
    position lengths - 1.  Returns (out (B, H, D), lk, lv).

    The kernel writes and reads the caches where they lie: the ranks are
    not padded, and a stacked cache is read at ``layer`` through the
    kernel's index_map.  Only an L off the tuned block grid is padded
    (masked via ``lengths``): the write then goes through XLA, and the
    kernel attends a padded copy of the layer.  The head dim stays
    TRUE-sized so the in-kernel RoPE rotate-half pairing is preserved.
    """
    b, h, d = q.shape
    kv = uk.shape[-1] // d
    uk3 = uk.reshape(uk.shape[0], kv, d).transpose(1, 0, 2)  # (KV, r_k, D)
    uv3 = uv.reshape(uv.shape[0], kv, d).transpose(1, 0, 2)
    take = functools.partial(jax.lax.dynamic_index_in_dim, index=layer,
                             axis=0, keepdims=False)
    if not (use_pallas() or force_pallas):
        lk = _latent_write(lk, lk_new, lengths, layer)
        lv = _latent_write(lv, lv_new, lengths, layer)
        lkl, lvl = (lk, lv) if layer is None else (take(lk), take(lv))
        out = ref.flash_decode_ref(q, jnp.swapaxes(lkl, 1, 2),
                                   jnp.swapaxes(lvl, 1, 2), uk3, uv3,
                                   lengths, cos, sin, rope=rope)
        return out, lk, lv
    l0 = lk.shape[-1]
    tune = autotune.flash_decode_blocks(
        b, h, kv, l0, d, lk.shape[-2], lv.shape[-2], dtype=q.dtype,
        use_rope=rope, interpret=interpret)
    bk = min(tune.blocks["bk"], l0)
    if l0 % bk == 0:
        return _flash_decode_kernel(q, lk, lv, uk3, uv3, lengths, cos, sin,
                                    lk_new, lv_new, layer, use_rope=rope,
                                    bk=bk, interpret=interpret)
    lk = _latent_write(lk, lk_new, lengths, layer)
    lv = _latent_write(lv, lv_new, lengths, layer)
    lkl, lvl = (lk, lv) if layer is None else (take(lk), take(lv))
    lkl, _ = _pad_dim(lkl, 2, bk)
    lvl, _ = _pad_dim(lvl, 2, bk)
    cos, _ = _pad_dim(cos, 0, bk)
    sin, _ = _pad_dim(sin, 0, bk)
    out, _, _ = _flash_decode_kernel(q, lkl, lvl, uk3, uv3, lengths, cos,
                                     sin, lk_new, lv_new, use_rope=rope,
                                     bk=bk, interpret=interpret)
    return out, lk, lv
