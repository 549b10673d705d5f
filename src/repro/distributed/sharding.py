"""Partition rules: pytree path + shape -> PartitionSpec.

Mesh axes: ``(pod, data, model)`` multi-pod or ``(data, model)`` single-pod.
FSDP (ZeRO-3) shards parameters/grads/optimizer state over the combined data
axes; TP shards heads / d_ff / vocab over ``model``; EP shards the expert
axis of MoE banks over ``model``.  Every rule degrades gracefully: an axis is
applied only if the dimension is divisible by the axis size (GSPMD handles
uneven shards, but divisible layouts avoid padded collectives — we prefer
replication over ragged shards for the small dims this hits, e.g. gemma3's
4 q-heads on a 16-way model axis).

The rules are *name-based* (pytree paths), so compressed (u, v) factors get
their own layouts: the contracted rank axis stays unsharded and the original
TP axis follows the factor that owns it.
"""

from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

def fsdp_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def dp_axes(mesh: Mesh):
    return fsdp_axes(mesh)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _fit(mesh: Mesh, spec_axes, shape) -> P:
    """Drop spec axes whose size does not divide the dimension."""
    out = []
    for dim, axes in zip(shape, spec_axes):
        if axes is None:
            out.append(None)
            continue
        if dim % _axis_size(mesh, axes) == 0:
            out.append(axes)
        else:
            out.append(None)
    return P(*out)


# ---------------------------------------------------------------------------
# parameter rules


_COL = "col"   # (in, out): shard out over model, in over fsdp
_ROW = "row"   # (in, out): shard in over model, out over fsdp

_PARAM_RULES = [
    # embeddings / head
    (r"embed/table$", ("model", "fsdp")),
    (r"lm_head/w$", ("fsdp", "model")),
    # attention projections
    (r"attn/wq/w$", _COL), (r"attn/wk/w$", _COL), (r"attn/wv/w$", _COL),
    (r"attn/wkv_a/w$", _COL), (r"attn/wk_b/w$", _COL), (r"attn/wv_b/w$", _COL),
    (r"attn/wo/w$", _ROW),
    (r"xattn/wq/w$", _COL), (r"xattn/wk/w$", _COL), (r"xattn/wv/w$", _COL),
    (r"xattn/wo/w$", _ROW),
    # ffn
    (r"ffn/gate/w$", _COL), (r"ffn/up/w$", _COL), (r"ffn/down/w$", _ROW),
    (r"shared/gate/w$", _COL), (r"shared/up/w$", _COL), (r"shared/down/w$", _ROW),
    (r"router/w$", ("fsdp", None)),
    # MoE banks: EP over model on the expert axis
    (r"experts/(gate|up)/w$", ("model", "fsdp", None)),
    (r"experts/down/w$", ("model", "fsdp", None)),
    (r"experts/(gate|up)/v$", ("model", "fsdp", None)),
    (r"experts/(gate|up)/u$", ("model", None, None)),
    (r"experts/down/v$", ("model", "fsdp", None)),
    (r"experts/down/u$", ("model", None, None)),
    # mamba
    (r"mixer/in_proj/w$", _COL), (r"mixer/out_proj/w$", _ROW),
    (r"mixer/x_proj/w$", ("model", None)),
    (r"mixer/dt_proj/w$", (None, "model")),
    (r"mixer/conv_w$", ("model", None)),
    (r"mixer/conv_b$", ("model",)),
    (r"mixer/A_log$", ("model", None)),
    (r"mixer/(D|dt_bias)$", ("model",)),
]

# factorized (u, v) layouts (perf iteration C4).  Col-type linears put TP on
# the RANK of v (the x@v GEMM shards over k; the small (·, k) intermediate is
# all-gathered — 0.3× the bytes of a full-output psum) and on the OUT dim of
# u; row-type linears contract their model-sharded input in v (one small
# rank-k psum) and keep the (k, d) u replicated.  Both factors stay sharded
# in serving (weights are THE decode bandwidth), and the same layout serves
# train/prefill/decode — no re-layout between phases.
_FACTOR_RULES = [
    (r"(wq|wk|wv|wkv_a|wk_b|wv_b|gate|up|in_proj)/v$", ("fsdp", "model")),
    (r"(wq|wk|wv|wkv_a|wk_b|wv_b|gate|up|in_proj)/u$", (None, "model")),
    (r"(wo|down|out_proj)/v$", ("model", "fsdp")),
    (r"(wo|down|out_proj)/u$", ("fsdp", "model")),
    (r"(x_proj|dt_proj)/v$", (None, None)),
    (r"(x_proj|dt_proj)/u$", (None, None)),
]


def _resolve(axes_tmpl, mesh: Mesh):
    fa = fsdp_axes(mesh)
    out = []
    for a in axes_tmpl:
        if a == "fsdp":
            out.append(fa)
        else:
            out.append(a)
    return out


_Q_HEAD_RE = re.compile(r"(attn|xattn)/(wq|wo)/(w|u|v)$")
_KV_HEAD_RE = re.compile(r"(attn|xattn)/(wk|wv)/(w|u|v)$")


def _attn_shardable(path: str, mesh: Mesh, cfg) -> bool:
    """Heads must divide the model axis, else GSPMD splits head_dim and the
    score einsum contracts a sharded dim -> per-chunk all-reduces of the
    (B, H, Lq, C) score tensors (gemma3: H=4 on a 16-way axis, 352 GiB per
    prefill).  Replicated attention weights cost only their own bytes."""
    if cfg is None:
        return True
    n_model = mesh.shape.get("model", 1)
    if _Q_HEAD_RE.search(path):
        return cfg.num_heads % n_model == 0
    if _KV_HEAD_RE.search(path):
        return cfg.num_kv_heads % n_model == 0
    return True


def param_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
               mode: str = "store", cfg=None) -> P:
    """mode="store": at-rest layout (FSDP × TP).  mode="use": the layout a
    layer computes with — FSDP axes stripped (ZeRO-3 gathers the weight,
    keeping activation contractions local) EXCEPT for expert banks, whose
    d_in stays fsdp-sharded (gathering 384 experts is not an option; the
    shard_map EP path owns their compute layout)."""
    strip = mode in ("use", "serve")
    is_bank = "experts/" in path

    attn_ok = _attn_shardable(path, mesh, cfg)

    def finish(axes):
        if strip and not is_bank:
            axes = [None if a == "fsdp" else a for a in axes]
        if not attn_ok:
            axes = [None if a == "model" else a for a in axes]
        axes = _resolve(axes, mesh)
        axes = list(axes) + [None] * (len(shape) - len(axes))
        return _fit(mesh, axes[: len(shape)], shape)

    if len(shape) <= 1:
        # 1-D: shard big vectors over fsdp, replicate small ones
        if shape and not strip and shape[0] % _axis_size(
                mesh, fsdp_axes(mesh)) == 0 and shape[0] >= 4096:
            return _fit(mesh, [fsdp_axes(mesh)], shape)
        return P()
    for pat, axes in _PARAM_RULES + _FACTOR_RULES:
        if re.search(pat, path):
            if axes == _COL:
                axes = ("fsdp", "model")
            elif axes == _ROW:
                axes = ("model", "fsdp")
            return finish(list(axes))
    # default 2-D+: FSDP the largest dim
    axes = [None] * len(shape)
    axes[int(np.argmax(shape))] = "fsdp"
    return finish(axes)


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
    return "/".join(parts)


def tree_shardings(tree: PyTree, mesh: Mesh, spec_fn) -> PyTree:
    def one(path, leaf):
        spec = spec_fn(_path_str(path), leaf.shape)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, tree)


_STAGE_RE = re.compile(r"^(encoder/)?stages/(\d+)/")


def _stacked_stages(cfg):
    """(encoder?, stage index) of every stage whose params carry a leading
    layer-stack dim (scanned stages of more than one iteration)."""
    from repro.models import blocks as B

    out = set()
    for enc, stages in ((False, B.stage_program(cfg)),
                        (True, B.encoder_stages(cfg) or [])):
        out |= {(enc, i) for i, st in enumerate(stages)
                if st.scan and st.n > 1}
    return out


def param_shardings(params: PyTree, mesh: Mesh, mode: str = "store",
                    cfg=None) -> PyTree:
    """Per-leaf shardings of a params-shaped tree.  With ``cfg``, leaves of
    scanned stages keep their leading layer-stack dim unsharded and the
    rules apply to the per-layer shape — so expert banks shard over their
    expert axis, not over the layer axis."""
    stacked = _stacked_stages(cfg) if cfg is not None else set()

    def spec(path, shape):
        m = _STAGE_RE.match(path)
        if m and (bool(m.group(1)), int(m.group(2))) in stacked:
            return P(None, *param_spec(path, shape[1:], mesh, mode, cfg))
        return param_spec(path, shape, mesh, mode, cfg)

    return tree_shardings(params, mesh, spec)


def param_use_hints(p: PyTree) -> PyTree:
    """ZeRO-3 use-time constraint, applied per layer inside the scan body:
    re-lay each weight out with FSDP axes stripped, which materializes as a
    per-layer weight all-gather (weight bytes) instead of GSPMD's
    partial-sum all-reduce over activation-sized tensors.  No-op without an
    active mesh."""
    mesh = active_mesh()
    if mesh is None or p is None:
        return p
    mode = active_mode()
    cfg = active_cfg()

    def one(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim < 2:
            return leaf
        spec = param_spec(_path_str(path), leaf.shape, mesh, mode=mode,
                          cfg=cfg)
        return jax.lax.with_sharding_constraint(leaf,
                                                NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(one, p)


# ---------------------------------------------------------------------------
# batch / activation / cache rules


def batch_spec(path: str, shape: Tuple[int, ...], mesh: Mesh) -> P:
    dp = dp_axes(mesh)
    axes = [dp] + [None] * (len(shape) - 1)
    return _fit(mesh, axes, shape)


def batch_shardings(batch: PyTree, mesh: Mesh) -> PyTree:
    return tree_shardings(batch, mesh,
                          lambda p, s: batch_spec(p, s, mesh))


def activation_spec(mesh: Mesh) -> P:
    return P(dp_axes(mesh), None, None)


def dp_degree(mesh: Mesh) -> int:
    """Total data-parallel degree of a mesh (product of the data axes)."""
    return _axis_size(mesh, dp_axes(mesh))


# ---------------------------------------------------------------------------
# calibration-collection rules (sharded stage-1, core.streaming)
#
# The scanned collection sweep folds dp consecutive microbatches onto one
# scan step — (B, mb, L, d) -> (B/dp, dp·mb, L, d) — and shards the folded
# batch dim so every DP worker runs the tapped forward on exactly its own
# microbatches.  Covariance accumulation contracts token rows across that
# sharded dim, so each worker produces partial {XᵀX, XᵀX', X'ᵀX'} products;
# the accumulator carry is constrained to ``cov_spec`` (replicated), which
# GSPMD materializes as one n×n psum per update.


def calib_stream_spec(shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Stacked calibration stream (scan, batch, ...): the scan axis stays
    replicated (lax.scan iterates it), the per-step batch dim shards over
    the data axes.  Degrades to replication when the batch dim does not
    divide the DP degree."""
    axes = [None, dp_axes(mesh)] + [None] * (len(shape) - 2)
    return _fit(mesh, axes, shape)


def calib_stream_sharding(x, mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, calib_stream_spec(x.shape, mesh))


def cov_spec(mesh: Mesh) -> P:
    """Covariance accumulators are always fully replicated: the carry is the
    all-reduced sum of per-worker partial products, and the downstream solve
    must be bitwise-independent of the DP degree."""
    return P()


def data_shard_map(f, mesh: Mesh, in_specs, out_specs):
    """``shard_map`` with varying-axes checking off.

    The SPMD cov path in ``kernels.ops`` maps a Pallas call over the data
    axes; ``pallas_call`` carries no replication rule, so the check must be
    disabled."""
    # repro-check: allow[raw-unreplicated-shardmap] — this IS the blessed wrapper the rule routes callers to
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _cache_leaf_spec(kind: str, name: str, shape, mesh: Mesh) -> P:
    """Spec for one cache leaf with NO leading layer-stack dim.

    Attn (B, L, KV, D): batch over dp; KV heads over model when divisible,
    else the sequence dim carries the model axis (sequence-sharded cache —
    the long_500k b=1 layout).  MLA compressed caches shard the latent dim
    over model (the absorbed-decode contraction).  SSM states shard channels
    over model.
    """
    dp = dp_axes(mesh)
    first = dp if shape[0] % _axis_size(mesh, dp) == 0 else None
    if name in ("k", "v", "xk", "xv"):            # (B, L, KV, D)
        if shape[2] % mesh.shape["model"] == 0:
            return _fit(mesh, [first, None, "model", None], shape)
        return _fit(mesh, [first, "model", None, None], shape)
    if name in ("c", "kr", "lk", "lv"):
        # (B, L, r) MLA compressed / (B, r, L) rank-major latent-GQA
        # cache: SEQUENCE-sharded over model.  The
        # absorbed-decode score einsum contracts r against head-sharded
        # q_eff; r-sharding forces a full-cache all-gather per layer, while
        # L-sharding keeps scores local (softmax reduces with tiny psums).
        if name in ("lk", "lv"):
            return _fit(mesh, [first, None, "model"], shape)
        return _fit(mesh, [first, "model", None], shape)
    if name == "conv":                            # (B, W-1, C)
        return _fit(mesh, [first, None, "model"], shape)
    if name == "h":
        if len(shape) == 3:                       # mamba1 (B, di, N)
            return _fit(mesh, [first, "model", None], shape)
        return _fit(mesh, [first, "model", None, None], shape)  # (B,nh,hp,N)
    return _fit(mesh, [first] + [None] * (len(shape) - 1), shape)


def cache_shardings(cache: PyTree, cfg, mesh: Mesh) -> PyTree:
    """Walk the model's cache structure (list[stage][kind] of leaf dicts,
    scan stages carrying a leading layer-stack dim) and assign specs."""
    from repro.models import blocks as B

    out = []
    for st, per_kind in zip(B.stage_program(cfg), cache):
        stage_out = []
        stacked = st.scan and st.n > 1
        for kind, leafs in zip(st.kinds, per_kind):
            def one(path, leaf):
                name = _path_str(path).split("/")[-1]
                shape = leaf.shape[1:] if stacked else leaf.shape
                spec = _cache_leaf_spec(kind, name, shape, mesh)
                if stacked:
                    spec = P(*((None,) + spec))
                return NamedSharding(mesh, spec)

            stage_out.append(jax.tree_util.tree_map_with_path(one, leafs))
        out.append(stage_out)
    return out


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# refinement-engine rules (sharded stage-2, core.refine)
#
# The scanned refinement sweep threads a (params, AdamW state) carry through
# every optimizer step while the shifted-input/anchor streams keep the
# ``calib_stream_spec`` batch sharding (each step's microbatch dim shards
# over the data axes — no folding: SGD steps are sequential, so DP splits
# each step's *sequences*, never merges steps).  The carry is replicated:
# every worker holds the same weights and moments, and GSPMD lowers the
# value_and_grad over the sharded microbatch to per-worker grads + one psum
# per step.


def refine_carry_constraint(tree: PyTree, mesh: Optional[Mesh]) -> PyTree:
    """Refinement (params, optimizer) carry: fully replicated, mirroring
    ``cov_spec`` — the refined weights must be independent of which worker
    held which sequences.  Constrains every carry leaf inside the scanned
    step (the jit-internal counterpart of placing the carry with
    ``replicated``); no-op without a mesh so the unsharded trace stays
    constraint-free."""
    if mesh is None:
        return tree
    sh = replicated(mesh)
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, sh), tree)


# ---------------------------------------------------------------------------
# active-mesh hints: lets model internals place sharding constraints without
# threading the mesh through every call.  The launch layer activates the mesh
# around step-function *tracing*; with no active mesh, hints are no-ops (CPU
# tests, eager code).

import contextlib

_ACTIVE_MESH: list = []


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], mode: str = "use", cfg=None):
    _ACTIVE_MESH.append((mesh, mode, cfg))
    try:
        yield
    finally:
        _ACTIVE_MESH.pop()


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE_MESH[-1][0] if _ACTIVE_MESH else None


def active_mode() -> str:
    return _ACTIVE_MESH[-1][1] if _ACTIVE_MESH else "use"


def active_cfg():
    return _ACTIVE_MESH[-1][2] if _ACTIVE_MESH else None


def hint(x, *spec):
    """Constrain ``x`` to spec axes ("dp", "model", None per dim), dropping
    any axis that does not divide the dimension.  No-op without a mesh."""
    mesh = active_mesh()
    if mesh is None or x.ndim != len(spec):
        return x
    axes = [dp_axes(mesh) if s == "dp" else s for s in spec]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, _fit(mesh, axes, x.shape)))
