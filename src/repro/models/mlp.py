"""Feed-forward layers: SwiGLU / GELU MLP and mixture-of-experts.

Two MoE dispatch formulations, selected by ``MoEConfig.dispatch``:

* ``capacity`` (default) — sort-free capacity dispatch built from one-hot
  cumsums (the GShard/Switch construction) but factored so the biggest
  intermediate is the (E, C, d) expert input buffer — never a (T, E, C)
  dispatch tensor.  Tokens past the per-expert capacity are dropped, so
  outputs depend on the batch they were dispatched with.
* ``dropfree`` — sort + segment-sum dispatch: the (T·k) routed choices are
  sorted by expert id into contiguous ragged segments, fed through a
  grouped expert GEMM (``kernels.ops.grouped_matmul``), unsorted, and
  combined per token in fixed choice order.  No token is ever dropped and
  every output row is a pure per-row function of (token, expert weights),
  making the layer output exactly batch-size-invariant — the property
  stage-1 calibration needs to fold microbatches by dp for expert-bank
  units (see ``core/streaming.py``).

Experts are stacked on a leading axis so expert parallelism is a single
PartitionSpec('model', ...) on the weights; the scatter/gather token
movement lowers to all-to-all-class collectives under GSPMD.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L


# ---------------------------------------------------------------------------
# dense FFN


def ffn_init(key, d: int, d_ff: int, act_fn: str, num_layers: int,
             dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    p = {
        "up": L.linear_init(ks[1], d, d_ff, dtype=dtype),
        "down": L.linear_init(ks[2], d_ff, d, dtype=dtype,
                              scale=1.0 / math.sqrt(d_ff * 2 * num_layers)),
    }
    if act_fn == "silu":
        p["gate"] = L.linear_init(ks[0], d, d_ff, dtype=dtype)
    return p


def ffn_apply(p, x, act_fn: str):
    L.sow("in", x)
    up = L.linear(p["up"], x)
    if "gate" in p:
        up = L.act(act_fn, L.linear(p["gate"], x)) * up
    else:
        up = L.act(act_fn, up)
    L.sow("down_in", up)
    return L.linear(p["down"], up)


# ---------------------------------------------------------------------------
# mixture of experts


def moe_init(key, cfg, dtype=jnp.float32):
    d, m = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 5)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(m.d_ff * 2 * cfg.num_layers)

    def expert_bank(k, n_e):
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "gate": {"w": (jax.random.normal(k1, (n_e, d, m.d_ff)) * scale_in).astype(dtype)},
            "up": {"w": (jax.random.normal(k2, (n_e, d, m.d_ff)) * scale_in).astype(dtype)},
            "down": {"w": (jax.random.normal(k3, (n_e, m.d_ff, d)) * scale_out).astype(dtype)},
        }

    p = {
        "router": L.linear_init(ks[0], d, m.num_experts, dtype=jnp.float32),
        "experts": expert_bank(ks[1], m.num_experts),
    }
    if m.num_shared_experts:
        p["shared"] = ffn_init(ks[2], d, m.d_ff * m.num_shared_experts,
                               cfg.act_fn, cfg.num_layers, dtype=dtype)
    return p


def moe_apply(p, x, cfg, *, capacity_factor=None, dispatch=None):
    """x: (B, L, d) -> (B, L, d), plus aux load-balance loss (fp32 scalar).

    Dispatch (``cfg.moe.dispatch``; both keywords override per call):

    * ``capacity`` — flatten to T=B*L tokens, take top-k experts per token,
      assign slot positions within each expert via a one-hot cumsum, scatter
      tokens into an (E, C, d) buffer, run the 3 batched expert GEMMs, and
      gather-combine weighted by the (renormalized) router gates.  Tokens
      over capacity C = ceil(T·k/E · capacity_factor) are dropped
      (contribute zero) — standard Switch semantics.  C is floored at top_k
      identically in the flat, EP, and decode-EP paths, so degenerate
      decode shapes (t < k local tokens) keep at least one slot per choice.
    * ``dropfree`` — sort the (T·k) routed choices by expert id
      (``jax.lax.sort_key_val``), run the expert GEMMs over the resulting
      contiguous ragged segments, unsort, and sum the k choices per token
      in fixed choice order.  Nothing drops; outputs are exactly
      batch-size-invariant (see module docstring).

    With an active production mesh this routes to the shard_map expert-
    parallel path (perf iteration B — GSPMD partitions the scatter/gather
    dispatch catastrophically: ~90 TB/device of all-reduce on the kimi-k2
    train cell).
    """
    m = cfg.moe
    if dispatch is None:
        dispatch = m.dispatch
    if dispatch not in ("capacity", "dropfree"):
        raise ValueError(f"unknown moe dispatch {dispatch!r} "
                         "(capacity | dropfree)")
    if capacity_factor is None:
        capacity_factor = m.capacity_factor
    from repro.distributed import sharding as SH
    mesh = SH.active_mesh()
    if mesh is not None:
        n_model = mesh.shape.get("model", 1)
        dp_size = SH._axis_size(mesh, SH.dp_axes(mesh))
        t_loc = (x.shape[0] // dp_size) * x.shape[1]
        if n_model > 1 and cfg.moe.num_experts % n_model == 0 \
                and x.shape[0] % dp_size == 0:
            if t_loc >= 256:
                return _moe_apply_ep(p, x, cfg, mesh, capacity_factor,
                                     dispatch)
            if (cfg.d_model % dp_size == 0 and cfg.moe.d_ff % dp_size == 0
                    and "w" in p["experts"]["gate"]):
                # decode: a handful of tokens cannot amortize moving expert
                # weights — gather the TOKENS instead (decode-EP; dense
                # banks only: the partial-GEMM slicing assumes (E, d, f))
                return _moe_apply_ep_decode(p, x, cfg, mesh, capacity_factor,
                                            dispatch)
    b, l, d = x.shape
    t = b * l
    e, k = m.num_experts, m.top_k

    xt = x.reshape(t, d)
    logits = L.linear(p["router"], xt.astype(jnp.float32), dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                     # (T, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)             # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

    # aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32), axis=1),
        axis=0)
    aux = m.aux_loss_coef * e * jnp.sum(me * ce)

    if dispatch == "dropfree":
        y = _dispatch_dropfree(p["experts"], xt, gate_vals, expert_ids, cfg)
        y = y.astype(x.dtype)
    else:
        cap = int(math.ceil(t * k / e * capacity_factor))
        cap = max(cap, k)

        # --- slot assignment: flatten (T, k) choices in priority order ---
        flat_ids = expert_ids.T.reshape(-1)                      # (k*T,) choice-major
        onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)    # (kT, E)
        pos = jnp.cumsum(onehot, axis=0) - 1                     # slot per choice
        slot = jnp.sum(pos * onehot, axis=1)                     # (kT,)
        keep = slot < cap
        slot = jnp.clip(slot, 0, cap - 1)
        dest = flat_ids * cap + slot                             # (kT,) in [0, E*cap)

        token_idx = jnp.tile(jnp.arange(t), k)                   # choice-major order
        gates_flat = gate_vals.T.reshape(-1) * keep.astype(jnp.float32)
        # [dropped, total] routed choices — the per-layer drop rate the
        # compression report surfaces for capacity-vs-dropfree deltas
        L.sow("experts_dropped", jnp.stack(
            [jnp.sum(1.0 - keep.astype(jnp.float32)),
             jnp.asarray(float(k * t), jnp.float32)]))

        # --- scatter tokens into the expert buffer -----------------------
        buf = jnp.zeros((e * cap, d), x.dtype)
        src = jnp.where(keep[:, None], xt[token_idx], 0).astype(x.dtype)
        buf = buf.at[dest].add(src, mode="drop")
        buf = buf.reshape(e, cap, d)

        # --- expert GEMMs (batched over E; EP shards the leading axis) ---
        w = p["experts"]
        L.sow("experts_in", buf)
        h = L.act(cfg.act_fn, bank_apply(w["gate"], buf)) \
            * bank_apply(w["up"], buf)
        L.sow("experts_down_in", h)
        y_buf = bank_apply(w["down"], h).reshape(e * cap, d)

        # --- gather-combine ----------------------------------------------
        y = jnp.zeros((t, d), jnp.float32)
        y = y.at[token_idx].add(
            y_buf[dest].astype(jnp.float32) * gates_flat[:, None],
            mode="drop")
        y = y.astype(x.dtype)

    if "shared" in p:
        with L.scope("shared"):
            y = y + ffn_apply(p["shared"], xt, cfg.act_fn)
    return y.reshape(b, l, d), aux


def _dispatch_dropfree(w, xt, gate_vals, expert_ids, cfg):
    """Drop-free routed expert compute for one flat token matrix.

    Lays the (T, k) routed choices out choice-major as (k·T, d) rows, sorts
    rows by expert id into contiguous segments (stable ``sort_key_val``, so
    ties keep choice-major order), runs the three expert GEMMs grouped over
    the ragged segments, unsorts via the inverse permutation, and sums the k
    gate-weighted choices per token in fixed choice order (fp32).

    Every output row is dot(x_token, W_expert) with a fixed contraction
    order along d — independent of which other rows share its segment — so
    the result is exactly invariant to batch concatenation/splitting.

    Taps are sown in the ORIGINAL choice-major order (not sorted) together
    with the expert ids, so original- and shifted-stream rows pair
    positionally per (token, choice) and the calibration engine can bin
    per-expert covariances itself (``ops.cov_accum_grouped``).

    Returns the combined (T, d) routed output in fp32 (shared experts and
    dtype cast happen in the caller).
    """
    t, d = xt.shape
    k = cfg.moe.top_k
    e = cfg.moe.num_experts
    kt = k * t

    flat_ids = expert_ids.T.reshape(-1).astype(jnp.int32)        # (kT,) choice-major
    token_idx = jnp.tile(jnp.arange(t), k)
    rows = xt[token_idx]                                         # (kT, d)
    L.sow("experts_in", rows)
    L.sow("experts_ids", flat_ids)

    iota = jnp.arange(kt, dtype=jnp.int32)
    _, order = jax.lax.sort_key_val(flat_ids, iota)              # stable
    inv = jnp.zeros((kt,), jnp.int32).at[order].set(iota)
    group_sizes = jnp.bincount(flat_ids, length=e).astype(jnp.int32)

    xs = jnp.take(rows, order, axis=0)                           # segment-contiguous
    h = L.act(cfg.act_fn, grouped_bank_apply(w["gate"], xs, group_sizes)) \
        * grouped_bank_apply(w["up"], xs, group_sizes)
    # down-projection input tap in original order (dead code — DCE'd by
    # XLA — unless the forward is being sown)
    L.sow("experts_down_in", jnp.take(h, inv, axis=0))
    y_rows = grouped_bank_apply(w["down"], h, group_sizes)
    y_rows = jnp.take(y_rows, inv, axis=0)                       # choice-major again

    gates_flat = gate_vals.T.reshape(-1)
    y = jnp.sum((y_rows.astype(jnp.float32)
                 * gates_flat[:, None]).reshape(k, t, d), axis=0)
    return y


def grouped_bank_apply(bp, xs, group_sizes):
    """Grouped expert GEMM over segment-sorted rows.  xs: (R, d_in) with
    the first group_sizes[0] rows belonging to expert 0 and so on; bank
    dense (E, d_in, d_out) or factorized {"u": (E, k, d_out),
    "v": (E, d_in, k)}."""
    from repro.kernels import ops
    if "w" in bp:
        return ops.grouped_matmul(xs, bp["w"].astype(xs.dtype), group_sizes)
    t = ops.grouped_matmul(xs, bp["v"].astype(xs.dtype), group_sizes)
    return ops.grouped_matmul(t, bp["u"].astype(xs.dtype), group_sizes)


def bank_apply(bp, x):
    """Batched expert GEMM.  x: (E, C, d_in); bank dense (E, d_in, d_out) or
    factorized {"u": (E, k, d_out), "v": (E, d_in, k)}."""
    if "w" in bp:
        return jnp.einsum("ecd,edf->ecf", x, bp["w"].astype(x.dtype))
    t = jnp.einsum("ecd,edk->eck", x, bp["v"].astype(x.dtype))
    return jnp.einsum("eck,ekf->ecf", t, bp["u"].astype(x.dtype))


# ---------------------------------------------------------------------------
# shard_map expert parallelism (perf iteration B)


def _bank_spec(bp, mesh):
    """in_specs for an expert bank: expert axis on 'model', rest gathered."""
    from jax.sharding import PartitionSpec as P
    return jax.tree.map(lambda a: P("model", *([None] * (a.ndim - 1))), bp)


def _ep_dropfree_local(experts, xt, gate_vals, expert_ids, cfg, e_loc, e0,
                       x_dtype):
    """Local-expert drop-free compute shared by the EP bodies.

    Choices targeting non-local experts keep their row POSITION (so the
    choice-major layout — and with it batch invariance — is preserved) but
    have the row zeroed and binned into a clamped local group; a zero row
    through any expert GEMM is a zero row out, and the gate is also masked,
    so non-local choices contribute exactly zero to the partial output that
    the caller completes with one psum over 'model'.
    """
    t, d = xt.shape
    k = cfg.moe.top_k
    kt = k * t
    flat_ids = expert_ids.T.reshape(-1).astype(jnp.int32)
    token_idx = jnp.tile(jnp.arange(t), k)
    local_id = flat_ids - e0
    is_local = (local_id >= 0) & (local_id < e_loc)
    gid = jnp.where(is_local, local_id, e_loc - 1).astype(jnp.int32)
    rows = jnp.where(is_local[:, None], xt[token_idx], 0).astype(x_dtype)

    iota = jnp.arange(kt, dtype=jnp.int32)
    _, order = jax.lax.sort_key_val(gid, iota)
    inv = jnp.zeros((kt,), jnp.int32).at[order].set(iota)
    group_sizes = jnp.bincount(gid, length=e_loc).astype(jnp.int32)

    xs = jnp.take(rows, order, axis=0)
    h = L.act(cfg.act_fn, grouped_bank_apply(experts["gate"], xs, group_sizes)) \
        * grouped_bank_apply(experts["up"], xs, group_sizes)
    y_rows = grouped_bank_apply(experts["down"], h, group_sizes)
    y_rows = jnp.take(y_rows, inv, axis=0)

    gates_flat = gate_vals.T.reshape(-1) * is_local.astype(jnp.float32)
    y = jnp.sum((y_rows.astype(jnp.float32)
                 * gates_flat[:, None]).reshape(k, t, d), axis=0)
    return y


def _moe_apply_ep(p, x, cfg, mesh, capacity_factor: float, dispatch: str):
    """Explicit expert parallelism:

    * every (dp, model) device holds its dp-shard of tokens (replicated over
      'model') and E/n_model local experts;
    * each device routes its tokens, keeps only choices targeting its local
      experts, runs the three expert GEMMs on them — capacity dispatch
      scatters into a local (E_loc, C, d) buffer, drop-free dispatch sorts
      the local choices into ragged segments — and combines with gates,
      producing a PARTIAL (T_loc, d) output that one psum over 'model'
      completes (the same wire cost as the dense-TP FFN all-reduce, vs.
      GSPMD's scatter partitioning at ~90 TB/device on kimi-k2 train);
    * aux load-balance loss is pmean'd over dp and model (fully replicated).
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as SH

    m = cfg.moe
    dp = SH.dp_axes(mesh)
    n_model = mesh.shape["model"]
    e, k = m.num_experts, m.top_k
    e_loc = e // n_model
    b, l, d = x.shape

    def body(x_blk, router_w, experts):
        bl, _, _ = x_blk.shape
        t_loc = bl * l
        cap = max(int(math.ceil(t_loc * k / e * capacity_factor)), k)
        xt = x_blk.reshape(t_loc, d)
        # router GEMM in the compute dtype (softmax still fp32): keeps the
        # dx cotangent — which is psum'd over 'model' in backward — in bf16
        logits = (xt @ router_w.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)

        me = jax.lax.pmean(jnp.mean(probs, axis=0), dp)
        ce = jax.lax.pmean(jnp.mean(
            jnp.sum(jax.nn.one_hot(expert_ids, e, dtype=jnp.float32), axis=1),
            axis=0), dp)
        aux = m.aux_loss_coef * e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, "model")   # certify model-replication

        e0 = jax.lax.axis_index("model") * e_loc
        if dispatch == "dropfree":
            y = _ep_dropfree_local(experts, xt, gate_vals, expert_ids, cfg,
                                   e_loc, e0, x_blk.dtype)
            y = jax.lax.psum(y.astype(x_blk.dtype), "model")
            return y.reshape(bl, l, d), aux
        flat_ids = expert_ids.T.reshape(-1)               # (k·T_loc,)
        local_id = flat_ids - e0
        is_local = (local_id >= 0) & (local_id < e_loc)
        oh = jax.nn.one_hot(jnp.where(is_local, local_id, e_loc), e_loc + 1,
                            dtype=jnp.int32)[:, :e_loc]
        pos = jnp.cumsum(oh, axis=0) - 1
        slot = jnp.sum(pos * oh, axis=1)
        keep = is_local & (slot < cap)
        slot = jnp.clip(slot, 0, cap - 1)
        dest = jnp.where(keep, jnp.clip(local_id, 0, e_loc - 1) * cap + slot,
                         e_loc * cap)                      # overflow row
        token_idx = jnp.tile(jnp.arange(t_loc), k)
        gates_flat = gate_vals.T.reshape(-1) * keep.astype(jnp.float32)

        buf = jnp.zeros((e_loc * cap + 1, d), x_blk.dtype)
        src = jnp.where(keep[:, None], xt[token_idx], 0).astype(x_blk.dtype)
        buf = buf.at[dest].add(src)[: e_loc * cap].reshape(e_loc, cap, d)

        h = L.act(cfg.act_fn, bank_apply(experts["gate"], buf)) \
            * bank_apply(experts["up"], buf)
        y_buf = bank_apply(experts["down"], h).reshape(e_loc * cap, d)
        y_buf = jnp.concatenate(
            [y_buf, jnp.zeros((1, d), y_buf.dtype)], axis=0)

        y = jnp.zeros((t_loc, d), jnp.float32)
        y = y.at[token_idx].add(
            y_buf[dest].astype(jnp.float32) * gates_flat[:, None])
        # combine across expert shards in bf16 (halves the dominant wire
        # term; local accumulation above stays fp32)
        y = jax.lax.psum(y.astype(x_blk.dtype), "model")
        return y.reshape(bl, l, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  _bank_spec(p["experts"], mesh)),
        out_specs=(P(dp, None, None), P()),
    )(x, p["router"]["w"], p["experts"])

    if "shared" in p:
        with L.scope("shared"):
            y = y + ffn_apply(p["shared"], x.reshape(-1, d),
                              cfg.act_fn).reshape(b, l, d)
    return y, aux


def _moe_apply_ep_decode(p, x, cfg, mesh, capacity_factor: float,
                         dispatch: str):
    """Decode-time expert parallelism: move TOKENS, never weights.

    At decode, tokens are a few kB while the expert banks are TBs; the
    training-EP body's bank d_in gather (2.1 GB/layer on kimi-k2) cannot
    amortize.  Here every device all-gathers the (global-batch, d) token
    matrix over dp (~MBs), routes identically, and computes its LOCAL
    (model-sharded experts × dp-sharded d_in/d_ff contraction) partial GEMMs
    in the banks' AT-REST layout — weights never cross a link.  Three tiny
    psums ((E_loc, C, ·) with C≈⌈T·k/E⌉ and a (T, d) combine) complete the
    result.
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as SH

    m = cfg.moe
    dp = SH.dp_axes(mesh)
    dp_size = SH._axis_size(mesh, dp)
    n_model = mesh.shape["model"]
    e, k = m.num_experts, m.top_k
    e_loc = e // n_model
    b, l, d = x.shape
    d_loc = d // dp_size
    f_loc = m.d_ff // dp_size
    dp_sizes = [mesh.shape[a] for a in dp]

    def dp_index():
        idx = jax.lax.axis_index(dp[0])
        for a, sz in zip(dp[1:], dp_sizes[1:]):
            idx = idx * sz + jax.lax.axis_index(a)
        return idx

    def body(x_blk, router_w, experts):
        bl = x_blk.shape[0]
        xt = jax.lax.all_gather(x_blk.reshape(-1, d), dp,
                                axis=0, tiled=True)          # (T, d)
        t = xt.shape[0]
        cap = max(int(math.ceil(t * k / e * capacity_factor)), k)
        logits = (xt @ router_w.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, -1, keepdims=True), 1e-9)
        aux = m.aux_loss_coef * e * jnp.sum(
            jnp.mean(probs, axis=0) *
            jnp.mean(jnp.sum(jax.nn.one_hot(expert_ids, e,
                                            dtype=jnp.float32), 1), 0))
        aux = jax.lax.pmean(aux, tuple(dp))  # identical on every dp shard

        e0 = jax.lax.axis_index("model") * e_loc
        if dispatch == "dropfree":
            from repro.kernels import ops
            flat_ids = expert_ids.T.reshape(-1).astype(jnp.int32)
            token_idx = jnp.tile(jnp.arange(t), k)
            local_id = flat_ids - e0
            is_local = (local_id >= 0) & (local_id < e_loc)
            gid = jnp.where(is_local, local_id, e_loc - 1).astype(jnp.int32)
            rows = jnp.where(is_local[:, None], xt[token_idx],
                             0).astype(x_blk.dtype)
            iota = jnp.arange(k * t, dtype=jnp.int32)
            _, order = jax.lax.sort_key_val(gid, iota)
            inv = jnp.zeros((k * t,), jnp.int32).at[order].set(iota)
            group_sizes = jnp.bincount(gid, length=e_loc).astype(jnp.int32)
            xs = jnp.take(rows, order, axis=0)
            # d_in-sharded grouped partial GEMMs against the at-rest bank
            # shards, fp32 partials completed by one psum over dp each
            i = dp_index()
            xs_d = jax.lax.dynamic_slice_in_dim(xs, i * d_loc, d_loc, axis=1)
            hg = jax.lax.psum(ops.grouped_matmul(
                xs_d, experts["gate"]["w"], group_sizes,
                out_dtype=jnp.float32), dp)
            hu = jax.lax.psum(ops.grouped_matmul(
                xs_d, experts["up"]["w"], group_sizes,
                out_dtype=jnp.float32), dp)
            h = L.act(cfg.act_fn, hg) * hu                    # (kT, f) fp32
            h_f = jax.lax.dynamic_slice_in_dim(h, i * f_loc, f_loc, axis=1)
            y_rows = jax.lax.psum(ops.grouped_matmul(
                h_f.astype(x_blk.dtype), experts["down"]["w"], group_sizes,
                out_dtype=jnp.float32), dp)
            y_rows = jnp.take(y_rows, inv, axis=0)
            gates_flat = gate_vals.T.reshape(-1) \
                * is_local.astype(jnp.float32)
            y = jnp.sum((y_rows * gates_flat[:, None]).reshape(k, t, d),
                        axis=0)
            y = jax.lax.psum(y.astype(x_blk.dtype), "model")
            y = jax.lax.dynamic_slice_in_dim(y, dp_index() * bl * l,
                                             bl * l, 0)
            return y.reshape(bl, l, d), aux
        flat_ids = expert_ids.T.reshape(-1)
        local_id = flat_ids - e0
        is_local = (local_id >= 0) & (local_id < e_loc)
        oh = jax.nn.one_hot(jnp.where(is_local, local_id, e_loc), e_loc + 1,
                            dtype=jnp.int32)[:, :e_loc]
        pos = jnp.cumsum(oh, axis=0) - 1
        slot = jnp.sum(pos * oh, axis=1)
        keep = is_local & (slot < cap)
        slot = jnp.clip(slot, 0, cap - 1)
        dest = jnp.where(keep, jnp.clip(local_id, 0, e_loc - 1) * cap + slot,
                         e_loc * cap)
        token_idx = jnp.tile(jnp.arange(t), k)
        gates_flat = gate_vals.T.reshape(-1) * keep.astype(jnp.float32)

        buf = jnp.zeros((e_loc * cap + 1, d), x_blk.dtype)
        src = jnp.where(keep[:, None], xt[token_idx], 0).astype(x_blk.dtype)
        buf = buf.at[dest].add(src)[: e_loc * cap].reshape(e_loc, cap, d)

        # d_in-sharded gate/up GEMMs against the at-rest bank shards
        i = dp_index()
        buf_d = jax.lax.dynamic_slice_in_dim(buf, i * d_loc, d_loc, axis=2)
        hg = jax.lax.psum(bank_apply_partial(experts["gate"], buf_d), dp)
        hu = jax.lax.psum(bank_apply_partial(experts["up"], buf_d), dp)
        h = L.act(cfg.act_fn, hg) * hu                     # (E_loc, C, f)
        h_f = jax.lax.dynamic_slice_in_dim(h, i * f_loc, f_loc, axis=2)
        y_buf = jax.lax.psum(
            bank_apply_partial(experts["down"], h_f.astype(x_blk.dtype)), dp)
        y_buf = y_buf.reshape(e_loc * cap, d)
        y_buf = jnp.concatenate(
            [y_buf, jnp.zeros((1, d), y_buf.dtype)], axis=0)

        y = jnp.zeros((t, d), jnp.float32)
        y = y.at[token_idx].add(
            y_buf[dest].astype(jnp.float32) * gates_flat[:, None])
        y = jax.lax.psum(y.astype(x_blk.dtype), "model")   # (T, d)
        y = jax.lax.dynamic_slice_in_dim(y, dp_index() * bl * l, bl * l, 0)
        return y.reshape(bl, l, d), aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None, None), P(None, None),
                  jax.tree.map(lambda a: P("model", dp, None), p["experts"])),
        out_specs=(P(dp, None, None), P()),
    )(x, p["router"]["w"], p["experts"])

    if "shared" in p:
        with L.scope("shared"):
            y = y + ffn_apply(p["shared"], x.reshape(-1, d),
                              cfg.act_fn).reshape(b, l, d)
    return y, aux


def bank_apply_partial(bp, x_part):
    """Partial expert GEMM on a d_in shard: x (E, C, d_loc) × bank shard
    (E, d_loc, f) -> fp32 partial (E, C, f); caller psums over dp."""
    if "w" in bp:
        return jnp.einsum("ecd,edf->ecf", x_part, bp["w"],
                          preferred_element_type=jnp.float32)
    t = jnp.einsum("ecd,edk->eck", x_part, bp["v"],
                   preferred_element_type=jnp.float32)
    return jnp.einsum("eck,ekf->ecf", t.astype(bp["u"].dtype), bp["u"],
                      preferred_element_type=jnp.float32)
