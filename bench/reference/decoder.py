"""Plain reference of the dense decoder (qwen3-0.6b, granite-3-8b).

Straightforward ``jax.numpy`` in float32 with every matmul at HIGHEST
precision, no kernels, no cache, no batching tricks: pre-norm RMSNorm,
optional per-head q/k RMSNorm (qwen3), rotate-half RoPE, GQA causal
softmax attention, SwiGLU, tied or untied head.  Linears are dense
``{"w"}`` or factor pairs ``{"v", "u"}`` applied as ``(x @ v) @ u``.
It imports nothing of the program; it reads params in the layout the
program takes (see ``bench/weights.py``), regenerated from the seed.

``prec="low"`` is the control: every matmul operand is rounded to fp8
(e4m3, per-tensor absmax scaling), the precision below the bfloat16 the
configurations state.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def to_fp8(x):
    """Round to float8_e4m3fn with per-tensor absmax scaling, back to f32."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _ops(a, b, prec):
    a, b = a.astype(F32), b.astype(F32)
    if prec == "low":
        a, b = to_fp8(a), to_fp8(b)
    return a, b


def mm(a, b, prec="f32"):
    a, b = _ops(a, b, prec)
    return jnp.matmul(a, b, precision=HIGHEST)


def einsum(spec, a, b, prec="f32"):
    a, b = _ops(a, b, prec)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def linear(p, x, prec="f32"):
    if "w" in p:
        return mm(x, p["w"], prec)
    return mm(mm(x, p["v"], prec), p["u"], prec)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x, positions, theta):
    """x (..., S, H, D): rotate-half RoPE at integer ``positions`` (S,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attend_one(q, k, v, prec):
    """One sequence: q (S, H, D), k/v (S, KV, D) -> (S, H, D)."""
    g = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = einsum("qhd,khd->hqk", q, k, prec) / math.sqrt(q.shape[-1])
    n = q.shape[0]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return einsum("hqk,khd->qhd", p, v, prec)


def layer(p: Dict, x, m: Dict, prec="f32", taps: bool = False):
    """One decoder layer.  x (B, S, d) f32; ``m`` the configuration's
    ``model`` section.  With ``taps`` also returns the inputs of the
    linears: ``qkv`` (q/k/v), ``o`` (wo), ``ffn_in`` (gate/up) and
    ``down``."""
    b, n, _ = x.shape
    h_, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    pos = jnp.arange(n)
    a = p["attn"]
    h = rms_norm(x, p["ln1"]["scale"], eps)
    q = linear(a["wq"], h, prec).reshape(b, n, h_, hd)
    k = linear(a["wk"], h, prec).reshape(b, n, kv, hd)
    v = linear(a["wv"], h, prec).reshape(b, n, kv, hd)
    if m["qk_norm"]:
        q = rms_norm(q, a["q_norm"]["scale"], eps)
        k = rms_norm(k, a["k_norm"]["scale"], eps)
    q = rope(q, pos, m["rope_theta"])
    k = rope(k, pos, m["rope_theta"])
    attend = jax.checkpoint(lambda t: _attend_one(*t, prec))
    o = jax.lax.map(attend, (q, k, v)).reshape(b, n, h_ * hd)
    x = x + linear(a["wo"], o, prec)
    h2 = rms_norm(x, p["ln2"]["scale"], eps)
    f = p["ffn"]
    act = jax.nn.silu(linear(f["gate"], h2, prec)) * linear(f["up"], h2, prec)
    y = x + linear(f["down"], act, prec)
    if taps:
        return y, {"qkv": h, "o": o, "ffn_in": h2, "down": act}
    return y


def layer_params(params, m: Dict, i: int):
    """Layer ``i``'s params from the program-layout tree (one scanned
    stage, stacked on a leading axis when there is more than one layer)."""
    block = params["stages"][0][0]
    if m["num_layers"] == 1:
        return block
    return jax.tree.map(lambda a: a[i], block)


def head_weight(params, m: Dict):
    if m["tie_embeddings"]:
        return params["embed"]["table"].T
    return params["lm_head"]["w"]


def logits(params, m: Dict, tokens, prec="f32", pad_to: int = 0):
    """Logits (S, V) of one sequence ``tokens`` (S,), layer by layer so
    that only one layer's params are upcast at a time.  ``pad_to`` pads
    the sequence (causal attention: later positions change nothing
    before them) so that every length shares one compiled layer."""
    n = len(tokens)
    toks = np.zeros((max(pad_to, n),), np.int32)
    toks[:n] = np.asarray(tokens)
    stacked = m["num_layers"] > 1
    items = tuple(sorted(m.items()))
    x = _embed(params["embed"]["table"], jnp.asarray(toks))[None]
    block = params["stages"][0][0]
    for i in range(m["num_layers"]):
        x = _layer_at(block, x, jnp.int32(i) if stacked else None, items,
                      prec)
    return _head(params["final_norm"]["scale"], head_weight(params, m),
                 x[0], m["norm_eps"], prec)[:n]


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(F32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_at(block, x, i, items, prec):
    p = block if i is None else jax.tree.map(lambda a: a[i], block)
    return layer(p, x, dict(items), prec)


@functools.partial(jax.jit, static_argnums=(4,))
def _head(scale, w, x, eps, prec):
    return mm(rms_norm(x, scale, eps), w, prec)
