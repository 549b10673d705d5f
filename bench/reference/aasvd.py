"""Plain reference of AA-SVD compression (Algorithm 2), layer by layer.

For each decoder layer, in the order q/k/v, o, gate/up, down (linears
that share an input share its statistics):

1. sequential calibration: the original layer runs on the original
   stream X, the partly compressed layer on the shifted stream X'; the
   inputs of the group's linears give C = Σ xᵀx' and S = Σ x'ᵀx'.
2. the anchored solve (Theorem 3.2) in float64 on the host: S = Q Λ Qᵀ
   with Λ clamped at eps·max Λ, L⁻ᵀ = Q Λ^-1/2, M = Wᵀ C L⁻ᵀ, and the
   rank-k truncated SVD M ≈ A Bᵀ gives v = L⁻ᵀ B, u = Aᵀ.  The rank is
   floor(ratio·mn/(m+n)) rounded up to the rank multiple.
3. block refinement: AdamW (lr 1e-4, betas 0.9/0.999, eps 1e-8, global
   gradient clip 1.0, no decay) on every param of the layer, over
   ``epochs`` passes of the microbatches in order, lr scaled by a linear
   warmup over the first 10% of steps (the first step at 0) and a cosine
   decay to 0; the loss is the mean squared error of the compressed
   layer on X' against the original layer's output on X.
4. X ← layer(X), X' ← compressed layer(X').

Everything but the solve runs in float32 at HIGHEST precision.
``prec="low"`` is the control: every matmul operand of the layer
forwards (calibration, anchors, refinement) in fp8, as ``decoder`` rounds
them; the statistics still accumulate in float32 and the solve runs in
float64.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import decoder as D

F32 = jnp.float32
GROUPS = (("qkv", (("attn", "wq"), ("attn", "wk"), ("attn", "wv"))),
          ("o", (("attn", "wo"),)),
          ("ffn_in", (("ffn", "gate"), ("ffn", "up"))),
          ("down", (("ffn", "down"),)))


def rank_for_ratio(n: int, m: int, ratio: float, multiple: int) -> int:
    cap = max(1, (m * n) // (m + n))
    k = max(1, int(math.floor(ratio * m * n / (m + n))))
    if multiple > 1:
        k = min(-(-k // multiple) * multiple, cap)
    return max(1, k)


def anchored_solve(w: np.ndarray, c: np.ndarray, s: np.ndarray, k: int,
                   eps: float = 1e-6):
    """w (n, m), c = Σ xᵀx', s = Σ x'ᵀx' (n, n) -> (v (n, k), u (k, m))."""
    w, c, s = (np.asarray(a, np.float64) for a in (w, c, s))
    s = 0.5 * (s + s.T)
    lam, q = np.linalg.eigh(s)
    lam = np.maximum(lam, eps * max(lam.max(), 1e-12))
    l_inv_t = q / np.sqrt(lam)[None, :]
    mat = w.T @ (c @ l_inv_t)
    u_, sig, vt = np.linalg.svd(mat, full_matrices=False)
    v = l_inv_t @ vt[:k].T
    u = (u_[:, :k] * sig[:k][None, :]).T
    return v.astype(np.float32), u.astype(np.float32)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


@functools.lru_cache(maxsize=None)
def _fns(model_items, prec: str):
    m = dict(model_items)

    @jax.jit
    def fwd(p, x):
        return D.layer(p, x, m, prec)

    @functools.partial(jax.jit, static_argnames=("group",))
    def covs(p_orig, p_cur, x, xp, c, s, group):
        _, ta = D.layer(p_orig, x, m, prec, taps=True)
        _, tb = D.layer(p_cur, xp, m, prec, taps=True)
        a = ta[group].reshape(-1, ta[group].shape[-1])
        b = tb[group].reshape(-1, tb[group].shape[-1])
        return (c + D.mm(a.T, b, prec), s + D.mm(b.T, b, prec))

    def loss(p, xp, y):
        def one(tot, xy):
            out = D.layer(p, xy[0][None], m, prec)
            return tot + jnp.mean(jnp.square(out - xy[1][None])), None
        tot, _ = jax.lax.scan(one, jnp.zeros((), F32), (xp, y))
        return tot / xp.shape[0]

    @jax.jit
    def step(p, mom, vel, t, xp, y, lr_scale):
        g = jax.grad(loss)(p, xp, y)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(a))
                             for a in jax.tree.leaves(g)) + 1e-20)
        clip = jnp.minimum(1.0, 1.0 / jnp.maximum(gnorm, 1e-12))
        t = t + 1
        b1t = 1.0 - 0.9 ** t.astype(F32)
        b2t = 1.0 - 0.999 ** t.astype(F32)
        g = jax.tree.map(lambda a: a * clip, g)
        mom = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, mom, g)
        vel = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, vel, g)
        p = jax.tree.map(
            lambda w, a, b: w - 1e-4 * lr_scale
            * (a / b1t) / (jnp.sqrt(b / b2t) + 1e-8), p, mom, vel)
        return p, mom, vel, t

    return fwd, covs, step


def lr_scale(step: int, total: int, warmup: int) -> float:
    if step < warmup:
        return step / max(warmup, 1)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return 0.5 * (1.0 + math.cos(math.pi * prog))


def compress(layers: List[Dict], m: Dict, x0: List, *, ratio: float,
             rank_multiple: int, epochs: int, eps: float = 1e-6,
             prec: str = "f32") -> List[Dict]:
    """Compress ``layers`` (dense per-layer param dicts) calibrated on the
    embedded stream ``x0`` (list of (mb, S, d) microbatches), refining
    each for ``epochs`` (0: no refinement); returns the compressed
    per-layer param dicts (float32)."""
    fwd, covs, step = _fns(tuple(sorted(m.items())), prec)
    xs = [jnp.asarray(x, F32) for x in x0]
    xps = list(xs)
    out = []
    for dense in layers:
        orig = _f32(dense)
        cur = jax.tree.map(lambda a: a, orig)
        for group, members in GROUPS:
            n = orig[members[0][0]][members[0][1]]["w"].shape[0]
            c = jnp.zeros((n, n), F32)
            s = jnp.zeros((n, n), F32)
            for x, xp in zip(xs, xps):
                c, s = covs(orig, cur, x, xp, c, s, group=group)
            c, s = np.asarray(c), np.asarray(s)
            for sub, name in members:
                w = np.asarray(orig[sub][name]["w"])
                k = rank_for_ratio(w.shape[0], w.shape[1], ratio,
                                   rank_multiple)
                v, u = anchored_solve(w, c, s, k, eps)
                cur[sub] = dict(cur[sub])
                cur[sub][name] = {"v": jnp.asarray(v), "u": jnp.asarray(u)}
        ys = [fwd(orig, x) for x in xs]
        total = max(1, epochs * len(xps))
        warmup = max(1, int(0.1 * total))
        mom = jax.tree.map(jnp.zeros_like, cur)
        vel = jax.tree.map(jnp.zeros_like, cur)
        t = jnp.zeros((), jnp.int32)
        i = 0
        for _ in range(epochs):
            for xp, y in zip(xps, ys):
                cur, mom, vel, t = step(cur, mom, vel, t, xp, y,
                                        jnp.float32(lr_scale(i, total,
                                                             warmup)))
                i += 1
        xps = [fwd(cur, xp) for xp in xps]
        xs = ys
        out.append(cur)
    return out


def stream_output(layers: List[Dict], m: Dict, x0: List,
                  prec: str = "f32") -> List:
    """Output of ``layers`` (dense or factorized) on the embedded stream."""
    fwd, _, _ = _fns(tuple(sorted(m.items())), prec)
    xs = [jnp.asarray(x, F32) for x in x0]
    for p in layers:
        p = _f32(p)
        xs = [fwd(p, x) for x in xs]
    return xs
