"""Theorem 3.2: closed-form anchored-adaptive low-rank solve.

Paper convention: weight W ∈ R^{m×n}, activations X, X' ∈ R^{n×l} stacked
column-wise; objective  min_{rank k} ||W X − W' X'||_F².  With
C = X X'ᵀ and S = X' X'ᵀ = L Lᵀ, the optimum is

    W'* = SVD_k(W C S⁻¹ L) L⁻¹ = U Vᵀ,   U = U_k Σ_k,  V = L⁻ᵀ V_k.

Our linear layers store w = Wᵀ (in, out) and compute y = x @ w, so the
factor pair returned here is {"v": V (n, k), "u": Uᵀ (k, m)} with
y = (x @ v) @ u — identical math, row-major activations.

Factorization of S: the default is the eigendecomposition path
L = Q Λ^{1/2} (SVD-LLM-V2 style) — on TPU ``eigh`` is robust and gives the
Tikhonov fallback for free (eigenvalue clamping); a Cholesky path is provided
for parity with SVD-LLM.  Both are covered by the same theorem (App. A).

Everything here operates on n×n covariances, never raw activations, so cost
is independent of the calibration token count (App. B.1).

Decompositions on TPU.  The TPU's default ``eigh`` and ``svd`` (QDWH
divide-and-conquer) take minutes to compile at these widths — 82 s for one
1024×1024 ``eigh`` on a v5e, and a qwen3-0.6b compression spent over 15
minutes compiling its solves.  On TPU, ``_eigh`` therefore uses the native
Jacobi eigensolver, which compiles in seconds, and ``_svd`` is taken from
the Jacobi eigendecomposition of the smaller Gram matrix.  Every matmul
of the solve runs at HIGHEST precision: the TPU's default takes an fp32
matmul in one bf16 pass, which the Gram matrix's squared spectrum cannot
afford.  Other platforms keep their LAPACK-backed ``eigh``/``svd``.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.lax.linalg import EighImplementation

_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


def _eigh_jacobi(a):
    v, w = jax.lax.linalg.eigh(a, symmetrize_input=False,
                               implementation=EighImplementation.JACOBI)
    return w, v


def _eigh(a):
    """(ascending eigenvalues, eigenvectors) of a symmetric fp32 matrix."""
    return jax.lax.platform_dependent(
        a, tpu=_eigh_jacobi, default=lambda x: tuple(jnp.linalg.eigh(x)))


def _svd_via_gram(mat, eigh=_eigh_jacobi):
    """Thin SVD (u, s, vt) of ``mat`` (m, n) from the eigendecomposition of
    its smaller Gram matrix, singular values descending.  Directions whose
    singular value is zero get zero vectors on the other side (they carry
    no energy, so every rank-k product is unchanged)."""
    m, n = mat.shape
    tall = m >= n
    gram = _mm(mat.T, mat) if tall else _mm(mat, mat.T)
    w, q = eigh(gram)
    w, q = w[::-1], q[:, ::-1]
    s = jnp.sqrt(jnp.maximum(w, 0.0))
    inv = jnp.where(s > 0, 1.0 / jnp.where(s > 0, s, 1.0), 0.0)
    if tall:
        return _mm(mat, q) * inv[None, :], s, q.T
    return q, s, _mm(q.T, mat) * inv[:, None]


def _svd(mat):
    """Thin SVD (u, s, vt) of a fp32 matrix; see the module docstring."""
    return jax.lax.platform_dependent(
        mat, tpu=_svd_via_gram,
        default=lambda x: tuple(jnp.linalg.svd(x, full_matrices=False)))


def _svd_truncate(mat: jnp.ndarray, k: int):
    """Rank-k SVD factors of ``mat`` (m, n) plus the FULL spectrum (the
    same decomposition serves the solve and the adaptive loss estimate):
    returns (A (m,k), B (n,k), σ) with mat ≈ A @ B.T."""
    u, s, vt = _svd(mat.astype(jnp.float32))
    return u[:, :k] * s[:k][None, :], vt[:k].T, s


def eckart_young(mat: jnp.ndarray, k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Best rank-k factors of ``mat`` (m, n): returns (A (m,k), B (n,k)) with
    mat ≈ A @ B.T (Lemma 3.1)."""
    a_fac, b_fac, _ = _svd_truncate(mat, k)
    return a_fac, b_fac


def _whitening_factors(s_cov: jnp.ndarray, *, eps: float, method: str):
    """Return (L, L^{-T}) for S = L Lᵀ with regularization.

    eigh path: L = Q Λ^{1/2}, L^{-T} = Q Λ^{-1/2} (symmetric whitening).
    cholesky path: lower-triangular L of S + εI.
    """
    n = s_cov.shape[0]
    s_cov = 0.5 * (s_cov + s_cov.T)
    if method == "cholesky":
        ridge = eps * jnp.maximum(jnp.trace(s_cov) / n, 1e-12)
        l_fac = jnp.linalg.cholesky(s_cov + ridge * jnp.eye(n, dtype=s_cov.dtype))
        l_inv_t = jax.scipy.linalg.solve_triangular(
            l_fac, jnp.eye(n, dtype=s_cov.dtype), lower=True).T
        return l_fac, l_inv_t
    lam, q = _eigh(s_cov)
    floor = eps * jnp.maximum(jnp.max(lam), 1e-12)
    lam = jnp.maximum(lam, floor)                     # Tikhonov clamp
    sqrt_lam = jnp.sqrt(lam)
    l_fac = q * sqrt_lam[None, :]                     # Q Λ^{1/2}
    l_inv_t = q / sqrt_lam[None, :]                   # Q Λ^{-1/2} = L^{-T}
    return l_fac, l_inv_t


def _anchored_core(w, cov_ab, cov_bb, k: int, eps: float, method: str):
    """Shared body of the anchored solve: returns the factor pair AND the
    full singular spectrum of M (the SVD computes it either way — the
    adaptive estimate sweep reads the tail instead of re-running the
    whitening + SVD a second time)."""
    n, m = w.shape
    k = min(k, n, m)
    wf = w.astype(jnp.float32)
    l_fac, l_inv_t = _whitening_factors(cov_bb.astype(jnp.float32),
                                        eps=eps, method=method)
    # M = W C S^{-1} L = W C L^{-T}   (since S^{-1} L = L^{-T})
    mat = _mm(wf.T, _mm(cov_ab.astype(jnp.float32), l_inv_t))  # (m, n)
    a_fac, b_fac, s = _svd_truncate(mat, k)                    # M ≈ A Bᵀ
    v = _mm(l_inv_t, b_fac)                                    # (n, k)
    u = a_fac.T                                                # (k, m)
    return {"v": v, "u": u}, s


@functools.partial(jax.jit, static_argnames=("k", "method"))
def solve_anchored(w: jnp.ndarray, cov_ab: jnp.ndarray, cov_bb: jnp.ndarray,
                   k: int, *, eps: float = 1e-6,
                   method: str = "eigh") -> Dict[str, jnp.ndarray]:
    """Solve min_{rank k} ||W A − W' B||² from covariances (Thm 3.2).

    w:      (n, m)  — our storage Wᵀ (y = x @ w)
    cov_ab: (n, n)  — A Bᵀ accumulated as Σ x_rowᵀ x'_row
    cov_bb: (n, n)  — B Bᵀ accumulated as Σ x'_rowᵀ x'_row
    Returns {"v": (n, k), "u": (k, m)} with W' = (x@v)@u.
    """
    return _anchored_core(w, cov_ab, cov_bb, k, eps, method)[0]


@functools.partial(jax.jit, static_argnames=("k", "method"))
def solve_anchored_with_spectrum(w, cov_ab, cov_bb, k: int, *,
                                 eps: float = 1e-6, method: str = "eigh"):
    """The anchored solve plus the full spectrum of M — one whitening, one
    SVD (the adaptive estimate sweep's path)."""
    return _anchored_core(w, cov_ab, cov_bb, k, eps, method)


def _agnostic_core(w, k: int):
    n, m = w.shape
    k = min(k, n, m)
    a_fac, b_fac, s = _svd_truncate(w.astype(jnp.float32).T, k)  # W ≈ A Bᵀ
    return {"v": b_fac, "u": a_fac.T}, s


@functools.partial(jax.jit, static_argnames=("k",))
def solve_agnostic(w: jnp.ndarray, k: int) -> Dict[str, jnp.ndarray]:
    """Input-agnostic truncated SVD: min ||W − W'||_F (Eckart–Young)."""
    return _agnostic_core(w, k)[0]


@functools.partial(jax.jit, static_argnames=("k",))
def solve_agnostic_with_spectrum(w: jnp.ndarray, k: int):
    """The agnostic solve plus the full weight spectrum."""
    return _agnostic_core(w, k)


@functools.partial(jax.jit, static_argnames=("method",))
def whitened_spectrum(w: jnp.ndarray, cov_ab: jnp.ndarray,
                      cov_bb: jnp.ndarray, *, eps: float = 1e-6,
                      method: str = "eigh") -> jnp.ndarray:
    """Singular values of M = Wᵀ C L^{-T} — the spectrum the anchored solve
    truncates, so the exact objective loss of keeping rank k is the tail
    energy Σ_{j>k} σ_j² (Thm 3.2).  This is the per-linear signal the
    adaptive rank allocator water-fills on; it is pure linalg on the
    accumulated covariances (no forwards)."""
    wf = w.astype(jnp.float32)
    _, l_inv_t = _whitening_factors(cov_bb.astype(jnp.float32),
                                    eps=eps, method=method)
    mat = _mm(wf.T, _mm(cov_ab.astype(jnp.float32), l_inv_t))
    return _svd(mat)[1]


@jax.jit
def weight_spectrum(w: jnp.ndarray) -> jnp.ndarray:
    """Plain singular values of W — the agnostic-objective analogue of
    ``whitened_spectrum`` (Eckart–Young tail energy)."""
    return _svd(w.astype(jnp.float32))[1]


def spectrum_tail_energy(spectrum, k: int) -> float:
    """Truncation-loss estimate Σ_{j>k} σ_j² (summed over leading bank
    axes for vmapped expert spectra)."""
    import numpy as np
    s = np.asarray(spectrum)
    return float(np.sum(s[..., k:] ** 2))


def factor_error(w, factors, cov_ab, cov_bb, cov_aa) -> jnp.ndarray:
    """||W A − W' B||² from covariances only:
    tr(W S_aa Wᵀ) − 2 tr(W C W'ᵀ) + tr(W' S_bb W'ᵀ)."""
    wf = w.astype(jnp.float32).T                               # (m, n)
    wp = (factors["v"] @ factors["u"]).astype(jnp.float32).T   # (m, n)
    t1 = jnp.sum((wf @ cov_aa) * wf)
    t2 = jnp.sum((wf @ cov_ab) * wp)
    t3 = jnp.sum((wp @ cov_bb) * wp)
    return t1 - 2.0 * t2 + t3


def merge_factors(factors) -> jnp.ndarray:
    """Dense (n, m) reconstruction of the factorized weight."""
    return factors["v"] @ factors["u"]
