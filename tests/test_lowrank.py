"""Theorem 3.2 / Lemma 3.1 correctness, incl. hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="dev dependency (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import calibration as C
from repro.core import lowrank as LR

KEY = jax.random.PRNGKey(0)


def _problem(seed, n=16, m=12, l=100, shift=0.1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    w_paper = jax.random.normal(ks[0], (m, n))          # y = W x
    a = jax.random.normal(ks[1], (n, l))
    b = a + shift * jax.random.normal(ks[2], (n, l))
    return w_paper, a, b


def _objective(w_paper, a, b, factors):
    wp = LR.merge_factors(factors).T
    return float(jnp.sum((w_paper @ a - wp @ b) ** 2))


class TestClosedForm:
    def test_matches_both_whitening_paths(self):
        w, a, b = _problem(0)
        f1 = LR.solve_anchored(w.T, a @ b.T, b @ b.T, 5, method="eigh")
        f2 = LR.solve_anchored(w.T, a @ b.T, b @ b.T, 5, method="cholesky")
        assert abs(_objective(w, a, b, f1) - _objective(w, a, b, f2)) < 1e-2

    def test_rank_constraint_respected(self):
        w, a, b = _problem(1)
        f = LR.solve_anchored(w.T, a @ b.T, b @ b.T, 4)
        assert f["v"].shape == (16, 4) and f["u"].shape == (4, 12)
        assert np.linalg.matrix_rank(np.asarray(LR.merge_factors(f))) <= 4

    def test_corollary_3_3_whitening(self):
        """A = B reduces to SVD_k(W L) L^-1 (SVD-LLM / DRONE solution)."""
        w, a, _ = _problem(2)
        f = LR.solve_anchored(w.T, a @ a.T, a @ a.T, 5)
        lam, q = np.linalg.eigh(np.asarray(a @ a.T))
        lmat = q * np.sqrt(np.maximum(lam, 1e-9))
        mm = np.asarray(w) @ lmat
        uu, ss, vt = np.linalg.svd(mm, full_matrices=False)
        wk = (uu[:, :5] * ss[:5]) @ vt[:5] @ np.linalg.inv(lmat)
        got = _objective(w, a, a, f)
        want = float(np.sum((np.asarray(w @ a) - wk @ np.asarray(a)) ** 2))
        assert abs(got - want) / max(want, 1e-6) < 1e-3

    def test_full_rank_recovers_exact_regression(self):
        """k = min(m, n): no truncation — residual equals unconstrained
        least-squares optimum."""
        w, a, b = _problem(3, n=8, m=8, l=64)
        f = LR.solve_anchored(w.T, a @ b.T, b @ b.T, 8)
        # unconstrained optimum: W* = W A Bᵀ (B Bᵀ)⁻¹
        wstar = np.asarray(w @ a @ b.T) @ np.linalg.inv(np.asarray(b @ b.T))
        want = float(np.sum((np.asarray(w @ a) - wstar @ np.asarray(b)) ** 2))
        got = _objective(w, a, b, f)
        assert got <= want * 1.001 + 1e-4

    def test_agnostic_matches_eckart_young(self):
        w, _, _ = _problem(4)
        f = LR.solve_agnostic(w.T, 5)
        s = np.linalg.svd(np.asarray(w), compute_uv=False)
        got = float(jnp.sum((w - LR.merge_factors(f).T) ** 2))
        assert abs(got - float((s[5:] ** 2).sum())) < 1e-3

    def test_tikhonov_handles_singular_covariance(self):
        """Rank-deficient B (fewer samples than dims): remark after Thm 3.2."""
        w, a, _ = _problem(5, n=16, m=12, l=8)   # l < n -> singular BBᵀ
        b = a
        f = LR.solve_anchored(w.T, a @ b.T, b @ b.T, 4)
        assert np.isfinite(np.asarray(LR.merge_factors(f))).all()

    def test_factor_error_formula(self):
        w, a, b = _problem(6)
        f = LR.solve_anchored(w.T, a @ b.T, b @ b.T, 5)
        via_cov = float(LR.factor_error(w.T, f, a @ b.T, b @ b.T, a @ a.T))
        direct = _objective(w, a, b, f)
        assert abs(via_cov - direct) / max(direct, 1e-6) < 1e-3


class TestGramSVD:
    """The TPU's SVD path (eigendecomposition of the smaller Gram matrix),
    run here with the CPU eigensolver, against LAPACK's SVD."""

    @pytest.mark.parametrize("shape", [(96, 40), (40, 96), (64, 64)])
    def test_matches_lapack_svd(self, shape):
        rng = np.random.default_rng(0)
        # well-separated spectrum 1 .. 1e-2: the Gram squares it to 1e-4,
        # far above fp32 rounding
        r = min(shape)
        u0, _ = np.linalg.qr(rng.standard_normal((shape[0], r)))
        v0, _ = np.linalg.qr(rng.standard_normal((shape[1], r)))
        s0 = np.logspace(0, -2, r)
        mat = jnp.asarray((u0 * s0) @ v0.T, jnp.float32)
        eigh = lambda a: tuple(jnp.linalg.eigh(a))  # noqa: E731
        u, s, vt = LR._svd_via_gram(mat, eigh)
        np.testing.assert_allclose(np.asarray(s), s0, rtol=1e-3, atol=1e-5)
        for k in (1, r // 3, r):
            got = (u[:, :k] * s[:k]) @ vt[:k]
            want = (u0[:, :k] * s0[:k]) @ v0[:, :k].T
            err = np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want)
            assert err < 1e-4, (k, err)

    def test_zero_directions_get_zero_vectors(self):
        mat = jnp.zeros((8, 5), jnp.float32).at[0, 0].set(2.0)
        eigh = lambda a: tuple(jnp.linalg.eigh(a))  # noqa: E731
        u, s, vt = LR._svd_via_gram(mat, eigh)
        assert np.isfinite(np.asarray(u)).all()
        np.testing.assert_allclose(np.asarray((u * s) @ vt), np.asarray(mat),
                                   atol=1e-6)


class TestOptimality:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10_000), k=st.integers(1, 8))
    def test_closed_form_beats_perturbations(self, seed, k):
        """Property: no perturbed factorization does better (local optimality
        certificate of Thm 3.2 on random instances)."""
        w, a, b = _problem(seed)
        f = LR.solve_anchored(w.T, a @ b.T, b @ b.T, k)
        base = _objective(w, a, b, f)
        rng = np.random.RandomState(seed)
        for scale in (1e-3, 1e-2, 1e-1):
            fp = {"u": f["u"] + scale * rng.randn(*f["u"].shape),
                  "v": f["v"] + scale * rng.randn(*f["v"].shape)}
            assert _objective(w, a, b, fp) >= base - 1e-3 - 1e-4 * base

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 10_000))
    def test_anchored_optimal_for_its_own_objective(self, seed):
        """The anchored solution beats input-aware and shift-aware solutions
        ON the anchored objective ||WX − W'X'||² (they solve different
        problems; Thm 3.2 is the optimum of this one)."""
        w, a, b = _problem(seed, shift=0.3)
        covs = {"xx": a @ a.T, "xxp": a @ b.T, "xpxp": b @ b.T}
        f_anch = LR.solve_anchored(w.T, covs["xxp"], covs["xpxp"], 5)
        f_in = LR.solve_anchored(w.T, covs["xx"], covs["xx"], 5)
        f_sh = LR.solve_anchored(w.T, covs["xpxp"], covs["xpxp"], 5)
        e_anch = _objective(w, a, b, f_anch)
        assert e_anch <= _objective(w, a, b, f_in) + 1e-3
        assert e_anch <= _objective(w, a, b, f_sh) + 1e-3


class TestCalibration:
    def test_streaming_equals_batch(self):
        ks = jax.random.split(KEY, 4)
        x = jax.random.normal(ks[0], (64, 12))
        xp = jax.random.normal(ks[1], (64, 12))
        covs = C.init_covs(12)
        for i in range(0, 64, 16):
            covs = C.update_covs(covs, x[i:i + 16], xp[i:i + 16])
        np.testing.assert_allclose(np.asarray(covs["xx"]),
                                   np.asarray(x.T @ x), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(covs["xxp"]),
                                   np.asarray(x.T @ xp), rtol=1e-5)
        assert float(covs["count"]) == 64

    def test_expert_bank_accumulation_ignores_zero_slots(self):
        ks = jax.random.split(KEY, 2)
        e, c, n = 3, 8, 6
        x = jax.random.normal(ks[0], (e, c, n))
        x = x.at[:, 4:].set(0.0)     # empty capacity slots
        covs = C.init_covs(n, experts=e)
        covs = C.update_covs(covs, x, x)
        want = np.einsum("ecn,ecm->enm", np.asarray(x[:, :4]),
                         np.asarray(x[:, :4]))
        np.testing.assert_allclose(np.asarray(covs["xx"]), want, rtol=1e-5)

    def test_objective_covs_mapping(self):
        covs = {"xx": 1, "xxp": 2, "xpxp": 3}
        assert C.objective_covs(covs, "input_aware") == (1, 1)
        assert C.objective_covs(covs, "shift_aware") == (3, 3)
        assert C.objective_covs(covs, "anchored") == (2, 3)
        with pytest.raises(ValueError):
            C.objective_covs(covs, "agnostic")
