"""Streaming calibration engine: forward-count bounds and seed parity.

The engine's contract (ISSUE 1, extended by ISSUE 2):
  * ``calib_mode="sequential"`` reproduces the seed per-group replay loop
    bit-for-bit (same covariances, same solves, same compressed params) at
    2·G·B tapped block forwards per unit;
  * ``calib_mode="fused"`` issues ≤ (G+1)·B tapped forwards per unit (one
    tapped pass per microbatch per stream feeds every accumulator);
  * the scan-batched collection sweep (``scan=True``: one jitted
    ``lax.scan`` over microbatches with the accumulators as carry) matches
    the per-microbatch loop to fp32 tolerance on unaligned shapes, ragged
    tails included (the three-mode policy itself is locked down in
    tests/test_calib_parity.py).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import CompressConfig, compress_model
from repro.core import calibration as C
from repro.core import pipeline as P
from repro.core import streaming as S
from repro.data import calibration_set
from repro.kernels import ref
from repro.launch.mesh import make_mesh
from repro.models import layers as L
from repro.models import model as M

KEY = jax.random.PRNGKey(0)


def setup(arch="llama-7b", n=8, l=16):
    cfg = get_smoke_config(arch).replace(dtype="float32")
    params = M.init_params(cfg, KEY)
    calib = calibration_set(cfg, n, l)
    return cfg, params, calib


def seed_reference_compress(params, cfg, calib, ccfg):
    """The seed driver's stage-1 + propagate loop, verbatim semantics
    (refine off, decoder-only archs): per tap group, replay BOTH streams
    over every microbatch, accumulate that group's covariances, solve, and
    swap — the parity oracle for calib_mode="sequential"."""
    params = jax.tree.map(lambda x: x, params)
    units = P.unroll_units(params, cfg)
    mb = ccfg.microbatch
    xs = P._embed_stream(params, cfg, calib, mb)
    xps = [jnp.copy(x) for x in xs]

    for unit in units:
        seq_len = xs[0].shape[1]
        orig_p = jax.tree.map(lambda x: x, unit.params)
        cur_p = unit.params
        fwd_taps = P.make_unit_apply(unit.kind, cfg, seq_len, want_taps=True)
        fwd = P.make_unit_apply(unit.kind, cfg, seq_len, want_taps=False)
        for tap, group in P.tap_groups(P.linear_specs(unit.kind, cfg)):
            covs = None
            is_bank = group[0][2]
            if ccfg.objective != "agnostic":
                for i in range(len(xs)):
                    _, taps_o = fwd_taps(orig_p, xs[i], None)
                    _, taps_c = fwd_taps(cur_p, xps[i], None)
                    a_act, b_act = taps_o[tap], taps_c[tap]
                    if not is_bank:
                        a_act = a_act.reshape(-1, a_act.shape[-1])
                        b_act = b_act.reshape(-1, b_act.shape[-1])
                    if covs is None:
                        experts = a_act.shape[0] if is_bank else 0
                        covs = C.init_covs(a_act.shape[-1], experts)
                    covs = C.update_covs(covs, a_act, b_act)
            for path, _, _bank, *_ in group:
                wp = P.get_path(cur_p, path)
                w = wp["w"]
                k = P._weight_rank(w, ccfg)
                factors = P._solve_weight(w, covs, k, ccfg)
                new_p = {kk: vv for kk, vv in wp.items() if kk != "w"}
                new_p.update(factors)
                P.set_path(cur_p, path, new_p)
        y_anchor = [fwd(orig_p, xs[i], None).astype(jnp.float32)
                    for i in range(len(xs))]
        for i in range(len(xs)):
            xs[i] = y_anchor[i].astype(xs[i].dtype)
            xps[i] = fwd(cur_p, xps[i], None)
        unit.params = cur_p
    return P.restack_units(params, cfg, units)


class TestForwardCounts:
    @pytest.mark.parametrize("mode", ["sequential", "fused"])
    def test_tapped_forward_bounds(self, mode):
        n_calib, mb = 8, 4
        cfg, params, calib = setup(n=n_calib)
        _, rep = compress_model(
            params, cfg, calib,
            CompressConfig(ratio=0.6, refine=False, rank_multiple=1,
                           microbatch=mb, calib_mode=mode))
        b = math.ceil(n_calib / mb)
        checked = 0
        for u in rep["units"]:
            if u.get("reused") or "tapped_forwards" not in u:
                continue
            g = len(P.tap_groups(P.linear_specs(u["kind"], cfg)))
            if mode == "sequential":
                assert u["tapped_forwards"] == 2 * g * b, u["name"]
            else:
                assert u["tapped_forwards"] <= (g + 1) * b, u["name"]
            checked += 1
        assert checked > 0
        assert rep["calibration"]["mode"] == mode
        assert rep["calibration"]["tapped_forwards"] == sum(
            u.get("tapped_forwards", 0) for u in rep["units"])

    def test_fused_strictly_cheaper_than_sequential(self):
        cfg, params, calib = setup()
        counts = {}
        for mode in ("sequential", "fused"):
            _, rep = compress_model(
                params, cfg, calib,
                CompressConfig(ratio=0.6, refine=False, rank_multiple=1,
                               microbatch=4, calib_mode=mode))
            counts[mode] = rep["calibration"]["tapped_forwards"]
        assert counts["fused"] < counts["sequential"], counts

    def test_agnostic_needs_no_tapped_forwards(self):
        cfg, params, calib = setup(n=4)
        _, rep = compress_model(
            params, cfg, calib,
            CompressConfig(ratio=0.6, objective="agnostic", refine=False,
                           rank_multiple=1, microbatch=4))
        assert rep["calibration"]["tapped_forwards"] == 0


class TestSeedParity:
    def test_sequential_bit_for_bit_matches_seed_loop(self):
        cfg, params, calib = setup()
        ccfg = CompressConfig(ratio=0.6, refine=False, rank_multiple=1,
                              microbatch=4, calib_mode="sequential")
        want = seed_reference_compress(params, cfg, calib, ccfg)
        got, _ = compress_model(params, cfg, calib, ccfg)
        w_leaves, w_def = jax.tree_util.tree_flatten(want)
        g_leaves, g_def = jax.tree_util.tree_flatten(got)
        assert w_def == g_def
        for i, (a, b) in enumerate(zip(g_leaves, w_leaves)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"leaf {i}")

    def test_fused_same_structure_and_finite(self):
        cfg, params, calib = setup()
        seq, _ = compress_model(
            params, cfg, calib,
            CompressConfig(ratio=0.6, refine=False, rank_multiple=1,
                           microbatch=4, calib_mode="sequential"))
        fused, _ = compress_model(
            params, cfg, calib,
            CompressConfig(ratio=0.6, refine=False, rank_multiple=1,
                           microbatch=4, calib_mode="fused"))
        t1 = jax.tree.map(lambda x: x.shape, seq)
        t2 = jax.tree.map(lambda x: x.shape, fused)
        assert jax.tree_util.tree_structure(t1) == \
            jax.tree_util.tree_structure(t2)
        assert jax.tree.leaves(t1) == jax.tree.leaves(t2)
        batch = {"tokens": calib["tokens"][:4], "labels": calib["tokens"][:4]}
        assert np.isfinite(float(M.loss_fn(fused, cfg, batch)[0]))

    @pytest.mark.parametrize("objective", ["anchored", "agnostic"])
    def test_unknown_calib_mode_raises(self, objective):
        cfg, params, calib = setup(n=4)
        with pytest.raises(ValueError, match="calib_mode"):
            compress_model(params, cfg, calib,
                           CompressConfig(objective=objective, refine=False,
                                          rank_multiple=1,
                                          calib_mode="bogus"))

    @pytest.mark.parametrize("bad", ["bogus", "dataless"])
    def test_bad_calib_mesh_raises(self, bad):
        """Unknown strings and meshes without a data axis both get a clear
        ValueError, not a KeyError from deep inside the sharding rules."""
        cfg, params, calib = setup(n=4)
        mesh = bad if bad == "bogus" else make_mesh((1,), ("model",))
        with pytest.raises(ValueError, match="calib_mesh"):
            compress_model(params, cfg, calib,
                           CompressConfig(refine=False, rank_multiple=1,
                                          calib_mesh=mesh))


class TestEngineUnits:
    def _toy_groups_and_fwd(self):
        groups = [("mlp/in", [("mlp.w", "mlp/in", False)]),
                  ("bank/in", [("bank.w", "bank/in", True)])]

        def fwd(p, x, aux):
            store = {}
            with L.sowing(store):
                L.sow("mlp/in", x)
                # (E=2, C, n) capacity buffer built from the first sequence
                L.sow("bank/in", jnp.stack([x[0], 2.0 * x[0]]))
            return x, store
        return groups, fwd

    def test_tap_shapes_discovers_all_taps(self):
        groups, fwd = self._toy_groups_and_fwd()
        x = jnp.ones((2, 3, 8))
        shapes = L.tap_shapes(fwd, None, x, None)
        assert set(shapes) == {"mlp/in", "bank/in"}
        assert shapes["mlp/in"].shape == (2, 3, 8)
        assert shapes["bank/in"].shape == (2, 3, 8)

    def test_engine_accumulates_like_reference(self):
        groups, fwd = self._toy_groups_and_fwd()
        x = jax.random.normal(KEY, (2, 5, 8))
        xp = x + 0.1
        eng = S.CalibrationEngine.for_unit(groups, fwd, None, x, None)
        assert eng.accumulators == {}  # lazy: nothing allocated yet
        assert eng.covs_for("mlp/in")["xx"].shape == (8, 8)
        _, taps_o = fwd(None, x, None)
        _, taps_c = fwd(None, xp, None)
        eng.consume(taps_o, taps_c)
        eng.consume(taps_o, taps_c)
        want = ref.cov_accum_ref(x.reshape(-1, 8), xp.reshape(-1, 8))
        covs = eng.covs_for("mlp/in")
        for key, w in zip(("xx", "xxp", "xpxp"), want):
            np.testing.assert_allclose(np.asarray(covs[key]),
                                       2 * np.asarray(w), rtol=1e-5)
        assert float(covs["count"]) == 20.0
        assert eng.stats["tap_updates"] == 4

    def test_consume_only_filters(self):
        groups, fwd = self._toy_groups_and_fwd()
        x = jax.random.normal(KEY, (1, 4, 8))
        eng = S.CalibrationEngine.for_unit(groups, fwd, None, x, None)
        _, taps = fwd(None, x, None)
        eng.consume(taps, taps, only={"mlp/in"})
        # only= keeps the other tap unallocated (sequential peak memory)
        assert set(eng.accumulators) == {"mlp/in"}
        assert float(eng.covs_for("mlp/in")["count"]) == 4.0
        assert float(eng.covs_for("bank/in")["count"]) == 0.0

    def test_release_frees_and_rejects_resurrection(self):
        groups, fwd = self._toy_groups_and_fwd()
        x = jax.random.normal(KEY, (1, 4, 8))
        eng = S.CalibrationEngine.for_unit(groups, fwd, None, x, None)
        _, taps = fwd(None, x, None)
        eng.consume(taps, taps, only={"mlp/in"})
        eng.release("mlp/in")
        assert "mlp/in" not in eng.accumulators
        # a solved tap must never silently come back as zeroed state
        with pytest.raises(RuntimeError, match="released"):
            eng.covs_for("mlp/in")

    def test_collect_fused_returns_anchor_outputs(self):
        groups, fwd = self._toy_groups_and_fwd()
        xs = [jax.random.normal(KEY, (1, 4, 8)), jnp.ones((1, 4, 8))]
        eng = S.CalibrationEngine.for_unit(groups, fwd, None, xs[0], None)
        ys = eng.collect_fused(fwd, None, None, xs, xs, None, None)
        assert len(ys) == 2  # one original-stream output per microbatch
        for y, x in zip(ys, xs):  # toy fwd is identity
            np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        assert eng.stats["tapped_forwards"] == 4

    def test_collect_fused_skip_excludes_taps(self):
        """Hybrid's fused pass must not mix pre-solve statistics into the
        accumulators its replay groups fill later."""
        groups, fwd = self._toy_groups_and_fwd()
        x = jax.random.normal(KEY, (1, 4, 8))
        eng = S.CalibrationEngine.for_unit(groups, fwd, None, x, None)
        ys = eng.collect_fused(fwd, None, None, [x], [x], None, None,
                               skip={"bank/in"})
        assert len(ys) == 1  # anchors still produced
        assert set(eng.accumulators) == {"mlp/in"}
        assert float(eng.covs_for("bank/in")["count"]) == 0.0


class TestScanCollection:
    """Scan-batched sweep vs the per-microbatch loop (ISSUE 2 regression):
    same covariances to fp32 tolerance on the unaligned shapes exercised by
    tests/test_kernels.py, same anchors, same forward accounting."""

    # (tokens, features) pairs not divisible by the kernel block multiples
    UNALIGNED = [(300, 192), (130, 100), (513, 384), (96, 72)]

    def _groups_and_fwd(self):
        groups = [("mlp/in", [("mlp.w", "mlp/in", False)]),
                  ("bank/in", [("bank.w", "bank/in", True)])]

        def fwd(p, x, aux):
            store = {}
            with L.sowing(store):
                L.sow("mlp/in", x)
                L.sow("bank/in", jnp.stack([x[0], 2.0 * x[0]]))
            return 3.0 * x, store
        return groups, fwd

    def _engines(self, xs, xps, *, skip=None):
        groups, fwd = self._groups_and_fwd()
        out = {}
        for scan in (False, True):
            eng = S.CalibrationEngine.for_unit(groups, fwd, None, xs[0],
                                               None)
            ys = eng.collect_fused(fwd, None, None, xs, xps, None, None,
                                   skip=skip, scan=scan)
            out[scan] = (eng, ys)
        return out

    @pytest.mark.parametrize("t,n", UNALIGNED)
    def test_scan_matches_loop_unaligned(self, t, n):
        k1, k2 = jax.random.split(KEY)
        xs = [jax.random.normal(jax.random.fold_in(k1, i), (1, t, n))
              for i in range(3)]
        xps = [x + 0.1 * jax.random.normal(jax.random.fold_in(k2, i),
                                           (1, t, n))
               for i, x in enumerate(xs)]
        out = self._engines(xs, xps)
        eng_loop, ys_loop = out[False]
        eng_scan, ys_scan = out[True]
        assert eng_scan.stats == eng_loop.stats  # 2·B forwards, G·B updates
        for tap in ("mlp/in", "bank/in"):
            cl, cs = eng_loop.covs_for(tap), eng_scan.covs_for(tap)
            for key in ("xx", "xxp", "xpxp"):
                np.testing.assert_allclose(
                    np.asarray(cs[key]), np.asarray(cl[key]),
                    rtol=2e-5, atol=2e-5, err_msg=f"{tap}/{key} t={t} n={n}")
            assert float(cs["count"]) == float(cl["count"])
        for ya, yb in zip(ys_scan, ys_loop):
            np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                                       rtol=1e-6)

    def test_scan_handles_ragged_tail(self):
        """Calibration size not divisible by the microbatch: the scan path
        sweeps the uniform prefix and loops the ragged remainder."""
        t, n = 130, 100
        k1, k2 = jax.random.split(KEY)
        shapes = [(2, t, n), (2, t, n), (1, t, n)]  # ragged last microbatch
        xs = [jax.random.normal(jax.random.fold_in(k1, i), s)
              for i, s in enumerate(shapes)]
        xps = [x + 0.1 * jax.random.normal(jax.random.fold_in(k2, i),
                                           x.shape)
               for i, x in enumerate(xs)]
        out = self._engines(xs, xps)
        eng_loop, ys_loop = out[False]
        eng_scan, ys_scan = out[True]
        assert eng_scan.stats["tapped_forwards"] == 6
        assert len(ys_scan) == len(ys_loop) == 3
        for tap in ("mlp/in", "bank/in"):
            cl, cs = eng_loop.covs_for(tap), eng_scan.covs_for(tap)
            for key in ("xx", "xxp", "xpxp", "count"):
                np.testing.assert_allclose(
                    np.asarray(cs[key]), np.asarray(cl[key]),
                    rtol=2e-5, atol=2e-5, err_msg=f"{tap}/{key}")
        for ya, yb in zip(ys_scan, ys_loop):
            np.testing.assert_allclose(np.asarray(ya), np.asarray(yb),
                                       rtol=1e-6)

    def test_scan_handles_ragged_aux_stream(self):
        """A ragged AUX stream (whisper-style encoder outputs whose tail
        microbatch is shorter) must break the scan's uniform prefix too:
        only xs/xps shapes used to be checked, so mismatched aux shapes
        crashed the stack instead of falling back to the loop."""
        groups = [("mlp/in", [("mlp.w", "mlp/in", False)])]

        def fwd(p, x, aux):
            store = {}
            with L.sowing(store):
                L.sow("mlp/in", x + aux.mean())
            return x, store

        xs = [jax.random.normal(jax.random.fold_in(KEY, i), (1, 96, 72))
              for i in range(3)]
        # x/x' shapes are uniform; ONLY the aux tail is ragged
        aux = [jnp.ones((1, 16, 8)), jnp.ones((1, 16, 8)),
               jnp.ones((1, 7, 8))]
        engines = []
        for scan in (False, True):
            eng = S.CalibrationEngine.for_unit(groups, fwd, None, xs[0],
                                               aux[0])
            eng.collect_fused(fwd, None, None, xs, xs, aux, aux, scan=scan)
            engines.append(eng)
        cl = engines[0].covs_for("mlp/in")
        cs = engines[1].covs_for("mlp/in")
        for key in ("xx", "xxp", "xpxp", "count"):
            np.testing.assert_allclose(np.asarray(cs[key]),
                                       np.asarray(cl[key]),
                                       rtol=2e-5, atol=2e-5)
        assert engines[1].stats["tapped_forwards"] == 6

    def test_scanned_sequential_group_collection(self):
        """collect_group(scan=True) matches the loop for the one-tap
        (sequential/replay) path too."""
        groups, fwd = self._groups_and_fwd()
        xs = [jax.random.normal(jax.random.fold_in(KEY, i), (1, 96, 72))
              for i in range(4)]
        engines = []
        for scan in (False, True):
            eng = S.CalibrationEngine.for_unit(groups, fwd, None, xs[0],
                                               None)
            eng.collect_group("bank/in", fwd, None, None, xs, xs, None,
                              None, scan=scan)
            assert set(eng.accumulators) == {"bank/in"}
            engines.append(eng)
        cl, cs = engines[0].covs_for("bank/in"), engines[1].covs_for(
            "bank/in")
        for key in ("xx", "xxp", "xpxp", "count"):
            np.testing.assert_allclose(np.asarray(cs[key]),
                                       np.asarray(cl[key]),
                                       rtol=2e-5, atol=2e-5)
