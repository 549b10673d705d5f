"""Fault-tolerance: atomic checkpoints, restore, elastic re-shard, retention,
simulated crash/preemption recovery."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_mesh


def state_like(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (8, 8)),
                       "b": jnp.zeros((8,))},
            "step": jnp.asarray(seed, jnp.int32)}


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        st = state_like(3)
        mgr.save(3, st, blocking=True)
        step, got = mgr.restore(None, jax.eval_shape(lambda: st))
        assert step == 3
        np.testing.assert_array_equal(np.asarray(got["params"]["w"]),
                                      np.asarray(st["params"]["w"]))

    def test_async_save_then_restore(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, state_like(1))
        mgr.wait()
        assert mgr.latest_step() == 1

    def test_incomplete_checkpoint_ignored(self, tmp_path):
        """A crash mid-save leaves a .tmp dir — restore must skip it."""
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(1, state_like(1), blocking=True)
        os.makedirs(tmp_path / "step_000000002.tmp")
        (tmp_path / "step_000000002.tmp" / "leaf_00000.npy").write_bytes(b"x")
        assert mgr.latest_step() == 1

    def test_corrupt_dir_without_manifest_ignored(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(5, state_like(5), blocking=True)
        os.makedirs(tmp_path / "step_000000009")   # no manifest
        assert mgr.latest_step() == 5

    def test_retention_keeps_last_k(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2, async_save=False)
        for s in (1, 2, 3, 4):
            mgr.save(s, state_like(s), blocking=True)
        assert mgr.all_steps() == [3, 4]

    def test_elastic_reshard_restore(self, tmp_path):
        """Restore onto a different sharding than save time."""
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        st = state_like(7)
        mgr.save(7, st, blocking=True)
        mesh = make_mesh((1,), ("data",))
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), st)
        step, got = mgr.restore(None, jax.eval_shape(lambda: st), sh)
        assert step == 7
        assert got["params"]["w"].sharding == NamedSharding(mesh, P())

    def test_crash_restart_resumes_training(self, tmp_path):
        """Simulated node failure: train k steps, 'crash', restart — the
        loop resumes from the checkpoint and the data pipeline regenerates
        the same batches (determinism-by-step)."""
        from repro.configs import get_smoke_config
        from repro.launch.train import train

        cfg = get_smoke_config("qwen3-0.6b").replace(dtype="float32")
        d = str(tmp_path / "ck")
        train(cfg, steps=4, batch=2, seq_len=16, ckpt_dir=d, ckpt_every=2,
              log_every=100)
        # "crash" after step 4; restart with a longer horizon
        _, info = train(cfg, steps=6, batch=2, seq_len=16, ckpt_dir=d,
                        ckpt_every=2, log_every=100)
        assert info["step"] == 6
        mgr = CheckpointManager(d, async_save=False)
        assert mgr.latest_step() == 6

    def test_straggler_deadline_aborts_cleanly(self, tmp_path):
        from repro.configs import get_smoke_config
        from repro.launch.train import train

        cfg = get_smoke_config("qwen3-0.6b").replace(dtype="float32")
        d = str(tmp_path / "ck")
        # deadline of 0.0000001s trips immediately -> straggler abort path
        _, info = train(cfg, steps=4, batch=2, seq_len=16, ckpt_dir=d,
                        step_deadline_s=1e-7, log_every=100)
        assert info.get("aborted_straggler")
        mgr = CheckpointManager(d, async_save=False)
        assert mgr.latest_step() is not None   # progress was persisted


def _bank_state():
    """Factorized-style tree with per-expert zero-masked bank tails.

    Expert ranks 2 and 3 out of kmax=4; a ``-0.0`` inside the live region
    guards the bitwise (not value-wise) padding detection.
    """
    u = np.zeros((2, 4, 6), np.float32)   # (E, kmax, m), rank axis -2
    v = np.zeros((2, 5, 4), np.float32)   # (E, n, kmax), rank axis -1
    u[0, :2] = 1.5
    u[0, 1, 3] = -0.0
    u[1, :3] = 2.5
    v[0, :, :2] = 3.5
    v[1, :, :3] = 4.5
    return {"stages": [[{"ffn": {"experts": {"down": {"u": u, "v": v}}}}]],
            "w": np.arange(4, dtype=np.float32)}


def _bitwise_equal_trees(a, b):
    fa, fb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for la, lb in zip(fa, fb):
        xa, xb = np.asarray(la), np.asarray(lb)
        assert xa.dtype == xb.dtype, (xa.dtype, xb.dtype)
        assert xa.tobytes() == xb.tobytes()


class TestFactorizedRoundtrip:
    """ISSUE 10 satellite: lossless round-trip of factorized leaves."""

    def test_bf16_dtype_survives_roundtrip(self, tmp_path):
        """np.save/np.load degrade ml_dtypes bf16 to raw void — the
        manager must view-encode and restore the logical dtype."""
        import ml_dtypes

        st = {"w": (np.arange(12, dtype=np.float32) * 0.37)
              .astype(ml_dtypes.bfloat16).reshape(3, 4),
              "b": np.ones((3,), np.float16)}
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(0, st, blocking=True)
        _, got = mgr.restore(None, jax.eval_shape(lambda: st))
        assert np.asarray(got["w"]).dtype == ml_dtypes.bfloat16
        _bitwise_equal_trees(st, got)

    def test_restore_tree_needs_no_template(self, tmp_path):
        """``restore_tree`` rebuilds nested dicts/lists purely from the
        manifest — the serving reload path — and returns the meta."""
        st = _bank_state()
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(2, st, blocking=True, meta={"arch": "unit-test"})
        step, got, meta = mgr.restore_tree()
        assert step == 2
        assert meta == {"arch": "unit-test"}
        assert isinstance(got["stages"], list)
        _bitwise_equal_trees(st, got)

    def test_restore_tree_preserves_leafless_containers(self, tmp_path):
        """Hybrid stage params carry ``None`` placeholders for shared-attn
        sites and may hold empty dicts / tuples; ``tree_flatten`` drops
        leafless slots, so the manifest's structure descriptor must carry
        them or reloaded params break ``jax.tree.map`` arity against the
        decode cache (zamba2 regression)."""
        st = {"stages": [[{"w": np.ones((2,), np.float32)},
                          {"w": np.full((2,), 2.0, np.float32)},
                          None],
                         (np.zeros((3,), np.float32), None)],
              "shared": {}, "extra": None}
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(0, st, blocking=True)
        _, got, _ = mgr.restore_tree(0)
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(st))
        assert got["stages"][0][2] is None
        assert got["shared"] == {}
        assert isinstance(got["stages"][1], tuple)
        _bitwise_equal_trees(st, got)

    def test_bank_rank_metadata_recorded(self, tmp_path):
        st = _bank_state()
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        mgr.save(0, st, blocking=True)
        banks = {e["name"]: e for e in mgr.manifest()["leaves"]
                 if "rank_per_expert" in e}
        assert len(banks) == 2, sorted(banks)
        for e in banks.values():
            assert e["rank_per_expert"] == [2, 3], e

    def test_resliced_export_restores_bit_identical(self, tmp_path):
        """Padded and re-sliced checkpoints must restore the SAME bits:
        re-padding the sliced per-expert factors with zeros is lossless
        because the masked tails are exactly zero."""
        st = _bank_state()
        mgr = CheckpointManager(str(tmp_path), keep=5, async_save=False)
        mgr.save(0, st, blocking=True)                       # padded
        mgr.save(1, st, blocking=True, reslice_banks=True)   # re-sliced
        _, padded, _ = mgr.restore_tree(0)
        _, resliced, _ = mgr.restore_tree(1)
        _bitwise_equal_trees(st, padded)
        _bitwise_equal_trees(st, resliced)
        # the re-sliced export actually sliced: per-expert files exist
        entries = [e for e in mgr.manifest(1)["leaves"] if "files" in e]
        assert len(entries) == 2
        assert all(len(e["files"]) == 2 for e in entries)

    @pytest.mark.slow
    def test_padded_and_resliced_checkpoints_serve_identically(
            self, tmp_path):
        """End-to-end satellite check on a real MoE artifact: a server
        reloaded from the re-sliced export decodes token-for-token
        against one reloaded from the padded export."""
        from repro.core import zoo
        from repro.launch.serve import Server, _prefill_extra_len

        cfg, _, comp, _ = zoo.compress_smoke("deepseek-v2-lite-16b")
        d = str(tmp_path / "ck")
        mgr = CheckpointManager(d, keep=5, async_save=False)
        mgr.save(0, comp, blocking=True)
        mgr.save(1, comp, blocking=True, reslice_banks=True)
        prompts, extras = zoo.smoke_inputs(cfg)
        steps = 8
        max_len = (prompts.shape[1] + _prefill_extra_len(cfg) + steps + 8)
        srv_pad = Server.from_checkpoint(cfg, d, step=0, max_len=max_len,
                                         batch=prompts.shape[0])
        srv_res = Server.from_checkpoint(cfg, d, step=1, max_len=max_len,
                                         batch=prompts.shape[0])
        out_pad = np.asarray(srv_pad.generate(prompts, steps=steps,
                                              extras=extras))
        out_res = np.asarray(srv_res.generate(prompts, steps=steps,
                                              extras=extras))
        np.testing.assert_array_equal(out_pad, out_res)
