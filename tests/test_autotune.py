"""Block-shape autotuner: heuristic determinism, VMEM filtering, disk-cache
round trips (including across processes), and tuned-vs-default parity."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets a fresh in-memory state and its own disk cache."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune.reset()
    yield
    autotune.reset()


def test_heuristic_reproduces_anchors_on_aligned_shapes():
    """On shapes the hand-picked constants were chosen for, the heuristic
    must reproduce them exactly — the autotuner is a strict generalization
    of the old ops.py block picks."""
    assert autotune.cov_blocks(1024, 512).blocks == {"bt": 512, "bi": 256}
    assert autotune.lowrank_blocks(512, 512, 128, 512).blocks == \
        {"bt": 256, "bn": 512, "bm": 256}
    assert autotune.flash_blocks(1, 4, 4, 512, 512, 64).blocks == \
        {"bq": 256, "bk": 256}


def test_heuristic_is_deterministic_and_cpu_default():
    """mode="auto" on a CPU backend resolves to the heuristic (never times
    interpret-mode kernels implicitly) and is a pure function of shape."""
    picks = [autotune.cov_blocks(513, 384) for _ in range(3)]
    assert all(p.source == "heuristic" and p.us is None for p in picks)
    assert len({tuple(sorted(p.blocks.items())) for p in picks}) == 1


def test_blocks_never_exceed_lane_padded_dims():
    """Small/odd dims must still get a usable candidate: the chosen block
    may require padding, but only within the lattice floor."""
    for t, n in [(64, 72), (8, 128), (130, 100), (1, 8)]:
        blocks = autotune.cov_blocks(t, n).blocks
        assert blocks["bt"] in autotune._LATTICES["cov_accum"]["bt"]
        assert blocks["bi"] in autotune._LATTICES["cov_accum"]["bi"]


def test_vmem_budget_filters_candidates(monkeypatch):
    """A tight VMEM budget must drop big blocks; every surviving candidate
    fits; a degenerate budget still yields the minimal-footprint pick."""
    cands = autotune.cov_candidates(2048, 1024)
    big = max(c.vmem_bytes for c in cands)
    monkeypatch.setenv("REPRO_AUTOTUNE_VMEM_BYTES", str(big - 1))
    tight = autotune.cov_candidates(2048, 1024)
    assert tight and all(c.vmem_bytes < big for c in tight)
    assert len(tight) < len(cands)
    # degenerate: nothing fits -> the smallest-footprint fallback survives
    monkeypatch.setenv("REPRO_AUTOTUNE_VMEM_BYTES", "1")
    floor = autotune.cov_candidates(2048, 1024)
    assert len(floor) == 1
    assert autotune.cov_blocks(2048, 1024).blocks == floor[0].blocks


def test_measure_mode_persists_and_cache_hits(monkeypatch):
    """mode="measure" on CPU times interpret-mode candidates, persists the
    winner to disk, and a fresh in-memory state replays it as a cache hit
    with identical blocks."""
    monkeypatch.setenv("REPRO_AUTOTUNE_MAX_CANDIDATES", "2")
    first = autotune.cov_blocks(256, 256, mode="measure", interpret=True)
    assert first.source == "measured" and first.us > 0
    with open(os.environ["REPRO_AUTOTUNE_CACHE"]) as f:
        disk = json.load(f)
    assert len(disk) == 1
    key = next(iter(disk))
    assert key.startswith(f"cov_accum|v{autotune.CACHE_VERSION}|")
    assert ":interp|" in key

    autotune.reset()  # drop in-memory state, keep disk
    hit = autotune.cov_blocks(256, 256, mode="measure", interpret=True)
    assert hit.source == "cache"
    assert hit.blocks == first.blocks and hit.us == first.us

    autotune.clear_disk_cache()
    assert not os.path.exists(os.environ["REPRO_AUTOTUNE_CACHE"])


def test_cache_determinism_across_processes(monkeypatch):
    """Two child interpreters sharing one cache file: the first measures,
    the second must report source=cache with the SAME blocks — the property
    that makes every process after the first trace identical shapes."""
    monkeypatch.setenv("REPRO_AUTOTUNE_MAX_CANDIDATES", "2")
    child = """
import json, sys
from repro.kernels import autotune
r = autotune.cov_blocks(256, 256, mode="measure", interpret=True)
print(json.dumps({"source": r.source, "blocks": r.blocks}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    outs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", child], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr
        outs.append(json.loads(out.stdout.splitlines()[-1]))
    assert outs[0]["source"] == "measured"
    assert outs[1]["source"] == "cache"
    assert outs[0]["blocks"] == outs[1]["blocks"]


def test_env_override_pins_mode(monkeypatch):
    """REPRO_AUTOTUNE=heuristic beats an explicit measure request — runs
    can be pinned from the environment (CI smoke, clusters w/o cache)."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "heuristic")
    r = autotune.cov_blocks(256, 256, mode="measure", interpret=True)
    assert r.source == "heuristic"
    assert not os.path.exists(os.environ["REPRO_AUTOTUNE_CACHE"])


def test_tuned_blocks_match_default_on_unaligned_shapes(monkeypatch):
    """Numerical safety of the tuned picks: ops results with measured
    blocks must match the heuristic-block results on unaligned shapes
    (padding policy is block-dependent, correctness must not be)."""
    from repro.kernels import ops, ref
    monkeypatch.setenv("REPRO_AUTOTUNE_MAX_CANDIDATES", "2")
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (300, 200), jnp.float32)
    xp = x + 0.1 * jax.random.normal(k2, (300, 200), jnp.float32)
    want = ref.cov_accum_ref(x, xp)
    for mode in ("heuristic", "measure"):
        monkeypatch.setenv("REPRO_AUTOTUNE", mode)
        autotune.reset()
        outs = ops.cov_accum(x, xp, force_pallas=True, interpret=True)
        for o, w in zip(outs, want):
            np.testing.assert_allclose(np.asarray(o), np.asarray(w),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=mode)


def test_measure_raises_when_every_candidate_fails(caplog):
    """A kernel the compiler rejects in every block shape surfaces as an
    error naming the blocks tried — never as a silent default pick."""
    cands = autotune.cov_candidates(512, 512)[:2]

    def thunk(c):
        def broken(x):
            raise ValueError(f"rejected {c.blocks}")
        return broken, (jnp.ones((8, 128)),)

    with caplog.at_level("WARNING", logger="repro.kernels.autotune"):
        with pytest.raises(RuntimeError, match="every measured candidate"):
            autotune._measure_best(cands, thunk)
    logged = " ".join(r.getMessage() for r in caplog.records)
    for c in cands:
        assert str(c.blocks) in logged


def test_measure_skips_a_failing_candidate(caplog):
    """One failing candidate is logged and skipped; the survivor wins."""
    cands = autotune.cov_candidates(512, 512)[:2]

    def thunk(c):
        if c is cands[0]:
            def broken(x):
                raise ValueError("rejected")
            return broken, (jnp.ones((8, 128)),)
        return (lambda x: x * 2), (jnp.ones((8, 128)),)

    with caplog.at_level("WARNING", logger="repro.kernels.autotune"):
        best, us = autotune._measure_best(cands, thunk)
    assert best is cands[1] and us > 0
    assert str(cands[0].blocks) in caplog.text


def test_measure_inside_a_trace_times_concrete_arrays(monkeypatch):
    """The tuner is consulted while the calling step is being traced; the
    timed call must still get concrete arrays (a staged call would time
    tracing, and a compile error would only show at the outer compile)."""
    seen = []
    real = autotune._time_call

    def spy(fn, args, **kw):
        seen.extend(type(a) for a in args if a is not None)
        return real(fn, args, **kw)

    monkeypatch.setattr(autotune, "_time_call", spy)
    from repro.kernels import ops

    f = jax.jit(lambda x: ops.cov_accum(x, x, force_pallas=True,
                                        interpret=True))
    monkeypatch.setenv("REPRO_AUTOTUNE", "measure")
    f(jnp.ones((256, 256)))
    assert seen and all(issubclass(t, jax.Array) for t in seen)
    assert not any(issubclass(t, jax.core.Tracer) for t in seen)
