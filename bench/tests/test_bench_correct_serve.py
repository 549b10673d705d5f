"""``correct`` for the serving cells at smoke size: a sound run passes;
the control (the reference at fp8 choosing the tokens) and each fault
planted under the timed path fail."""

import time

import jax.numpy as jnp
import pytest

from bench import control, harness
from bench.drivers import serve as S

CELLS = ["granite-3-8b.serve-chat", "qwen3-0.6b.serve-chat"]


def drive(cell):
    run = S.run(cell, seed=2**31 + 5, seconds=0.0, trace=False,
                t0=time.monotonic())
    return run, harness.result_line(run, {"platform": "cpu"}, False)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(smoke, name):
    run, line = drive(smoke(name))
    assert line["correct"], run.checks
    assert run.attempted == 4 and run.failed == 0


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(smoke, name):
    cell = smoke(name)
    got = control.readings(cell, 11)
    limit = cell.limits["checks"]["logit_gap"]["limit"]
    assert got["program"]["logit_gap"] <= limit
    assert got["control"]["logit_gap"] > limit


def _state_unchanged(real):
    def make(cfg, mesh):
        step = real(cfg, mesh)

        def serve_step(params, cache, tokens, pos):
            tok, _ = step(params, cache, tokens, pos)
            return tok, cache
        return serve_step
    return make


def _half_batch(real):
    def make(cfg, mesh):
        step = real(cfg, mesh)

        def serve_step(params, cache, tokens, pos):
            tok, cache = step(params, cache, tokens, pos)
            half = tok.shape[0] // 2
            return jnp.concatenate([tok[:half], tok[:tok.shape[0] - half]]), \
                cache
        return serve_step
    return make


def _altered(real):
    def run(self, requests):
        out = real(self, requests)
        for r in out.values():
            r["tokens"] = r["tokens"].copy()
            r["tokens"][1] = (r["tokens"][1] + 1) % self.cfg.vocab_size
        return out
    return run


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_fails(smoke, monkeypatch, fault):
    from repro.launch import serve, steps
    if fault == "state_unchanged":
        monkeypatch.setattr(steps, "make_serve_step",
                            _state_unchanged(steps.make_serve_step))
    elif fault == "half_batch":
        monkeypatch.setattr(steps, "make_serve_step",
                            _half_batch(steps.make_serve_step))
    else:
        monkeypatch.setattr(serve.ContinuousBatchingServer, "run",
                            _altered(serve.ContinuousBatchingServer.run))
    run, line = drive(smoke(CELLS[0]))
    assert not line["correct"], run.checks
