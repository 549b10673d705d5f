"""``correct`` for the compression cell at smoke size: a sound run passes;
the control (the reference at fp8 in the program's place) and each fault
planted under the timed path fail."""

import time

import jax
import numpy as np
import pytest

from bench import control, harness
from bench.drivers import compress as C

CELL = "qwen3-0.6b.compress-norefine"


def drive(cell):
    run = C.run(cell, seed=2**31 + 3, seconds=0.0, trace=False,
                t0=time.monotonic())
    return run, harness.result_line(run, {"platform": "cpu"}, False)


def test_sound_run_is_correct(smoke):
    run, line = drive(smoke(CELL))
    assert line["correct"], run.checks
    assert list(line)[-1] == "checks"


def test_control_fails(smoke):
    cell = smoke(CELL)
    got = control.readings(cell, 7)
    limits = cell.limits["checks"]
    assert all(got["program"][k] <= limits[k]["limit"] for k in limits)
    assert any(got["control"][k] > limits[k]["limit"] for k in limits)


def _update_unchanged(covs, x, xp, mesh=None, ids=None):
    return covs


def _half_batch(real):
    def fn(params, cfg, calib, ccfg):
        n = calib["tokens"].shape[0] // 2
        return real(params, cfg, {"tokens": calib["tokens"][:n]}, ccfg)
    return fn


def _altered(real):
    def fn(*a, **k):
        out, report = real(*a, **k)
        out = jax.tree.map(lambda x: x, out)
        blk = out["stages"][0][0]["ffn"]["down"]
        blk["u"] = blk["u"].at[0].multiply(1.05)
        return out, report
    return fn


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_fails(smoke, monkeypatch, fault):
    import repro.core as core
    from repro.core import calibration
    if fault == "state_unchanged":
        monkeypatch.setattr(calibration, "update_covs", _update_unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(core, "compress_model",
                            _half_batch(core.compress_model))
    else:
        monkeypatch.setattr(core, "compress_model",
                            _altered(core.compress_model))
    run, line = drive(smoke(CELL))
    assert not line["correct"], {k: c for k, c in run.checks.items()}
    assert np.isfinite(run.end_to_end["compress_layer_s"])
