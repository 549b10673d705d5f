"""The trace reduction: busy union, idle share, program and kernel time,
top ops and idle gaps named by the benchmark's spans."""

from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce as T

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def planes():
    ops = [ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 10, 20),
           ev("%cov_accum.3 = (f32[8,8]) custom-call(bf16[4,8] %x), "
              "custom_call_target=\"tpu_custom_call\"", 25, 10),
           ev("%while.2 = (s32[]) while((s32[]) %t)", 50, 10),
           ev("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %q)", 50, 10),
           ev("%convolution.2 = f32[8]{0} convolution()", 80, 5),
           ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 95, 20)]
    modules = [ev("jit_update_covs(12)", 10, 26), ev("jit_fn", 50, 10),
               ev("jit_solve_anchored.3", 80, 5)]
    return [
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                        NS(name="XLA Modules",
                                           events=modules)]),
        NS(name="/device:TPU:0 SparseCore", lines=[
            NS(name="XLA Ops", events=[ev("other", 0, 100)])]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=[
            ev("bench.window", 0, 100), ev("bench.job", 40, 60),
            ev("unrelated", 0, 5)])]),
    ]


def test_busy_idle_and_window():
    s = T.summarize(planes(), "bench.window")
    assert s.window_s == pytest.approx(0.1)
    # ops cover [10,35) [50,60) [80,85) [95,100) inside the window
    assert s.busy_s == pytest.approx(0.045)


def test_module_and_op_seconds():
    s = T.summarize(planes(), "bench.window")
    assert s.module_seconds(["update_covs"]) == pytest.approx(0.026)
    assert s.module_seconds(["fn", "solve_anchored"]) == pytest.approx(0.015)
    assert s.module_seconds(["missing"]) is None
    assert s.module_count(["fn"]) == 1
    assert s.op_seconds(r"cov_accum$") == pytest.approx(0.010)
    assert s.op_seconds(r"cov_accum$", ["update_covs"]) == \
        pytest.approx(0.010)
    assert s.op_seconds(r"cov_accum$", ["fn"]) is None
    assert s.op_seconds(r"fusion$") == pytest.approx(0.035)


def test_breakdown():
    s = T.summarize(planes(), "bench.window")
    top = s.top_ops(2)
    assert top[0] == ["fusion", pytest.approx(0.050)]
    assert all(name != "while" for name, _ in s.top_ops(10))
    assert s.top_modules(1) == [["update_covs", pytest.approx(0.026)]]
    gaps = s.idle_gaps(10)
    assert [g[1] for g in gaps] == pytest.approx([0.020, 0.015, 0.010,
                                                  0.010])
    # [60, 80) and [35, 50) lie inside bench.job; [0, 10) only inside
    # the window ("unrelated" is no benchmark span)
    assert [g[0] for g in gaps[:2]] == ["bench.job", "bench.job"]
    assert sorted(g[0] for g in gaps[2:]) == ["bench.job", "bench.window"]


def test_names():
    assert T.module_name("jit_update_covs(12)") == "update_covs"
    assert T.module_name("jit_solve_anchored.3") == "solve_anchored"
    assert T.module_name("jit_fn") == "fn"
    assert T.op_label("%flash_decode.12 = bf16[64,32,128] custom-call()") \
        == "flash_decode"
    assert T.op_label("%multiply_add_fusion.17 = (f32[4]) fusion()") == \
        "multiply_add_fusion"
    assert T.union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]


def test_missing_window_span():
    with pytest.raises(ValueError):
        T.summarize(planes(), "bench.nothing")


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: ``cov_accum`` and ``flash_decode``
    each called once, jitted as ``covs`` and ``dec``, inside a
    ``bench.window`` span."""
    import os

    import jax
    path = os.path.join(os.path.dirname(__file__), "data",
                        "tpu_v5e_kernels.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    s = T.summarize(data.planes, "bench.window")
    assert len(s.devices) == 1
    # this recording's device timestamps lie 0.58 ms before the host's
    # dispatch of the same call; widen the 2.6 ms window to hold both
    s.window = (s.window[0] - 1_000_000, s.window[1])
    assert [m for m, _ in s.top_modules()] == ["covs", "dec"]
    assert s.op_seconds(r"cov_accum$", ["covs"]) == pytest.approx(
        591021e-9, rel=1e-6)
    assert s.op_seconds(r"flash_decode$", ["dec"]) == pytest.approx(
        129342e-9, rel=1e-6)
    assert s.op_seconds(r"flash_decode$", ["covs"]) is None
    assert 0.0007 < s.busy_s < s.window_s
    assert s.top_ops(1)[0][0] == "cov_accum"


def test_programs_only():
    """Without the op line, busy time and the breakdown come from the
    program events."""
    s = T.summarize(planes(), "bench.window", ops=False)
    assert s.busy_s == pytest.approx(0.041)     # [10,36) [50,60) [80,85)
    assert s.top_ops(1) == [["update_covs", pytest.approx(0.026)]]
    assert s.op_seconds(r"cov_accum$") is None


def test_truncated_trace_is_read_to_its_last_event():
    """Events stop 5 s before a 10 s window ends, as when the profiler's
    buffers fill: busy, idle and gaps cover the first 5 s only."""
    S = 1000
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%fusion.1 = f()", 0, 2 * S),
                                   ev("%fusion.2 = f()", 3 * S, 2 * S)]),
        NS(name="XLA Modules", events=[ev("jit_fn", 0, 5 * S)])])
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 0, 10 * S)])])
    s = T.summarize([dev, host], "bench.window")
    assert s.truncated
    assert s.window_s == pytest.approx(5.0)
    assert s.busy_s == pytest.approx(4.0)
    assert [g[1] for g in s.idle_gaps()] == pytest.approx([1.0])
    full = T.summarize(planes(), "bench.window")
    assert not full.truncated
