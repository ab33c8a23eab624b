"""The reduction from a profiler trace to busy time, per-program device time,
the top operations and the idle time by host span."""
import os

import pytest

import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_reduce_on_synthetic_events():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [(0, 3), (5, 9)]
    ms = 1_000_000
    ex = {
        "spans": [(0, 100 * ms, "bench.window"),
                  (0, 40 * ms, "bench.step"),
                  (5 * ms, 30 * ms, "bench.prefill"),
                  (60 * ms, 100 * ms, "bench.idle"),
                  (-50 * ms, 0, "bench.step")],
        "ops": {"/device:TPU:0": [
            (-10 * ms, 10 * ms, "fusion.1"),       # half inside
            (20 * ms, 30 * ms, "fusion.2"),
            (25 * ms, 35 * ms, "copy.3"),          # overlaps fusion.2
            (70 * ms, 80 * ms, "fusion.1")]},
        "modules": {"/device:TPU:0": [
            (20 * ms, 35 * ms, "jit_prefill_fn(3)"),
            (70 * ms, 80 * ms, "jit__step_fn(7)"),
            (-10 * ms, 10 * ms, "jit__step_fn(7)")]},    # not whole inside
    }
    r = tracing.reduce(ex)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.035)   # 10 + 15 + 10 ms
    assert r["idle_pct"] == pytest.approx(65.0)
    assert r["program_s"] == pytest.approx({"prefill": 0.015,
                                            "decode": 0.010})
    assert dict(r["device_ops"]) == pytest.approx(
        {"decode/fusion.1": 0.020, "prefill/fusion.2": 0.010,
         "prefill/copy.3": 0.010})
    # gaps: 10-20 ms (mid 15: prefill), 35-70 (mid 52.5: none open),
    # 80-100 (mid 90: idle)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.prefill": 0.010, "no host span": 0.035, "bench.idle": 0.020})


def test_no_window_or_no_device_reads_nothing():
    assert tracing.reduce({"spans": [], "ops": {"d": [(0, 1, "x")]},
                           "modules": {}}) is None
    assert tracing.reduce({"spans": [(0, 5, "bench.window")], "ops": {},
                           "modules": {}}) is None


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three steps of a jitted matmul and a
    jitted tanh inside a ``bench.window`` span.  The device clock of that
    trace runs ~1.2 ms behind the host's, so the first program's module
    starts before the window span opens and is not counted."""
    path = os.path.join(DATA, "small.xplane.pb")
    from jax.profiler import ProfileData
    ex = tracing.extract(ProfileData.from_file(path))
    r = tracing.reduce(ex)
    assert r is not None and r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(v for _, v in r["device_ops"]) >= r["busy_s"] * 0.999
    assert any(name == "bench.idle" for name, _ in r["idle_gaps"])
    assert r["program_n"] == {"other": 5}
    assert r["busy_s"] == pytest.approx(1.2428e-05)
