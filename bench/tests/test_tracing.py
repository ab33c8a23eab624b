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



MS = 1_000_000          # ns


def _steady(n_blocks: int = 10, cut_ms=None, devices: int = 1):
    """A window of ``n_blocks`` blocks of 124 ms: an engine step with a
    prefill (4 ms of host work, then a 20 ms program), then ten decode
    steps (4 ms of host work, then a 6 ms program of two ops), on each of
    ``devices`` devices.  With ``cut_ms`` (one per device, or one for all)
    the devices' operations and programs end there, as when the profiler
    stops recording them; the host's spans go on."""
    spans, ops, mods, t = [], [], [], 0
    for _ in range(n_blocks):
        spans += [(t, t + 24 * MS, "bench.step"),
                  (t, t + 24 * MS, "bench.prefill")]
        mods.append((t + 4 * MS, t + 24 * MS, "jit_prefill_fn(1)"))
        ops.append((t + 4 * MS, t + 24 * MS, "fusion.9"))
        t += 24 * MS
        for _ in range(10):
            spans += [(t, t + 10 * MS, "bench.step"),
                      (t, t + 10 * MS, "bench.decode")]
            mods.append((t + 4 * MS, t + 10 * MS, "jit__step_fn(2)"))
            ops += [(t + 4 * MS, t + 7 * MS, "fusion.1"),
                    (t + 7 * MS, t + 10 * MS, "fusion.2")]
            t += 10 * MS
    spans.append((0, t, "bench.window"))
    cuts = cut_ms if isinstance(cut_ms, tuple) else (cut_ms,) * devices
    ex = {"spans": spans, "ops": {}, "modules": {}}
    for d, cut in enumerate(cuts):
        end = float("inf") if cut is None else cut * MS
        ex["ops"][f"/device:TPU:{d}"] = [o for o in ops if o[1] <= end]
        ex["modules"][f"/device:TPU:{d}"] = [m for m in mods if m[1] <= end]
    return ex


def _run(ex):
    """A run record whose host calls are the trace's, on its clock."""
    from cell import RunRecord
    from families.dense_gqa import Dims
    from yardstick import peaks_for
    dims = Dims(layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
                d_ff=128, vocab=1000, padded_vocab=1024, tied=True,
                qkv_bias=False)
    calls = sorted(s for s in ex["spans"]
                   if s[2] in ("bench.decode", "bench.prefill"))
    decodes, prefills, j = [], [], 0
    for s, e, name in calls:
        if name == "bench.prefill":
            prefills.append((s * 1e-9, e * 1e-9, 300))
            j = 0
        else:
            decodes.append((s * 1e-9, e * 1e-9, [300 + 10 * j, 5 + j]))
            j += 1
    return RunRecord(
        yardstick=dims, peaks=peaks_for("TPU v5 lite"), setup_s=1.0,
        setup_stages=[], window=(0.0, 1.24 + 1e-9), requests=[],
        prefills=prefills, decodes=decodes, steps=[], decode_counter=None,
        kv_held_bytes=0, live_kv=[], tracer_events=[], tracer_offset=0.0,
        device_trace=tracing.reduce(ex), memory_peak_bytes=0,
        compiles_in_window=0, served={}, prompts={})


def test_a_cut_trace_reads_the_part_it_holds():
    """Cut in the middle, the shares are those of the whole trace: idle is
    read over the part the trace holds, and the roofline and the MFUs over
    the calls whose programs it holds."""
    whole = tracing.reduce(_steady())
    cut = tracing.reduce(_steady(cut_ms=620))
    assert whole["window_s"] == whole["span_s"] == pytest.approx(1.24)
    assert cut["span_s"] == whole["span_s"]
    assert cut["window_s"] == pytest.approx(0.62)
    assert whole["idle_pct"] == pytest.approx(100 * (1 - 0.8 / 1.24))
    assert cut["idle_pct"] == pytest.approx(whole["idle_pct"])
    assert cut["program_n"] == {"decode": 50, "prefill": 5}
    assert sum(v for _, v in cut["idle_gaps"]) == pytest.approx(
        cut["window_s"] - cut["busy_s"])
    from test_window import reader
    for name in ("decode_roofline", "decode_mfu", "prefill_mfu"):
        a, b = reader(name)(_run(_steady())), reader(name)(
            _run(_steady(cut_ms=620)))
        assert 0 < a < 100
        assert b == pytest.approx(a), name


def test_a_cut_trace_of_two_devices_reads_the_calls_both_hold():
    """Two devices whose recording stops at different points: numbers are
    read up to the first stop, and the calls read are those whose programs
    both devices hold, not the programs of both counted together."""
    whole = tracing.reduce(_steady(devices=2))
    cut = tracing.reduce(_steady(cut_ms=(868, 620)))
    assert cut["window_s"] == pytest.approx(0.62)
    assert cut["program_n"] == {"decode": 100, "prefill": 10}
    assert cut["held_n"] == {"decode": 50, "prefill": 5}
    assert whole["held_n"] == {"decode": 100, "prefill": 10}
    assert cut["idle_pct"] == pytest.approx(whole["idle_pct"])
    from test_window import reader
    for name in ("decode_roofline", "decode_mfu", "prefill_mfu"):
        a = reader(name)(_run(_steady(devices=2)))
        b = reader(name)(_run(_steady(cut_ms=(868, 620))))
        one = reader(name)(_run(_steady()))
        assert b == pytest.approx(a), name
        assert a == pytest.approx(one / 2), name    # device time of both


def test_a_device_idle_at_the_window_end_is_no_cut():
    """The device's last op ends before the window does, but no engine
    step starts after it: the trace holds the window whole."""
    ex = _steady()
    lo, hi, _ = next(s for s in ex["spans"] if s[2] == "bench.window")
    ex["spans"] = [s for s in ex["spans"] if s[2] != "bench.window"] + [
        (lo, hi + 300 * MS, "bench.window"), (hi, hi + 300 * MS, "bench.idle")]
    r = tracing.reduce(ex)
    assert r["window_s"] == r["span_s"] == pytest.approx(1.54)


def test_the_recorded_chip_trace_cut_in_the_middle():
    from jax.profiler import ProfileData
    ex = tracing.extract(ProfileData.from_file(
        os.path.join(DATA, "small.xplane.pb")))
    whole = tracing.reduce(ex)
    cut_ns = 52 * MS
    ex["ops"] = {d: [o for o in evs if o[1] <= cut_ns]
                 for d, evs in ex["ops"].items()}
    ex["modules"] = {d: [m for m in evs if m[1] <= cut_ns]
                     for d, evs in ex["modules"].items()}
    cut = tracing.reduce(ex)
    last = max(o[1] for o in ex["ops"]["/device:TPU:0"])
    lo = min(s[0] for s in ex["spans"] if s[2] == "bench.window")
    assert cut["window_s"] == pytest.approx((last - lo) * 1e-9)
    assert cut["span_s"] == whole["span_s"] == whole["window_s"]
    assert cut["program_n"] == {"other": 2}
    assert 0 < cut["busy_s"] < whole["busy_s"]
