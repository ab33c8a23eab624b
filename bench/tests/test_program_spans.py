"""The readers of the program's own spans, on a hand-built run record:
windowing, self time, the device-trace subtraction, and nothing to read
without tracer events or without spans."""
import importlib.util
import os
import types

import pytest

from cell import ReqRecord, RunRecord
from repro.obs.trace import TraceEvent
from families.dense_gqa import Dims
from yardstick import peaks_for

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ("decode_host_gap_ms", "runner_launch_ms", "engine_host_ms",
       "first_token_hold_p50_ms", "gc_pause_ms", "compile_ms")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def make_run(events, device_trace=None, requests=()):
    """Window [10 s, 20 s) on the host clock; the tracer started at 9 s,
    so an event at ts (us) lies at 9 + ts * 1e-6 s."""
    dims = Dims(layers=2, d_model=8, n_heads=2, n_kv_heads=1, head_dim=4,
                d_ff=16, vocab=100, padded_vocab=256, tied=True,
                qkv_bias=False)
    return RunRecord(
        yardstick=dims, peaks=peaks_for("TPU v5 lite"), setup_s=5.0,
        setup_stages=[], window=(10.0, 20.0), requests=list(requests),
        prefills=[], decodes=[], steps=[(19.0, 21.0)],
        decode_counter=((0.0, 0), (1.0, 100)), kv_held_bytes=0, live_kv=[],
        tracer_events=list(events), tracer_offset=9.0,
        device_trace=device_trace, memory_peak_bytes=0,
        compiles_in_window=0, served={}, prompts={})


S = 1e6             # one second in tracer microseconds
MS = 1e3


def span(name, sid, parent, ts, dur, cat="serving", **args):
    return TraceEvent(name=name, cat=cat, ph="X", ts=ts, step=0, dur=dur,
                      args=args, span_id=sid, parent_id=parent)


def engine_step(sid, ts, first_rid=None, gc_ms=0.0):
    """One step span of 20 ms at ``ts``: a 2 ms prefill, then a 16 ms
    decode whose runner spans take 0.5 + 1.0 + 12.5 ms, a gc of ``gc_ms``
    (at most 1) inside the decode, and a first-token stamp after the
    prefill."""
    out = [span("step", sid, 0, ts, 20 * MS),
           span("prefill", sid + 1, sid, ts + 1 * MS, 2 * MS),
           span("decode", sid + 2, sid, ts + 3 * MS, 16 * MS),
           span("runner.put", sid + 3, sid + 2, ts + 4 * MS, 0.5 * MS),
           span("runner.launch", sid + 4, sid + 2, ts + 4.5 * MS, 1 * MS),
           span("runner.readback", sid + 5, sid + 2, ts + 5.5 * MS,
                12.5 * MS)]
    if gc_ms:
        out.append(span("gc", sid + 6, sid + 2, ts + 19 * MS - gc_ms * MS,
                        gc_ms * MS, cat="host", generation=2, collected=0))
    if first_rid is not None:
        out.append(TraceEvent(name="first-token", cat="serving", ph="i",
                              ts=ts + 3 * MS, step=0, args={"rid": first_rid},
                              parent_id=sid))
    return out


def req(rid, due):
    return ReqRecord(rid=rid, due=due, prompt_len=4, gen_len=4,
                     phase="window", sent=due, first=due + 0.1)


def two_steps_in_window():
    # steps at 2 s and 3 s of the tracer (11 s, 12 s host: inside), and one
    # at 0.5 s (9.5 s host: before the window), whose spans must not count
    return (engine_step(100, 0.5 * S, first_rid=7, gc_ms=0.9) +
            engine_step(1, 2 * S, first_rid=1, gc_ms=0.6) +
            engine_step(20, 3 * S, first_rid=2) +
            [span("backend-compile", 0, 0, 2.5 * S, 250 * MS, cat="host",
                  seconds=0.25)])


def test_runner_spans_less_the_device_time():
    dt = {"program_s": {"decode": 0.024, "prefill": 0.5},
          "program_n": {"decode": 2, "prefill": 3}}
    run = make_run(two_steps_in_window(), device_trace=dt)
    # 14 ms of runner spans a step, 12 ms of device time a program
    assert reader("decode_host_gap_ms")(run) == pytest.approx(2.0)
    assert reader("runner_launch_ms")(run) == pytest.approx(1.5)
    # without a device trace there is nothing to subtract
    assert reader("decode_host_gap_ms")(make_run(two_steps_in_window())) \
        is None


def test_engine_self_time_per_step():
    run = make_run(two_steps_in_window())
    # each step: 20 - (2 + 16) = 2 ms; each decode 16 - 14 = 2 ms, less the
    # 0.6 ms gc inside the first one: (2 + 1.4 + 2 + 2) / 2 steps
    assert reader("engine_host_ms")(run) == pytest.approx(3.7)


def test_first_token_hold_and_host_spans():
    run = make_run(two_steps_in_window(),
                   requests=[req(1, 10.5), req(2, 11.5), req(3, 12.0)])
    # the stamps sit 3 ms into 20 ms steps: 17 ms each; rid 3 has no stamp
    # and rid 7 is not due in the window
    assert reader("first_token_hold_p50_ms")(run) == pytest.approx(17.0)
    assert reader("gc_pause_ms")(run) == pytest.approx(0.6)
    assert reader("compile_ms")(run) == pytest.approx(250.0)


def test_a_window_without_collections_reads_zero():
    run = make_run(engine_step(1, 2 * S))
    assert reader("gc_pause_ms")(run) == 0.0
    assert reader("compile_ms")(run) == 0.0


def test_replan_ms_reads_the_replan_spans():
    events = engine_step(1, 2 * S) + [
        span("replan", 9, 1, 2 * S + 19.5 * MS, 4.5 * MS, cat="arena",
             seconds=0.004, cause="decode-outrun"),
        span("replan", 10, 0, 0.2 * S, 9 * MS, cat="arena", seconds=0.008)]
    assert reader("replan_ms")(make_run(events)) == pytest.approx(4.0)


def test_nothing_to_read_without_tracer_events_or_spans():
    run = make_run([], device_trace={"program_s": {"decode": 1.0},
                                     "program_n": {"decode": 10}},
                   requests=[req(1, 10.5)])
    for name in NEW:
        assert reader(name)(run) is None, name
    # a program that emits instants only (no spans, no span ids) reads as
    # having nothing for these metrics, and nothing raises
    instants = [types.SimpleNamespace(name=n, cat="serving", ph="i",
                                      ts=2 * S, step=0, track="engine",
                                      dur=0.0, args={"rid": 1})
                for n in ("admit", "prefill", "decode", "finish")]
    run = make_run(instants, device_trace={"program_s": {"decode": 1.0},
                                           "program_n": {"decode": 10}},
                   requests=[req(1, 10.5)])
    for name in NEW:
        assert reader(name)(run) is None, name


def test_a_traced_tiny_run_reports_the_span_metrics(tmp_path):
    """A whole traced run of the tiny cell on the CPU: the readers find the
    program's spans.  The CPU trace has no device plane, so the reader that
    subtracts device time has nothing to read."""
    import io
    import json
    from contextlib import redirect_stdout

    import run as run_lib
    import tiny
    root = tiny.make_root(str(tmp_path))
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run_lib.main(["--workload", "tiny.chat", "--seed",
                           str(2 ** 31 + 78), "--seconds", "2", "--trace",
                           "1"], root=root, bench=f"{root}/bench",
                          require_chip=False)
    assert rc == 0
    got = json.loads(buf.getvalue().strip().splitlines()[-1])["metrics"]
    for name in NEW:
        if name == "decode_host_gap_ms":
            assert name not in got
            continue
        assert got[name]["value"] >= 0.0, name
    assert got["runner_launch_ms"]["value"] > 0.0
    assert 0.0 < got["first_token_hold_p50_ms"]["value"] < 1e3


def test_a_decode_step_carries_the_program_events_inside_it():
    """What a family's yardstick gets of a decode step: its contexts and
    the tracer events that start inside it, on the host clock."""
    run = make_run(two_steps_in_window())
    run.decodes = [(9.4, 9.6, [3]),                 # before the window
                   (11.0 + 0.0025, 11.0 + 0.02, [5, 9]),
                   (12.0 + 0.0025, 12.0 + 0.02, [6])]
    got = run.window_decodes()
    assert [s.contexts for s in got] == [[5, 9], [6]]
    # the decode span, its runner children and the first-token stamp
    # start inside each step; the step's own span and prefill do not
    names = ["decode", "first-token", "runner.launch", "runner.put",
             "runner.readback"]
    assert [sorted(ev.name for ev in s.events) for s in got] == [
        sorted(names + ["gc"]), names]
