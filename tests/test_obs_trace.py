"""Tracer ring buffer + Chrome-trace export schema and rectangle invariants.

The export is the observability contract: traces must load in Perfetto
(object format, required keys, sorted timestamps, pid/tid metadata per
process and track) and the packed-plan rendering must inherit the planner's
no-overlap invariant — re-checked here with the independent rectangle
checker from ``test_packing_invariants``, reconstructed purely from the
exported JSON.
"""
import gc
import json
import types

import jax
import pytest

from repro.core import MemoryProfile, best_fit, make_profile
from repro.core.arena import ArenaAllocator
from repro.core.events import Block
from repro.obs import trace as obs_trace
from repro.obs import (ChromeTraceBuilder, ManualClock, TraceEvent, Tracer,
                       disable, enable, get_tracer, plan_rectangles,
                       use_tracer, validate_chrome_trace)
from repro.serving.pages import paged_request_blocks

from test_packing_invariants import (assert_no_live_overlap, _serving_cfg,
                                     random_profile, staircase_trace)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_ring_buffer_drops_oldest_and_accounts():
    t = Tracer(capacity=4, clock=ManualClock(tick=1e-6))
    with pytest.warns(RuntimeWarning, match="ring buffer full"):
        for i in range(10):
            t.instant(f"e{i}", "arena")
    evs = t.events()
    assert len(evs) == 4
    assert t.n_dropped == 6
    assert [e.name for e in evs] == ["e6", "e7", "e8", "e9"]
    assert t.stats()["n_emitted"] == 10


def test_ring_buffer_drop_warns_once_and_counts_on_registry():
    """Drops surface as a metrics counter + a single RuntimeWarning, so a
    long run can't silently truncate its exported spans."""
    from repro.obs import MetricsRegistry, use_registry
    reg = MetricsRegistry()
    with use_registry(reg):
        t = Tracer(capacity=2, clock=ManualClock(tick=1e-6))
        with pytest.warns(RuntimeWarning, match="ring buffer full"):
            for i in range(5):
                t.instant(f"e{i}", "arena")
    (c,) = [m for m in reg.metrics()
            if m.name == "trace_dropped_events_total"]
    assert c.value == t.n_dropped == 3
    # the warning fires once, not per drop
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        t.instant("more", "arena")          # would raise if warned again


def test_ring_buffer_drop_prefers_explicit_registry():
    from repro.obs import MetricsRegistry, use_registry
    mine, active = MetricsRegistry(), MetricsRegistry()
    with use_registry(active):
        t = Tracer(capacity=1, registry=mine, clock=ManualClock(tick=1e-6))
        with pytest.warns(RuntimeWarning):
            t.instant("a", "arena")
            t.instant("b", "arena")
    assert [m.name for m in mine.metrics()] == ["trace_dropped_events_total"]
    assert active.metrics() == []


def test_manual_clock_makes_timestamps_deterministic():
    def run():
        clk = ManualClock(start=5.0)
        t = Tracer(clock=clk)
        t.instant("a", "arena")
        clk.advance(0.001)
        t.instant("b", "arena")
        return [e.ts for e in t.events()]

    assert run() == run() == [0.0, pytest.approx(1000.0)]


def test_step_stamp_and_span():
    clk = ManualClock()
    t = Tracer(clock=clk)
    t.set_step(7)
    with t.span("work", "serving", track="engine", what="x"):
        clk.advance(0.002)
    (ev,) = t.events()
    assert ev.ph == "X" and ev.step == 7 and ev.track == "engine"
    assert ev.dur == pytest.approx(2000.0)
    assert ev.args["what"] == "x"


def test_span_ids_and_parents_nest():
    clk = ManualClock()
    t = Tracer(clock=clk)
    with t.span("outer", "serving", track="engine"):
        t.instant("mark", "serving")
        with t.span("inner", "serving", track="runner") as sp:
            clk.advance(0.001)
            sp.note(late=1)
        with t.span("second", "serving"):
            pass
    t.instant("after", "serving")
    by = {e.name: e for e in t.events()}
    # spans are emitted as they close, innermost first
    assert [e.name for e in t.events()] == ["mark", "inner", "second",
                                            "outer", "after"]
    outer, inner, second = by["outer"], by["inner"], by["second"]
    assert outer.span_id and inner.span_id and second.span_id
    assert len({outer.span_id, inner.span_id, second.span_id}) == 3
    assert outer.parent_id == 0
    assert inner.parent_id == second.parent_id == outer.span_id
    assert by["mark"].parent_id == outer.span_id and by["mark"].span_id == 0
    assert by["after"].parent_id == 0
    assert inner.args == {"late": 1}
    assert outer.ts <= inner.ts and \
        inner.ts + inner.dur <= outer.ts + outer.dur


def test_span_helper_without_tracer_is_the_shared_no_op():
    assert get_tracer() is None
    sp = obs_trace.span("step", "serving", "engine", step=1)
    assert sp is obs_trace.NO_SPAN
    with sp as inner:
        inner.note(x=1)
    mine = Tracer()
    with use_tracer(mine):
        with obs_trace.span("step", "serving", "engine", step=1):
            pass
    assert [(e.name, e.ph, e.args) for e in mine.events()] == \
        [("step", "X", {"step": 1})]
    with obs_trace.span("step", "serving"):
        pass
    assert len(mine.events()) == 1          # nothing after the tracer left


def test_gc_spans_only_while_enabled():
    t = enable(Tracer())
    try:
        gc.collect()
    finally:
        assert disable() is t
    spans = [e for e in t.events() if e.name == "gc"]
    assert spans and all(e.cat == "host" and e.ph == "X" for e in spans)
    assert spans[-1].args["generation"] == 2
    assert "collected" in spans[-1].args
    n = len(t.events())
    gc.collect()
    assert len(t.events()) == n


def test_backend_compile_span_while_enabled():
    t = enable(Tracer())
    try:
        jax.jit(lambda x: x * 3 + 1).lower(
            jax.ShapeDtypeStruct((7, 5), "float32")).compile()
    finally:
        disable()
    (ev,) = [e for e in t.events() if e.name == "backend-compile"]
    assert ev.cat == "host" and ev.ph == "X" and ev.dur > 0
    assert ev.args["seconds"] == pytest.approx(ev.dur * 1e-6)
    assert ev.ts + ev.dur <= t.now_us()


def test_arena_replan_is_a_span_with_its_seconds():
    arena = ArenaAllocator(make_profile([(64, 1, 3), (128, 2, 5)]))
    t = Tracer()
    with use_tracer(t):
        arena.reset_iteration()
        arena.free(arena.alloc(256))            # larger than profiled
        arena.request_replan("decode-outrun")
        arena.reset_iteration()                 # the boundary replan
    (ev,) = [e for e in t.events() if e.name == "replan"]
    assert ev.ph == "X" and ev.cat == "arena" and ev.span_id
    assert ev.args["seconds"] == pytest.approx(arena.last_replan_s)
    assert ev.args["seconds"] * 1e6 <= ev.dur
    assert ev.args["new_peak"] == arena.plan.peak
    assert ev.args["n_reopt"] == arena.n_reopt


def test_global_tracer_install_and_restore():
    assert get_tracer() is None
    mine = Tracer()
    with use_tracer(mine):
        assert get_tracer() is mine
        inner = Tracer()
        with use_tracer(inner):
            assert get_tracer() is inner
        assert get_tracer() is mine
    assert get_tracer() is None
    # enable() accepts an existing tracer or builds one from a capacity
    assert enable(mine) is mine
    assert disable() is mine
    fresh = enable(16)
    assert fresh.capacity == 16
    assert disable() is fresh
    assert get_tracer() is None


def test_instrumented_arena_emits_when_enabled_only():
    prof = make_profile([(64, 1, 3), (128, 2, 5)])
    arena = ArenaAllocator(prof)
    a = arena.alloc(64)          # no tracer: must not fail, emits nothing
    arena.free(a)
    t = Tracer()
    with use_tracer(t):
        arena.reset_iteration()
        addr = arena.alloc(64)
        arena.free(addr)
        arena.request_replan("decode-outrun")
    names = [e.name for e in t.events()]
    assert "alloc" in names and "free" in names
    assert "replan-request" in names
    assert all(e.cat == "arena" for e in t.events())


# ---------------------------------------------------------------------------
# export schema
# ---------------------------------------------------------------------------


def _sample_events():
    clk = ManualClock(tick=1e-6)
    t = Tracer(clock=clk)
    t.set_step(0)
    for step in range(3):
        t.set_step(step)
        t.instant("admit", "serving", track="tenant-a", rid=step)
        t.instant("admit", "serving", track="tenant-b", rid=10 + step)
        t.counter("queue_depth", "serving", value=step)
    t.instant("replan", "arena", track="arena", cause="novel-block")
    return t.events()


def test_export_schema_required_keys_and_sorted_ts(tmp_path):
    tb = ChromeTraceBuilder()
    tb.add_events(_sample_events())
    path = tmp_path / "t.json"
    trace = tb.write(str(path))
    validate_chrome_trace(trace)                 # builder output passes
    loaded = json.loads(path.read_text())
    validate_chrome_trace(loaded)                # survives the round trip
    evs = [e for e in loaded["traceEvents"] if e["ph"] != "M"]
    assert evs, "no runtime events exported"
    for e in evs:
        for key in ("name", "cat", "ph", "pid", "tid", "ts"):
            assert key in e
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    # step stamp rides along in args (counters carry only their value)
    assert all("step" in e["args"] for e in evs if e["ph"] != "C")


def test_export_pid_per_category_tid_per_track(tmp_path):
    tb = ChromeTraceBuilder()
    tb.add_events(_sample_events())
    trace = tb.build()
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    procs = {e["args"]["name"]: e["pid"] for e in meta
             if e["name"] == "process_name"}
    threads = {(e["pid"], e["args"]["name"]): e["tid"] for e in meta
               if e["name"] == "thread_name"}
    # one process per category, named
    assert set(procs) == {"serving", "arena"}
    assert len(set(procs.values())) == 2
    # each tenant track is its own thread within the serving process
    spid = procs["serving"]
    assert (spid, "tenant-a") in threads and (spid, "tenant-b") in threads
    assert threads[(spid, "tenant-a")] != threads[(spid, "tenant-b")]
    # events reference exactly the declared pid/tid pairs
    declared = {(p, t) for (p, _n), t in threads.items()}
    for e in trace["traceEvents"]:
        if e["ph"] != "M":
            assert (e["pid"], e["tid"]) in declared


def test_validator_rejects_malformed_traces():
    with pytest.raises(ValueError):
        validate_chrome_trace([])                         # array format
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": []})        # empty
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "i"}]})   # missing keys
    bad_order = {"traceEvents": [
        {"name": "a", "ph": "i", "pid": 1, "tid": 1, "ts": 5},
        {"name": "b", "ph": "i", "pid": 1, "tid": 1, "ts": 1},
    ]}
    with pytest.raises(ValueError):
        validate_chrome_trace(bad_order)
    no_dur = {"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0}]}
    with pytest.raises(ValueError):
        validate_chrome_trace(no_dur)


# ---------------------------------------------------------------------------
# packing rectangles: the export inherits the no-overlap invariant
# ---------------------------------------------------------------------------


def _check_plan_export(profile: MemoryProfile) -> None:
    """Export a plan, reconstruct it from the JSON alone, and re-verify the
    invariant with the independent checker; also check that no two slices
    sharing a Perfetto track overlap in time (what a human would see)."""
    plan = best_fit(profile)
    tb = ChromeTraceBuilder()
    tb.add_plan("p", profile, plan=plan)
    trace = tb.build()
    validate_chrome_trace(trace)
    rects = plan_rectangles(trace, "p")
    live = [b for b in profile.blocks if b.size > 0]
    assert len(rects) == len(live)
    if not live:                # only empty blocks: nothing to draw or place
        assert rects == [] and plan.peak == 0
        return

    # reconstruction: blocks + offsets straight from the exported args
    blocks = [Block(bid=r["bid"], size=r["size"], start=r["start"],
                    end=r["end"]) for r in rects]
    offsets = {r["bid"]: r["offset"] for r in rects}
    peak = rects[0]["peak"]
    rec_profile = MemoryProfile(blocks=blocks,
                                clock_end=max(b.end for b in blocks))
    rec_plan = types.SimpleNamespace(offsets=offsets, peak=peak)
    assert_no_live_overlap(rec_profile, rec_plan)

    # per-track: same tid => same address => slices never overlap in time
    by_tid: dict = {}
    for r in rects:
        by_tid.setdefault(r["tid"], []).append(r)
    for tid, rs in by_tid.items():
        assert len({r["offset"] for r in rs}) == 1
        rs = sorted(rs, key=lambda r: r["start"])
        for a, b in zip(rs, rs[1:]):
            assert a["end"] <= b["start"], (
                f"track {tid}: rectangles {a['bid']} and {b['bid']} overlap")


@pytest.mark.parametrize("seed", range(5))
def test_exported_rectangles_never_overlap_random(seed):
    _check_plan_export(random_profile(seed, 6 + 4 * seed))


@pytest.mark.parametrize("seed", range(3))
def test_exported_rectangles_never_overlap_staircase(seed):
    prof = paged_request_blocks(staircase_trace(seed, 3 + seed),
                                _serving_cfg(), 16)
    _check_plan_export(prof)


if HAVE_HYPOTHESIS:
    block_strategy = st.tuples(
        st.integers(min_value=0, max_value=1 << 14),
        st.integers(min_value=0, max_value=30),
        st.integers(min_value=1, max_value=15),
    ).map(lambda t: (t[0], t[1], t[1] + t[2]))
    profiles = st.lists(block_strategy, min_size=1,
                        max_size=24).map(make_profile)

    @given(profiles)
    @settings(max_examples=50, deadline=None)
    def test_prop_exported_rectangles_never_overlap(prof):
        _check_plan_export(prof)
