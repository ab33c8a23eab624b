"""Drive one cell: the program's serving engine under wall-clock traffic.

The system under test is ``repro.serving.ServeEngine`` with its defaults
(gather attention, the ``DecodeRunner`` bucket ladder, a ``PagedKVCache``
planned from a sample of ``max_batch`` requests of the cell's own mix, all
in flight at once), driven through its
public ``enqueue()`` and ``step()``.  Everything else here is the load
generator and the bookkeeping: host-clock stamps of every request and step,
``jax.profiler.TraceAnnotation`` spans around the calls into each layer, and
the program's own tracer events when a run is traced.
"""
from __future__ import annotations

import bisect
import gc
import time
from dataclasses import dataclass, field
from functools import cached_property
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

import traffic
from yardstick import Peaks

CLOCK = time.perf_counter


@dataclass
class ReqRecord:
    rid: int
    due: float                 # absolute, host clock
    prompt_len: int
    gen_len: int
    phase: str
    sent: Optional[float] = None
    first: Optional[float] = None
    token_times: list = field(default_factory=list)   # new tokens only
    preempted: int = 0


class DecodeStep(NamedTuple):
    """One decode call of the window, as a family's yardstick reads it."""
    start: float
    end: float
    contexts: list              # each running row's context, in slot order
    events: list                # the program's tracer events inside it


@dataclass
class RunRecord:
    """What a run leaves for the metric readers: plain host data only."""
    yardstick: object           # the family's, for the configuration run
    peaks: Peaks
    setup_s: float
    setup_stages: list          # (stage, seconds), in order
    window: tuple               # (start, end), host clock
    requests: list              # ReqRecord
    prefills: list              # (start, end, prompt_len)
    decodes: list               # (start, end, [context per row])
    steps: list                 # (start, end)
    decode_counter: tuple       # ((time_s, steps) at window start, at end)
    kv_held_bytes: int
    live_kv: list               # (step end, [context of each active request])
    tracer_events: list         # repro.obs.trace events (traced runs)
    tracer_offset: float        # host clock of tracer ts 0
    device_trace: Optional[dict]
    memory_peak_bytes: int
    compiles_in_window: int
    served: dict                # rid -> served token ids (finished)
    prompts: dict               # rid -> prompt ids

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def window_prefills(self) -> list:
        """(start, end, prompt_len) of the prefills inside the window."""
        return [p for p in self.prefills
                if self.in_window(p[0]) and self.in_window(p[1])]

    @cached_property
    def _events_by_time(self) -> tuple:
        """Tracer events sorted by start, and their starts on the host
        clock."""
        evs = sorted(self.tracer_events, key=lambda ev: ev.ts)
        return evs, [self.tracer_offset + ev.ts * 1e-6 for ev in evs]

    def window_decodes(self) -> list:
        """The decode calls inside the window, in order, each with the
        program's tracer events that start inside it."""
        evs, ts = self._events_by_time
        return [DecodeStep(start, end, ctx, evs[bisect.bisect_left(ts, start):
                                                bisect.bisect_left(ts, end)])
                for start, end, ctx in self.decodes
                if self.in_window(start) and self.in_window(end)]

    def window_requests(self) -> list:
        return [r for r in self.requests if r.phase == "window"]

    def ttft_ms(self) -> list:
        """Time to first token of every request due in the window, from its
        due time to the end of the engine step that brought the token to
        the host; one that never got a token counts until the run ended."""
        end = self.steps[-1][1] if self.steps else self.window[1]
        return [((r.first if r.first is not None else max(end, r.due))
                 - r.due) * 1e3 for r in self.window_requests()]


class _Recorder:
    """Receives the engine's per-token callbacks (a ``ServeMetrics``
    subclass is made around it, so the engine's own accounting still
    runs)."""

    def __init__(self):
        self.step_tokens: list[int] = []
        self.preempted: list[int] = []


def _make_metrics(rec: _Recorder):
    from repro.obs.metrics import MetricsRegistry
    from repro.serving import ServeMetrics

    class Metrics(ServeMetrics):
        def on_token(self, rid):
            rec.step_tokens.append(rid)
            super().on_token(rid)

        def on_preempt(self, rid, discarded_tokens=0):
            rec.preempted.append(rid)
            super().on_preempt(rid, discarded_tokens)

    return Metrics(registry=MetricsRegistry())


class _CompileCount:
    """Backend compiles seen through JAX's monitoring events."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def build_model(cfg_file: dict, family):
    """The program's model, as the configuration file names it (a list in
    ``overrides`` is the program's tuple)."""
    from repro.configs import get_config
    from repro.models.transformer import Transformer
    prog = cfg_file["program"]
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in prog.get("overrides", {}).items()}
    cfg = get_config(prog["arch"]).with_overrides(**over)
    family.check_program(cfg, cfg_file)
    return Transformer(cfg)


def _prefill_buckets(lo: int, hi: int, max_len: int) -> list[int]:
    """The engine's power-of-two prompt ladder (floor 8), as far as prompts
    of ``lo``..``hi`` tokens reach."""
    out, b = [], 8
    while True:
        p = min(b, max_len)
        if p >= lo:
            out.append(min(p, hi))
        if p >= hi:
            return out
        b *= 2


def run_cell(cfg_file: dict, family, mix: dict, cell: dict, *, seed: int,
             seconds: float, traced: bool, peaks: Peaks, t_proc0: float,
             trace_dir: Optional[str] = None, fault=None) -> RunRecord:
    """Set up, serve the schedule, and return the record.  ``family`` is
    the configuration's module of ``families/``.  Every device buffer of
    the program is dropped before this returns.  ``fault``, for the
    harness's tests, is called with the warmed engine to break it."""
    from repro.obs.trace import Tracer, disable, enable
    from repro.runtime.serve_lib import Request
    from repro.serving import ServeEngine
    from repro.serving.scheduler import GenRequest

    stages = []

    def stage(name):
        stages.append((name, CLOCK()))

    stage("imports")
    yard = family.yardstick(cfg_file)
    eng_cfg = cfg_file["engine"]
    model = build_model(cfg_file, family)
    params = family.program_params(seed, cfg_file, model)
    jax.block_until_ready(params)
    stage("weights")
    rate = float(cell["rate_per_s"])
    sample = traffic.sample_trace(mix, eng_cfg["max_batch"])
    sample = [Request(rid=i + 1, prompt_len=p, gen_len=g, arrival=a)
              for i, (p, g, a) in enumerate(sample)]
    rec = _Recorder()
    engine = ServeEngine(model, params, sample_trace=sample,
                         max_len=eng_cfg["max_len"],
                         max_batch=eng_cfg["max_batch"],
                         metrics=_make_metrics(rec))
    stage("plan and engine")
    engine.warmup()
    stage("runner and prefill warm-up")

    prefills, decodes = [], []
    raw_prefill = engine._model_prefill

    def prefill(sr):
        t0 = CLOCK()
        with jax.profiler.TraceAnnotation("bench.prefill"):
            raw_prefill(sr)
        prefills.append((t0, CLOCK(), sr.prompt_len))
    engine._model_prefill = prefill

    raw_decode = engine.runner.step_greedy

    def decode(params, cache, tokens, slots):
        ctx = [sr.prompt_len + len(sr.out) for sr in
               sorted(engine.sched.running(), key=lambda s: s.slot)]
        t0 = CLOCK()
        with jax.profiler.TraceAnnotation("bench.decode"):
            out = raw_decode(params, cache, tokens, slots)
        decodes.append((t0, CLOCK(), ctx))
        return out
    engine.runner.step_greedy = decode

    arena = engine.kv.arena
    raw_install = arena._install

    def install(*a, **k):
        with jax.profiler.TraceAnnotation("bench.replan"):
            return raw_install(*a, **k)
    arena._install = install

    # warm the prompt ladder through the whole prefill path (prefill, slab
    # merge, first-token pick) at each bucket this traffic reaches
    p = mix["prompt"]
    rng = np.random.default_rng(seed)
    for i, plen in enumerate(_prefill_buckets(p["min"], p["max"],
                                              eng_cfg["max_len"])):
        engine.enqueue(GenRequest(
            rid=-1 - i, prompt=jnp.asarray(
                rng.integers(0, yard.vocab, plen, dtype=np.int32)),
            gen_len=2))
    while not engine.sched.idle:
        engine.step()
    stage("serve one request per prompt bucket")
    # the engine pads each prompt with an eager op whose program depends on
    # the prompt's exact length: compile those of every length this run
    # will send, here and not inside the window
    reqs = traffic.schedule(mix, rate, seconds, seed)
    for plen in sorted({r.prompt_len for r in reqs}):
        jax.block_until_ready(engine._prefill_batch(
            jnp.zeros((plen,), jnp.int32)))
    jax.block_until_ready(engine.cache)
    stage("prompt pads")
    prefills.clear()
    decodes.clear()
    rec.step_tokens.clear()
    rec.preempted.clear()

    prompts = traffic.prompt_tokens(reqs, yard.vocab, seed)
    prompt_dev = {rid: jnp.asarray(t) for rid, t in prompts.items()}
    jax.block_until_ready(list(prompt_dev.values()))
    if fault is not None:
        fault(engine)
    compiles = _CompileCount()
    kv_held = sum(int(x.nbytes) for x in jax.tree.leaves(engine.cache))

    tracer = None
    tracer_offset = 0.0
    if traced:
        tracer = Tracer(capacity=4_000_000)
        tracer_offset = CLOCK() - tracer.now_us() * 1e-6
        enable(tracer)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # the harness's spans, not every call
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    stage("prompts and tracing")
    t_start = CLOCK()
    setup_s = t_start - t_proc0
    setup_stages = [(name, t - (stages[i - 1][1] if i else t_proc0))
                    for i, (name, t) in enumerate(stages)]
    w0 = t_start + float(mix["warmup_s"])
    w1 = w0 + seconds
    drain_end = w1 + float(mix["drain_s"])
    records = [ReqRecord(rid=r.rid, due=t_start + r.due,
                         prompt_len=r.prompt_len, gen_len=r.gen_len,
                         phase=r.phase) for r in reqs]
    by_rid = {r.rid: r for r in records}
    waiting = {r.rid for r in records if r.phase == "window"}  # no token yet
    n_out: dict[int, int] = {}          # rid -> highest token count reached
    steps, live_kv = [], []
    counter0 = counter1 = None
    compiles0 = compiles1 = None
    i = 0
    window_span = None
    while True:
        now = CLOCK()
        if counter0 is None and now >= w0:
            counter0 = (engine.decode_time_s, engine.decode_steps)
            compiles0 = compiles.n
            window_span = jax.profiler.TraceAnnotation("bench.window")
            window_span.__enter__()
        if counter1 is None and now >= w1:
            counter1 = (engine.decode_time_s, engine.decode_steps)
            compiles1 = compiles.n
            if window_span is not None:
                window_span.__exit__(None, None, None)
        if now >= w1 and (not waiting or now >= drain_end):
            break
        with jax.profiler.TraceAnnotation("bench.enqueue"):
            while i < len(records) and records[i].due <= now:
                r = records[i]
                engine.enqueue(GenRequest(rid=r.rid, prompt=prompt_dev[r.rid],
                                          gen_len=r.gen_len))
                r.sent = CLOCK()
                i += 1
        if engine.sched.idle:
            nxt = records[i].due if i < len(records) else drain_end
            with jax.profiler.TraceAnnotation("bench.idle"):
                while CLOCK() < min(nxt, drain_end):
                    time.sleep(2e-4)
            continue
        t0 = CLOCK()
        with jax.profiler.TraceAnnotation("bench.step"):
            engine.step()
        t1 = CLOCK()
        steps.append((t0, t1))
        for rid in rec.preempted:
            by_rid[rid].preempted += 1
        rec.preempted.clear()
        for rid in rec.step_tokens:
            r = by_rid[rid]
            k = n_out.get(rid, 0)
            if rid in engine.completed:
                got = len(engine.completed[rid])
            elif rid in engine.sched.active:
                got = len(engine.sched.active[rid].out)
            else:
                continue                # preempted later in the same step
            if got > k:                 # a new token, not a recomputed one
                n_out[rid] = got
                r.token_times.append(t1)
                if r.first is None:
                    r.first = t1
                    waiting.discard(rid)
        rec.step_tokens.clear()
        live_kv.append((t1, [sr.prompt_len + len(sr.out)
                             for sr in engine.sched.active.values()]))
    if counter1 is None:
        counter1 = (engine.decode_time_s, engine.decode_steps)
        compiles1 = compiles.n

    device_trace = None
    tracer_events = []
    if traced:
        jax.profiler.stop_trace()
        disable()
        tracer_events = tracer.events()
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    served = {rid: list(map(int, toks)) for rid, toks in
              engine.completed.items() if rid >= 0}
    # drop every device buffer of the program before the reference runs
    engine._model_prefill = engine.runner.step_greedy = None
    arena._install = raw_install
    del engine, params, prompt_dev, raw_prefill, raw_decode
    gc.collect()
    if traced:
        import tracing
        device_trace = tracing.reduce_dir(trace_dir)
    return RunRecord(
        yardstick=yard, peaks=peaks, setup_s=setup_s, window=(w0, w1),
        setup_stages=setup_stages,
        requests=records, prefills=prefills, decodes=decodes, steps=steps,
        decode_counter=(counter0 or counter1, counter1),
        kv_held_bytes=kv_held, live_kv=live_kv,
        tracer_events=tracer_events, tracer_offset=tracer_offset,
        device_trace=device_trace, memory_peak_bytes=peak,
        compiles_in_window=(compiles1 or 0) - (compiles0 or 0),
        served=served, prompts=prompts)
