"""Typed structured-event tracer for the planner stack.

Ring-buffered, and stamped on two clocks at once: the wall clock
(injectable, so tests are deterministic) and the *step* clock of whatever
subsystem is emitting (engine step, arena iteration, search round).  The
instrumented modules — ``ArenaAllocator``, ``ServeEngine``/``Scheduler``,
``remat.search``, ``SharedArena`` — emit through the module-global active
tracer; when none is installed every hook is a single ``None`` check, so the
hot paths stay O(1).

Typical use::

    from repro.obs import trace as obs_trace
    tracer = obs_trace.enable()
    ... run the engine ...
    events = tracer.events()           # list[TraceEvent], oldest dropped first
    obs_trace.disable()

Spans nest: each gets an id and the id of the span open around it (events
emitted inside a span name it as their parent).  A span also opens a
``jax.profiler.TraceAnnotation`` named ``<cat>.<name>``, so under a profiler
session it lands in the host plane on the device ops' clock.  Call sites use
the module-level :func:`span`, which costs one ``None`` check when no tracer
is active.  ``enable()`` adds two host-runtime sources (category "host"):
``gc`` spans from ``gc.callbacks`` and ``backend-compile`` spans from JAX's
compile-duration monitoring event.

Categories double as Chrome-trace processes (see ``obs.export``): "arena",
"serving", "remat", "unified", "host".  Tracks become threads within a
process — tenants, scheduler, engine, runner, individual decode slots.
"""
from __future__ import annotations

import gc
import time
import warnings
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Callable, Iterator, Optional

import jax
from jax.profiler import TraceAnnotation

from . import metrics as _metrics

DEFAULT_CAPACITY = 65_536

# Phases mirror the Chrome trace event format: instant, complete, counter.
PH_INSTANT = "i"
PH_COMPLETE = "X"
PH_COUNTER = "C"

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass(frozen=True)
class TraceEvent:
    """One structured event: what happened, where, and on both clocks."""

    name: str                 # e.g. "replan", "admit", "shrink-round"
    cat: str                  # subsystem: "arena" | "serving" | "host" | ...
    ph: str                   # PH_INSTANT | PH_COMPLETE | PH_COUNTER
    ts: float                 # microseconds since tracer start (wall clock)
    step: int                 # subsystem step stamp (-1 = unknown)
    track: str = "main"       # logical thread within the subsystem
    dur: float = 0.0          # microseconds (PH_COMPLETE only)
    args: dict = field(default_factory=dict)
    span_id: int = 0          # this span's id (spans only; 0 = not a span)
    parent_id: int = 0        # the span open around this event (0 = none)


class _Span:
    """One open span: enters a profiler annotation, pushes its id on the
    tracer's stack, and emits a PH_COMPLETE event on exit.  ``note()`` adds
    args known only at the end (a replan's new peak, its seconds)."""

    __slots__ = ("_tracer", "name", "cat", "track", "args", "span_id",
                 "parent_id", "_step", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str, track: str,
                 args: dict):
        self._tracer = tracer
        self.name, self.cat, self.track, self.args = name, cat, track, args

    def __enter__(self) -> "_Span":
        t = self._tracer
        t._last_id += 1
        self.span_id = t._last_id
        self.parent_id = t._stack[-1] if t._stack else 0
        t._stack.append(self.span_id)
        self._step = t.step
        self._annotation = TraceAnnotation(f"{self.cat}.{self.name}")
        self._annotation.__enter__()
        self._t0 = t.now_us()
        return self

    def __exit__(self, *exc) -> bool:
        t = self._tracer
        dur = max(0.0, t.now_us() - self._t0)
        self._annotation.__exit__(*exc)
        t._stack.pop()
        t.emit(TraceEvent(name=self.name, cat=self.cat, ph=PH_COMPLETE,
                          ts=self._t0, step=self._step, track=self.track,
                          dur=dur, args=self.args, span_id=self.span_id,
                          parent_id=self.parent_id))
        return False

    def note(self, **args) -> None:
        self.args.update(args)


class _NoSpan:
    """What :func:`span` returns with no active tracer: does nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def note(self, **args) -> None:
        pass


NO_SPAN = _NoSpan()


class Tracer:
    """Ring buffer of :class:`TraceEvent` with drop accounting.

    ``clock`` returns seconds (monotonic); inject a fake for determinism.
    ``capacity`` bounds memory: the oldest events are dropped, and
    ``n_dropped`` says how many — exporters surface it so a truncated trace
    never silently reads as a complete one.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock: Callable[[], float] = time.perf_counter,
                 registry: Optional["_metrics.MetricsRegistry"] = None):
        """``registry``: where the drop counter is surfaced
        (``trace_dropped_events_total``).  Defaults to the active registry
        (``obs.metrics.get_registry()``) at first-drop time, so long
        scenario runs can't silently lose spans."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._clock = clock
        self._t0 = clock()
        self._ring: deque[TraceEvent] = deque(maxlen=capacity)
        self.n_emitted = 0
        self.step = -1          # current step stamp; see set_step()
        self._registry = registry
        self._drop_counter = None
        self._warned_drop = False
        self._stack: list[int] = []     # ids of the spans open, innermost last
        self._last_id = 0
        self._gc_open = None            # (start us, annotation) of a collection

    # -- clocks -----------------------------------------------------------------
    def now_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def set_step(self, step: int) -> None:
        """Stamp subsequent events with this subsystem step."""
        self.step = step

    # -- emission ---------------------------------------------------------------
    def emit(self, event: TraceEvent) -> None:
        if len(self._ring) == self.capacity:
            self._on_drop()
        self._ring.append(event)
        self.n_emitted += 1

    def _on_drop(self) -> None:
        """The ring is full: the oldest event is about to be lost.  Warn
        once (so a long scenario run never silently truncates its spans)
        and count every drop on the metrics registry."""
        if not self._warned_drop:
            self._warned_drop = True
            warnings.warn(
                f"Tracer ring buffer full (capacity={self.capacity}): "
                "oldest events are being dropped; exported spans may be "
                "truncated.  Raise Tracer(capacity=...) for long runs.",
                RuntimeWarning, stacklevel=4)
        if self._drop_counter is None:
            reg = self._registry if self._registry is not None \
                else _metrics.get_registry()
            if reg is None:
                return
            self._drop_counter = reg.counter(
                "trace_dropped_events_total",
                "trace events dropped by the ring buffer")
        self._drop_counter.inc()

    def _parent(self) -> int:
        return self._stack[-1] if self._stack else 0

    def instant(self, name: str, cat: str, track: str = "main",
                **args) -> None:
        self.emit(TraceEvent(name=name, cat=cat, ph=PH_INSTANT,
                             ts=self.now_us(), step=self.step, track=track,
                             args=args, parent_id=self._parent()))

    def complete(self, name: str, cat: str, track: str, ts: float,
                 dur: float, **args) -> None:
        self.emit(TraceEvent(name=name, cat=cat, ph=PH_COMPLETE, ts=ts,
                             step=self.step, track=track, dur=dur, args=args,
                             parent_id=self._parent()))

    def counter(self, name: str, cat: str, value: float,
                track: str = "counters") -> None:
        self.emit(TraceEvent(name=name, cat=cat, ph=PH_COUNTER,
                             ts=self.now_us(), step=self.step, track=track,
                             args={"value": value}))

    def span(self, name: str, cat: str, track: str = "main",
             **args) -> _Span:
        """A PH_COMPLETE slice covering the with-block, nested under the
        span open around it and annotated for the JAX profiler."""
        return _Span(self, name, cat, track, args)

    # -- inspection ---------------------------------------------------------------
    @property
    def n_dropped(self) -> int:
        return self.n_emitted - len(self._ring)

    def events(self) -> list[TraceEvent]:
        return list(self._ring)

    def stats(self) -> dict:
        return {"capacity": self.capacity, "n_emitted": self.n_emitted,
                "n_buffered": len(self._ring), "n_dropped": self.n_dropped}


# -- module-global active tracer ------------------------------------------------
_ACTIVE: Optional[Tracer] = None


def get_tracer() -> Optional[Tracer]:
    """The active tracer, or None (instrumentation hooks check this)."""
    return _ACTIVE


def span(name: str, cat: str, track: str = "main", **args):
    """``Tracer.span`` on the active tracer; the shared do-nothing
    ``NO_SPAN`` when there is none."""
    t = _ACTIVE
    if t is None:
        return NO_SPAN
    return _Span(t, name, cat, track, args)


# -- host runtime sources (installed by enable) -----------------------------------
_compile_listener = False   # registered with jax.monitoring, once a process


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``gc`` span per collection."""
    t = _ACTIVE
    if t is None:
        return
    if phase == "start":
        annotation = TraceAnnotation("host.gc")
        annotation.__enter__()
        t._gc_open = (t.now_us(), annotation)
    elif t._gc_open is not None:
        t0, annotation = t._gc_open
        t._gc_open = None
        annotation.__exit__(None, None, None)
        t.complete("gc", "host", "gc", ts=t0, dur=max(0.0, t.now_us() - t0),
                   generation=info.get("generation"),
                   collected=info.get("collected"))


def _on_duration(event: str, secs: float, **_) -> None:
    """``jax.monitoring`` listener: a ``backend-compile`` span that ended
    now, while a tracer is active."""
    t = _ACTIVE
    if t is None or event != BACKEND_COMPILE_EVENT:
        return
    dur = secs * 1e6
    t.complete("backend-compile", "host", "compile", ts=t.now_us() - dur,
               dur=dur, seconds=secs)


def enable(tracer: "Tracer | int" = DEFAULT_CAPACITY,
           clock: Callable[[], float] = time.perf_counter) -> Tracer:
    """Install (and return) the active tracer, with the ``gc`` hook and the
    compile-duration listener.

    Pass a ``Tracer`` to install it, or a capacity int (the default) to
    build a fresh one."""
    global _ACTIVE, _compile_listener
    if not isinstance(tracer, Tracer):
        tracer = Tracer(capacity=tracer, clock=clock)
    _ACTIVE = tracer
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    if not _compile_listener:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _compile_listener = True
    return _ACTIVE


def disable() -> Optional[Tracer]:
    """Uninstall the active tracer and the ``gc`` hook; returns the tracer
    for a final export."""
    global _ACTIVE
    t, _ACTIVE = _ACTIVE, None
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    return t


@contextmanager
def use_tracer(tracer: Tracer) -> Iterator[Tracer]:
    """Temporarily install ``tracer`` as the active one (test helper)."""
    global _ACTIVE
    prev = _ACTIVE
    _ACTIVE = tracer
    try:
        yield tracer
    finally:
        _ACTIVE = prev
