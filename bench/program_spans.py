"""The program's own spans (``repro.obs.trace``) as the metric readers see
them: tracer events are stamped in microseconds from the tracer's start,
and ``run.tracer_offset`` is that start on the harness's host clock."""
from __future__ import annotations

from collections import defaultdict

PH_COMPLETE = "X"


def window_events(run):
    """Tracer events that start inside the window; None where the run has
    no tracer events (an untraced run)."""
    if not run.tracer_events:
        return None
    lo, hi = run.window
    return [ev for ev in run.tracer_events
            if lo <= run.tracer_offset + ev.ts * 1e-6 < hi]


def spans(events, *names, prefix: str = ""):
    """Complete events called one of ``names`` or starting with
    ``prefix``."""
    return [ev for ev in events if ev.ph == PH_COMPLETE and
            (ev.name in names or (prefix and ev.name.startswith(prefix)))]


def self_time_us(run, parents) -> float:
    """Summed duration of ``parents`` less that of their direct children."""
    ids = {ev.span_id for ev in parents}
    child = defaultdict(float)
    for ev in run.tracer_events:
        if ev.ph == PH_COMPLETE and ev.parent_id in ids:
            child[ev.parent_id] += ev.dur
    return sum(ev.dur - child[ev.span_id] for ev in parents)


def host_spans_ms(run, name: str):
    """Summed duration of the window's ``name`` spans (category ``host``),
    0 where there were none; None without tracer events, or where the
    program opened no engine ``step`` span (it has no such source)."""
    evs = window_events(run)
    if evs is None or not spans(evs, "step"):
        return None
    return 1e-3 * sum(ev.dur for ev in spans(evs, name) if ev.cat == "host")
