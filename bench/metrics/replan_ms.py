"""Host time of the arena's replans in the window: the ``seconds`` of the
engine tracer's ``replan`` events, summed (traced runs only; 0 where the
window had none)."""


def read(run):
    if not run.tracer_events:
        return None
    lo, hi = run.window
    return 1e3 * sum(ev.args.get("seconds", 0.0) for ev in run.tracer_events
                     if ev.name == "replan"
                     and lo <= run.tracer_offset + ev.ts * 1e-6 < hi)
