"""Host time of the engine loop itself, per engine step: the self time (less
the direct child spans) of each ``step`` span in the window and of the
``decode`` span inside it, averaged over the steps.  It is admission, the
per-row token bookkeeping and the page accounting."""
from program_spans import self_time_us, spans, window_events


def read(run):
    evs = window_events(run)
    if evs is None:
        return None
    steps = spans(evs, "step")
    if not steps:
        return None
    ids = {ev.span_id for ev in steps}
    decodes = [ev for ev in spans(evs, "decode") if ev.parent_id in ids]
    return 1e-3 * (self_time_us(run, steps) +
                   self_time_us(run, decodes)) / len(steps)
