"""Host time before a decode program can start: the ``runner.put`` and
``runner.launch`` spans of the window, summed, per ``decode`` span."""
from program_spans import spans, window_events


def read(run):
    evs = window_events(run)
    if evs is None:
        return None
    steps = spans(evs, "decode")
    if not steps:
        return None
    return 1e-3 * sum(ev.dur for ev in spans(evs, "runner.put",
                                             "runner.launch")) / len(steps)
