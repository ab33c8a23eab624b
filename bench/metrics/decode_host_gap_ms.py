"""Device idle inside the decode runner call, per decode step: the mean host
time of the ``runner.*`` spans (slot vector put, executable launch, token
readback) per ``decode`` span in the window, less the mean device time of
one decode program in the traced window."""
from program_spans import spans, window_events


def read(run):
    evs = window_events(run)
    dt = run.device_trace
    if evs is None or not dt or not dt["program_n"].get("decode"):
        return None
    steps = spans(evs, "decode")
    runner = spans(evs, prefix="runner.")
    if not steps or not runner:
        return None
    host_ms = 1e-3 * sum(ev.dur for ev in runner) / len(steps)
    return host_ms - 1e3 * dt["program_s"]["decode"] / dt["program_n"]["decode"]
