"""Share of the traced window, as far as the trace holds the device's
operations, in which no operation ran on the device."""


def read(run):
    dt = run.device_trace
    return dt["idle_pct"] if dt else None
