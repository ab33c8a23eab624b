"""The engine's own decode clock over the window: ``decode_time_s`` over
``decode_steps``, as they grew between the window's start and end."""


def read(run):
    (t0, n0), (t1, n1) = run.decode_counter
    return (t1 - t0) / (n1 - n0) * 1e3 if n1 > n0 else None
