"""Backend compile time inside the window: the ``backend-compile`` spans,
summed (0 where nothing compiled)."""
from program_spans import host_spans_ms


def read(run):
    return host_spans_ms(run, "backend-compile")
