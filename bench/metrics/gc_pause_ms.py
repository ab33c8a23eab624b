"""Host time the Python garbage collector held the process in the window:
the ``gc`` spans, summed (0 where no collection ran)."""
from program_spans import host_spans_ms


def read(run):
    return host_spans_ms(run, "gc")
