"""Peak bytes the device held at any time of the run, set-up included
(``peak_bytes_in_use`` after the drain), in GiB."""


def read(run):
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
