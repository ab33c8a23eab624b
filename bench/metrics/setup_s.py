"""Process start to the first request sent: imports, weights, planning,
engine construction and warm-up (compilation too, where a program is not in
the persistent cache)."""


def read(run):
    return run.setup_s
