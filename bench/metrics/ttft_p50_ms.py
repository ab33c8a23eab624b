"""Median time to first token over every request due in the window: from
when the request was due to the end of the engine step in which its first
token reached the host.  A request that never got one counts with the time
it waited until the run ended."""
from stats import percentile


def read(run):
    return percentile(run.ttft_ms(), 50)
