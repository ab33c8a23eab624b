"""95th percentile over every gap between two consecutive new output tokens
of any request, where the later token reached the host inside the window."""
from stats import percentile


def read(run):
    gaps = []
    for r in run.requests:
        t = r.token_times
        gaps.extend((b - a) * 1e3 for a, b in zip(t, t[1:])
                    if run.in_window(b))
    return percentile(gaps, 95)
