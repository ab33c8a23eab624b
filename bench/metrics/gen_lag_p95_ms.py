"""How late the load generator sent the requests due in the window: 95th
percentile of send time minus due time.  The generator shares its thread
with the engine loop, so a request due during an engine step waits for it."""
from stats import percentile


def read(run):
    return percentile([(r.sent - r.due) * 1e3 for r in run.window_requests()
                       if r.sent is not None], 95)
