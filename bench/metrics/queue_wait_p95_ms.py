"""95th percentile of due time to admission for the requests due in the
window, from the engine tracer's ``admit`` events (traced runs only)."""
from stats import percentile


def read(run):
    if not run.tracer_events:
        return None
    admit = {}
    for ev in run.tracer_events:
        if ev.name == "admit" and ev.args.get("rid") is not None:
            admit.setdefault(ev.args["rid"],
                             run.tracer_offset + ev.ts * 1e-6)
    end = run.steps[-1][1] if run.steps else run.window[1]
    return percentile([(admit.get(r.rid, max(end, r.due)) - r.due) * 1e3
                       for r in run.window_requests()], 95)
