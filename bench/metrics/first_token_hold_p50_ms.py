"""How long a first token waits inside the engine once it is on the host:
median, over the requests due in the window, of the end of the ``step``
span around the request's ``first-token`` stamp less the stamp (the step
goes on to decode every running row before it returns)."""
from program_spans import PH_COMPLETE
from stats import percentile


def read(run):
    stamp = {}
    for ev in run.tracer_events:
        if ev.name == "first-token" and ev.args.get("rid") is not None:
            stamp.setdefault(ev.args["rid"], ev)
    if not stamp:
        return None
    by_id = {ev.span_id: ev for ev in run.tracer_events
             if ev.ph == PH_COMPLETE and ev.span_id}
    holds = []
    for r in run.window_requests():
        ev = stamp.get(r.rid)
        step = by_id.get(ev.parent_id) if ev is not None else None
        while step is not None and step.name != "step":
            step = by_id.get(step.parent_id)
        if step is not None:
            holds.append(1e-3 * (step.ts + step.dur - ev.ts))
    return percentile(holds, 50)
