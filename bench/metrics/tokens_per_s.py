"""Prompt tokens prefilled plus tokens generated inside the window, over the
window's length."""


def read(run):
    lo, hi = run.window
    prompt = sum(n for _, end, n in run.prefills if lo <= end < hi)
    generated = sum(1 for r in run.requests for t in r.token_times
                    if lo <= t < hi)
    return (prompt + generated) / (hi - lo)
