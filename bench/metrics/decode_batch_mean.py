"""Running rows per decode step, over the decode steps that ended inside the
window."""
from stats import mean


def read(run):
    return mean(len(ctx) for _, end, ctx in run.decodes if run.in_window(end))
