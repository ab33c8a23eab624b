"""Mean host time of one prefill call (the jitted prefill, the merge into the
slab, and the ``block_until_ready`` that ends it), over the calls that ended
inside the window."""
from stats import mean


def read(run):
    return mean((end - start) * 1e3 for start, end, _ in run.prefills
                if run.in_window(end))
