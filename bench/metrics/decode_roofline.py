"""The decode program's share of its roofline: for each decode step whose
program the trace holds, the least time the chip could take (the family's
yardstick: its FLOPs over the bf16 peak, or the bytes it must move over the
HBM bandwidth, whichever is larger), summed, over the decode programs'
device time, in percent.  Gather attention runs no kernel of its own, so
the whole decode program is read."""
from tracing import recorded


def read(run):
    dt = run.device_trace
    if not dt or not dt["program_s"].get("decode"):
        return None
    least = sum(run.yardstick.decode_least_time(step, run.peaks)[0]
                for step in recorded(run.window_decodes(), dt, "decode"))
    return 100.0 * least / dt["program_s"]["decode"]
