"""The decode program's share of its roofline: for each decode step in the
traced window the least time the chip could take (its FLOPs over the bf16
peak, or the bytes it must move over the HBM bandwidth, whichever is
larger: weights once, each row's live KV once, the new KV written), summed,
over the decode programs' device time, in percent.  Gather attention runs
no kernel of its own, so the whole decode program is read."""


def read(run):
    dt = run.device_trace
    if not dt or not dt["program_s"].get("decode"):
        return None
    least = sum(run.dims.decode_least_time(ctx, run.peaks)[0]
                for start, end, ctx in run.decodes
                if run.in_window(start) and run.in_window(end))
    return 100.0 * least / dt["program_s"]["decode"]
