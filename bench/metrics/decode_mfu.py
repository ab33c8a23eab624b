"""Useful FLOPs of the decode steps in the traced window (real rows only,
each at its own context) over the decode programs' device time times the
chip's bf16 peak, in percent."""


def read(run):
    dt = run.device_trace
    if not dt or not dt["program_s"].get("decode"):
        return None
    flops = sum(run.dims.decode_flops(ctx) for start, end, ctx in run.decodes
                if run.in_window(start) and run.in_window(end))
    return 100.0 * flops / (dt["program_s"]["decode"] *
                            run.peaks.bf16_flops)
