"""Useful FLOPs of the prefills in the traced window (causal attention
counted, padding not) over the prefill program's device time times the
chip's bf16 peak, in percent."""


def read(run):
    dt = run.device_trace
    if not dt or not dt["program_s"].get("prefill"):
        return None
    flops = sum(run.dims.prefill_flops(n) for start, end, n in run.prefills
                if run.in_window(start) and run.in_window(end))
    return 100.0 * flops / (dt["program_s"]["prefill"] *
                            run.peaks.bf16_flops)
