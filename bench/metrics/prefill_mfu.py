"""Useful FLOPs of the prefills whose programs the trace holds (causal
attention counted, padding not) over the prefill program's device time
times the chip's bf16 peak, in percent."""
from tracing import recorded


def read(run):
    dt = run.device_trace
    if not dt or not dt["program_s"].get("prefill"):
        return None
    flops = sum(run.yardstick.prefill_flops(n) for _, _, n in
                recorded(run.window_prefills(), dt, "prefill"))
    return 100.0 * flops / (dt["program_s"]["prefill"] *
                            run.peaks.bf16_flops)
