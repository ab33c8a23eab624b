"""Useful FLOPs of the decode steps whose programs the trace holds (real
rows only, each at its own context) over the decode programs' device time
times the chip's bf16 peak, in percent."""
from tracing import recorded


def read(run):
    dt = run.device_trace
    if not dt or not dt["program_s"].get("decode"):
        return None
    flops = sum(run.yardstick.decode_flops(step.contexts)
                for step in recorded(run.window_decodes(), dt, "decode"))
    return 100.0 * flops / (dt["program_s"]["decode"] *
                            run.peaks.bf16_flops)
