"""The benchmark's own arithmetic: the chip's peaks.

Kept apart from the program on purpose, so that a change to the program
cannot change how it is judged.  The FLOPs and bytes of a step are each
family's yardstick (``families/__init__.py`` says what one gives): they
read the sizes from a configuration file of ``configs/``, never from the
program's config objects.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    device_kind: str
    bf16_flops: float          # FLOP/s
    hbm_bytes: int
    hbm_bw: float              # bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        device_kind="TPU v5 lite", bf16_flops=197e12, hbm_bytes=16 * 2 ** 30,
        hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The row of ``device_kind``; a device not in the table is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
