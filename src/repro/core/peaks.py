"""Published per-chip peaks, keyed by ``device_kind`` as JAX reports it.

One table for every consumer: the planner's budgets (VMEM, HBM) and the
roofline terms (FLOP/s, HBM and ICI bandwidth) read the same row, that of
the attached chip (``attached_peaks``).  A host without an accelerator (the
tests, the chip-less dry run) plans for ``PLANNING_TARGET``.  An accelerator
that is not in the table is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax


@dataclass(frozen=True)
class ChipPeaks:
    device_kind: str
    bf16_flops: float          # FLOP/s
    hbm_bytes: int
    hbm_bw: float              # bytes/s
    ici_bw: float              # bytes/s per link
    vmem_bytes: int            # scoped VMEM a Pallas kernel may use by default
    source: str


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        device_kind="TPU v5 lite",
        bf16_flops=197e12,
        hbm_bytes=16 * 1024 ** 3,
        hbm_bw=819e9,
        ici_bw=50e9,           # 1,600 Gbit/s per chip over 4 links
        vmem_bytes=16 * 1024 * 1024,
        source="Google Cloud documentation, 'TPU v5e' (system architecture); "
               "VMEM: Mosaic's default scoped limit on v5e",
    ),
}

# the chip a host without an accelerator plans for
PLANNING_TARGET = "TPU v5 lite"


def peaks_for(device_kind: str) -> ChipPeaks:
    """The table row for ``device_kind``; raises on an unknown device."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def attached_peaks() -> ChipPeaks:
    """The row for the chip JAX runs on (``jax.devices()[0]``): the planning
    target on a CPU host, and an error for an accelerator not in the
    table."""
    dev = jax.devices()[0]
    return peaks_for(PLANNING_TARGET if dev.platform == "cpu"
                     else dev.device_kind)
