"""Published peaks of one chip, keyed by ``device_kind``.

One table, with the source of each number. A kind that is not listed is
an error, never a default: a share of a peak that nobody looked up is
not a measurement.
"""

from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    ici_bytes_per_s: float  # chip-to-chip interconnect, all links
    hbm_bytes: float
    source: str


_V5E = Peak(
    bf16_flops_per_s=197e12,
    hbm_bytes_per_s=819e9,
    ici_bytes_per_s=1600e9 / 8,  # 1,600 Gbit/s
    hbm_bytes=16e9,
    source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
    "16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interchip interconnect",
)

# JAX reports a v5e chip as "TPU v5 lite"; both spellings are the chip.
PEAKS: dict[str, Peak] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add it "
            f"to benchmarks/harness/peaks.py with its source "
            f"(known: {sorted(PEAKS)})"
        ) from None
