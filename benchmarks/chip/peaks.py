"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip that is not in the table is an error,
never a default: a roofline share against a guessed peak means nothing.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peak:
    bf16_flops: float           # dense bf16 matrix FLOP/s of one chip
    hbm_bytes_per_s: float      # HBM bandwidth of one chip
    hbm_bytes: float            # HBM capacity of one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' (per-chip peaks)"),
}


def peak_of(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
