"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

A device that is not in the table is an error, never a default: a
share of a peak taken against the wrong chip means nothing.
"""
from __future__ import annotations

from typing import NamedTuple


class Peak(NamedTuple):
    flops_bf16: float        # FLOP/s, dense bf16 matrix multiplication
    hbm_bytes_per_s: float   # HBM bandwidth, bytes/s
    hbm_bytes: float         # HBM capacity, bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
