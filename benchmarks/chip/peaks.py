"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip.  A device kind that is
not in this table is an error, never a default.
"""
from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    flops: float        # bf16 FLOP/s
    hbm_bytes_s: float  # HBM bandwidth, bytes/s
    hbm_bytes: float    # HBM capacity, bytes
    source: str


PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        "Google Cloud documentation, 'TPU v5e'"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
