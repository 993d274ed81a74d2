"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

A device that is not in the table is an error, never a default: a share
of a peak divided by the wrong chip's peak is a wrong number.
"""

from __future__ import annotations

from typing import Dict

#: device_kind -> peaks of ONE chip
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' "
                  "(197 TFLOP/s bf16, 16 GB HBM at 819 GB/s)",
    },
}


def peak_for(device_kind: str) -> Dict[str, object]:
    """The peaks of ``device_kind``; raises ``KeyError`` naming the
    known kinds when the chip is not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"device_kind {device_kind!r} has no entry in the "
                       f"peak table (known: {sorted(PEAKS)})") from None
