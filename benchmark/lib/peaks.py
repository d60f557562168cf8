"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

The benchmark's own table: roofline shares and MFU are worked out against
these numbers and no others. A device that is not listed is an error, never
a default.
"""

from __future__ import annotations

from typing import Any, Dict

# source: Google Cloud documentation, "TPU v5e" system architecture page
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud TPU v5e",
    },
}


def peaks_for(device_kind: str) -> Dict[str, Any]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"device kind {device_kind!r} is in no peak table "
            f"({sorted(PEAKS)}): add its published peaks with their source "
            "to benchmark/lib/peaks.py") from None
