"""Peak rates of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
per chip 197 TFLOP/s in bfloat16, 393 TOP/s in int8, 16 GB of HBM at
819 GB/s.  A device missing here is an error, never a default.
"""

from __future__ import annotations

from typing import Dict

SOURCE = "Google Cloud documentation, TPU v5e system architecture"

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16": 197e12, "int8": 393e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16": 197e12, "int8": 393e12, "hbm_bytes_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {', '.join(sorted(PEAKS))}") from None
