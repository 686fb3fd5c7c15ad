"""Published peaks of each chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16 and
819 GB/s of HBM bandwidth per chip.  A device that is not in the table is
an error, never a default.
"""

from __future__ import annotations

from typing import Dict

_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def of(device_kind: str) -> Dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/core/peaks.py"
                       ) from None
