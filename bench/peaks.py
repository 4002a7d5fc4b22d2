"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

A device kind that is not here has no roofline and no utilization: the
lookup raises, and the benchmark refuses to run rather than guess.
"""
from __future__ import annotations

from typing import Dict

#: ``device_kind`` as JAX reports it → peaks of one chip.
PEAKS: Dict[str, Dict] = {
    "TPU v5 lite": {
        "name": "TPU v5e",
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s",
    },
}


def peaks_for(device_kind: str) -> Dict:
    """The peaks of ``device_kind``; ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None

