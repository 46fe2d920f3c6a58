"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip missing from the table is an error,
never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

from typing import Dict

SOURCE = 'Google Cloud documentation, "TPU v5e" system architecture'

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"the table ({SOURCE}) holds {sorted(PEAKS)}")
    return PEAKS[device_kind]
