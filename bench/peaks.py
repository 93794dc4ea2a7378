"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM at 819 GB/s.  (Copied from the program's
``roofline/analysis.py`` table, so that the yardstick stays with the
benchmark.)  A device kind that is not here is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Peaks:
    name: str
    ops_per_s: float  # the highest published compute rate (the MXU's bf16 FLOP/s)
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(name="tpu_v5e", ops_per_s=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (known: {sorted(PEAKS)})"
        ) from None
