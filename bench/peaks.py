"""Published peaks of each chip a run may report, keyed by JAX's
`device_kind`. A kind that is not here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
16 GB of HBM at 819 GB/s. JAX names the chip "TPU v5 lite".
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip."""
    flops: float
    hbm_bytes_per_s: float
    hbm_bytes: float


_V5E = Peaks(flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)

PEAKS_BY_DEVICE_KIND = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of `device_kind`; KeyError for a chip not in the table."""
    try:
        return PEAKS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}") from None
