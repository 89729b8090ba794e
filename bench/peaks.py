"""Published peaks of each accelerator the benchmark may run on.

Keyed by the ``device_kind`` JAX reports.  A kind missing from the table is
an error, never a default: a roofline drawn against another chip's peaks is
a wrong number.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float        # FLOP/s per chip, bf16 on the MXU
    hbm_bw: float       # bytes/s per chip, HBM
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
