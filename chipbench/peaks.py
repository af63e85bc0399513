"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error: a
share of a peak that nobody looked up means nothing."""
from __future__ import annotations

__all__ = ["Peaks", "PEAKS", "peaks_for"]

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # dense bf16 matmul, FLOP/s
    int8_ops: float          # int8 matmul, OP/s
    hbm_bytes_per_s: float   # HBM bandwidth, bytes/s
    hbm_bytes: float         # HBM capacity, bytes
    source: str


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
             hbm_bytes=16e9,
             source="Google Cloud documentation, 'TPU v5e' system "
                    "architecture page: 197 TFLOP/s bf16, 393 TOP/s int8, "
                    "16 GB HBM at 819 GB/s per chip")

PEAKS = {
    "TPU v5 lite": _V5E,   # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
