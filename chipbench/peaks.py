"""Published peaks of each accelerator the benchmark may run on, keyed by
JAX's `device_kind`.  A device that is not listed is an error: a share of
an unknown peak would be a guess."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # dense bf16 matmul FLOP/s
    hbm_bytes: float         # HBM bytes/s
    source: str


# JAX names a TPU v5e chip "TPU v5 lite".  Google Cloud documentation,
# "TPU v5e": per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
# 819 GB/s.
TABLE = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes=819e9,
                         source="Google Cloud TPU v5e documentation"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None
