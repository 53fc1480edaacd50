"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s.  A device that is not in the table is an error: a roofline or
MFU against a guessed peak is no number at all.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Peaks", "PEAKS", "peaks_for"]


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s
    int8_ops: float          # OP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # B
    source: str


_V5E = Peaks(
    bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
    hbm_bytes=16e9, source='Google Cloud documentation, "TPU v5e"')

PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
