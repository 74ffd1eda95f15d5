"""Published peaks per accelerator, keyed by ``jax.Device.device_kind``.

A kind missing from the table is an error, never a default.

Source for "TPU v5 lite" (TPU v5e): Google Cloud documentation,
"TPU v5e" -- 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    kind: str
    flops_bf16: float       # FLOP/s per chip
    hbm_bytes_s: float      # bytes/s per chip
    hbm_bytes: int          # bytes per chip
    source: str


TABLE: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        kind="TPU v5 lite", flops_bf16=197e12, hbm_bytes_s=819e9,
        hbm_bytes=16 * 10**9,
        source="Google Cloud documentation, TPU v5e"),
}


def peaks(kind: str) -> Peaks:
    """Peaks of one device kind; raises KeyError for an unknown kind."""
    try:
        return TABLE[kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {kind!r} "
                       f"(known: {sorted(TABLE)}); add it to "
                       "bench/peaks.py with its source") from None
