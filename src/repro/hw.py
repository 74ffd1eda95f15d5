"""Accelerator peaks and power, keyed by ``jax.Device.device_kind``.

Code that reads a peak for an attached accelerator looks its kind up
here (``chip(kind)`` / ``attached()``): a kind missing from the table
is an error, never a silent default.  The ESE energy model prices the
modeled v5e fleet, so it reads ``V5E`` by name.

Sources:
  TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
  197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s,
  1,600 Gbit/s inter-chip interconnect (4 links, 50 GB/s each).  Board
  power (TDP / idle) is an approximate public figure, not a datasheet
  value.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Chip:
    kind: str                   # jax.Device.device_kind
    peak_flops_bf16: float      # FLOP/s per chip
    hbm_bw: float               # bytes/s per chip
    hbm_bytes: int              # per chip
    ici_bw: float               # bytes/s per inter-chip link
    tdp_w: float                # peak board power
    idle_w: float
    source: str


CHIPS: dict[str, Chip] = {
    "TPU v5 lite": Chip(
        kind="TPU v5 lite", peak_flops_bf16=197e12, hbm_bw=819e9,
        hbm_bytes=16 * 2**30, ici_bw=50e9, tdp_w=220.0, idle_w=60.0,
        source="Google Cloud TPU v5e documentation; board power "
               "approximate"),
}
V5E = CHIPS["TPU v5 lite"]


def chip(kind: str) -> Chip:
    """Peaks of one device kind; raises for a kind not in the table."""
    try:
        return CHIPS[kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {kind!r} "
                       f"(known: {sorted(CHIPS)}); add it to repro/hw.py "
                       "with its source") from None


def attached() -> Chip:
    """Peaks of the first attached JAX device (raises off the table)."""
    import jax

    return chip(jax.devices()[0].device_kind)


# Facility / fleet constants of the ESE model (per chip, not per kind)
HOST_OVERHEAD_W = 40.0          # per-chip share of host/NIC
PUE = 1.1                       # cooling + facility overhead multiplier

# Embodied energy (ESE linear model): total embodied energy per chip and
# amortization lifetime.  TBE follows LCA estimates for a ~300mm2 5nm
# accelerator package + board share.
CHIP_TBE_J = 4.3e9              # ~1.2 MWh embodied per chip incl. share of rack
CHIP_LIFETIME_S = 5 * 365 * 24 * 3600.0
RECYCLED_TBE_DISCOUNT = 0.35    # recycled hardware carries 35% of fresh TBE
