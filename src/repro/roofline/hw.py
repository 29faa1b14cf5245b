"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

A roofline share divides by these, so it exists only for a chip listed
here.  Any other device kind, the CPU backend included, is an error where a
share is asked for (``peaks``), never a silent default: a CPU or Pallas
interpreter wall divided by a TPU's peaks is not a device metric.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float   # FLOP/s per chip, bf16 matmul
    hbm_bw: float       # bytes/s per chip
    ici_bw: float       # bytes/s per chip-to-chip link
    source: str


# the device_kind string JAX reports for a TPU v5e chip
V5E = "TPU v5 lite"

PEAKS = {
    V5E: ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9,
        ici_bw=50e9,  # 1,600 Gbit/s per chip over its four links
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s interchip interconnect"),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; raises for a device that has
    none on record (the CPU backend, an unlisted chip)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} (have "
            f"{sorted(PEAKS)}): a roofline share needs a listed chip") from None


DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
