"""Per-chip hardware peaks used by the roofline model, keyed by the
``device_kind`` JAX reports.

TPU v5e figures are from the Google Cloud documentation, "TPU v5e":
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.  The ICI
per-link and DCN per-chip bandwidths are this model's own estimates.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float            # FLOP/s per chip (bf16 MXU)
    ops_int8: float              # OP/s per chip (s8 MXU path)
    hbm_bw: float                # bytes/s per chip
    hbm_bytes: float             # per-chip HBM capacity
    ici_bw_per_link: float       # bytes/s per ICI link (estimate)
    # inter-pod effective per-chip bandwidth — the paper's "host hop":
    # ~6.4 Tbps/pod aggregate over 256 chips ≈ 3 GB/s/chip sustained
    dcn_bw_per_chip: float


TPU_V5E = ChipPeaks(flops_bf16=197e12, ops_int8=393e12, hbm_bw=819e9,
                    hbm_bytes=16e9, ici_bw_per_link=50e9,
                    dcn_bw_per_chip=3e9)

# ``device_kind`` -> peaks.  JAX reports a v5e chip as "TPU v5 lite".
PEAKS = {"TPU v5 lite": TPU_V5E}


def peaks_for(device) -> ChipPeaks:
    """The peaks of ``device``.  A TPU whose ``device_kind`` is not in
    :data:`PEAKS` is an error, never a default.  Other backends (the
    CPU test runs) model the v5e target."""
    if device.platform != "tpu":
        return TPU_V5E
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no roofline peaks for TPU device_kind "
            f"{device.device_kind!r}; add it to repro.roofline.hw.PEAKS "
            f"with its published source") from None
