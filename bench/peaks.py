"""Published peaks of one accelerator chip, keyed by the ``device_kind``
JAX reports.  The yardstick for every roofline and ``mfu`` share.

TPU v5e (JAX reports it as "TPU v5 lite"), from the Google Cloud
documentation, "TPU v5e" (https://cloud.google.com/tpu/docs/v5e):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect.  The page gives no float32 peak: the MXU
computes float32 products as several bf16 passes, so the bf16 peak is
the most a float32 product can reach and stands for it here (a least
time taken from it is never too long).

This table is a copy of the program's ``repro.roofline.hw.PEAKS`` kept
with the benchmark, so that the yardstick does not move with the
program.  A device that is not in it is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "f32_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
    },
}


class UnknownDevice(RuntimeError):
    """The chip is not in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; add it "
            f"to bench/peaks.py with its source") from None


def least_time_s(work: dict, peaks: dict) -> float:
    """The least time the chip could take for ``work``: the larger of
    its operations over the peak for their datatype and its bytes over
    the HBM bandwidth.  ``work`` is ``{"ops": {dtype: count}, "bytes":
    n}`` with ``dtype`` one of ``int8``, ``bf16``, ``f32``."""
    compute = sum(n / peaks[f"{dt}_ops" if dt == "int8" else f"{dt}_flops"]
                  for dt, n in work["ops"].items())
    return max(compute, work["bytes"] / peaks["hbm_bytes_per_s"])
