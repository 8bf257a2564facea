"""Published peaks of the chips the benchmark may run on, by `device_kind`.

A device whose kind is not in the table is refused: a share of a peak with
a guessed peak means nothing. TPU v5e (JAX reports "TPU v5 lite"): 197
TFLOP/s bf16, 16 GB of HBM at 819 GB/s (Google Cloud documentation, "TPU
v5e"). The program computes float32 at Precision.HIGHEST, several bf16
passes per product, so the bf16 peak is a conservative ceiling for it.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_per_s: float       # dense bf16 matmul peak
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         'Google Cloud documentation, "TPU v5e"'),
}


class UnsupportedDevice(RuntimeError):
    pass


def peaks_for(device) -> Peaks:
    """The peaks of `device`; refuses anything but a TPU in the table."""
    if device.platform != "tpu":
        raise UnsupportedDevice(
            f"no TPU: the first device is {device.platform!r} "
            f"({device.device_kind}); the benchmark never runs on the CPU")
    if device.device_kind not in PEAKS:
        raise UnsupportedDevice(
            f"device_kind {device.device_kind!r} has no entry in the peaks "
            f"table (known: {sorted(PEAKS)})")
    return PEAKS[device.device_kind]
