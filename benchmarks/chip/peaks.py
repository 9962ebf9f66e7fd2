"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip.  JAX reports a v5e as "TPU v5 lite".

The tables carry no float32 vector-unit peak, so an operation count is held
against the bf16 matrix peak: that overstates what the chip can do on f32
elementwise work, which makes a least time shorter and a roofline share
lower, never higher.  Every design the benchmark runs today is bound by
HBM bytes, where the published figure applies as it stands.
"""
from __future__ import annotations

from typing import Dict, Mapping

SOURCE = "Google Cloud documentation, TPU v5e"

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


class UnknownDevice(KeyError):
    """The device is not in the table: there is no peak to hold it to."""


def peak(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown device is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


def least_time_s(work: Mapping[str, float], pk: Mapping[str, float]) -> float:
    """The least time one chip needs for ``work``: the larger of its bytes
    over peak HBM bandwidth and its operations over peak compute."""
    return max(work["bytes"] / pk["hbm_bytes_per_s"],
               work["ops"] / pk["flops_per_s"])
