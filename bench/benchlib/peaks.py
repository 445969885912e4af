"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect per chip.  A device missing here is an error:
a roofline share against a guessed peak is no measurement.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to bench/benchlib/peaks.py with their source") from None


def least_time_s(flops: float, nbytes: float, device_kind: str) -> float:
    """The roofline's least time: the larger of operations over the bf16
    peak and bytes over the HBM bandwidth."""
    pk = peaks(device_kind)
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
