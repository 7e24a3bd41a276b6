"""Device peaks, keyed by `device_kind` as JAX reports it.

A device that is not in the table is an error, never a default: a roofline
share or a utilisation over a guessed peak is a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # dense bf16 FLOP/s of one chip
    hbm_bytes_per_s: float     # HBM bandwidth of one chip
    hbm_bytes: float           # HBM capacity of one chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


class DeviceError(RuntimeError):
    """No accelerator, too few chips, or a chip with no known peaks."""


def peaks_for(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise DeviceError(f"no peaks known for device kind {kind!r}; "
                          f"known: {sorted(PEAKS)}") from None


def chips(count: int):
    """The first ``count`` TPU devices, or DeviceError."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise DeviceError(f"no TPU found: JAX reports platform "
                          f"{devices[0].platform!r}")
    if len(devices) < count:
        raise DeviceError(f"the cell needs {count} chips, JAX finds "
                          f"{len(devices)}")
    return devices[:count]
