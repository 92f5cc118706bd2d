"""Published peaks of the chips the benchmark runs on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud, "TPU v5e" (system architecture page): 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
interconnect per chip.
"""
from __future__ import annotations

SOURCE = "Google Cloud, TPU v5e"

_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes": 16e9,
    "hbm_bytes_per_s": 819e9,
    "ici_bytes_per_s": 1600e9 / 8,
}

PEAKS = {
    "TPU v5 lite": _V5E,   # what JAX reports for a v5e chip
}


class UnknownDevice(KeyError):
    """A device kind with no row in :data:`PEAKS`: no roofline can be read."""


def peaks_for(device_kind: str) -> dict:
    """The peak row of ``device_kind``; raises :class:`UnknownDevice`."""
    try:
        return dict(PEAKS[device_kind], device_kind=device_kind,
                    source=SOURCE)
    except KeyError:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None
