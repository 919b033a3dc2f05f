"""Published peaks, keyed by ``device_kind``. A device that is not here is an error."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture: per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "https://cloud.google.com/tpu/docs/v5e"},
}


class UnknownDevice(KeyError):
    """No published peaks for this ``device_kind``."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind {device_kind!r}; add it to chipbench/peaks.py with its source") from None
