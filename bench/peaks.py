"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A kind missing here is an error, never a
default: a roofline share against the wrong chip's peak means nothing."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, \"TPU v5e\" "
                  "(cloud.google.com/tpu/docs/v5e): per-chip peaks",
    },
}


class UnknownDevice(KeyError):
    """The device kind has no row in the peaks table."""


def peaks_for(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"to bench/peaks.py with its source") from None
