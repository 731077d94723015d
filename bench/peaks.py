"""Peak rates of each accelerator the benchmark runs on, keyed by JAX's
``device_kind``.  A device missing from the table is an error: a share of
a peak is never computed against a guess."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud TPU v5e documentation (per chip)",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source")
    return PEAKS[device_kind]
