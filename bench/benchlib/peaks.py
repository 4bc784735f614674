"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

A device that is not in the table is an error, never a default: a share of
a peak computed against the wrong chip is worse than none.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, 'TPU v5e' (system "
                  "architecture, per-chip specifications)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; the table knows "
            f"{sorted(PEAKS)} — add the chip with its published source"
        ) from None
