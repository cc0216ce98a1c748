"""Peak rates of each accelerator, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): per chip 197 TFLOP/s in bf16, 16 GB of HBM at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect.  A device kind missing here is
an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"device kind {device_kind!r} has no peaks in "
                         f"chipbench/peaks.py") from None
