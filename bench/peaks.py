"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not listed is an error, not a default.

The v5e figures are Google Cloud's ("TPU v5e" in the Cloud TPU
documentation): 197 TFLOP/s in bfloat16 on the matrix units, 16 GB of HBM
at 819 GB/s. No peak is published for the float32 vector and
transcendental units that the ``frontier_grid`` kernel runs on, so its
share of the bfloat16 peak reads low and serves as a relative yardstick.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/peaks.py with their source")
    return PEAKS[device_kind]
