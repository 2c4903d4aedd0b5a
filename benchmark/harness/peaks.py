"""Published peaks of one chip, keyed by a substring of JAX's
``device_kind``. An unknown device is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture:
197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB HBM per chip.
"""

from __future__ import annotations

PEAKS = {
    # device_kind substring: (bf16 FLOP/s, HBM bytes/s, HBM bytes)
    "v5 lite": (197e12, 819e9, 16e9),
    "v5e": (197e12, 819e9, 16e9),
}


def of(device_kind: str) -> dict:
    for key, (flops, bw, hbm) in PEAKS.items():
        if key in device_kind.lower():
            return {"flops": flops, "bytes_per_s": bw, "hbm_bytes": hbm}
    raise KeyError(f"no peaks known for device kind {device_kind!r}: "
                   f"add it to benchmark/harness/peaks.py with its source")
