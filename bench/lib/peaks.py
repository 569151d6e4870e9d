"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports. Source: Google Cloud documentation, "TPU
v5e" (system architecture): 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 394e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def lookup(kind: str) -> dict:
    """The peaks of ``kind``; a chip that is not in the table is an
    error, never a default."""
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"them to bench/lib/peaks.py with their source")
    return PEAKS[kind]
